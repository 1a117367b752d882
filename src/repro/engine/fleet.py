"""Fleet-scale Voltron: per-DIMM safe-voltage tables from characterization,
and the W workloads x D DIMMs controller cross-product as one flat sweep.

The paper's two halves finally meet here.  Sections 4-5 characterize each
DIMM's V_min / min-latency surface (:mod:`repro.engine.population`,
:mod:`repro.engine.test1`); Section 6's Voltron controller retimes DRAM
against a voltage-latency table.  The stock controller uses one global
Table-3 grid for every workload — but safe voltage/latency is *per-DIMM
and per-vendor* (that is the entire point of the characterization), so a
fleet deployment must hand each DIMM its own table:

- :func:`build_tables` derives each DIMM's safe candidate table: for every
  Algorithm-1 candidate voltage, the platform-quantized error-free
  (tRCD, tRP) pair from :func:`repro.engine.test1.find_min_latency_batch`.
  A NaN pair *excludes* that candidate for that DIMM (e.g. every Vendor-C
  candidate below the vendor recovery floor), and the exclusion mask rides
  into Algorithm 1 so the controller can never select a voltage the DIMM
  cannot run error-free.  tRAS keeps the circuit-model value per candidate
  (Test 1 overlaps tRAS with the column reads — footnote 8 — so the
  characterization does not retime it).  On top of the error-free floor
  rides the *disturbance* floor (arxiv 2206.09999): a candidate whose
  worst-cell hammer threshold (``errors.hammer_threshold`` — voltage
  shifts first-flip hammer counts) undercuts the refresh-window exposure
  at the candidate's own timings is excluded with the same NaN semantics,
  and the per-candidate hammer margin (threshold / exposure) is carried
  as a table row and surfaced per-vendor in :class:`FleetBatchResult`.

- :func:`run_fleet_batched` runs the interval controller over the
  flattened W x D cross-product (lane ``n = w * D + d``) as one dispatched
  ``lax.scan``: each lane carries its own DIMM's [K] timing table, latency
  features and exclusion row through
  :func:`repro.engine.controller.run_flat`, which buckets/shards the flat
  axis via :mod:`repro.engine.dispatch` (entry ``"fleet"`` — warm AOT
  executable reuse across fleet request shapes, chunked streaming past the
  resident budget).  Results come back as [W, D] per-DIMM distributions of
  the Fig. 14/17 quantities, with per-vendor aggregation helpers.

Parity contract: lane (w, d) of the fleet is the same computation as
``voltron.run_suite([w], tables=tables.select([d]))`` — per-lane bit-equal
selections (tests/test_fleet.py asserts it on a 2 x 2 grid).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro import power as power_lib
from repro.dram import circuit, errors
from repro.engine import controller
from repro.engine import dispatch as dispatch_lib
from repro.engine import solve as engine_solve
from repro.engine import test1 as engine_test1
from repro.engine.batch import WorkloadBatch
from repro.engine.population import DimmGrid


@dataclasses.dataclass(frozen=True)
class FleetTables:
    """Per-DIMM safe candidate tables (the characterization-to-Voltron
    bridge).  K candidates, ascending voltage, last entry = the nominal
    fallback (must be valid on every DIMM)."""

    modules: tuple
    vendors: tuple
    cand_v: np.ndarray      # [K] candidate voltages
    timings: np.ndarray     # [D, K, 3] (tRCD, tRP, tRAS); NaN where invalid
    valid: np.ndarray       # [D, K] error-free latency pair AND hammer-safe
    lat_feat: np.ndarray    # [D, K-1] Algorithm-1 latency feature (tRP+tRAS)
    hammer_margin: np.ndarray   # [D, K] worst-cell threshold / exposure;
    #                             NaN where min-latency already excluded
    hammer_window_ms: float = errors.HAMMER_WINDOW_MS
    # per-DIMM device-model name ([D]; repro.power registry) — the
    # heterogeneous-fleet column.  Defaults to ddr3l on every DIMM.
    device_models: tuple = ()
    # per-candidate reliability-transparency rows (arxiv 2204.10378): the
    # beat-error rates the active ECC profile would correct / detect / pass
    # through silently, [D, K] each, evaluated at every candidate's own
    # table timings (probe timings where only ECC admits it).  NaN exactly
    # where ``valid`` excludes the candidate — the same NaN-exclusion
    # convention as ``timings``.  None when the policy stack carries no
    # ECC policy.
    correctable: np.ndarray | None = None
    detectable: np.ndarray | None = None
    silent: np.ndarray | None = None
    # the active policy-stack identity: one descriptor string per applied
    # ReliabilityPolicy, in pipeline order.  () on hand-built tables that
    # predate the pipeline.
    policy_stack: tuple = ()

    def __post_init__(self):
        if not self.device_models:
            object.__setattr__(self, "device_models",
                               ("ddr3l",) * len(self.modules))
        elif len(self.device_models) != len(self.modules):
            raise ValueError("device_models must name one model per DIMM")

    @property
    def n_dimms(self) -> int:
        return len(self.modules)

    @property
    def safe_vmin(self) -> np.ndarray:
        """[D] lowest candidate voltage each DIMM can run error-free at
        some latency — the fleet-resolved Section 4.2 recovery boundary."""
        ok = np.where(self.valid, self.cand_v[None, :], np.inf)
        return ok.min(axis=1)

    @property
    def stack_name(self) -> str:
        """Short service-registry identity of the policy stack: the joined
        policy names (``"min_latency+hammer"`` for the default stack,
        ``"min_latency+ecc+hammer"`` for the ECC-aware one), ``"legacy"``
        on hand-built tables that predate the pipeline.  Stacks differing
        only in parameters share a name — pass ``install_tables(...,
        stack=)`` an explicit one to keep both installed."""
        if not self.policy_stack:
            return "legacy"
        return "+".join(d.split("(", 1)[0] for d in self.policy_stack)

    def select(self, modules) -> "FleetTables":
        idx = [self.modules.index(m) for m in modules]
        row = lambda a: None if a is None else a[idx]
        return FleetTables(
            tuple(self.modules[i] for i in idx),
            tuple(self.vendors[i] for i in idx),
            self.cand_v, self.timings[idx], self.valid[idx],
            self.lat_feat[idx], self.hammer_margin[idx],
            self.hammer_window_ms,
            tuple(self.device_models[i] for i in idx),
            correctable=row(self.correctable),
            detectable=row(self.detectable),
            silent=row(self.silent),
            policy_stack=self.policy_stack)

    def with_device_models(self, models) -> "FleetTables":
        """A copy assigning device models per DIMM: ``models`` is a
        ``{module: name}`` mapping (unlisted DIMMs keep their model) or a
        full [D] sequence of registered model names."""
        if isinstance(models, dict):
            assigned = tuple(models.get(m, cur) for m, cur
                             in zip(self.modules, self.device_models))
        else:
            assigned = tuple(models)
        for name in assigned:
            power_lib.get(name)          # fail fast on unknown models
        return dataclasses.replace(self, device_models=assigned)


# --------------------------------------------------------------------------
# The reliability-policy pipeline (candidate admission, composable)
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class PolicyContext:
    """Read-only characterization scope every policy sees: the grid, the
    candidate grid, and the build knobs (latency step/ceiling, operating
    temperature, dispatch plumbing) shared by the whole stack."""

    grid: DimmGrid
    cand_v: np.ndarray
    step: float
    max_latency: float
    temp_c: float
    mesh: object
    dispatch: str


@dataclasses.dataclass
class PolicyState:
    """Mutable admission state threaded through the pipeline.

    ``timings`` [D, K, 3] / ``valid`` [D, K] carry the usual NaN-exclusion
    semantics (NaN timings exactly where ``valid`` is False); ``margins``
    maps policy names to named [D, K] margin rows; the three reliability
    rows are filled by an ECC policy (None otherwise).
    """

    timings: np.ndarray | None = None
    valid: np.ndarray | None = None
    margins: dict = dataclasses.field(default_factory=dict)
    correctable: np.ndarray | None = None
    detectable: np.ndarray | None = None
    silent: np.ndarray | None = None


class ReliabilityPolicy:
    """One stage of the candidate-admission pipeline.

    ``apply`` maps characterization outputs to an updated per-(DIMM,
    candidate) validity mask + named margin rows, composing with the
    NaN-exclusion semantics: a policy may *restrict* (clear ``valid``
    bits — the timings are re-NaN'd once after the stack) or *widen*
    (set bits, in which case it must fill finite ``timings`` rows for the
    candidates it admits).  ``descriptor`` renders the policy's identity
    (name + parameters) for the table's ``policy_stack``.
    """

    name = "?"

    def apply(self, ctx: PolicyContext, state: PolicyState) -> PolicyState:
        raise NotImplementedError

    def descriptor(self, ctx: PolicyContext) -> str:
        return self.name


@dataclasses.dataclass(frozen=True)
class MinLatencyFloor(ReliabilityPolicy):
    """The error-free latency floor (built-in; must open the pipeline).

    For each (DIMM, candidate), ``find_min_latency_batch`` yields the
    smallest error-free platform-quantized (tRCD, tRP) <= the context's
    ``max_latency`` — NaN (candidate excluded) where no latency recovers
    correct operation (or the candidate sits below the vendor recovery /
    signal-integrity floors).  tRAS keeps the circuit-model value per
    candidate (footnote 8: Test 1 overlaps tRAS with the column reads).
    """

    name = "min_latency"

    def apply(self, ctx: PolicyContext, state: PolicyState) -> PolicyState:
        minlat = engine_test1.find_min_latency_batch(
            ctx.grid, ctx.cand_v, step=ctx.step, max_latency=ctx.max_latency,
            temp_c=ctx.temp_c, mesh=ctx.mesh,
            dispatch=ctx.dispatch)                        # [D, K, 2]
        valid = np.isfinite(minlat).all(axis=-1)          # [D, K]
        t_ras = circuit.timings_for_voltages(ctx.cand_v)[:, 2]     # [K]
        timings = np.concatenate(
            [minlat, np.broadcast_to(t_ras, valid.shape)[..., None]],
            axis=-1)
        state.timings = np.where(valid[..., None], timings, np.nan)
        state.valid = valid
        return state

    def descriptor(self, ctx: PolicyContext) -> str:
        return (f"min_latency(max_latency={ctx.max_latency},"
                f"temp_c={ctx.temp_c})")


@dataclasses.dataclass(frozen=True)
class HammerFloor(ReliabilityPolicy):
    """The disturbance floor (built-in).

    A surviving candidate's worst-cell hammer threshold
    (``errors.hammer_threshold`` at the candidate voltage — non-decreasing
    in voltage) must exceed the refresh-window exposure
    (``errors.hammer_exposure`` over ``window_ms`` at the candidate's own
    table timings).  A candidate whose margin (threshold / exposure) drops
    below 1 is excluded with the same NaN semantics as the min-latency
    floor; the margin itself lands in ``margins["hammer"]`` (NaN where a
    prior policy had already excluded the candidate).  ``scale`` — an
    optional ``{module: factor}`` threshold multiplier — is the
    failure-injection knob for degraded parts.
    """

    window_ms: float = errors.HAMMER_WINDOW_MS
    scale: dict | None = None
    name = "hammer"

    def apply(self, ctx: PolicyContext, state: PolicyState) -> PolicyState:
        grid = ctx.grid
        field_max = grid.susceptibility.reshape(grid.n_dimms, -1).max(axis=1)
        threshold = errors.hammer_threshold(field_max[:, None],
                                            ctx.cand_v[None, :])   # [D, K]
        if self.scale is not None:
            s = np.array([float(self.scale.get(m, 1.0))
                          for m in grid.modules], np.float64)
            threshold = threshold * s[:, None]
        with np.errstate(invalid="ignore"):
            exposure = errors.hammer_exposure(
                state.timings[..., 2], state.timings[..., 1], self.window_ms)
            margin = threshold / exposure                 # NaN where invalid
            state.valid = state.valid & (margin >= 1.0)   # NaN compares False
        state.margins["hammer"] = margin
        return state

    def descriptor(self, ctx: PolicyContext) -> str:
        parts = [f"window_ms={self.window_ms}"]
        if self.scale:
            inner = ",".join(f"{k}:{float(f)}"
                             for k, f in sorted(self.scale.items()))
            parts.append("scale={" + inner + "}")
        return "hammer(" + ",".join(parts) + ")"


@dataclasses.dataclass(frozen=True)
class EccAdmission(ReliabilityPolicy):
    """ECC-aware admission (the widening policy).

    A candidate the min-latency floor excluded is re-admitted — at
    ``probe_latency`` (tRCD, tRP) — if the chosen ECC profile handles its
    residual beat-error distribution (Fig. 9, evaluated at the context's
    operating temperature through ``population.beat_error_batch``, one
    dispatched D x K call): either the profile fully corrects at least
    ``sufficiency`` of erroneous beats (the Section 4.4 criterion —
    ``errors.SECDED_SUFFICIENCY_THRESHOLD`` by default), or the
    post-correction rates fit the transparency budget (silent rate <=
    ``max_silent`` AND detected+silent <= ``max_residual``).  The vendor
    recovery and signal-integrity floors stay binding — ECC corrects beat
    errors, it cannot revive a DIMM that stops responding or a channel
    corrupting transfers wholesale — so the widening is exactly the
    candidates excluded for lacking an *error-free* latency within the
    ceiling (e.g. the at-speed fleet: tables built at ``max_latency=10``
    where every candidate must run the reliable-minimum timings and ECC
    absorbs the residual).

    For every candidate the policy also records the transparency rows
    (correctable / detectable / silent beat rates at the candidate's
    evaluation timings) into the state — the per-module report
    arxiv 2204.10378 argues systems should expose.
    """

    profile: str = "secded"
    sufficiency: float = errors.SECDED_SUFFICIENCY_THRESHOLD
    max_silent: float = 1e-5
    max_residual: float = 1e-4
    probe_latency: float = 10.0
    name = "ecc"

    def apply(self, ctx: PolicyContext, state: PolicyState) -> PolicyState:
        from repro.engine import population as engine_population
        prof = errors.ecc_profile(self.profile)
        grid, cand_v = ctx.grid, ctx.cand_v
        # evaluate each candidate at its own table timings; probe timings
        # where the min-latency floor left no error-free pair
        t_rcd = np.where(state.valid, state.timings[..., 0],
                         self.probe_latency)
        t_rp = np.where(state.valid, state.timings[..., 1],
                        self.probe_latency)
        dist = engine_population.beat_error_batch(
            grid, cand_v, t_rcd, t_rp, (ctx.temp_c,), mesh=ctx.mesh,
            dispatch=ctx.dispatch)
        dist = {k: a[..., 0] for k, a in dist.items()}    # [D, K]
        correctable, detectable, silent = prof.rates(dist)
        residual = detectable + silent
        total_bad = correctable + residual
        ratio = np.where(total_bad > 0.0,
                         correctable / np.maximum(total_bad, 1e-300), 1.0)
        recovery = np.array([circuit.VENDORS[vd].recovery_floor
                             for vd in grid.vendors], np.float64)
        floors_ok = ((cand_v[None, :] >= recovery[:, None])
                     & (cand_v[None, :] >= grid.fail_floor[:, None]))
        ecc_ok = ((total_bad <= 0.0) | (ratio >= self.sufficiency)
                  | ((silent <= self.max_silent)
                     & (residual <= self.max_residual)))
        admitted = floors_ok & ecc_ok & ~state.valid
        if admitted.any():
            t_ras = circuit.timings_for_voltages(cand_v)[:, 2]     # [K]
            probe = np.stack(
                [np.full(admitted.shape, self.probe_latency),
                 np.full(admitted.shape, self.probe_latency),
                 np.broadcast_to(t_ras, admitted.shape)], axis=-1)
            state.timings = np.where(admitted[..., None], probe,
                                     state.timings)
            state.valid = state.valid | admitted
        state.correctable = correctable
        state.detectable = detectable
        state.silent = silent
        return state

    def descriptor(self, ctx: PolicyContext) -> str:
        return (f"ecc(profile={self.profile},sufficiency={self.sufficiency},"
                f"max_silent={self.max_silent},"
                f"max_residual={self.max_residual},"
                f"probe={self.probe_latency})")


def legacy_policies(*, hammer_window_ms: float = errors.HAMMER_WINDOW_MS,
                    hammer_scale=None) -> tuple:
    """The pre-pipeline ``build_tables`` admission, as a policy stack —
    bit-exact against the historical two-floor construction."""
    return (MinLatencyFloor(), HammerFloor(float(hammer_window_ms),
                                           hammer_scale))


def ecc_policies(*, profile: str = "secded",
                 sufficiency: float = errors.SECDED_SUFFICIENCY_THRESHOLD,
                 max_silent: float = 1e-5, max_residual: float = 1e-4,
                 probe_latency: float = 10.0,
                 hammer_window_ms: float = errors.HAMMER_WINDOW_MS,
                 hammer_scale=None) -> tuple:
    """The ECC-aware stack: ECC admission between the two legacy floors,
    so the disturbance floor also screens the candidates ECC re-admits."""
    return (MinLatencyFloor(),
            EccAdmission(profile, float(sufficiency), float(max_silent),
                         float(max_residual), float(probe_latency)),
            HammerFloor(float(hammer_window_ms), hammer_scale))


@dispatch_lib.span("tables")
def build_tables(grid: DimmGrid, cand_v, *, step: float = 2.5,
                 max_latency: float = 20.0, temp_c: float = 20.0,
                 mesh=None, dispatch: str = "auto",
                 hammer_window_ms: float = errors.HAMMER_WINDOW_MS,
                 hammer_scale=None, device_models=None,
                 policies=None) -> FleetTables:
    """Derive every DIMM's safe candidate table through the
    reliability-policy pipeline.

    ``cand_v`` must be ascending with the nominal fallback last.
    ``policies`` is an ordered ``ReliabilityPolicy`` sequence opening with
    :class:`MinLatencyFloor` (it establishes the timings/validity state the
    later policies restrict or widen); None means the legacy two-floor
    stack (:func:`legacy_policies` — min-latency + hammer, bit-exact
    against the pre-pipeline construction), in which case
    ``hammer_window_ms`` / ``hammer_scale`` parameterize its
    :class:`HammerFloor` exactly as before.  :func:`ecc_policies` builds
    the ECC-aware stack.  Raising ``max_latency`` can only keep or extend
    each DIMM's valid set, so the per-DIMM safe floor (``safe_vmin``) is
    non-increasing in it.

    After the stack runs, the fallback (last) candidate must be valid on
    every DIMM — the controller needs somewhere safe to land — and the
    timings are NaN'd exactly where the final mask excludes.

    ``device_models``: optional ``{module: name}`` / [D] sequence of
    :mod:`repro.power` model names assigning a power model per DIMM (the
    heterogeneous-fleet column; default ``ddr3l`` everywhere).
    """
    cand_v = np.atleast_1d(np.asarray(cand_v, np.float64))
    if cand_v.size < 2 or not (np.diff(cand_v) > 0).all():
        raise ValueError("cand_v must be >= 2 ascending voltages "
                         "(fallback last)")
    if policies is None:
        policies = legacy_policies(hammer_window_ms=hammer_window_ms,
                                   hammer_scale=hammer_scale)
    policies = tuple(policies)
    if not policies or not isinstance(policies[0], MinLatencyFloor):
        raise ValueError("the policy pipeline must open with "
                         "MinLatencyFloor; got "
                         f"{[p.name for p in policies]}")
    ctx = PolicyContext(grid, cand_v, float(step), float(max_latency),
                        float(temp_c), mesh, dispatch)
    state = PolicyState()
    for policy in policies:
        with dispatch_lib.span("tables." + type(policy).__name__):
            state = policy.apply(ctx, state)
    valid = state.valid
    if not valid[:, -1].all():
        bad = [m for m, ok in zip(grid.modules, valid[:, -1]) if not ok]
        stack = "+".join(p.name for p in policies)
        raise ValueError(
            f"fallback candidate {cand_v[-1]} V is unsafe under the "
            f"{stack} stack (no error-free latency <= {max_latency} ns, or "
            f"hammer threshold under the refresh window) for {bad}; the "
            "controller needs a valid fallback on every DIMM")
    timings = np.where(valid[..., None], state.timings, np.nan)
    lat_feat = timings[:, :-1, 1] + timings[:, :-1, 2]    # [D, K-1]
    hammer_margin = state.margins.get("hammer")
    if hammer_margin is None:
        hammer_margin = np.full(valid.shape, np.nan)
    window = next((p.window_ms for p in policies
                   if isinstance(p, HammerFloor)), float(hammer_window_ms))
    # reliability rows keep the NaN-exclusion convention: rates only for
    # candidates the final mask admits (an excluded candidate's rates at
    # its NaN timings would be meaningless in the transparency report)
    rel = lambda a: None if a is None else np.where(valid, a, np.nan)
    tables = FleetTables(grid.modules, grid.vendors, cand_v, timings, valid,
                         lat_feat, hammer_margin, float(window),
                         correctable=rel(state.correctable),
                         detectable=rel(state.detectable),
                         silent=rel(state.silent),
                         policy_stack=tuple(p.descriptor(ctx)
                                            for p in policies))
    if device_models is not None:
        tables = tables.with_device_models(device_models)
    return tables


@dataclasses.dataclass(frozen=True)
class FleetBatchResult:
    """Fleet controller results, per (workload, DIMM) — the Fig. 14/17
    quantities fleet-resolved.  Every array is [W, D] unless noted."""

    names: tuple                        # [W]
    modules: tuple                      # [D]
    vendors: tuple                      # [D]
    cand_v: np.ndarray                  # [K]
    selected_voltages: np.ndarray       # [W, D, T]
    perf_loss_pct: np.ndarray
    dram_power_savings_pct: np.ndarray
    dram_energy_savings_pct: np.ndarray
    system_energy_savings_pct: np.ndarray
    perf_per_watt_gain_pct: np.ndarray
    hammer_margin: np.ndarray | None = None   # [D, K] per-candidate margin
    # per-component DRAM energy (J) summed over intervals, [W, D, NC] in
    # repro.power.COMPONENTS order — the Fig. 15-17 analogue axis; base is
    # the same lane at nominal.  None on legacy constructions.
    base_component_j: np.ndarray | None = None
    pt_component_j: np.ndarray | None = None
    device_models: tuple = ()                 # [D] power-model names
    # reliability-transparency rows from the tables ([D, K] each; None on
    # stacks without an ECC policy) and the active stack identity.
    correctable: np.ndarray | None = None
    detectable: np.ndarray | None = None
    silent: np.ndarray | None = None
    policy_stack: tuple = ()

    @property
    def n_workloads(self) -> int:
        return len(self.names)

    @property
    def n_dimms(self) -> int:
        return len(self.modules)

    def vendor_distribution(self, field: str = "dram_energy_savings_pct"
                            ) -> dict:
        """Per-vendor distribution of one [W, D] quantity over every
        (workload, DIMM) pair: vendor -> {mean, min, p50, max}."""
        a = getattr(self, field)
        out = {}
        for vendor in sorted(set(self.vendors)):
            cols = [i for i, vd in enumerate(self.vendors) if vd == vendor]
            x = a[:, cols].reshape(-1)
            out[vendor] = {"mean": float(x.mean()), "min": float(x.min()),
                           "p50": float(np.median(x)), "max": float(x.max())}
        return out

    def vendor_hammer_margin(self) -> dict:
        """Per-vendor distribution of the per-candidate disturbance margin
        (worst-cell hammer threshold / refresh-window exposure) over every
        finite (DIMM, candidate) entry — the arxiv 2204.10378
        transparent-reliability report next to the energy quantities.
        Margins < 1 mark candidates the tables excluded as hammer-unsafe.
        """
        if self.hammer_margin is None:
            raise ValueError("this result was built without hammer margins "
                             "(tables predate the disturbance floor)")
        out = {}
        for vendor in sorted(set(self.vendors)):
            rows = [i for i, vd in enumerate(self.vendors) if vd == vendor]
            x = self.hammer_margin[rows].reshape(-1)
            x = x[np.isfinite(x)]
            out[vendor] = {"mean": float(x.mean()), "min": float(x.min()),
                           "p50": float(np.median(x)), "max": float(x.max())}
        return out

    def vendor_reliability(self) -> dict:
        """Per-vendor distribution of the per-candidate
        reliability-transparency rates — the arxiv 2204.10378 report next
        to :meth:`vendor_hammer_margin`: vendor -> rate name
        (``correctable`` / ``detectable`` / ``silent``) -> {mean, min, p50,
        max} over every finite (DIMM, candidate) table entry of that
        vendor.  Rates are evaluated at each candidate's own table timings
        (probe timings where only ECC admits it), so ``silent`` bounds the
        undetected-corruption exposure of running that candidate."""
        if self.silent is None:
            raise ValueError("this result carries no reliability rows "
                             "(tables built without an ECC policy)")
        out = {}
        rows_by = {"correctable": self.correctable,
                   "detectable": self.detectable, "silent": self.silent}
        for vendor in sorted(set(self.vendors)):
            rows = [i for i, vd in enumerate(self.vendors) if vd == vendor]
            out[vendor] = {}
            for key, a in rows_by.items():
                x = np.asarray(a)[rows].reshape(-1)
                x = x[np.isfinite(x)]
                out[vendor][key] = {
                    "mean": float(x.mean()), "min": float(x.min()),
                    "p50": float(np.median(x)), "max": float(x.max())}
        return out

    def vendor_component_energy(self) -> dict:
        """Per-vendor, per-component DRAM energy — the Fig. 15-17 analogue
        fleet-resolved: vendor -> component -> {base_j, pt_j, savings_pct},
        each a mean over that vendor's (workload, DIMM) lanes.  ``base`` is
        the same lane run at nominal, so ``savings_pct`` shows which
        component (array vs periph, static vs dynamic) the reduced-voltage
        savings come from."""
        if self.pt_component_j is None:
            raise ValueError("this result carries no component breakdown "
                             "(built before the per-component power axis)")
        out = {}
        for vendor in sorted(set(self.vendors)):
            cols = [i for i, vd in enumerate(self.vendors) if vd == vendor]
            base = self.base_component_j[:, cols].reshape(-1, len(
                power_lib.COMPONENTS))                       # [W*Dv, NC]
            pt = self.pt_component_j[:, cols].reshape(-1, len(
                power_lib.COMPONENTS))
            bm, pm = base.mean(axis=0), pt.mean(axis=0)
            out[vendor] = {
                name: {"base_j": float(bm[i]), "pt_j": float(pm[i]),
                       "savings_pct": float(100.0 * (1.0 - pm[i] / bm[i]))
                       if bm[i] else 0.0}
                for i, name in enumerate(power_lib.COMPONENTS)}
        return out


@dispatch_lib.span("fleet")
def run_fleet_batched(wb: WorkloadBatch, tables: FleetTables,
                      phases: np.ndarray, coef_lo, coef_hi,
                      target_loss_pct: float, *, impl: str = "auto",
                      dispatch: str = "auto", mesh=None,
                      max_elements_resident: int | None = None
                      ) -> FleetBatchResult:
    """Run the interval controller on every (workload, DIMM) pair at once.

    The W x D cross-product flattens into one leading batch axis (lane
    ``n = w * D + d``): workload features and the [T, W] phase schedule are
    repeated per DIMM, per-DIMM candidate tables are tiled per workload,
    and the whole fleet runs as one dispatched ``lax.scan`` through
    :func:`repro.engine.controller.run_flat` (entry ``"fleet"`` — bucketed
    to ``n_devices * 2**k``, sharded over the ``("batch",)`` mesh, chunked
    past the resident budget).  ``dispatch="direct"`` keeps the exact-shape
    jit call as the parity reference.

    ``phases`` may also be [T, W*D] — one column per *lane* in the
    ``n = w * D + d`` order — for the phase-decorrelation scenario where
    every (workload, DIMM) pair sees its own schedule
    (``voltron.fleet_phase_matrix`` builds it; ``run_suite(...,
    phase_seed=voltron._lane_phase_seed(name, module, seed))`` stays the
    per-lane parity reference).
    """
    w, d = wb.n_workloads, tables.n_dimms
    with dispatch_lib.span("fleet.lower"):
        feats = {key: np.asarray(a)
                 for key, a in engine_solve._wb_feats(wb).items()}
        rep_w = lambda a: np.repeat(a, d, axis=0)      # [W,...] -> [W*D,...]
        tile_d = lambda a: np.tile(a, (w,) + (1,) * (a.ndim - 1))
        flat_feats = {key: rep_w(a) for key, a in feats.items()}
        phases = np.asarray(phases)
        if phases.shape[1] == w * d:                   # per-lane columns
            phases_flat = phases
        elif phases.shape[1] == w:                     # per-workload columns
            phases_flat = np.repeat(phases, d, axis=1)     # [T, W*D]
        else:
            raise ValueError(f"phases must be [T, {w}] (per workload) or "
                             f"[T, {w * d}] (per lane); got {phases.shape}")
        cand_t = {"t_rcd": tile_d(tables.timings[:, :, 0]),
                  "t_rp": tile_d(tables.timings[:, :, 1]),
                  "t_ras": tile_d(tables.timings[:, :, 2])}
        # heterogeneous power models: one eager [D, NCOEFF] gather, tiled
        # per workload — the coefficients are more per-lane columns in jit.
        coeff_lanes = tile_d(power_lib.coeff_rows(tables.device_models,
                                                  np.float32))
        lat_feat, cand_valid = tile_d(tables.lat_feat), tile_d(tables.valid)
    out = controller.run_flat(
        "fleet", flat_feats, phases_flat, coef_lo, coef_hi, target_loss_pct,
        tables.cand_v, lat_feat, cand_t, cand_valid,
        model_coeffs=coeff_lanes, impl=impl, dispatch=dispatch, mesh=mesh,
        max_elements_resident=max_elements_resident)
    selected = np.asarray(tables.cand_v, np.float64)[out["selected_idx"]]
    shape2 = lambda a: a.reshape(w, d)
    return FleetBatchResult(
        wb.names, tables.modules, tables.vendors, tables.cand_v,
        selected.reshape(w, d, -1),
        shape2(out["perf_loss_pct"]),
        shape2(out["dram_power_savings_pct"]),
        shape2(out["dram_energy_savings_pct"]),
        shape2(out["system_energy_savings_pct"]),
        shape2(out["perf_per_watt_gain_pct"]),
        np.asarray(tables.hammer_margin),
        base_component_j=np.asarray(out["base_component_j"]).reshape(
            w, d, -1),
        pt_component_j=np.asarray(out["pt_component_j"]).reshape(w, d, -1),
        device_models=tables.device_models,
        correctable=tables.correctable, detectable=tables.detectable,
        silent=tables.silent, policy_stack=tables.policy_stack)
