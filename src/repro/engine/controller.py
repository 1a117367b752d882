"""Voltron's interval loop as a single ``lax.scan``, batched over workloads.

The scalar controller (`repro.core.voltron.run_controller`) walks 25
profiling intervals per workload in Python, simulating the baseline and the
chosen operating point at every step.  Here the whole suite runs as one
scan: the carried state is each workload's currently-selected candidate
index (plus the running baseline/point accumulators), the scanned axis is
the interval, and every per-interval simulation is a batched fixed-point
solve over all W workloads at once.  Candidate timings are resolved up
front into a *per-element* [N, K] table (9 Algorithm-1 candidates + the
1.35 V fallback) so voltage selection is a gather, and Algorithm 1 itself
is an ``argmax`` over the piecewise-linear loss predictions masked by each
element's candidate-validity row.

Per-element tables are what lets the fleet layer (:mod:`repro.engine
.fleet`) run the W workloads x D DIMMs cross-product through this same
scan: each flat lane carries its own DIMM's characterization-derived safe
(tRCD, tRP, tRAS) table and exclusion mask, while the plain suite
(``run_batched``) broadcasts one shared grid over its W lanes.  The
dispatched path routes the flat axis through
:func:`repro.engine.dispatch.dispatch_flat`, so buckets are
``n_devices * 2**k`` (mesh-divisible by construction) and any suite or
fleet size reuses a warm AOT executable.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import power as power_lib
from repro.engine import dispatch as dispatch_lib
from repro.engine import solve as engine_solve
from repro.engine.batch import WorkloadBatch
from repro.kernels.sweep_solve import ops as sweep_ops
from repro.memsim.workloads import MEM_INTENSIVE_MPKI

# fixed leading-axis order of the flat controller kernel's batched operands
_FEAT_KEYS = ("mpki", "ipc_base", "mlp", "row_hit", "eff_banks",
              "write_mult", "alone_row_hit", "alone_eff_banks",
              "alone_write_mult")


@dataclasses.dataclass(frozen=True)
class ControllerBatchResult:
    names: tuple
    selected_voltages: np.ndarray      # [W, T]
    perf_loss_pct: np.ndarray          # [W]
    dram_power_savings_pct: np.ndarray
    dram_energy_savings_pct: np.ndarray
    system_energy_savings_pct: np.ndarray
    perf_per_watt_gain_pct: np.ndarray
    # per-component DRAM energy summed over intervals, [W, NC] in
    # repro.power.COMPONENTS order (None on legacy constructions)
    base_component_j: np.ndarray | None = None
    pt_component_j: np.ndarray | None = None


def _predict(coef_lo, coef_hi, lat, mpki, stall):
    """Piecewise-linear Eq. 1 (jnp form of PiecewiseLinearModel.predict)."""
    lat, mpki, stall = jnp.broadcast_arrays(lat, mpki, stall)
    x = jnp.stack([jnp.ones_like(lat), lat, mpki, stall], axis=-1)
    lo = x @ coef_lo
    hi = x @ coef_hi
    return jnp.where(mpki < MEM_INTENSIVE_MPKI, lo, hi)


def _controller_scan_fn(feats, phases, coef_lo, coef_hi, target, cand_v,
                        lat_feat, cand_t, cand_valid, model_coeffs=None,
                        impl: str = "reference"):
    """The interval scan over W flat lanes.

    ``cand_t`` holds per-element [W, K] (tRCD, tRP, tRAS) candidate tables
    and ``lat_feat`` the per-element [W, K-1] Algorithm-1 latency features
    (the plain suite broadcasts one shared row; the fleet carries one row
    per (workload, DIMM) lane).  ``cand_valid`` [W, K] masks candidates a
    lane must never select (excluded fleet candidates hold NaN timings —
    a NaN prediction compares False, but the mask makes the exclusion
    explicit rather than an IEEE accident).  The fallback (last) candidate
    must be valid on every lane.

    ``model_coeffs``: optional [W, NCOEFF] per-lane device-model
    coefficient rows (:data:`repro.power.COEFF_FIELDS` order) — the
    heterogeneous-fleet column.  Baseline and point energy both use the
    lane's model (the baseline is the *same part* at nominal), and the
    per-component DRAM energy is accumulated through the scan carry.
    Selections are independent of the model: Algorithm 1 reads only the
    loss predictions, never the energy accumulators.
    """
    w, c = feats["mpki"].shape
    nominal = {k: jnp.broadcast_to(v, (w,))
               for k, v in engine_solve.NOMINAL_POINT.items()}
    gather = lambda a, idx: jnp.take_along_axis(a, idx[:, None], axis=1)[:, 0]

    def shared_solve(mpki_t, t_rcd, t_rp, t_ras):
        return sweep_ops.solve(
            mpki_t, feats["ipc_base"], feats["mlp"], feats["row_hit"],
            feats["eff_banks"], feats["write_mult"], t_rcd, t_rp, t_ras,
            nominal["transfer_ns"], nominal["peak_bw_gbps"], impl=impl)

    def metrics(out, alone, points):
        ipc = out["ipc"]
        ws = jnp.sum(ipc / alone, axis=-1)
        runtime_s = jnp.max(engine_solve.INSTR_PER_CORE
                            / (ipc * engine_solve.CPU_FREQ_HZ), axis=-1)
        pe = engine_solve._power_energy(points, out["acts_per_ns"],
                                        out["reads_per_ns"],
                                        jnp.sum(ipc, axis=-1), runtime_s,
                                        model_coeffs)
        return ws, pe

    def step(carry, f):
        v_idx, sums = carry
        mpki_t = feats["mpki"] * f[:, None]
        alone = engine_solve.alone_solve(feats, mpki=mpki_t, impl=impl)
        base = shared_solve(mpki_t, nominal["t_rcd"], nominal["t_rp"],
                            nominal["t_ras"])
        pt = shared_solve(mpki_t, gather(cand_t["t_rcd"], v_idx),
                          gather(cand_t["t_rp"], v_idx),
                          gather(cand_t["t_ras"], v_idx))
        base_ws, base_pe = metrics(base, alone, nominal)
        ones = jnp.ones((w,), jnp.float32)
        pt_points = {"v_array": cand_v[v_idx],
                     "v_periph": nominal["v_periph"], "freq_ratio": ones}
        pt_ws, pt_pe = metrics(pt, alone, pt_points)

        sums = {
            "base_ws": sums["base_ws"] + base_ws,
            "pt_ws": sums["pt_ws"] + pt_ws,
            "base_dram_e": sums["base_dram_e"] + base_pe["dram_j"],
            "pt_dram_e": sums["pt_dram_e"] + pt_pe["dram_j"],
            "base_sys_e": sums["base_sys_e"] + base_pe["system_j"],
            "pt_sys_e": sums["pt_sys_e"] + pt_pe["system_j"],
            "base_power": sums["base_power"] + base_pe["system_w"],
            "pt_power": sums["pt_power"] + pt_pe["system_w"],
            "base_dram_p": sums["base_dram_p"] + base_pe["dram_w"],
            "pt_dram_p": sums["pt_dram_p"] + pt_pe["dram_w"],
            "base_comp_e": sums["base_comp_e"] + base_pe["dram_comp_j"],
            "pt_comp_e": sums["pt_comp_e"] + pt_pe["dram_comp_j"],
        }

        # profile under the current operating point, then Algorithm 1:
        # smallest *valid* candidate (ascending voltage) within the loss
        # target, falling back to nominal when none qualifies.
        mean_mpki = jnp.mean(mpki_t, axis=-1)
        mean_stall = jnp.mean(pt["stall_frac"], axis=-1)
        preds = _predict(coef_lo, coef_hi, lat_feat,
                         mean_mpki[:, None], mean_stall[:, None])   # [W, K-1]
        ok = (preds <= target) & cand_valid[:, :-1]
        new_idx = jnp.where(ok.any(axis=-1),
                            jnp.argmax(ok, axis=-1),
                            jnp.full((w,), cand_v.shape[0] - 1))
        new_idx = new_idx.astype(jnp.int32)
        return (new_idx, sums), new_idx

    zeros = jnp.zeros((w,), jnp.float32)
    init_sums = {k: zeros for k in
                 ("base_ws", "pt_ws", "base_dram_e", "pt_dram_e",
                  "base_sys_e", "pt_sys_e", "base_power", "pt_power",
                  "base_dram_p", "pt_dram_p")}
    nc = len(power_lib.COMPONENTS)
    init_sums["base_comp_e"] = jnp.zeros((w, nc), jnp.float32)
    init_sums["pt_comp_e"] = jnp.zeros((w, nc), jnp.float32)
    init_idx = jnp.full((w,), cand_v.shape[0] - 1, jnp.int32)   # start at nom
    (_, s), chosen = jax.lax.scan(step, (init_idx, init_sums), phases)

    return {
        "selected_idx": chosen.T,                               # [W, T]
        "base_component_j": s["base_comp_e"],                   # [W, NC]
        "pt_component_j": s["pt_comp_e"],
        "perf_loss_pct": 100.0 * (1.0 - s["pt_ws"] / s["base_ws"]),
        "dram_power_savings_pct":
            100.0 * (1.0 - s["pt_dram_p"] / s["base_dram_p"]),
        "dram_energy_savings_pct":
            100.0 * (1.0 - s["pt_dram_e"] / s["base_dram_e"]),
        "system_energy_savings_pct":
            100.0 * (1.0 - s["pt_sys_e"] / s["base_sys_e"]),
        "perf_per_watt_gain_pct":
            100.0 * ((s["pt_ws"] / s["pt_power"])
                     / (s["base_ws"] / s["base_power"]) - 1.0),
    }


_controller_scan = jax.jit(_controller_scan_fn, static_argnames=("impl",))


def _controller_flat_fn(*args, impl: str):
    """``_controller_scan_fn`` in :func:`repro.engine.dispatch.dispatch_flat`
    form: every batched operand leads with the flat W (or W x D) axis —
    the [T, W] phase schedule rides transposed as [W, T] — followed by the
    replicated operands and the dispatch lane mask.  The scan reduces only
    over the core/interval axes, never across lanes, so padded lanes are
    dead copies sliced off by the dispatcher (no mask needed — the same
    contract as ``solve._grid_sim_fn``)."""
    (mpki, ipc_base, mlp, row_hit, eff_banks, write_mult, alone_row_hit,
     alone_eff_banks, alone_write_mult, phases_nt, lat_feat, t_rcd, t_rp,
     t_ras, cand_valid, model_coeffs, coef_lo, coef_hi, target, cand_v,
     _valid) = args
    feats = dict(zip(_FEAT_KEYS, (mpki, ipc_base, mlp, row_hit, eff_banks,
                                  write_mult, alone_row_hit, alone_eff_banks,
                                  alone_write_mult)))
    cand_t = {"t_rcd": t_rcd, "t_rp": t_rp, "t_ras": t_ras}
    return _controller_scan_fn(feats, phases_nt.T, coef_lo, coef_hi, target,
                               cand_v, lat_feat, cand_t, cand_valid,
                               model_coeffs, impl=impl)


def element_cost(n_intervals: int) -> int:
    """Per-lane dispatch footprint of the interval scan, in element-cost
    units — shared by ``run_flat`` and the serving front-end so admission
    accounting matches what dispatch actually charges."""
    return 16 * max(1, int(n_intervals))


def flat_operands(feats: dict, phases, coef_lo, coef_hi, target_loss_pct,
                  cand_v, lat_feat, cand_t: dict, cand_valid,
                  model_coeffs=None) -> tuple:
    """Lower interval-scan operands to ``dispatch_flat`` form.

    Returns ``(batched, replicated)`` exactly as ``run_flat`` passes them:
    batched = the 9 ``_FEAT_KEYS`` float32 feature arrays, the [N, T]
    transposed phase schedule, latency features, the three candidate-timing
    tables, the validity mask and the [N, NCOEFF] device-model coefficient
    rows; replicated = (coef_lo, coef_hi, target, cand_v) float32.  The
    serving front-end concatenates these per-lane arrays across requests,
    so the float32 conversions must happen here — once, identically — for
    coalesced lanes to stay bit-exact against the per-request path.

    ``model_coeffs``: per-lane [N, NCOEFF] rows (or a single model /
    name / None — broadcast to every lane).  The coefficient operand is
    *always* appended, defaulting to the ``ddr3l`` row, so the operand
    count (and hence every warm executable and megabatch concatenation)
    is the same for homogeneous and heterogeneous batches."""
    f32 = lambda x: np.asarray(x, np.float32)
    feats = {k: f32(feats[k]) for k in _FEAT_KEYS}
    n = feats["mpki"].shape[0]
    phases = f32(phases)
    cand_t = {k: f32(cand_t[k]) for k in ("t_rcd", "t_rp", "t_ras")}
    if model_coeffs is None or isinstance(model_coeffs,
                                          (str, power_lib.DeviceModel)):
        row = power_lib.coeff_rows(
            [model_coeffs if model_coeffs is not None else "ddr3l"],
            np.float32)
        coeff_rows = np.broadcast_to(row, (n, row.shape[1]))
    else:
        coeff_rows = f32(model_coeffs)
    batched = [feats[k] for k in _FEAT_KEYS] + [
        np.ascontiguousarray(phases.T), f32(lat_feat), cand_t["t_rcd"],
        cand_t["t_rp"], cand_t["t_ras"], np.asarray(cand_valid, bool),
        np.ascontiguousarray(coeff_rows)]
    replicated = (f32(coef_lo), f32(coef_hi), np.float32(target_loss_pct),
                  f32(cand_v))
    return batched, replicated


def run_flat(entry: str, feats: dict, phases, coef_lo, coef_hi,
             target_loss_pct, cand_v, lat_feat, cand_t: dict, cand_valid,
             *, impl: str = "auto", dispatch: str = "auto", mesh=None,
             max_elements_resident: int | None = None,
             model_coeffs=None) -> dict:
    """Run the interval scan over N flat lanes with per-element tables.

    ``feats``: dict of [N, C]/[N] workload features (``_wb_feats`` order);
    ``phases``: [T, N] — one column *per lane*, so callers control phase
    correlation across lanes: the plain fleet repeats each workload's
    schedule over its D lanes, while the phase-decorrelation scenario
    (``voltron.fleet_phase_matrix`` / ``run_fleet(decorrelate_phases=)``)
    passes a distinct per-(workload, DIMM) column for every lane;
    ``cand_t``: dict of [N, K] candidate timings;
    ``lat_feat``: [N, K-1]; ``cand_valid``: [N, K] bool.  ``entry`` names
    the dispatch-stats bucket ("controller_scan" for the plain suite,
    "fleet" for the W x D cross-product).  Returns the raw output dict
    (``selected_idx`` int [N, T], float64 metric arrays [N]).

    ``dispatch="auto"``/"bucketed"/"chunked" route the flat axis through
    :func:`repro.engine.dispatch.dispatch_flat` — padded to an
    ``n_devices * 2**k`` bucket (mesh-divisible by construction, sharded
    over the ``("batch",)`` mesh) with warm AOT executable reuse, or
    streamed in fixed-size chunks past the resident budget;  "direct"
    keeps the exact-shape jit call as the parity reference.
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "reference"
    with dispatch_lib.span(entry + ".lower"):
        batched, replicated = flat_operands(
            feats, phases, coef_lo, coef_hi, target_loss_pct, cand_v,
            lat_feat, cand_t, cand_valid, model_coeffs)
    coef_lo, coef_hi, target, cand_v = replicated
    n_intervals = batched[9].shape[1]

    if dispatch == "direct":
        out = _controller_scan(
            dict(zip(_FEAT_KEYS, (jnp.asarray(a) for a in batched[:9]))),
            jnp.asarray(batched[9].T), coef_lo, coef_hi, target, cand_v,
            jnp.asarray(batched[10]),
            {"t_rcd": jnp.asarray(batched[11]),
             "t_rp": jnp.asarray(batched[12]),
             "t_ras": jnp.asarray(batched[13])},
            jnp.asarray(batched[14]), jnp.asarray(batched[15]), impl=impl)
    elif dispatch in ("auto", "bucketed", "chunked"):
        cfg = None if max_elements_resident is None else \
            dispatch_lib.DispatchConfig(
                max_elements_resident=int(max_elements_resident))
        out = dispatch_lib.dispatch_flat(
            entry, functools.partial(_controller_flat_fn, impl=impl),
            batched, replicated, statics_key=(impl,), mesh=mesh,
            mode=dispatch, element_cost=element_cost(n_intervals),
            config=cfg)
    else:
        raise ValueError(f"unknown dispatch {dispatch!r}")
    out = {k: np.asarray(v) for k, v in out.items()}
    return {k: (a if k == "selected_idx" else a.astype(np.float64))
            for k, a in out.items()}


@dispatch_lib.span("controller_scan")
def run_batched(wb: WorkloadBatch, phases: np.ndarray, coef_lo, coef_hi,
                target_loss_pct: float, cand_v: np.ndarray,
                lat_feat: np.ndarray, cand_timings: np.ndarray,
                impl: str = "auto",
                dispatch: str = "auto",
                cand_valid: np.ndarray | None = None,
                mesh=None, device_model=None) -> ControllerBatchResult:
    """Run the interval loop for all W workloads in one scan.

    ``phases``: [T, W] per-interval memory-intensity factors.
    ``cand_v``: [K] candidate voltages, ascending, last entry = fallback.
    ``lat_feat``: [K-1] (or per-workload [W, K-1]) Algorithm-1 latency
    features of the candidates.
    ``cand_timings``: [K, 3] (or per-workload [W, K, 3]) resolved
    (tRCD, tRP, tRAS) per candidate.
    ``cand_valid``: optional [K] / [W, K] bool — candidates a workload may
    select (default: all; the fleet layer uses this to exclude voltages a
    DIMM cannot run error-free).
    ``dispatch``: "auto" buckets the workload axis through
    :mod:`repro.engine.dispatch` (mesh-divisible buckets, sharded flat
    axis); "direct" keeps the exact-shape jit call (the bucketed path's
    parity reference).
    ``device_model``: optional device model (name /
    :class:`repro.power.DeviceModel`) applied to every workload lane —
    single-model runs; per-lane mixes go through the fleet layer.
    """
    w = wb.n_workloads
    cand_v64 = np.atleast_1d(np.asarray(cand_v, np.float64))
    k = cand_v64.size
    timings = np.asarray(cand_timings, np.float64)
    if timings.ndim == 2:
        timings = np.broadcast_to(timings[None], (w, k, 3))
    lat = np.asarray(lat_feat, np.float64)
    if lat.ndim == 1:
        lat = np.broadcast_to(lat[None], (w, k - 1))
    valid = (np.ones((w, k), bool) if cand_valid is None
             else np.broadcast_to(np.asarray(cand_valid, bool), (w, k)))
    cand_t = {"t_rcd": timings[..., 0], "t_rp": timings[..., 1],
              "t_ras": timings[..., 2]}
    feats = {key: np.asarray(a)
             for key, a in engine_solve._wb_feats(wb).items()}
    out = run_flat("controller_scan", feats, np.asarray(phases), coef_lo,
                   coef_hi, target_loss_pct, cand_v64, lat, cand_t, valid,
                   impl=impl, dispatch=dispatch, mesh=mesh,
                   model_coeffs=device_model)
    # map indices back to the exact float64 candidate voltages so the
    # selections compare bit-equal against the scalar controller
    selected = cand_v64[out["selected_idx"]]
    return ControllerBatchResult(wb.names, selected,
                                 out["perf_loss_pct"],
                                 out["dram_power_savings_pct"],
                                 out["dram_energy_savings_pct"],
                                 out["system_energy_savings_pct"],
                                 out["perf_per_watt_gain_pct"],
                                 base_component_j=out["base_component_j"],
                                 pt_component_j=out["pt_component_j"])
