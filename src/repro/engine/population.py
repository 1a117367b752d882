"""Population-scale characterization: the paper's Secs. 4-5 sweeps, batched.

The characterization half of the paper (Figs. 4, 6, 8, 11) evaluates 31
DIMMs x voltages x temperatures x data patterns.  The scalar path walks that
grid one DIMM at a time through :mod:`repro.dram.chips` /
:mod:`repro.dram.errors` Python loops; this module runs the whole population
as struct-of-arrays JAX, the same substrate PR 1 built for the workload x
operating-point sweep:

- ``DimmGrid`` stacks the Table 7 identities and every derived per-DIMM
  parameter (latency scale, cell sigma, signal-integrity floor, spatial
  susceptibility field) into one array per field;
- ``characterize_batch`` resolves the required raw latencies up front
  through the eager circuit model (one vectorized call per vendor x
  temperature, bitwise-equal to ``DIMM.required_latency``), flattens the
  D x V x T grid into a single batch axis, and evaluates the error-onset
  (Fig. 4), min-latency (Fig. 6), spatial-probability (Fig. 8) and
  retention (Fig. 11) models in one jit-compiled float64 call;
- the flat axis is sharded over the available devices with a
  ``jax.sharding.NamedSharding`` built from :func:`repro.launch.mesh
  .make_batch_mesh` — a transparent no-op on one device, a population-scale
  fan-out on a real mesh;
- the flat axis reaches the kernel through :mod:`repro.engine.dispatch`
  (``dispatch="auto"``): padded to a canonical bucket with a lane mask so
  arbitrary (D, V, T) grids reuse warm AOT executables, or streamed in
  fixed-size chunks when the grid overflows the resident budget —
  ``dispatch="direct"`` keeps the exact-shape jit call as the dispatched
  paths' parity reference.

The original per-DIMM loop survives as ``impl="scalar"`` (the same
convention as ``system.simulate_scalar`` / voltron ``impl="scalar"``) and is
the parity reference: ``tests/test_population.py`` asserts the batched path
matches it within 1e-6 on every Fig. 4/6/8/11 quantity.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import hw
from repro.dram import chips, circuit, timing
from repro.engine import dispatch as dispatch_lib
from repro.launch import mesh as mesh_lib

FIELD_SIZE = chips.BANKS * 256          # susceptibility entries per DIMM
_BITS_PER_LINE = hw.CACHE_LINE_BYTES * 8

# The standard characterization sweep of Section 4.1 (1.35 V down to 1.00 V
# in 0.025 V steps) and the Fig. 11 retention grid.
SWEEP_VOLTAGES = np.round(np.arange(1.35, 0.99, -0.025), 4)
RETENTION_GRID_MS = (64.0, 256.0, 512.0, 1024.0, 2048.0)


@dataclasses.dataclass(frozen=True)
class DimmGrid:
    """D simulated DIMMs as one array per derived parameter (SoA).

    Everything ``characterize_batch`` needs is resolved at construction:
    identity (module/vendor/Table-7 V_min), the per-DIMM multiplicative
    latency scale, the vendor cell sigma and signal-integrity floor, and
    the [D, banks, row-groups] spatial susceptibility field.  ``dimms``
    keeps the source :class:`repro.dram.chips.DIMM` objects when the grid
    was built from the population — the scalar parity path needs them;
    synthetic grids (``from_vendor_z``) carry ``None``.
    """

    modules: tuple
    vendors: tuple
    vmin: np.ndarray             # [D] Table 7 V_min (nan for synthetic)
    latency_scale: np.ndarray    # [D] multiplicative process factor
    cell_sigma: np.ndarray       # [D]
    fail_floor: np.ndarray       # [D] signal-integrity floor (V)
    susceptibility: np.ndarray   # [D, banks, row-groups]
    dimms: tuple | None = None

    @classmethod
    def from_dimms(cls, dimms) -> "DimmGrid":
        dimms = tuple(dimms)
        return cls(
            tuple(d.module for d in dimms),
            tuple(d.vendor for d in dimms),
            np.array([d.vmin for d in dimms], np.float64),
            np.array([d.latency_scale for d in dimms], np.float64),
            np.array([d.cell_sigma for d in dimms], np.float64),
            np.array([circuit.VENDORS[d.vendor].fail_floor for d in dimms],
                     np.float64),
            np.stack([d.susceptibility for d in dimms]),
            dimms)

    @classmethod
    def from_population(cls, modules=None) -> "DimmGrid":
        """The 31 Table 7 DIMMs, optionally restricted to ``modules``."""
        pop = chips.population()
        if modules is not None:
            by_mod = {d.module: d for d in pop}
            pop = tuple(by_mod[m] for m in modules)
        return cls.from_dimms(pop)

    @classmethod
    def from_vendor_z(cls, vendor: str, zs) -> "DimmGrid":
        """Synthetic process-variation grid: one DIMM per z-score, flat
        susceptibility.  ``t_rcd_min``/``t_rp_min`` from the batch then
        reproduce ``circuit.measured_min_latency(op, v, vendor, t, z)``
        (Fig. 6 distributions); error/BER quantities need a measured V_min
        and are NaN for these grids."""
        zs = np.atleast_1d(np.asarray(zs, np.float64))
        vm = circuit.VENDORS[vendor]
        d = zs.size
        return cls(
            tuple(f"{vendor}z{i}" for i in range(d)),
            (vendor,) * d,
            np.full(d, np.nan),
            1.0 + vm.dimm_sigma * zs,
            np.full(d, chips.CELL_SIGMA[vendor]),
            np.full(d, vm.fail_floor),
            np.zeros((d, chips.BANKS, 256)),
            None)

    def select(self, modules) -> "DimmGrid":
        idx = [self.modules.index(m) for m in modules]
        return DimmGrid(
            tuple(self.modules[i] for i in idx),
            tuple(self.vendors[i] for i in idx),
            self.vmin[idx], self.latency_scale[idx], self.cell_sigma[idx],
            self.fail_floor[idx], self.susceptibility[idx],
            None if self.dimms is None
            else tuple(self.dimms[i] for i in idx))

    @property
    def n_dimms(self) -> int:
        return len(self.modules)


@dataclasses.dataclass(frozen=True)
class CharacterizationBatch:
    """Results of one D x V x T characterization sweep.

    Array axes: D DIMMs, V voltages, T temperatures, P data patterns,
    R retention times, [B, G] = (banks, row-groups).
    """

    modules: tuple
    v_grid: np.ndarray                  # [V]
    t_grid: np.ndarray                  # [T]
    patterns: tuple                     # [P]
    retention_ms: np.ndarray            # [R]
    line_error_fraction: np.ndarray     # [D, V, T]        (Fig. 4)
    ber: np.ndarray                     # [D, V, T, P]     (Appendix B)
    t_rcd_min: np.ndarray               # [D, V, T]        (Fig. 6)
    t_rp_min: np.ndarray                # [D, V, T]        (Fig. 6)
    row_error_prob: np.ndarray          # [D, V, T, B, G]  (Fig. 8)
    line_error_prob: np.ndarray         # [D, V, T, B, G]
    expected_weak_cells: np.ndarray     # [V, T, R]        (Fig. 11)

    def vmin_measured(self, t_index: int = 0) -> np.ndarray:
        """Per-DIMM V_min re-measured the paper's way: lowest grid voltage
        with zero errors (NaN when every voltage errors).  Meaningful when
        ``v_grid`` covers the standard sweep."""
        frac = self.line_error_fraction[:, :, t_index]
        ok_v = np.where(frac <= 0.0, self.v_grid[None, :], np.inf)
        vmin = ok_v.min(axis=1)
        return np.where(np.isfinite(vmin), vmin, np.nan)


# --------------------------------------------------------------------------
# Batched implementation
# --------------------------------------------------------------------------
def required_latency32(grid: DimmGrid, v, temp_c: float) -> dict:
    """float32 [D, V] mean required raw latency per op at one temperature.

    One eager vectorized circuit call per (op, vendor) — no per-DIMM loop.
    ``DIMM.required_latency`` multiplies the float32 circuit output by a
    Python-float scale, which numpy keeps in float32 — this reproduces that
    rounding, so the values are bitwise-equal to the scalar method (same
    function, same input vector).  Shared by ``characterize_batch`` and the
    batched Test 1 (``repro.engine.test1``), which both depend on the exact
    float32 threshold convention."""
    vendors = sorted(set(grid.vendors))
    sel = {vd: np.asarray([i for i, x in enumerate(grid.vendors) if x == vd])
           for vd in vendors}
    scale32 = grid.latency_scale.astype(np.float32)
    req = {}
    for op in ("rcd", "rp"):
        r32 = np.zeros((grid.n_dimms, v.size), np.float32)
        for vd in vendors:
            raw = _vendor_raw_cached(op, vd, float(temp_c), v.tobytes())
            r32[sel[vd]] = raw[None, :] * scale32[sel[vd], None]
        req[op] = r32
    return req


def _required_latency_grid(grid: DimmGrid, v, t_grid) -> dict:
    """Mean required raw latency per (DIMM, voltage, temperature), ns —
    ``required_latency32`` stacked over the temperature grid (the f64
    arrays hold exactly-representable f32 values)."""
    req = {op: np.zeros((grid.n_dimms, v.size, len(t_grid)))
           for op in ("rcd", "rp")}
    for ti, temp in enumerate(t_grid):
        r32 = required_latency32(grid, v, float(temp))
        for op in ("rcd", "rp"):
            req[op][:, :, ti] = r32[op]
    return req


@functools.lru_cache(maxsize=256)
def _vendor_raw_cached(op: str, vendor: str, temp: float,
                       v_bytes: bytes) -> np.ndarray:
    """Memoized eager circuit call (the repeated-sweep hot path re-resolves
    the same voltage grid every call; the result is pure in its inputs)."""
    v = np.frombuffer(v_bytes, np.float64)
    out = np.asarray(circuit.vendor_raw_latency(op, v, vendor, temp))
    out.flags.writeable = False
    return out


def _ndtr(x):
    """Standard normal CDF via erfc — matches ``scipy.special.ndtr`` to the
    last float64 ulp and lowers to a much faster XLA:CPU kernel than
    ``jax.scipy.special.ndtr``."""
    return 0.5 * jax.lax.erfc(-x * (1.0 / np.sqrt(2.0)))


def x_threshold32(t_prog, req, sigma) -> np.ndarray:
    """float32 cell-failure z-thresholds ``(t_prog / req - 1) / sigma``,
    rounded step for step as ``errors._x_threshold`` (float32 required
    latency, float32 arithmetic), vectorized over lanes.

    Resolved on the host, never in a kernel: a TPU's float32 division is
    not correctly rounded, and the cancellation in ``t_prog / req - 1``
    turns its last-bit error into a threshold error of ~1e-6 relative."""
    return ((np.asarray(t_prog, np.float32) / np.asarray(req, np.float32)
             - np.float32(1.0)) / np.asarray(sigma, np.float32))


def _characterize_flat_fn(req_rcd, req_rp, x_rcd, x_rp, floor, vmin, v,
                          temp, field_n, pattern_h, retention_ms, valid):
    """The flat-batch characterization kernel (float64 under x64).

    All leading axes are the flattened N = D*V*T grid (sharded);
    ``x_rcd`` / ``x_rp`` are the lanes' float32 failure thresholds at the
    programmed latencies (:func:`x_threshold32`, held in float64);
    ``field_n`` [N, FIELD_SIZE] is each element's susceptibility field,
    gathered eagerly at dispatch so the executable shape depends only on
    the flat bucket, never on the DIMM count; ``pattern_h`` [P] and
    ``retention_ms`` [R] are replicated.  ``valid`` [N] masks padded lanes
    (bucketed/chunked dispatch): every per-element reduction lands on
    zero there, so dead lanes can hold arbitrary finite copies of lane 0.
    """
    xmax = chips.CELL_XMAX
    lo, hi = _ndtr(-jnp.asarray(xmax, req_rcd.dtype)), \
        _ndtr(jnp.asarray(xmax, req_rcd.dtype))

    def trunc_phi(x):
        p = (_ndtr(jnp.clip(x, -xmax, xmax)) - lo) / (hi - lo)
        return jnp.where(x <= -xmax, 0.0, jnp.where(x >= xmax, 1.0, p))

    # -- error onset (Fig. 4) + spatial maps (Fig. 8) ----------------------
    # the float32 thresholds come rounded from the host; the CDF runs in
    # float64 exactly like chips._trunc_phi
    p_ok = jnp.ones_like(field_n)
    for x in (x_rcd, x_rp):
        p_ok = p_ok * trunc_phi(x[:, None] - field_n)
    frac = 1.0 - jnp.mean(p_ok, axis=1)
    frac = jnp.where(v < floor, jnp.maximum(frac, 0.5), frac)
    line_map = 1.0 - p_ok
    row_map = 1.0 - p_ok ** hw.LINES_PER_ROW

    # -- measured minimum latencies (Fig. 6): platform 2.5 ns grid ---------
    step = hw.PLATFORM_LATENCY_STEP
    quant = lambda r: jnp.ceil(r / step - 1e-9) * step
    tmin_rcd, tmin_rp = quant(req_rcd), quant(req_rp)

    # -- BER (Appendix B / Fig. 9 densities) -------------------------------
    deficit = jnp.clip((vmin - v) / chips.DEFICIT_RANGE_V, 0.0, 1.5)
    mean_bad_bits = (chips.BEAT_BAD_FRAC * hw.BEATS_PER_LINE
                     * (hw.BEAT_BITS
                        * (chips.P_BIT_BASE + chips.P_BIT_SLOPE * deficit)))
    jitter = 1.0 + chips.PATTERN_JITTER * jnp.sin(pattern_h[None, :]
                                                  + v[:, None] * 40)
    ber = (frac * mean_bad_bits)[:, None] / _BITS_PER_LINE * jitter

    # -- retention (Fig. 11): jnp form of chips.expected_weak_cells --------
    tfrac = jnp.clip((temp - 20.0) / 50.0, 0.0, None)
    base = chips.RET_BASE_20C * (chips.RET_BASE_70C
                                 / chips.RET_BASE_20C) ** tfrac
    kv = chips.RET_KV * (1.0 - chips.RET_KV_SHRINK * tfrac)
    t_rel = jnp.clip((retention_ms[None, :] - chips.RET_T0_MS)
                     / (chips.RET_T1_MS - chips.RET_T0_MS), 0.0, None)
    weak = (base[:, None] * t_rel ** chips.RET_GAMMA
            * (1.0 + kv * jnp.maximum(hw.VDD_NOMINAL - v, 0.0)
               / chips.DEFICIT_RANGE_V)[:, None])

    out = {"frac": frac, "ber": ber, "tmin_rcd": tmin_rcd,
           "tmin_rp": tmin_rp, "line_map": line_map, "row_map": row_map,
           "weak": weak}
    return {k: jnp.where(valid.reshape((-1,) + (1,) * (a.ndim - 1)), a, 0.0)
            for k, a in out.items()}


_characterize_flat = jax.jit(_characterize_flat_fn)


def _pad_flat(arrays: list, n_devices: int) -> tuple:
    """Pad each array's leading (flat-batch) axis up to a multiple of the
    device count by repeating the first row; returns (padded, n_pad)."""
    n = arrays[0].shape[0]
    pad = (-n) % n_devices
    if pad == 0:
        return arrays, 0
    return [np.concatenate([a, np.repeat(a[:1], pad, axis=0)])
            for a in arrays], pad


@dispatch_lib.span("characterize.lower")
def characterize_inputs(grid: DimmGrid, v, t_grid, patterns, retention_ms,
                        t_rcd: float, t_rp: float) -> tuple:
    """Eager per-lane operands of ``_characterize_flat_fn`` for the
    flattened D x V x T grid: ``(inputs, replicated)``.

    Each lane's values depend only on its own (DIMM, voltage, temperature)
    — the required latencies resolve per vendor x temperature, the
    susceptibility field is gathered per lane — never on the batch
    composition, so the serving front-end can concatenate lanes from
    different requests and stay bit-exact against the per-request path
    (``characterize_batch`` shares this exact lowering).
    """
    d_, v_, t_ = grid.n_dimms, v.size, len(t_grid)
    req = _required_latency_grid(grid, v, t_grid)

    flat = lambda a: np.ascontiguousarray(
        np.broadcast_to(a, (d_, v_, t_)).reshape(-1))
    per_d = lambda a: flat(np.asarray(a, np.float64)[:, None, None])
    field64 = grid.susceptibility.reshape(d_, FIELD_SIZE)
    d_idx = flat(np.arange(d_)[:, None, None]).astype(np.int32)
    sigma = per_d(grid.cell_sigma)
    req_rcd, req_rp = req["rcd"].reshape(-1), req["rp"].reshape(-1)
    inputs = [
        req_rcd, req_rp,
        x_threshold32(t_rcd, req_rcd, sigma).astype(np.float64),
        x_threshold32(t_rp, req_rp, sigma).astype(np.float64),
        per_d(grid.fail_floor), per_d(grid.vmin),
        flat(np.asarray(v, np.float64)[None, :, None]),
        flat(np.asarray(t_grid, np.float64)[None, None, :]),
        field64[d_idx],     # eager gather: shape depends on N alone, not D
    ]
    pattern_h = np.array([chips.pattern_phase(p) for p in patterns],
                         np.float64)
    ret = np.asarray(retention_ms, np.float64)
    return inputs, (pattern_h, ret)


def _characterize_batched(grid, v, t_grid, patterns, retention_ms,
                          t_rcd, t_rp, mesh, dispatch_mode: str = "auto",
                          max_elements_resident: int | None = None):
    d_, v_, t_ = grid.n_dimms, v.size, len(t_grid)
    inputs, replicated = characterize_inputs(grid, v, t_grid, patterns,
                                             retention_ms, t_rcd, t_rp)
    pattern_h, ret = replicated

    mesh = mesh_lib.make_batch_mesh() if mesh is None else mesh
    n_devices = int(mesh.devices.size)
    with jax.enable_x64(True):
        if dispatch_mode == "direct":
            inputs, n_pad = _pad_flat(inputs, n_devices)
            args = [jnp.asarray(a) for a in inputs]
            valid = jnp.ones((args[0].shape[0],), bool)
            if n_devices > 1:
                args = [jax.device_put(a,
                                       mesh_lib.batch_sharding(mesh, a.ndim))
                        for a in args]
                valid = jax.device_put(valid,
                                       mesh_lib.batch_sharding(mesh, 1))
            out = _characterize_flat(*args, jnp.asarray(pattern_h),
                                     jnp.asarray(ret), valid)
            out = {k: np.asarray(a, np.float64) for k, a in out.items()}
            if n_pad:
                out = {k: a[:-n_pad] for k, a in out.items()}
        else:
            cfg = None if max_elements_resident is None else \
                dispatch_lib.DispatchConfig(
                    max_elements_resident=int(max_elements_resident))
            out = dispatch_lib.dispatch_flat(
                "characterize", _characterize_flat_fn, inputs, replicated,
                mesh=mesh, element_cost=8 * FIELD_SIZE, mode=dispatch_mode,
                config=cfg)
            out = {k: np.asarray(a, np.float64) for k, a in out.items()}

    shape3 = (d_, v_, t_)
    return CharacterizationBatch(
        grid.modules, np.asarray(v, np.float64),
        np.asarray(t_grid, np.float64), tuple(patterns), ret,
        out["frac"].reshape(shape3),
        out["ber"].reshape(*shape3, len(patterns)),
        out["tmin_rcd"].reshape(shape3), out["tmin_rp"].reshape(shape3),
        out["row_map"].reshape(*shape3, chips.BANKS, -1),
        out["line_map"].reshape(*shape3, chips.BANKS, -1),
        out["weak"].reshape(*shape3, ret.size)[0])


# --------------------------------------------------------------------------
# Batched beat-error distribution (Fig. 9) — the ECC-admission substrate
# --------------------------------------------------------------------------
def _beat_error_flat_fn(x_rcd, x_rp, floor, vmin, v, field_n, valid):
    """Fig. 9 beat-error classes over the flat N = D*K*T batch (float64
    under x64): the jnp form of ``DIMM.beat_error_distribution``.

    ``x_rcd`` / ``x_rp`` are each lane's float32 failure thresholds
    (:func:`x_threshold32`) at its *own* programmed latencies — the ECC
    admission policy evaluates every candidate at its table timings (probe
    timings where the min-latency floor excluded it).  The binomial beat
    classes are closed-form powers, so parity with the scipy-pmf scalar
    reference is to float64 round-off, not bit-exact (tests assert ~1e-9
    relative).
    """
    xmax = chips.CELL_XMAX
    lo, hi = _ndtr(-jnp.asarray(xmax, x_rcd.dtype)), \
        _ndtr(jnp.asarray(xmax, x_rcd.dtype))

    def trunc_phi(x):
        p = (_ndtr(jnp.clip(x, -xmax, xmax)) - lo) / (hi - lo)
        return jnp.where(x <= -xmax, 0.0, jnp.where(x >= xmax, 1.0, p))

    p_ok = jnp.ones_like(field_n)
    for x in (x_rcd, x_rp):
        p_ok = p_ok * trunc_phi(x[:, None] - field_n)
    frac = 1.0 - jnp.mean(p_ok, axis=1)
    frac = jnp.where(v < floor, jnp.maximum(frac, 0.5), frac)

    # within a failing line, ~55% of beats are affected; bad bits in an
    # affected beat ~ Binomial(BEAT_BITS, p_bit) conditioned on >= 1 flip
    p_beat_bad = frac * chips.BEAT_BAD_FRAC
    deficit = jnp.clip((vmin - v) / chips.DEFICIT_RANGE_V, 0.0, 1.5)
    p_bit = chips.P_BIT_BASE + chips.P_BIT_SLOPE * deficit
    n = hw.BEAT_BITS
    q = 1.0 - p_bit
    p0 = q ** n
    p1 = n * p_bit * q ** (n - 1)
    p2 = (n * (n - 1) / 2.0) * p_bit ** 2 * q ** (n - 2)
    denom = jnp.maximum(1.0 - p0, 1e-12)
    one = p_beat_bad * p1 / denom
    two = p_beat_bad * p2 / denom
    many = p_beat_bad * jnp.maximum(1.0 - p0 - p1 - p2, 0.0) / denom
    out = {"zero": 1.0 - (one + two + many), "one": one, "two": two,
           "many": many}
    return {k: jnp.where(valid, a, 0.0) for k, a in out.items()}


_beat_error_flat = jax.jit(_beat_error_flat_fn)


@dispatch_lib.span("beat_error.lower")
def beat_error_inputs(grid: DimmGrid, v, t_rcd, t_rp, t_grid) -> list:
    """Eager per-lane operands of ``_beat_error_flat_fn`` for the flattened
    D x K x T grid.

    ``v`` is the [K] candidate-voltage vector; ``t_rcd`` / ``t_rp`` are
    scalars or [D, K] per-(DIMM, candidate) programmed latencies (the ECC
    policy passes each candidate's own table timings).  Lane values depend
    only on their own (DIMM, candidate, temperature) — same composability
    contract as ``characterize_inputs``.
    """
    v = np.atleast_1d(np.asarray(v, np.float64))
    d_, k_, t_ = grid.n_dimms, v.size, len(t_grid)
    req = _required_latency_grid(grid, v, t_grid)       # [D, K, T] per op
    flat = lambda a: np.ascontiguousarray(
        np.broadcast_to(a, (d_, k_, t_)).reshape(-1))
    per_d = lambda a: flat(np.asarray(a, np.float64)[:, None, None])
    per_dk = lambda a: flat(np.broadcast_to(
        np.asarray(a, np.float64), (d_, k_))[:, :, None])
    field64 = grid.susceptibility.reshape(d_, FIELD_SIZE)
    d_idx = flat(np.arange(d_)[:, None, None]).astype(np.int32)
    sigma = per_d(grid.cell_sigma)
    x = [x_threshold32(per_dk(t_prog), req[op].reshape(-1), sigma)
         .astype(np.float64) for op, t_prog in (("rcd", t_rcd), ("rp", t_rp))]
    return [
        *x, per_d(grid.fail_floor), per_d(grid.vmin),
        flat(v[None, :, None]),
        field64[d_idx],
    ]


@dispatch_lib.span("beat_error")
def beat_error_batch(grid: DimmGrid, v, t_rcd=10.0, t_rp=10.0,
                     t_grid=(20.0,), *, mesh=None, impl: str = "auto",
                     dispatch: str = "auto") -> dict:
    """Fig. 9 beat-error distribution for every (DIMM, candidate,
    temperature) at once: dict of float64 [D, K, T] arrays keyed
    ``zero`` / ``one`` / ``two`` / ``many``.

    The D x K x T grid flattens into one batch axis dispatched as entry
    ``"beat_error"`` (bucketed AOT reuse / chunked streaming, same plane
    as ``characterize_batch``); ``dispatch="direct"`` keeps the
    exact-shape jit call.  ``impl="scalar"`` walks the per-DIMM
    ``DIMM.beat_error_distribution`` loop — the parity reference the ECC
    admission tests compare against (scipy binomial pmf vs the closed-form
    powers here: equal to float64 round-off).
    """
    v = np.atleast_1d(np.asarray(v, np.float64))
    d_, k_, t_ = grid.n_dimms, v.size, len(t_grid)
    if impl == "scalar":
        if grid.dimms is None:
            raise ValueError("impl='scalar' needs a grid built from real "
                             "DIMMs")
        t_rcd_dk = np.broadcast_to(np.asarray(t_rcd, np.float64), (d_, k_))
        t_rp_dk = np.broadcast_to(np.asarray(t_rp, np.float64), (d_, k_))
        out = {key: np.zeros((d_, k_, t_))
               for key in ("zero", "one", "two", "many")}
        for di, dimm in enumerate(grid.dimms):
            for ki, vv in enumerate(v):
                for ti, temp in enumerate(t_grid):
                    dist = dimm.beat_error_distribution(
                        float(vv), float(t_rcd_dk[di, ki]),
                        float(t_rp_dk[di, ki]), float(temp))
                    for key in out:
                        out[key][di, ki, ti] = float(
                            np.atleast_1d(dist[key])[0])
        return out
    if impl not in ("auto", "batched"):
        raise ValueError(f"unknown impl {impl!r}")
    if dispatch not in ("auto", "bucketed", "chunked", "direct"):
        raise ValueError(f"unknown dispatch {dispatch!r}")
    inputs = beat_error_inputs(grid, v, t_rcd, t_rp, t_grid)
    mesh = mesh_lib.make_batch_mesh() if mesh is None else mesh
    n_devices = int(mesh.devices.size)
    with jax.enable_x64(True):
        if dispatch == "direct":
            inputs, n_pad = _pad_flat(inputs, n_devices)
            args = [jnp.asarray(a) for a in inputs]
            valid = jnp.ones((args[0].shape[0],), bool)
            if n_devices > 1:
                args = [jax.device_put(a,
                                       mesh_lib.batch_sharding(mesh, a.ndim))
                        for a in args]
                valid = jax.device_put(valid,
                                       mesh_lib.batch_sharding(mesh, 1))
            out = _beat_error_flat(*args, valid)
            out = {k: np.asarray(a, np.float64) for k, a in out.items()}
            if n_pad:
                out = {k: a[:-n_pad] for k, a in out.items()}
        else:
            out = dispatch_lib.dispatch_flat(
                "beat_error", _beat_error_flat_fn, inputs, (),
                mesh=mesh, element_cost=8 * FIELD_SIZE, mode=dispatch)
            out = {k: np.asarray(a, np.float64) for k, a in out.items()}
    return {k: a.reshape(d_, k_, t_) for k, a in out.items()}


# --------------------------------------------------------------------------
# Scalar reference implementation (the original per-DIMM Python loop)
# --------------------------------------------------------------------------
def _characterize_scalar(grid, v, t_grid, patterns, retention_ms,
                         t_rcd, t_rp):
    from repro.dram import errors
    if grid.dimms is None:
        raise ValueError("impl='scalar' needs a grid built from real DIMMs "
                         "(DimmGrid.from_population / from_dimms)")
    d_, v_, t_ = grid.n_dimms, v.size, len(t_grid)
    ret = np.asarray(retention_ms, np.float64)
    frac = np.zeros((d_, v_, t_))
    ber = np.zeros((d_, v_, t_, len(patterns)))
    tmin = {op: np.zeros((d_, v_, t_)) for op in ("rcd", "rp")}
    row_map = np.zeros((d_, v_, t_, chips.BANKS, 256))
    line_map = np.zeros_like(row_map)
    weak = np.zeros((v_, t_, ret.size))
    for di, d in enumerate(grid.dimms):
        for ti, temp in enumerate(t_grid):
            temp = float(temp)
            frac[di, :, ti] = d.line_error_fraction(v, t_rcd, t_rp, temp)
            for op in ("rcd", "rp"):
                tmin[op][di, :, ti] = timing.platform_quantize(
                    d.required_latency(op, v, temp))
            for pi, p in enumerate(patterns):
                ber[di, :, ti, pi] = d.bit_error_rate(v, t_rcd, t_rp, temp, p)
            for vi, vv in enumerate(v):
                row_map[di, vi, ti] = errors.error_probability_map(
                    d, float(vv), t_rcd, t_rp, temp)
                line_map[di, vi, ti] = errors.row_line_probs(
                    d, float(vv), t_rcd, t_rp, temp)
    for ti, temp in enumerate(t_grid):
        for vi, vv in enumerate(v):
            weak[vi, ti] = chips.expected_weak_cells(ret, float(temp),
                                                     float(vv))
    return CharacterizationBatch(
        grid.modules, np.asarray(v, np.float64),
        np.asarray(t_grid, np.float64), tuple(patterns), ret, frac, ber,
        tmin["rcd"], tmin["rp"], row_map, line_map, weak)


@dispatch_lib.span("characterize")
def characterize_batch(grid: DimmGrid, v_grid, t_grid=(20.0,),
                       patterns=("0xaa",),
                       retention_ms=RETENTION_GRID_MS,
                       t_rcd: float = 10.0, t_rp: float = 10.0,
                       mesh=None, impl: str = "auto", dispatch: str = "auto",
                       max_elements_resident: int | None = None,
                       ) -> CharacterizationBatch:
    """Characterize every (DIMM, voltage, temperature) of the grid at once.

    The D x V x T grid flattens into one batch axis evaluated by a single
    jit-compiled float64 call, sharded over ``mesh`` (default: a 1-D mesh
    over all available devices — a no-op on one device).  ``impl="scalar"``
    runs the original per-DIMM chips/errors Python loop instead (parity
    reference and benchmark baseline).

    ``dispatch`` picks how the flat axis reaches the kernel: "auto" routes
    through :mod:`repro.engine.dispatch` (bucketed padding + AOT executable
    cache, chunked when the grid overflows the resident budget);
    "bucketed"/"chunked" force one dispatched path; "direct" keeps the
    exact-shape single jit call (one retrace per new grid shape — the
    dispatched paths' parity reference).
    """
    v = np.atleast_1d(np.asarray(v_grid, np.float64))
    if impl == "auto":
        impl = "batched"
    if impl == "scalar":
        return _characterize_scalar(grid, v, t_grid, patterns, retention_ms,
                                    t_rcd, t_rp)
    if impl != "batched":
        raise ValueError(f"unknown impl {impl!r}")
    if dispatch not in ("auto", "bucketed", "chunked", "direct"):
        raise ValueError(f"unknown dispatch {dispatch!r}")
    return _characterize_batched(grid, v, t_grid, patterns, retention_ms,
                                 t_rcd, t_rp, mesh, dispatch,
                                 max_elements_resident)
