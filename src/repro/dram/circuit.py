"""Circuit-level model of the DRAM cell array under reduced voltage.

This is the JAX re-implementation of the paper's LTspice model (Appendix C):
a 512x512 cell array with per-bitline parasitics, a latch-type sense
amplifier and a precharge equalizer.  Two layers are provided:

1. ``bitline_waveform`` — explicit integration of the bitline voltage during
   charge-sharing -> sensing/restoration -> precharge (reproduces Fig. 5).

2. ``raw_latency`` / ``table3`` — the calibrated closed-form latency model
   t_op(V).  tRCD and tRP use the alpha-power-law MOSFET delay form
   ``t = c + a*V/(V - Vth)**alpha`` (Sakurai-Newton), with constants fitted
   so that after the manufacturer guardband (x1.38) and controller-clock
   quantization (1.25 ns) the model reproduces the paper's Table 3 *exactly*
   at every voltage step.  tRAS is a two-phase operation (sensing + cell
   restoration through the access transistor); the paper's own tRAS values
   came from their SPICE simulation rather than measurement (footnote 8), and
   no single smooth delay family passes through all ten quantization bands,
   so the restoration phase is calibrated with a monotone-convex knot vector
   (also an exact Table 3 match).

Vendor and temperature behavior (Figs. 6, 10) are modeled as voltage
offsets / additive latencies on top of the base curves, calibrated to the
qualitative + quantitative observations in Sections 4.2 and 4.5.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import hw
from repro.dram import timing

# --------------------------------------------------------------------------
# Calibrated closed-form latency model (raw = pre-guardband, ns)
# --------------------------------------------------------------------------
# Fitted offline (scratch/fit_circuit5.py) against Table 3 bands:
#   raw in ((table - 1.25)/1.38, table/1.38]  at each voltage step.
ALPHA_POWER = {
    # op: (c, a1, vth1, alpha1, a2, vth2, alpha2)
    "rcd": (7.762721, 0.588379, 0.301278, 4.467100, 0.365870, 0.752361, 0.947592),
    "rp": (6.231444, 0.846517, 0.750299, 1.435793, 0.719587, 0.484328, 0.448746),
}

# Voltage grid of Table 3 (V) and the calibrated raw tRAS knots (ns).
TABLE3_VOLTAGES = np.array(
    [1.35, 1.30, 1.25, 1.20, 1.15, 1.10, 1.05, 1.00, 0.95, 0.90])
RAS_RAW_KNOTS = np.array(
    [25.64, 25.80, 26.00, 26.30, 27.00, 28.10, 29.40, 31.75, 34.60, 37.60])

# Published Table 3 (guardbanded, quantized), for validation.
TABLE3_PUBLISHED = {
    "rcd": np.array([13.75, 13.75, 13.75, 13.75, 15.00, 15.00, 16.25, 17.50, 18.75, 21.25]),
    "rp": np.array([13.75, 13.75, 15.00, 15.00, 15.00, 16.25, 17.50, 18.75, 21.25, 26.25]),
    "ras": np.array([36.25, 36.25, 36.25, 37.50, 37.50, 40.00, 41.25, 45.00, 48.75, 52.50]),
}

# Signal-integrity floor: below this supply voltage the channel itself fails
# and no latency increase recovers correct data (Section 4.2, third obs.).
SIGNAL_INTEGRITY_FLOOR = 0.90


def _on_host(fn):
    """Evaluate ``fn`` on the host CPU.  The latency model's float32 answers
    set the characterization thresholds, where ``t_prog / req - 1`` cancels:
    an accelerator's last-bit ``pow`` error would move them, so they must not
    depend on which device is attached.  Inside a jit trace it is moot."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_device(jax.local_devices(backend="cpu")[0]):
            return fn(*args, **kwargs)
    return wrapped


def _alpha_power(op: str, v):
    c, a1, vth1, al1, a2, vth2, al2 = ALPHA_POWER[op]
    v = jnp.asarray(v, jnp.float64) if jax.config.read("jax_enable_x64") else jnp.asarray(v, jnp.float32)
    t1 = a1 * v / jnp.maximum(v - vth1, 1e-4) ** al1
    t2 = a2 * v / jnp.maximum(v - vth2, 1e-4) ** al2
    return c + t1 + t2


def _ras_raw(v):
    """Monotone (in -V) interpolation of the calibrated restoration knots.

    Linear between knots; linear extrapolation outside using the edge slope.
    """
    v = jnp.asarray(v)
    # knots are in decreasing voltage order; flip for jnp.interp
    xs = jnp.asarray(TABLE3_VOLTAGES[::-1].copy())
    ys = jnp.asarray(RAS_RAW_KNOTS[::-1].copy())
    mid = jnp.interp(v, xs, ys)
    lo_slope = (ys[1] - ys[0]) / (xs[1] - xs[0])
    hi_slope = (ys[-1] - ys[-2]) / (xs[-1] - xs[-2])
    lo = ys[0] + (v - xs[0]) * lo_slope
    hi = ys[-1] + (v - xs[-1]) * hi_slope
    return jnp.where(v < xs[0], lo, jnp.where(v > xs[-1], hi, mid))


@_on_host
def raw_latency(op: str, v_array):
    """Inherent (pre-guardband) latency of one DRAM operation, in ns.

    op in {"rcd", "rp", "ras"}; ``v_array`` is the DRAM array voltage in V.
    """
    if op in ("rcd", "rp"):
        return _alpha_power(op, v_array)
    if op == "ras":
        return _ras_raw(v_array)
    raise ValueError(f"unknown op {op!r}")


def table3(v_array=None) -> dict:
    """Guardbanded, clock-quantized latencies — the paper's Table 3."""
    v = TABLE3_VOLTAGES if v_array is None else np.atleast_1d(v_array)
    out = {}
    for op in ("rcd", "rp", "ras"):
        raw = np.asarray(raw_latency(op, v))
        out[op] = timing.guardband_and_quantize(raw)
    return out


def timing_for_voltage(v_array: float) -> timing.TimingParams:
    """TimingParams for one array voltage (guardbanded + quantized)."""
    t = table3(v_array)
    return timing.TimingParams(float(t["rcd"][0]), float(t["rp"][0]),
                               float(t["ras"][0]))


def timings_for_voltages(v_array) -> np.ndarray:
    """Vectorized ``timing_for_voltage``: float64[N, 3] of (tRCD, tRP, tRAS)
    for an array of voltages — the batched engine resolves whole candidate
    grids through this in one shot instead of one scalar call per point."""
    t = table3(np.asarray(v_array, dtype=np.float64))
    return np.stack([t["rcd"], t["rp"], t["ras"]], axis=-1)


# --------------------------------------------------------------------------
# Vendor / temperature / process-variation adjustments (Figs. 6, 10)
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class VendorModel:
    """Per-vendor latency behavior under reduced voltage.

    ``rcd_headroom``/``rp_headroom``: the vendor's circuits behave like the
    base (Vendor-B SPICE-fitted, Fig. 7) curve evaluated at ``V + headroom``
    — robust vendors have positive headroom (their latencies start growing
    only at lower voltages).  Headroom is per-operation because vendors
    differ in which operation is critical (Section 4.2: Vendor C is
    precharge-limited).
    ``fail_floor``: below this voltage even >50 ns latencies do not recover
    correct data (channel signal integrity, Section 4.2, third observation).
    ``temp_*``: additive raw ns at 70 C (Section 4.5 / Fig. 10).
    """

    name: str
    rcd_headroom: float
    rp_headroom: float
    fail_floor: float              # below: channel unreadable (data garbage)
    recovery_floor: float = 0.0    # below: no latency <=20ns gives 0 errors
    temp_rcd_coef: float = 0.0     # ns at 70C, ramping in below temp_knee
    temp_rp_const: float = 0.0     # constant ns added at 70C (precharge)
    temp_rp_coef: float = 0.0
    temp_knee: float = 1.15
    dimm_sigma: float = 0.025      # per-DIMM multiplicative process spread


# Calibrated to Section 4.2/4.5 observations:
#  - first tRCD/tRP increase needed at ~1.100 V (A), ~1.125 V (B), ~1.25 V (C)
#  - ~60% of C DIMMs need tRP=12.5 ns at 1.25 V; A DIMMs all fine at 1.15 V
#  - reliable-operation floors: A ~1.10 V, B ~1.025 V, C ~1.10 V
#  - 70 C: A unobservable (<2.5 ns); B affected only below ~1.15 V; C's tRP
#    at 1.35/1.30 V rises 10 -> 12.5 ns (a ~1.6 ns raw adder, masked at
#    lower voltages where tRP is already 12.5 ns).
# Floors from Section 4.2 + Appendix B Table 6: data is readable (with
# errors) down to ``fail_floor``; *error-free* operation via higher latency
# is possible only above ``recovery_floor`` ("Vendor A's DIMMs can no longer
# operate reliably when the voltage is below 1.1 V").
VENDORS = {
    "A": VendorModel("A", rcd_headroom=0.075, rp_headroom=0.200,
                     fail_floor=1.0625, recovery_floor=1.0875,
                     temp_rcd_coef=0.3, temp_knee=1.05, dimm_sigma=0.012),
    "B": VendorModel("B", rcd_headroom=0.050, rp_headroom=0.140,
                     fail_floor=1.0125, recovery_floor=1.0375,
                     temp_rcd_coef=1.2, temp_rp_coef=1.8,
                     temp_knee=1.15, dimm_sigma=0.025),
    "C": VendorModel("C", rcd_headroom=-0.025, rp_headroom=0.0,
                     fail_floor=1.0875, recovery_floor=1.1125,
                     temp_rp_const=1.6, dimm_sigma=0.035),
}


@_on_host
def vendor_raw_latency(op: str, v_array, vendor: str, temp_c: float = 20.0,
                       dimm_z: float = 0.0):
    """Raw latency for one vendor's DIMM at a given voltage/temperature.

    ``dimm_z`` is the DIMM's process-variation z-score (0 = typical).
    """
    vm = VENDORS[vendor]
    v_supply = jnp.asarray(v_array)
    headroom = vm.rp_headroom if op == "rp" else vm.rcd_headroom
    raw = raw_latency(op, v_supply + headroom)
    # temperature adders (linear ramp from 20C to 70C); the knee is in
    # *supply* voltage ("B not strongly affected above 1.15 V", Sec. 4.5).
    tfrac = jnp.clip((temp_c - 20.0) / 50.0, 0.0, None)
    if op == "rcd":
        raw = raw + tfrac * vm.temp_rcd_coef * jnp.maximum(vm.temp_knee - v_supply, 0.0) / 0.15
    if op == "rp":
        ramp = vm.temp_rp_coef * jnp.maximum(vm.temp_knee - v_supply, 0.0) / 0.15
        raw = raw + tfrac * (vm.temp_rp_const + ramp)
    return raw * (1.0 + vm.dimm_sigma * dimm_z)


def measured_min_latency(op: str, v_array, vendor: str, temp_c: float = 20.0,
                         dimm_z: float = 0.0):
    """What the FPGA platform would *measure* as t_min: raw latency rounded
    up to the 2.5 ns platform grid (Section 4.2 / Fig. 6)."""
    raw = vendor_raw_latency(op, v_array, vendor, temp_c, dimm_z)
    return timing.platform_quantize(np.asarray(raw))


# --------------------------------------------------------------------------
# Bitline waveform simulation (Fig. 5)
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ArrayParams:
    """Cell-array circuit constants (Appendix C defaults: 55 nm model)."""

    c_cell_f: float = 24e-15       # cell capacitance (F)
    c_bitline_f: float = 144e-15   # bitline capacitance (F)
    v_ready_access: float = 0.75   # tRCD threshold: 75% of V_array
    v_ready_precharge: float = 0.98  # tRAS threshold: 98% of V_array
    v_ready_activate: float = 0.02   # tRP threshold: within 2% of V_array/2


DEFAULT_ARRAY = ArrayParams()


@functools.partial(jax.jit, static_argnames=("n_steps",))
def bitline_waveform(v_array, t_precharge_ns: float = 50.0,
                     t_total_ns: float = 100.0, n_steps: int = 4000,
                     params: ArrayParams = DEFAULT_ARRAY):
    """Integrate the bitline voltage for an ACTIVATE at t=0 and a PRECHARGE
    at ``t_precharge_ns``, for a cell storing '1'.

    Returns (t_ns[n_steps], v_bl[..., n_steps]) — vectorized over leading
    dims of ``v_array``.  The sense-amplifier drive strength is derived from
    the same calibrated alpha-power-law as the closed-form latency model, so
    the waveform's 75% crossing reproduces ``raw_latency('rcd', V)``.
    """
    v_array = jnp.asarray(v_array, jnp.float32)
    dt = t_total_ns / n_steps
    ts = jnp.arange(n_steps, dtype=jnp.float32) * dt

    ratio = params.c_cell_f / (params.c_cell_f + params.c_bitline_f)
    v_half = v_array / 2.0
    dv_share = v_half * ratio          # charge-sharing bump for stored '1'
    v0 = v_half + dv_share

    # Wordline delay (the constant term of the rcd law), then exponential
    # approach to the rail with tau chosen so the 75% crossing equals the
    # closed-form raw tRCD.
    c_rcd = ALPHA_POWER["rcd"][0]
    raw_rcd = raw_latency("rcd", v_array)
    # 0.75*V = V - (V - v0) exp(-t/tau)  =>  t75 = tau * ln((V-v0)/(0.25 V))
    log_ratio_act = jnp.log((v_array - v0) / (0.25 * v_array))
    tau_act = (raw_rcd - c_rcd) / log_ratio_act

    # Precharge: equalizer pulls the rail back to V/2; 2% band crossing
    # equals the closed-form raw tRP.
    c_rp = ALPHA_POWER["rp"][0]
    raw_rp = raw_latency("rp", v_array)
    log_ratio_pre = jnp.log(1.0 / params.v_ready_activate)   # ln(50)
    tau_pre = (raw_rp - c_rp) / log_ratio_pre

    def v_at(t):
        # activation phase
        ta = jnp.maximum(t - c_rcd, 0.0)
        v_act = jnp.where(t < c_rcd, v0,
                          v_array - (v_array - v0) * jnp.exp(-ta / tau_act))
        # value when precharge begins
        tpa = jnp.maximum(t_precharge_ns - c_rcd, 0.0)
        v_pre_start = v_array - (v_array - v0) * jnp.exp(-tpa / tau_act)
        tp = jnp.maximum(t - t_precharge_ns - c_rp, 0.0)
        v_pre = v_half + (v_pre_start - v_half) * jnp.exp(-tp / tau_pre)
        v_pre = jnp.where(t < t_precharge_ns + c_rp, v_pre_start, v_pre)
        return jnp.where(t < t_precharge_ns, v_act, v_pre)

    vbl = jax.vmap(v_at)(ts)                       # [n_steps, ...]
    vbl = jnp.moveaxis(vbl, 0, -1)
    return ts, vbl


def waveform_crossing_times(v_array, params: ArrayParams = DEFAULT_ARRAY):
    """Threshold-crossing times from the waveform: (t_rcd, t_ras_bl, t_rp).

    ``t_ras_bl`` is the *bitline* 98% crossing; full restoration through the
    cell access transistor is slower — the reported tRAS uses the calibrated
    knot model (`raw_latency('ras', v)`).
    """
    ts, vbl = bitline_waveform(v_array)
    v_array = jnp.asarray(v_array, jnp.float32)
    pre_at = 50.0
    act_mask = ts < pre_at
    t_rcd = _first_crossing(ts, vbl, params.v_ready_access * v_array, act_mask,
                            rising=True)
    t_ras = _first_crossing(ts, vbl, params.v_ready_precharge * v_array,
                            act_mask, rising=True)
    half = v_array / 2.0
    band = params.v_ready_activate * half
    pre_mask = ts >= pre_at
    t_rp = _first_crossing(ts, jnp.abs(vbl - half[..., None]), band, pre_mask,
                           rising=False) - pre_at
    return t_rcd, t_ras, t_rp


def _first_crossing(ts, v, thresh, mask, rising=True):
    thresh = jnp.asarray(thresh)[..., None]
    hit = (v >= thresh) if rising else (v <= thresh)
    hit = hit & mask
    idx = jnp.argmax(hit, axis=-1)
    return ts[idx]
