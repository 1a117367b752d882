"""Circuit-level model of the DRAM cell array under reduced voltage.

A copy of the program's model, closed-form part only (the Fig. 5 bitline
waveform is left out): ``raw_latency`` / ``table3``, the calibrated
closed-form latency model
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

from . import timing

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


def timings_for_voltages(v_array) -> np.ndarray:
    """Guardbanded, quantized float64[N, 3] (tRCD, tRP, tRAS)
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
