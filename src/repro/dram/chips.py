"""The tested-DIMM population model (Table 7) and its error behavior.

The paper characterizes 31 DDR3L DIMMs (124 chips) from three vendors.  We
embed Table 7 verbatim (vendor, manufacture date, die version and the
experimentally found V_min of every DIMM) and derive each DIMM's behavioral
model from it:

- a per-DIMM latency scale factor chosen so that the DIMM's *measured* V_min
  (errors appear below it at the 10 ns reliable-minimum latencies) is exactly
  the Table 7 value;
- a cell-level required-latency distribution (truncated normal) that yields
  the near-exponential error onset of Fig. 4;
- a spatial susceptibility field over (bank, row) reproducing the vendor-
  specific clustering of Fig. 8 (B: row bands across banks; C: whole banks);
- a per-beat multi-bit error model reproducing Fig. 9 (SECDED-defeating
  densities);
- a retention/weak-cell model reproducing Fig. 11.

Everything is deterministic given the DIMM's identity (seeded PRNG).
"""
from __future__ import annotations

import dataclasses
import functools
import zlib

import numpy as np

from repro import hw
from repro.dram import circuit, timing

# --------------------------------------------------------------------------
# Table 7 (verbatim): module, vendor, date (yy-ww), die version, V_min (V)
# --------------------------------------------------------------------------
TABLE7 = [
    ("A1", "A", "15-46", "B", 1.100), ("A2", "A", "15-47", "B", 1.125),
    ("A3", "A", "15-44", "F", 1.125), ("A4", "A", "16-01", "F", 1.125),
    ("A5", "A", "16-01", "F", 1.125), ("A6", "A", "16-10", "F", 1.125),
    ("A7", "A", "16-12", "F", 1.125), ("A8", "A", "16-09", "F", 1.125),
    ("A9", "A", "16-11", "F", 1.100), ("A10", "A", "16-10", "F", 1.125),
    ("B1", "B", "14-34", "Q", 1.100), ("B2", "B", "14-34", "Q", 1.150),
    ("B3", "B", "14-26", "Q", 1.100), ("B4", "B", "14-30", "Q", 1.100),
    ("B5", "B", "14-34", "Q", 1.125), ("B6", "B", "14-32", "Q", 1.125),
    ("B7", "B", "14-34", "Q", 1.100), ("B8", "B", "14-30", "Q", 1.125),
    ("B9", "B", "14-23", "Q", 1.125), ("B10", "B", "14-21", "Q", 1.125),
    ("B11", "B", "14-31", "Q", 1.100), ("B12", "B", "15-08", "Q", 1.100),
    ("C1", "C", "15-33", "A", 1.300), ("C2", "C", "15-33", "A", 1.250),
    ("C3", "C", "15-33", "A", 1.150), ("C4", "C", "15-33", "A", 1.150),
    ("C5", "C", "15-33", "C", 1.300), ("C6", "C", "15-33", "C", 1.300),
    ("C7", "C", "15-33", "C", 1.300), ("C8", "C", "15-33", "C", 1.250),
    ("C9", "C", "15-33", "C", 1.300),
]

# Cell-level required-latency spread (fraction of the mean) and the
# truncation that makes operation *exactly* error-free at/above V_min.
CELL_SIGMA = {"A": 0.012, "B": 0.022, "C": 0.030}
CELL_XMAX = 3.5       # truncated-normal support: x in [-XMAX, XMAX]

# Multi-bit-error (Fig. 9) and retention (Fig. 11) calibration constants —
# shared with the batched engine (repro.engine.population), which re-derives
# the same closed forms in jnp; keep the two in sync through these names.
BEAT_BAD_FRAC = 0.55              # beats affected within a failing line
P_BIT_BASE = 0.08                 # per-bit flip prob in a failing beat...
P_BIT_SLOPE = 0.3                 # ...growing with the voltage deficit
DEFICIT_RANGE_V = 0.2             # deficit normalization (V below V_min)
PATTERN_JITTER = 0.02             # amplitude of the (insignificant) pattern
#                                   effect on the BER (Appendix B ANOVA)
RET_BASE_20C = 66.0               # weak cells @2048 ms / 20 C / 1.35 V
RET_BASE_70C = 2510.0             # ... @70 C
RET_GAMMA = 1.86                  # retention-time growth exponent
RET_KV = 0.136                    # voltage sensitivity at 20 C
RET_KV_SHRINK = 0.62              # ...shrinking toward 70 C
RET_T0_MS, RET_T1_MS = 256.0, 2048.0   # onset / calibration retention times


def pattern_phase(data_pattern: str) -> int:
    """Stable per-pattern phase for the BER jitter term (crc32, not the
    per-process-salted builtin ``hash``, so results reproduce across runs)."""
    return zlib.crc32(str(data_pattern).encode()) % 7

BANKS = hw.BANKS_PER_RANK
ROWS = hw.ROWS_PER_BANK
LINES_PER_DIMM = hw.DIMM_BYTES // hw.CACHE_LINE_BYTES   # 32M lines / 2GB


def _phi(x):
    """Standard normal CDF."""
    from math import erf  # noqa: F401  (vectorized below)
    import scipy.special as sp  # lazy; scipy is available in this env
    return sp.ndtr(x)


def _trunc_phi(x, xmax=CELL_XMAX):
    """CDF of a normal truncated to [-xmax, xmax] (exactly 0/1 outside)."""
    x = np.asarray(x, dtype=np.float64)
    lo, hi = _phi(-xmax), _phi(xmax)
    p = (_phi(np.clip(x, -xmax, xmax)) - lo) / (hi - lo)
    return np.where(x <= -xmax, 0.0, np.where(x >= xmax, 1.0, p))


@dataclasses.dataclass(frozen=True)
class DIMM:
    """One simulated DIMM, fully determined by its Table 7 row."""

    module: str
    vendor: str
    date: str
    die: str
    vmin: float
    index: int                      # position in TABLE7 (seeds the PRNG)

    # -- derived -----------------------------------------------------------
    @functools.cached_property
    def rng(self) -> np.random.Generator:
        return np.random.default_rng(0xD1333 + self.index)

    @functools.cached_property
    def cell_sigma(self) -> float:
        return CELL_SIGMA[self.vendor]

    @functools.cached_property
    def _crit_op(self) -> str:
        """The operation whose latency requirement crosses its reliable
        minimum first (each op against its *own* threshold — tRCD vs 10 ns
        and tRP vs 10 ns happen to coincide today, but the comparison must
        not silently couple them)."""
        v = np.linspace(0.95, 1.35, 81)
        rcd = np.asarray(circuit.vendor_raw_latency("rcd", v, self.vendor))
        rp = np.asarray(circuit.vendor_raw_latency("rp", v, self.vendor))
        # crossing voltage = max v where raw > the op's reliable minimum
        def crossing(raw, t_min):
            above = v[raw > t_min]
            return above.max() if above.size else 0.0
        return ("rcd" if crossing(rcd, timing.RELIABLE_MIN_NOMINAL.t_rcd)
                >= crossing(rp, timing.RELIABLE_MIN_NOMINAL.t_rp) else "rp")

    @functools.cached_property
    def latency_scale(self) -> float:
        """Per-DIMM multiplicative latency factor, solved so that the worst
        cell's requirement crosses 10 ns exactly half a voltage step below
        the DIMM's Table 7 V_min."""
        v_edge = self.vmin - 0.0125
        raw = float(np.asarray(
            circuit.vendor_raw_latency(self._crit_op, v_edge, self.vendor)))
        t10 = (timing.RELIABLE_MIN_NOMINAL.t_rcd if self._crit_op == "rcd"
               else timing.RELIABLE_MIN_NOMINAL.t_rp)
        worst_x = CELL_XMAX + float(self.susceptibility.max())
        return t10 / (raw * (1.0 + self.cell_sigma * worst_x))

    @property
    def dimm_z(self) -> float:
        """The z-score equivalent of ``latency_scale`` for Fig. 6 plots."""
        return (self.latency_scale - 1.0) / circuit.VENDORS[self.vendor].dimm_sigma

    def required_latency(self, op: str, v, temp_c: float = 20.0):
        """Mean required raw latency of ``op`` for this DIMM, ns."""
        return np.asarray(circuit.vendor_raw_latency(
            op, v, self.vendor, temp_c)) * self.latency_scale

    # -- spatial susceptibility field (Fig. 8) ------------------------------
    @functools.cached_property
    def susceptibility(self) -> np.ndarray:
        """Per-(bank, row-group) susceptibility z-offsets, shape [8, 256].

        Row groups of 128 rows keep the field small; vendor-specific
        structure per Section 4.3: Vendor B clusters in row bands shared
        across banks; Vendor C concentrates whole banks; Vendor A shows
        localized row clusters in a few banks.
        """
        rng = self.rng
        n_groups = 256
        field = 0.25 * rng.standard_normal((BANKS, n_groups))
        if self.vendor == "B":
            bands = rng.choice(n_groups, size=6, replace=False)
            width = rng.integers(2, 8)
            for b in bands:
                sl = slice(int(b), min(int(b) + int(width), n_groups))
                field[:, sl] += 1.4 + 0.3 * rng.standard_normal()
        elif self.vendor == "C":
            n_weak = rng.integers(1, 4)
            weak_banks = rng.choice(BANKS, size=int(n_weak), replace=False)
            field[weak_banks, :] += 1.2 + 0.3 * rng.standard_normal()
        else:  # vendor A: a few localized clusters
            for _ in range(int(rng.integers(2, 5))):
                b = int(rng.integers(BANKS))
                g = int(rng.integers(n_groups - 8))
                field[b, g:g + int(rng.integers(2, 8))] += 1.1
        # zero-mean, bounded: susceptibility shifts cells within the
        # truncated support rather than past it
        field -= field.mean()
        return np.clip(field, -1.5, 1.5)

    # -- error rates ---------------------------------------------------------
    def line_error_fraction(self, v, t_rcd: float = 10.0, t_rp: float = 10.0,
                            temp_c: float = 20.0) -> np.ndarray:
        """Fraction of 64 B cache lines with >=1 bit error (Fig. 4).

        A line fails if any of its per-op required latencies exceed the
        programmed latency.  Per-line requirement = mean * (1 + sigma * x),
        x ~ TruncNormal(field_offset, 1) over the susceptibility field.
        """
        v = np.atleast_1d(np.asarray(v, dtype=np.float64))
        prog = {"rcd": t_rcd, "rp": t_rp}
        field = self.susceptibility.reshape(-1)                  # [F]
        p_ok = np.ones((v.size, field.size))
        for op, t_prog in prog.items():
            req = self.required_latency(op, v, temp_c)            # [V]
            # x threshold: req*(1+sigma x) <= t_prog
            with np.errstate(divide="ignore"):
                x_thr = (t_prog / req[:, None] - 1.0) / self.cell_sigma
            p_ok *= _trunc_phi(x_thr - field[None, :])
        frac = 1.0 - p_ok.mean(axis=1)
        # signal-integrity floor: below it, the channel corrupts transfers
        # regardless of latency (Section 4.2, third observation)
        floor = circuit.VENDORS[self.vendor].fail_floor
        frac = np.where(v < floor, np.maximum(frac, 0.5), frac)
        return frac

    def bit_error_rate(self, v, t_rcd: float = 10.0, t_rp: float = 10.0,
                       temp_c: float = 20.0, data_pattern: str = "0xaa"):
        """Approximate BER (Appendix B).  The data pattern has no
        statistically significant effect (paper's ANOVA): we add only a tiny
        pattern-dependent jitter so repeated measurements are not identical.
        """
        frac_line = self.line_error_fraction(v, t_rcd, t_rp, temp_c)
        bits_per_line = hw.CACHE_LINE_BYTES * 8
        # bits-in-error per failing line (Fig. 9: multi-bit beats dominate)
        mean_bad_bits = (BEAT_BAD_FRAC * hw.BEATS_PER_LINE
                         * self._beat_bad_bits_mean(v))
        jitter = 1.0 + PATTERN_JITTER * np.sin(
            pattern_phase(data_pattern) + np.atleast_1d(v) * 40)
        return frac_line * mean_bad_bits / bits_per_line * jitter

    def _beat_bad_bits_mean(self, v) -> np.ndarray:
        """Mean # bad bits in a *failing* 64-bit beat, grows as V drops."""
        v = np.atleast_1d(np.asarray(v, dtype=np.float64))
        deficit = np.clip((self.vmin - v) / DEFICIT_RANGE_V, 0.0, 1.5)
        p_bit = P_BIT_BASE + P_BIT_SLOPE * deficit   # per-bit flip prob
        return hw.BEAT_BITS * p_bit

    def beat_error_distribution(self, v, t_rcd: float = 10.0,
                                t_rp: float = 10.0,
                                temp_c: float = 20.0) -> dict:
        """Fractions of 64-bit data beats with 0 / 1 / 2 / >2 bit errors
        (Fig. 9).  Within a failing beat, bad bits ~ Binomial(64, p_bit).
        ``temp_c`` reaches the underlying line-error model so the Fig. 9
        densities compose with the Section 5.3 temperature scenarios.

        This is the scalar reference for the fleet's ECC admission:
        ``repro.engine.population.beat_error_batch`` mirrors exactly this
        math on the flat D x K x T batch axis (closed-form binomial
        powers instead of ``scipy.stats.binom.pmf`` — agreement is float64
        round-off, not bit-exact), so any change here must land in
        ``population._beat_error_flat_fn`` too."""
        from scipy import stats
        v_arr = np.atleast_1d(np.asarray(v, dtype=np.float64))
        frac_line = self.line_error_fraction(v_arr, t_rcd, t_rp, temp_c)
        # a failing line has ~55% of its 8 beats affected
        p_beat_bad = frac_line * BEAT_BAD_FRAC
        deficit = np.clip((self.vmin - v_arr) / DEFICIT_RANGE_V, 0.0, 1.5)
        p_bit = P_BIT_BASE + P_BIT_SLOPE * deficit
        p0 = stats.binom.pmf(0, hw.BEAT_BITS, p_bit)
        p1 = stats.binom.pmf(1, hw.BEAT_BITS, p_bit)
        p2 = stats.binom.pmf(2, hw.BEAT_BITS, p_bit)
        # renormalize within failing beats (conditioned on >=1 flip)
        denom = np.maximum(1.0 - p0, 1e-12)
        one = p_beat_bad * p1 / denom
        two = p_beat_bad * p2 / denom
        more = p_beat_bad * np.maximum(1 - p0 - p1 - p2, 0.0) / denom
        return {
            "zero": 1.0 - (one + two + more),
            "one": one,
            "two": two,
            "many": more,
        }

    # -- retention (Fig. 11) -------------------------------------------------
    def weak_cells(self, retention_ms: float, temp_c: float = 20.0,
                   v: float = hw.VDD_NOMINAL, round_idx: int = 0) -> int:
        """Number of weak cells at a given retention time (refresh off).

        Calibrated to Fig. 11: zero weak cells until 512 ms; at 2048 ms,
        ~66 cells @20C/1.35V -> ~75 @1.15V; ~2510 @70C/1.35V -> ~2641 @1.15V.
        """
        lam = expected_weak_cells(retention_ms, temp_c, v)
        rng = np.random.default_rng(
            0x5EED + self.index * 1009 + round_idx * 131
            + int(retention_ms) + int(temp_c))
        return int(rng.poisson(lam))


def expected_weak_cells(retention_ms, temp_c=20.0, v=hw.VDD_NOMINAL):
    """Mean weak-cell count per DIMM (Fig. 11 calibration)."""
    retention_ms = np.asarray(retention_ms, dtype=np.float64)
    tfrac = np.clip((temp_c - 20.0) / 50.0, 0.0, None)
    base = RET_BASE_20C * (RET_BASE_70C / RET_BASE_20C) ** tfrac
    # Fig. 11: 66 -> 75 cells (1.35 -> 1.15 V) at 20C; 2510 -> 2641 at 70C.
    kv = RET_KV * (1.0 - RET_KV_SHRINK * tfrac)   # sensitivity shrinks at 70C
    t_rel = np.clip((retention_ms - RET_T0_MS) / (RET_T1_MS - RET_T0_MS),
                    0.0, None)
    return base * t_rel ** RET_GAMMA * (
        1.0 + kv * np.maximum(hw.VDD_NOMINAL - v, 0.0) / DEFICIT_RANGE_V)


@functools.lru_cache(maxsize=1)
def population() -> tuple:
    """The 31 simulated DIMMs of Table 7."""
    return tuple(DIMM(m, v, d, die, vmin, i)
                 for i, (m, v, d, die, vmin) in enumerate(TABLE7))


def resampled_population(n: int, seed: int) -> tuple:
    """An operator's fleet of ``n`` DIMMs resampled from Table 7.

    DIMM ``i`` copies a Table 7 row (vendor, date, die, V_min) drawn
    uniformly with replacement by ``np.random.default_rng(seed)
    .integers(len(TABLE7), size=n)``, so the vendor mix is 10:12:9 in
    expectation.  Its ``index`` is ``len(TABLE7) + i``: distinct from the
    Table 7 DIMMs (0-30) and from every other DIMM of the fleet, it gives
    the DIMM its own susceptibility field and latency scale.  Its name is
    the row's module and its position, ``B7.r0413``."""
    rows = np.random.default_rng(seed).integers(len(TABLE7), size=int(n))
    width = max(4, len(str(int(n) - 1)))
    return tuple(DIMM(f"{TABLE7[r][0]}.r{i:0{width}d}", *TABLE7[r][1:],
                      len(TABLE7) + i)
                 for i, r in enumerate(rows))


def by_vendor(vendor: str) -> list:
    return [d for d in population() if d.vendor == vendor]


def measured_vmin(dimm: DIMM, voltages=None) -> float:
    """Re-measure V_min the way the paper does: lowest voltage with zero
    errors at the 10 ns reliable-minimum latencies (validates the model
    round-trips Table 7)."""
    if voltages is None:
        voltages = np.round(np.arange(1.35, 0.99, -0.025), 4)
    frac = dimm.line_error_fraction(voltages)
    ok = voltages[frac <= 0.0]
    return float(ok.min()) if ok.size else float("nan")
