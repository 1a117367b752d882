"""Chip-population model: Table 7 round-trip, Fig. 4/9/11 behaviors."""
import numpy as np
from _hypothesis_compat import given, settings, strategies as st

from repro.dram import chips


def test_table7_population():
    pop = chips.population()
    assert len(pop) == 31
    assert sum(d.vendor == "A" for d in pop) == 10
    assert sum(d.vendor == "B" for d in pop) == 12
    assert sum(d.vendor == "C" for d in pop) == 9


def test_vmin_roundtrip_all_31():
    """Re-measuring V_min the paper's way returns Table 7 exactly."""
    for d in chips.population():
        assert chips.measured_vmin(d) == d.vmin, d.module


def test_error_onset_and_growth():
    """Fig. 4: zero errors at/above V_min; near-exponential growth below."""
    d = chips.population()[0]
    v = np.round(np.arange(1.35, d.vmin - 1e-9, -0.025), 4)
    assert (d.line_error_fraction(v) == 0).all()
    below = np.round([d.vmin - 0.025, d.vmin - 0.05], 4)
    f = d.line_error_fraction(below)
    assert f[0] > 0 and f[1] > f[0] * 3        # steep growth


def test_higher_latency_removes_errors():
    """Section 4.2: +2.5 ns tRCD/tRP recovers correctness below V_min."""
    d = [x for x in chips.population() if x.module == "C2"][0]
    v = d.vmin - 0.025
    assert d.line_error_fraction(v, 10.0, 10.0)[0] > 0
    assert d.line_error_fraction(v, 12.5, 12.5)[0] == 0.0


def test_crit_op_uses_per_op_reliable_minimum(monkeypatch):
    """Regression: ``_crit_op`` compared *both* raw-latency curves against
    the tRCD reliable minimum (benign only while tRCD and tRP minima
    coincide at 10 ns).  Skewing one op's threshold must flip the critical
    op accordingly — each curve against its own threshold."""
    from repro.dram import timing
    fresh = lambda: chips.DIMM(*chips.TABLE7[0], index=0)
    # an unreachable tRP threshold: rp never crosses -> rcd is critical
    monkeypatch.setattr(timing, "RELIABLE_MIN_NOMINAL",
                        timing.TimingParams(t_rcd=10.0, t_rp=1e9))
    assert fresh()._crit_op == "rcd"
    # and symmetrically (the old code returned "rcd" here too)
    monkeypatch.setattr(timing, "RELIABLE_MIN_NOMINAL",
                        timing.TimingParams(t_rcd=1e9, t_rp=10.0))
    assert fresh()._crit_op == "rp"


def test_beat_error_distribution_threads_temp(monkeypatch):
    """Regression: ``beat_error_distribution`` pinned temp_c=20 while
    ``line_error_fraction`` accepts it.  At 70 C a Vendor-C DIMM fails
    lines at voltages that are error-free at 20 C (Fig. 10), and the beat
    densities must see that."""
    d = [x for x in chips.population() if x.module == "C2"][0]
    v = 1.275                    # error-free at 20 C, failing at 70 C
    assert d.line_error_fraction(v)[0] == 0.0
    assert d.line_error_fraction(v, temp_c=70.0)[0] > 0.0
    cold = d.beat_error_distribution(v)
    hot = d.beat_error_distribution(v, temp_c=70.0)
    assert float(np.atleast_1d(cold["zero"])[0]) == 1.0
    assert float(np.atleast_1d(hot["zero"])[0]) < 1.0
    # explicit 20 C == the default (unchanged behavior)
    explicit = d.beat_error_distribution(v, temp_c=20.0)
    for k in ("zero", "one", "two", "many"):
        np.testing.assert_array_equal(cold[k], explicit[k])


def test_beat_density_defeats_secded():
    """Fig. 9: failing beats are predominantly >2-bit."""
    d = [x for x in chips.population() if x.module == "C2"][0]
    dist = d.beat_error_distribution(d.vmin - 0.05)
    many = float(np.atleast_1d(dist["many"])[0])
    one = float(np.atleast_1d(dist["one"])[0])
    two = float(np.atleast_1d(dist["two"])[0])
    assert many > 10 * (one + two)


def test_retention_calibration():
    """Fig. 11: no weak cells until >256 ms; ~66 cells @2048 ms/20C/1.35V,
    ~75 @1.15V; ~2510/~2641 @70C."""
    assert chips.expected_weak_cells(256.0, 20.0, 1.35) == 0.0
    assert chips.expected_weak_cells(64.0, 70.0, 0.9) == 0.0
    np.testing.assert_allclose(chips.expected_weak_cells(2048, 20, 1.35), 66, rtol=0.02)
    np.testing.assert_allclose(chips.expected_weak_cells(2048, 20, 1.15), 75, rtol=0.05)
    np.testing.assert_allclose(chips.expected_weak_cells(2048, 70, 1.35), 2510, rtol=0.02)
    np.testing.assert_allclose(chips.expected_weak_cells(2048, 70, 1.15), 2641, rtol=0.05)


def test_retention_voltage_insensitive():
    """The paper's conclusion: reduced voltage does NOT require faster
    refresh (effect statistically insignificant / small)."""
    base = chips.expected_weak_cells(512, 20, 1.35)
    low = chips.expected_weak_cells(512, 20, 1.15)
    assert low <= base * 1.25 + 3


@settings(max_examples=20, deadline=None)
@given(vi=st.integers(0, 30), dv=st.floats(0.0, 0.2),
       extra=st.floats(0.0, 5.0))
def test_property_error_fraction_monotone(vi, dv, extra):
    """Errors never decrease as voltage drops, never increase as latency
    rises."""
    d = chips.population()[vi]
    v = max(d.vmin - dv, 1.02)
    f_low_lat = d.line_error_fraction(v, 10.0, 10.0)[0]
    f_hi_lat = d.line_error_fraction(v, 10.0 + extra, 10.0 + extra)[0]
    f_lower_v = d.line_error_fraction(max(v - 0.025, 1.0), 10.0, 10.0)[0]
    assert f_hi_lat <= f_low_lat + 1e-12
    assert f_lower_v >= f_low_lat - 1e-12


SEED = 2**31 + 4099


def test_resampled_population_is_deterministic_per_seed():
    a = chips.resampled_population(64, SEED)
    assert a == chips.resampled_population(64, SEED)
    assert a != chips.resampled_population(64, SEED + 1)
    # a longer fleet from the same seed starts with the same rows
    assert [d.vmin for d in chips.resampled_population(128, SEED)[:64]] \
        == [d.vmin for d in a]


def test_resampled_population_names_and_indices_are_fresh():
    fleet = chips.resampled_population(1024, SEED)
    assert len({d.module for d in fleet}) == 1024
    idx = [d.index for d in fleet]
    assert len(set(idx)) == 1024 and min(idx) >= len(chips.TABLE7)
    table7 = {d.module for d in chips.population()}
    for i, d in enumerate(fleet):
        row, _, pos = d.module.partition(".r")
        assert row in table7 and int(pos) == i


def test_resampled_population_keeps_the_drawn_row():
    """Vendor, date, die and V_min are the drawn Table 7 row's; the model
    re-measures that V_min on the fresh susceptibility field."""
    rows = {m: (v, date, die, vmin) for m, v, date, die, vmin in chips.TABLE7}
    fleet = chips.resampled_population(64, SEED)
    for d in fleet:
        assert (d.vendor, d.date, d.die, d.vmin) == rows[d.module.split(".")[0]]
    for d in fleet[:8]:
        assert chips.measured_vmin(d) == d.vmin, d.module


def test_resampled_vendor_mix_follows_table7():
    """At n = 1,024 each vendor's count lies within 4 binomial sigmas of
    its Table 7 share (10:12:9 of 31)."""
    n = 1024
    fleet = chips.resampled_population(n, SEED)
    for vendor, k in (("A", 10), ("B", 12), ("C", 9)):
        p = k / 31
        got = sum(d.vendor == vendor for d in fleet)
        assert abs(got - n * p) <= 4 * np.sqrt(n * p * (1 - p)), vendor
