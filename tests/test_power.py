"""The per-component power subsystem (repro.power) and heterogeneous fleets.

Invariants under test:

- scalar float64 (``memsim.energy``) and batched jnp component power agree
  per component at arbitrary operating points and device models (property
  test over the coefficient space);
- the component sums reproduce the legacy ``dram_power`` (dynamic, static)
  closed forms exactly — the component axis is purely additive reporting;
- every array-domain component is monotone non-decreasing in V_array and
  exactly invariant to it in the peripheral domain;
- a heterogeneous fleet (one DIMM on the HBM2 model) stays per-lane
  bit-equal (selections) / <= 1e-12 (metrics) to single-DIMM ``run_suite``
  on the same table row, and its component energies differ from the
  homogeneous fleet's on exactly the re-modelled DIMM.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro import engine, power
from repro.core import perf_model, voltron
from repro.memsim import energy, workloads

METRIC_FIELDS = ("perf_loss_pct", "dram_power_savings_pct",
                 "dram_energy_savings_pct", "system_energy_savings_pct",
                 "perf_per_watt_gain_pct")
ATOL = 1e-12


# --------------------------------------------------------------------------
# Scalar vs batched component parity (property test)
# --------------------------------------------------------------------------
def _f32_rate(hi):
    """Activity rates as the batched path holds them: float32-exact and
    never subnormal.  A float64 subnormal draw is stored by float32 as 0,
    which no relative tolerance can compare with the float64 scalar."""
    return st.floats(0.0, float(np.float32(hi)), width=32,
                     allow_subnormal=False)


class TestComponentParity:
    @given(v_array=st.floats(0.9, 1.35), v_periph=st.floats(1.0, 1.35),
           freq_ratio=st.floats(0.5, 1.0), acts=_f32_rate(0.05),
           lines=_f32_rate(0.2),
           model=st.sampled_from(["ddr3l", "hbm2", "lpddr4"]))
    @settings(max_examples=30)
    def test_scalar_matches_batched(self, v_array, v_periph, freq_ratio,
                                    acts, lines, model):
        scalar = energy.dram_component_power(v_array, v_periph, freq_ratio,
                                             acts, lines, device=model)
        # batched path: per-lane coefficient rows on a [N] batch axis,
        # exactly how the engine feeds heterogeneous fleets
        rows = power.coeff_rows([model, model])
        points = {"v_array": jnp.full(2, v_array),
                  "v_periph": jnp.full(2, v_periph),
                  "freq_ratio": jnp.full(2, freq_ratio)}
        activity = {"acts_per_ns": jnp.full(2, acts),
                    "lines_per_ns": jnp.full(2, lines)}
        batched = power.component_power(points, activity, jnp.asarray(rows))
        assert set(scalar) == set(power.COMPONENTS)
        for name in power.COMPONENTS:
            np.testing.assert_allclose(np.asarray(batched[name]),
                                       scalar[name], rtol=1e-6)

    @given(v_array=st.floats(0.9, 1.35), freq_ratio=st.floats(0.5, 1.0),
           acts=st.floats(0.0, 0.05), lines=st.floats(0.0, 0.2))
    @settings(max_examples=20)
    def test_component_sum_is_legacy_total(self, v_array, freq_ratio, acts,
                                           lines):
        """power_totals over the components == the pre-refactor closed
        forms (the regression oracle is the legacy arithmetic inline)."""
        c = energy.CONST
        v_periph = 1.35
        dyn, static = energy.dram_power(v_array, v_periph, freq_ratio,
                                        acts, lines)
        sa = (v_array / 1.35) ** 2
        sp = (v_periph / 1.35) ** 2
        legacy_dyn = (acts * c.e_act_pre_nj * sa
                      + lines * c.e_rw_array_nj * sa
                      + lines * c.e_rw_periph_nj * sp)
        legacy_static = (c.p_bg_array_w * sa
                         + c.p_bg_periph_w * sp * (0.35 + 0.65 * freq_ratio))
        assert dyn == pytest.approx(legacy_dyn, rel=1e-12)
        assert static == pytest.approx(legacy_static, rel=1e-12)
        comp = energy.dram_component_power(v_array, v_periph, freq_ratio,
                                           acts, lines)
        assert sum(comp.values()) == pytest.approx(dyn + static, rel=1e-12)

    def test_refresh_split_preserves_background(self):
        comp = energy.dram_component_power(1.35, 1.35, 1.0, 0.01, 0.05)
        assert comp["background_array"] + comp["refresh"] == pytest.approx(
            energy.CONST.p_bg_array_w, rel=1e-12)
        assert comp["refresh"] == pytest.approx(
            power.DDR3L.refresh_frac * energy.CONST.p_bg_array_w, rel=1e-12)


# --------------------------------------------------------------------------
# Domain structure
# --------------------------------------------------------------------------
class TestDomainStructure:
    @given(model=st.sampled_from(["ddr3l", "hbm2", "lpddr4"]))
    @settings(max_examples=3)
    def test_array_components_monotone_in_v_array(self, model):
        v_grid = np.linspace(0.9, 1.35, 10)
        comps = [energy.dram_component_power(v, 1.35, 1.0, 0.01, 0.05,
                                             device=model) for v in v_grid]
        for name in power.ARRAY_COMPONENTS:
            vals = np.array([c[name] for c in comps])
            assert (np.diff(vals) > 0).all(), name
        for name in power.PERIPH_COMPONENTS:
            vals = np.array([c[name] for c in comps])
            np.testing.assert_allclose(vals, vals[0], rtol=0, atol=0)

    def test_components_partition_the_domains(self):
        assert set(power.ARRAY_COMPONENTS) | set(power.PERIPH_COMPONENTS) \
            == set(power.COMPONENTS)
        assert not set(power.ARRAY_COMPONENTS) & set(power.PERIPH_COMPONENTS)

    def test_registry(self):
        assert {"ddr3l", "hbm2", "lpddr4"} <= set(power.registered())
        assert power.get("hbm2") is power.HBM2
        assert power.get(power.HBM2) is power.HBM2
        with pytest.raises(KeyError):
            power.get("ddr5-imaginary")
        rows = power.coeff_rows(["ddr3l", "hbm2"])
        assert rows.shape == (2, len(power.COEFF_FIELDS))
        np.testing.assert_array_equal(rows[0], power.DDR3L.coeffs())

    def test_dvfs_ladder_lives_on_the_model(self):
        from repro.core import memdvfs
        assert memdvfs.FREQ_STEPS == [1600.0, 1333.0, 1066.0]
        assert power.DDR3L.rail_for_rate(1333.0) == 1.30
        with pytest.raises(ValueError):
            power.DDR3L.rail_for_rate(800.0)
        with pytest.raises(ValueError):
            power.HBM2.rail_for_rate(1600.0)   # no DVFS ladder on HBM


# --------------------------------------------------------------------------
# Engine integration: component axis on the flat batch
# --------------------------------------------------------------------------
class TestEngineComponents:
    @pytest.fixture(scope="class")
    def batch(self):
        wls = workloads.homogeneous_workloads()[:2]
        wb = engine.WorkloadBatch.from_workloads(wls)
        pg = engine.PointGrid.from_voltages(np.array([1.0, 1.35]))
        return engine.simulate_batch(wb, pg)

    def test_component_sum_matches_totals(self, batch):
        comp_w = sum(batch.components_w[k] for k in power.COMPONENTS)
        comp_j = sum(batch.components_j[k] for k in power.COMPONENTS)
        np.testing.assert_allclose(comp_w, batch.power["dram_w"], rtol=1e-5)
        np.testing.assert_allclose(comp_j, batch.energy["dram_j"], rtol=1e-5)

    def test_device_model_changes_components_not_selections(self, batch):
        wls = workloads.homogeneous_workloads()[:2]
        wb = engine.WorkloadBatch.from_workloads(wls)
        pg = engine.PointGrid.from_voltages(np.array([1.0, 1.35]))
        hbm = engine.simulate_batch(wb, pg, device_model="hbm2")
        assert hbm.device_model == "hbm2" and batch.device_model == "ddr3l"
        assert not np.allclose(hbm.power["dram_w"], batch.power["dram_w"])
        # performance is power-model independent
        np.testing.assert_array_equal(hbm.ipc, batch.ipc)


# --------------------------------------------------------------------------
# Heterogeneous fleet
# --------------------------------------------------------------------------
class TestHeterogeneousFleet:
    @pytest.fixture(scope="class")
    def tables(self):
        grid = engine.DimmGrid.from_population(("A1", "B2"))
        t = voltron.fleet_tables(grid)
        return t.with_device_models({"B2": "hbm2"})

    @pytest.fixture(scope="class")
    def wls(self):
        homog = workloads.homogeneous_workloads()
        mem = [x for x in homog if x[1][0].memory_intensive]
        non = [x for x in homog if not x[1][0].memory_intensive]
        return [mem[0], non[0]]

    @pytest.fixture(scope="class")
    def model(self):
        return perf_model.fit()

    def test_device_model_column(self, tables):
        assert tables.device_models == ("ddr3l", "hbm2")
        assert tables.select(["B2"]).device_models == ("hbm2",)
        with pytest.raises(KeyError):
            tables.with_device_models({"B2": "not-a-model"})
        with pytest.raises(ValueError):
            voltron.fleet_tables(
                engine.DimmGrid.from_population(("A1",)),
                device_models=("ddr3l", "hbm2"))   # length mismatch

    def test_per_lane_parity_with_run_suite(self, tables, wls, model):
        """Each heterogeneous lane == run_suite on that DIMM's table (which
        carries the DIMM's device model): selections bit-equal, metrics to
        1e-12 — one dispatched call, two power models."""
        res = voltron.run_fleet(wls, tables=tables, n_intervals=4,
                                model=model)
        assert res.device_models == ("ddr3l", "hbm2")
        for wi, wl in enumerate(wls):
            for di, m in enumerate(tables.modules):
                solo = voltron.run_suite([wl], n_intervals=4, model=model,
                                         tables=tables.select([m]))[0]
                np.testing.assert_array_equal(
                    res.selected_voltages[wi, di], solo.selected_voltages)
                for field in METRIC_FIELDS:
                    assert abs(getattr(res, field)[wi, di]
                               - getattr(solo, field)) <= ATOL, field

    def test_remodelled_dimm_changes_only_its_lanes(self, tables, wls,
                                                    model):
        homog = tables.with_device_models(("ddr3l", "ddr3l"))
        r_het = voltron.run_fleet(wls, tables=tables, n_intervals=4,
                                  model=model)
        r_hom = voltron.run_fleet(wls, tables=homog, n_intervals=4,
                                  model=model)
        # selections never depend on the power model
        np.testing.assert_array_equal(r_het.selected_voltages,
                                      r_hom.selected_voltages)
        # DIMM 0 kept its model: bit-equal energy; DIMM 1 was re-modelled
        np.testing.assert_array_equal(r_het.pt_component_j[:, 0],
                                      r_hom.pt_component_j[:, 0])
        assert not np.allclose(r_het.pt_component_j[:, 1],
                               r_hom.pt_component_j[:, 1])

    def test_component_report(self, tables, wls, model):
        res = voltron.run_fleet(wls, tables=tables, n_intervals=4,
                                model=model)
        nc = len(power.COMPONENTS)
        assert res.pt_component_j.shape == (len(wls), 2, nc)
        assert np.isfinite(res.pt_component_j).all()
        assert (res.pt_component_j >= 0).all()
        rep = res.vendor_component_energy()
        assert set(rep) == set(res.vendors)
        for comp_stats in rep.values():
            assert set(comp_stats) == set(power.COMPONENTS)
            for s in comp_stats.values():
                assert s["base_j"] > 0 and s["pt_j"] > 0
