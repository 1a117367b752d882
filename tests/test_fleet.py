"""Fleet-scale Voltron: per-DIMM safe candidate tables, the W x D
controller cross-product, and the dispatched min-latency search.

Invariants under test:

- candidates are excluded exactly where ``find_min_latency_batch`` returns
  NaN (and never below a vendor's recovery floor);
- each DIMM's safe voltage floor is non-increasing as the allowed latency
  grows;
- fleet lane (w, d) is bit-equal (selections) / <= 1e-12 (metrics) to a
  per-DIMM ``run_suite`` call on that DIMM's table;
- fleet requests reuse warm AOT executables across shapes
  (``dispatch.stats("fleet")``), and ``find_min_latency_batch`` no longer
  retraces per shape.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro import engine
from repro.core import perf_model, voltron
from repro.dram import circuit
from repro.engine import dispatch, fleet
from repro.engine import test1 as engine_test1
from repro.memsim import workloads

MODULES = ("A1", "B2", "C2")
METRIC_FIELDS = ("perf_loss_pct", "dram_power_savings_pct",
                 "dram_energy_savings_pct", "system_energy_savings_pct",
                 "perf_per_watt_gain_pct")
ATOL = 1e-12


@pytest.fixture(scope="module")
def grid():
    return engine.DimmGrid.from_population(MODULES)


@pytest.fixture(scope="module")
def tables(grid):
    return voltron.fleet_tables(grid)


@pytest.fixture(scope="module")
def model():
    return perf_model.fit()


@pytest.fixture(scope="module")
def wls():
    homog = workloads.homogeneous_workloads()
    mem = [x for x in homog if x[1][0].memory_intensive]
    non = [x for x in homog if not x[1][0].memory_intensive]
    return [mem[0], non[0]]


class TestFleetTables:
    def test_excluded_exactly_where_min_latency_nan(self, grid, tables):
        minlat = engine_test1.find_min_latency_batch(grid, tables.cand_v)
        np.testing.assert_array_equal(tables.valid,
                                      np.isfinite(minlat).all(axis=-1))
        # invalid candidates carry NaN timings, valid ones the measured pair
        np.testing.assert_array_equal(
            np.isfinite(tables.timings).all(axis=-1), tables.valid)
        np.testing.assert_array_equal(tables.timings[..., :2][tables.valid],
                                      minlat[tables.valid])

    def test_no_candidate_below_recovery_floor(self, tables):
        for di, vd in enumerate(tables.vendors):
            below = tables.cand_v < circuit.VENDORS[vd].recovery_floor
            assert not tables.valid[di, below].any(), tables.modules[di]

    def test_fallback_valid_on_every_dimm(self, tables):
        assert tables.valid[:, -1].all()
        assert np.isfinite(tables.timings[:, -1]).all()

    def test_safe_vmin_non_increasing_as_latency_grows(self, grid, tables):
        floors = [fleet.build_tables(grid, tables.cand_v,
                                     max_latency=ml).safe_vmin
                  for ml in (10.0, 12.5, 20.0)]
        assert (floors[1] <= floors[0]).all()
        assert (floors[2] <= floors[1]).all()
        # the extra latency headroom genuinely unlocks lower voltages
        assert (floors[2] < floors[0]).any()

    def test_vendor_c_floors_highest(self, tables):
        """Section 4.2: Vendor C needs the highest safe voltages."""
        by_vendor = {vd: tables.safe_vmin[[i for i, x in
                                           enumerate(tables.vendors)
                                           if x == vd]].min()
                     for vd in set(tables.vendors)}
        assert by_vendor["C"] > by_vendor["A"]
        assert by_vendor["C"] > by_vendor["B"]

    def test_ascending_candidates_required(self, grid):
        with pytest.raises(ValueError, match="ascending"):
            fleet.build_tables(grid, [1.2, 1.1])

    def test_select_roundtrip(self, tables):
        sub = tables.select(("C2", "A1"))
        assert sub.modules == ("C2", "A1")
        ci = tables.modules.index("C2")
        np.testing.assert_array_equal(sub.timings[0], tables.timings[ci])
        np.testing.assert_array_equal(sub.valid[0], tables.valid[ci])
        np.testing.assert_array_equal(sub.hammer_margin[0],
                                      tables.hammer_margin[ci])


class TestHammerExclusion:
    """The disturbance safety floor in build_tables: candidates whose
    voltage-dependent hammer threshold undercuts the refresh-window
    activation count are excluded with the same NaN semantics as the
    min-latency floor."""

    SKEW_MODULE = "B2"

    @pytest.fixture(scope="class")
    def skewed(self, grid, tables):
        """Tables with SKEW_MODULE's hammer threshold pushed just below the
        refresh window at its lowest previously-valid candidate."""
        di = tables.modules.index(self.SKEW_MODULE)
        k_low = np.where(tables.valid[di])[0][0]
        scale = 0.9 / tables.hammer_margin[di, k_low]
        return fleet.build_tables(grid, tables.cand_v,
                                  hammer_scale={self.SKEW_MODULE: scale})

    def test_default_margins_all_safe(self, tables):
        """The calibrated model leaves every min-latency-valid candidate
        hammer-safe at defaults — the floor only bites under skew."""
        assert (tables.hammer_margin[tables.valid] >= 1.0).all()
        # margin is NaN exactly where the min-latency floor already
        # excluded the candidate (same-NaN-semantics acceptance)
        np.testing.assert_array_equal(np.isfinite(tables.hammer_margin),
                                      tables.valid)

    def test_margin_monotone_in_voltage(self, tables):
        """Higher wordline voltage -> higher threshold and (weakly) shorter
        row cycle -> the margin grows along the candidate axis."""
        for di in range(tables.n_dimms):
            m = tables.hammer_margin[di][tables.valid[di]]
            assert (np.diff(m) > 0).all(), tables.modules[di]

    def test_skew_excludes_exactly_that_dimm(self, tables, skewed):
        di = tables.modules.index(self.SKEW_MODULE)
        k_low = np.where(tables.valid[di])[0][0]
        diff = tables.valid != skewed.valid
        # exactly the skewed DIMM's lowest-valid candidate flips
        assert np.argwhere(diff).tolist() == [[di, k_low]]
        assert not skewed.valid[di, k_low]
        # NaN semantics identical to the min-latency floor: the excluded
        # candidate's timings go NaN, and the safe floor rises
        assert np.isnan(skewed.timings[di, k_low]).all()
        assert skewed.safe_vmin[di] > tables.safe_vmin[di]
        # the margin itself stays finite (< 1) so reports can show *why*
        assert np.isfinite(skewed.hammer_margin[di, k_low])
        assert skewed.hammer_margin[di, k_low] < 1.0
        # untouched DIMMs keep their margins bit-for-bit
        keep = [i for i in range(tables.n_dimms) if i != di]
        np.testing.assert_array_equal(skewed.hammer_margin[keep],
                                      tables.hammer_margin[keep])

    def test_run_suite_parity_holds_on_skewed_tables(self, skewed, wls,
                                                     model):
        """Per-lane parity survives the hammer exclusion: every fleet lane
        on the skewed tables reproduces a per-DIMM run_suite call."""
        res = voltron.run_fleet(wls, tables=skewed, n_intervals=4,
                                model=model)
        for di, m in enumerate(skewed.modules):
            suite = voltron.run_suite(wls, n_intervals=4, model=model,
                                      tables=skewed.select([m]))
            for wi, r in enumerate(suite):
                np.testing.assert_array_equal(
                    res.selected_voltages[wi, di], r.selected_voltages,
                    err_msg=f"{m}/{r.workload}")
                for f in METRIC_FIELDS:
                    np.testing.assert_allclose(
                        getattr(res, f)[wi, di], getattr(r, f), atol=ATOL,
                        err_msg=f"{m}/{r.workload}/{f}")

    def test_hammer_unsafe_fallback_raises(self, grid, tables):
        with pytest.raises(ValueError, match="hammer|refresh window"):
            fleet.build_tables(grid, tables.cand_v,
                               hammer_scale={self.SKEW_MODULE: 1e-9})

    def test_margin_reported_per_vendor(self, tables, wls, model):
        res = voltron.run_fleet(wls, tables=tables, n_intervals=3,
                                model=model)
        np.testing.assert_array_equal(res.hammer_margin,
                                      tables.hammer_margin)
        dist = res.vendor_hammer_margin()
        assert set(dist) == set(tables.vendors)
        for d in dist.values():
            assert d["min"] <= d["p50"] <= d["max"]
            assert d["min"] >= 1.0          # defaults are all safe

    def test_wider_window_lowers_margin(self, grid, tables):
        wide = fleet.build_tables(grid, tables.cand_v, hammer_window_ms=0.5)
        assert wide.hammer_window_ms == 0.5
        m = tables.valid & wide.valid
        assert (wide.hammer_margin[m] < tables.hammer_margin[m]).all()


class TestPhaseDecorrelation:
    """Per-(workload, DIMM) phase schedules on the fleet's flat lane axis."""

    def test_lane_matches_solo_run_suite(self, tables, wls, model):
        """A decorrelated lane (w, d) is reproducible solo: run_suite on
        that DIMM's table with the lane's own phase seed."""
        res = voltron.run_fleet(wls, tables=tables, n_intervals=4,
                                model=model, decorrelate_phases=True)
        for di, m in enumerate(tables.modules):
            for wi, (name, _) in enumerate(wls):
                seed = voltron._lane_phase_seed(name, m, None)
                solo = voltron.run_suite([wls[wi]], n_intervals=4,
                                         model=model, phase_seed=seed,
                                         tables=tables.select([m]))[0]
                np.testing.assert_array_equal(
                    res.selected_voltages[wi, di], solo.selected_voltages,
                    err_msg=f"{m}/{name}")
                np.testing.assert_allclose(
                    res.perf_loss_pct[wi, di], solo.perf_loss_pct,
                    atol=ATOL, err_msg=f"{m}/{name}")

    def test_decorrelated_differs_from_shared(self, tables, wls, model):
        shared = voltron.run_fleet(wls, tables=tables, n_intervals=6,
                                   model=model)
        dec = voltron.run_fleet(wls, tables=tables, n_intervals=6,
                                model=model, decorrelate_phases=True)
        assert not np.allclose(shared.perf_loss_pct, dec.perf_loss_pct)
        # shared mode: every DIMM of a workload sees identical phases, so
        # decorrelation is the only thing breaking column symmetry here
        ph_shared = voltron._phase_matrix(["x"], 6,
                                          voltron.DEFAULT_INTERVAL_CYCLES,
                                          None, 0.15)
        assert ph_shared.shape == (6, 1)

    def test_explicit_lane_phases_accepted(self, tables, wls, model):
        """run_fleet_batched takes a [T, W*D] matrix directly and rejects
        any other width."""
        wb = engine.WorkloadBatch.from_workloads(wls)
        w, d, t = wb.n_workloads, tables.n_dimms, 3
        lane_phases = voltron.fleet_phase_matrix(
            wb.names, tables.modules, t, voltron.DEFAULT_INTERVAL_CYCLES,
            None, 0.15)
        assert lane_phases.shape == (t, w * d)
        res = fleet.run_fleet_batched(wb, tables, lane_phases,
                                      model.coef_low, model.coef_high, 5.0)
        assert res.perf_loss_pct.shape == (w, d)
        with pytest.raises(ValueError):
            fleet.run_fleet_batched(wb, tables, lane_phases[:, :-1],
                                    model.coef_low, model.coef_high, 5.0)

    def test_lane_seed_independent_of_batch_composition(self):
        a = voltron._lane_phase_seed("stream", "B2", None)
        b = voltron._lane_phase_seed("stream", "B2", None)
        assert a == b
        assert a != voltron._lane_phase_seed("stream", "B3", None)
        assert a != voltron._lane_phase_seed("mcf", "B2", None)
        assert a != voltron._lane_phase_seed("stream", "B2", 7)


class TestMinLatencyDispatch:
    V = [1.25, 1.15, 1.075, 1.05]      # spans recovery floors -> NaNs

    def test_dispatched_matches_direct_and_scalar(self, grid):
        a = engine_test1.find_min_latency_batch(grid, self.V)
        d = engine_test1.find_min_latency_batch(grid, self.V,
                                                dispatch="direct")
        s = engine_test1.find_min_latency_batch(grid, self.V, impl="scalar")
        np.testing.assert_array_equal(a, d)
        np.testing.assert_array_equal(a, s)
        assert np.isnan(a).any() and np.isfinite(a).any()

    def test_same_bucket_single_trace(self, grid):
        """Two differently-shaped requests in one bucket => one compile —
        the ROADMAP item: no more private exact-shape jit retracing per
        fleet request shape."""
        dispatch.clear_cache()
        dispatch.reset_stats()
        engine_test1.find_min_latency_batch(
            grid, [1.2, 1.15, 1.1, 1.05, 1.0])            # N = 15 -> 16
        engine_test1.find_min_latency_batch(
            grid.select(("A1", "B2")),
            [1.3, 1.25, 1.2, 1.15, 1.1, 1.05, 1.0])       # N = 14 -> 16
        s = dispatch.stats("min_latency")
        assert s["calls"] == 2
        assert s["compiles"] == 1
        assert s["hits"] == 1

    def test_unknown_dispatch_rejected(self, grid):
        with pytest.raises(ValueError):
            engine_test1.find_min_latency_batch(grid, [1.2],
                                                dispatch="banana")


class TestFleetController:
    def test_bit_equal_to_per_dimm_run_suite(self, tables, wls, model):
        """The 2-DIMM x 2-workload parity grid: every fleet lane (w, d)
        reproduces a per-DIMM run_suite call on that DIMM's table."""
        sub = tables.select(("A1", "C2"))
        res = voltron.run_fleet(wls, tables=sub, n_intervals=4, model=model)
        for di, m in enumerate(sub.modules):
            suite = voltron.run_suite(wls, n_intervals=4, model=model,
                                      tables=sub.select([m]))
            for wi, r in enumerate(suite):
                np.testing.assert_array_equal(
                    res.selected_voltages[wi, di], r.selected_voltages,
                    err_msg=f"{m}/{r.workload}")
                for f in METRIC_FIELDS:
                    np.testing.assert_allclose(
                        getattr(res, f)[wi, di], getattr(r, f), atol=ATOL,
                        err_msg=f"{m}/{r.workload}/{f}")

    def test_dispatched_matches_direct(self, tables, wls, model):
        a = voltron.run_fleet(wls, tables=tables, n_intervals=3,
                              model=model)
        d = voltron.run_fleet(wls, tables=tables, n_intervals=3,
                              model=model, dispatch="direct")
        np.testing.assert_array_equal(a.selected_voltages,
                                      d.selected_voltages)
        for f in METRIC_FIELDS:
            np.testing.assert_allclose(getattr(a, f), getattr(d, f),
                                       atol=ATOL, err_msg=f)

    def test_warm_executable_reuse_across_fleet_shapes(self, tables, wls,
                                                       model):
        """Acceptance: a second *differently-shaped* fleet request lands in
        the same canonical bucket and reuses the warm executable."""
        dispatch.clear_cache()
        dispatch.reset_stats()
        # 2 workloads x 3 DIMMs and 3 workloads x 2 DIMMs: different
        # request shapes, same flat bucket (6 -> 8)
        voltron.run_fleet(wls, tables=tables, n_intervals=3, model=model)
        voltron.run_fleet(wls + wls[:1], tables=tables.select(("A1", "C2")),
                          n_intervals=3, model=model)
        s = dispatch.stats("fleet")
        assert s["calls"] == 2
        assert s["compiles"] == 1
        assert s["hits"] >= 1

    def test_chunked_mode_reaches_dispatcher(self, tables, wls, model):
        """Regression: run_flat accepted dispatch="chunked" but never
        forwarded the mode, silently running the bucketed path."""
        dispatch.reset_stats()
        a = voltron.run_fleet(wls, tables=tables, n_intervals=3,
                              model=model, dispatch="chunked")
        d = voltron.run_fleet(wls, tables=tables, n_intervals=3,
                              model=model, dispatch="direct")
        assert dispatch.stats("fleet")["chunked_calls"] == 1
        np.testing.assert_array_equal(a.selected_voltages,
                                      d.selected_voltages)
        for f in METRIC_FIELDS:
            np.testing.assert_allclose(getattr(a, f), getattr(d, f),
                                       atol=ATOL, err_msg=f)

    def test_selections_respect_exclusions(self, tables, wls, model):
        """Even with a permissive loss target the controller never selects
        a candidate the DIMM cannot run error-free: each DIMM floors at
        its characterized safe voltage."""
        res = voltron.run_fleet(wls, tables=tables, n_intervals=5,
                                model=model, target_loss_pct=50.0)
        for di in range(tables.n_dimms):
            allowed = set(tables.cand_v[tables.valid[di]])
            chosen = set(np.unique(res.selected_voltages[:, di]))
            assert chosen <= allowed, tables.modules[di]
            assert (res.selected_voltages[:, di].min()
                    >= tables.safe_vmin[di])

    def test_vendor_distribution_shape(self, tables, wls, model):
        res = voltron.run_fleet(wls, tables=tables, n_intervals=3,
                                model=model)
        dist = res.vendor_distribution()
        assert set(dist) == set(tables.vendors)
        for d in dist.values():
            assert d["min"] <= d["p50"] <= d["max"]

    def test_run_fleet_rejects_build_args_with_explicit_tables(self, tables,
                                                               wls):
        with pytest.raises(ValueError, match="fleet_tables"):
            voltron.run_fleet(wls, n_intervals=2, tables=tables,
                              temp_c=70.0)

    def test_run_suite_rejects_multi_dimm_tables(self, tables, wls):
        with pytest.raises(ValueError, match="single-DIMM"):
            voltron.run_suite(wls, n_intervals=2, tables=tables)

    def test_run_suite_rejects_bank_locality_with_tables(self, tables, wls):
        with pytest.raises(ValueError, match="bank_locality"):
            voltron.run_suite(wls, n_intervals=2, bank_locality=True,
                              tables=tables.select(("A1",)))


@pytest.mark.slow
def test_multidevice_controller_and_fleet_mesh_divisible():
    """8 forced host devices: the controller's bucketed W axis and the
    fleet's W x D axis both pad to mesh-divisible ``n_devices * 2**k``
    buckets (regression: the old path hardcoded ``bucket_ladder(1)``) and
    match the direct exact-shape calls."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count=8"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import sys
        sys.path.insert(0, "src")
        import numpy as np
        import jax
        from repro import engine
        from repro.core import perf_model, voltron
        from repro.engine import dispatch
        from repro.memsim import workloads

        assert len(jax.devices()) == 8
        wls = workloads.homogeneous_workloads()[:3]
        model = perf_model.fit()
        wb = engine.WorkloadBatch.from_workloads(wls)
        phases = voltron._phase_matrix(
            wb.names, 4, voltron.DEFAULT_INTERVAL_CYCLES, None, 0.15)
        cand_v, lat_feat, timings = voltron._candidate_grid(False)
        args = (wb, phases, model.coef_low, model.coef_high, 5.0, cand_v,
                lat_feat, timings)
        got = engine.run_batched(*args)
        ref = engine.run_batched(*args, dispatch="direct")
        np.testing.assert_array_equal(got.selected_voltages,
                                      ref.selected_voltages)
        for f in ("perf_loss_pct", "dram_energy_savings_pct",
                  "perf_per_watt_gain_pct"):
            np.testing.assert_allclose(getattr(got, f), getattr(ref, f),
                                       atol=1e-12, err_msg=f)
        # W=3 pads to 8 (not 4): buckets stay divisible by the 8-way mesh
        assert dispatch.stats("controller_scan")["max_resident"] % 8 == 0

        grid = engine.DimmGrid.from_population(("A1", "B2", "C2"))
        tables = voltron.fleet_tables(grid)
        assert dispatch.stats("min_latency")["max_resident"] % 8 == 0
        a = voltron.run_fleet(wls, tables=tables, n_intervals=3,
                              model=model)
        d = voltron.run_fleet(wls, tables=tables, n_intervals=3,
                              model=model, dispatch="direct")
        np.testing.assert_array_equal(a.selected_voltages,
                                      d.selected_voltages)
        np.testing.assert_allclose(a.perf_loss_pct, d.perf_loss_pct,
                                   atol=1e-12)
        assert dispatch.stats("fleet")["max_resident"] % 8 == 0
        print("FLEET_SHARDED_OK")
    """)
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=600, cwd=os.path.dirname(os.path.dirname(__file__)),
        env=dict(os.environ))
    assert "FLEET_SHARDED_OK" in out.stdout, out.stderr[-3000:]


RESAMPLE_SEED = 2**31 + 4099
CANDIDATES = np.array(voltron.CANDIDATE_VOLTAGES + [1.35])


def test_ecc_tables_keep_the_fallback_on_a_resampled_fleet():
    """The ECC stack at the paper's 20 ns ceiling builds a table for every
    DIMM of a resampled fleet: the 1.35 V fallback is valid everywhere,
    with finite reliability rows, and no candidate below a vendor's
    recovery floor is admitted."""
    from repro.dram import chips
    dimms = chips.resampled_population(16, RESAMPLE_SEED)
    grid = engine.DimmGrid.from_dimms(dimms)
    t = fleet.build_tables(grid, CANDIDATES, policies=fleet.ecc_policies())
    assert t.modules == tuple(d.module for d in dimms)
    assert t.stack_name == "min_latency+ecc+hammer"
    assert t.valid[:, -1].all()
    for rates in (t.correctable, t.detectable, t.silent):
        assert np.isfinite(rates[:, -1]).all()
    floor = np.array([circuit.VENDORS[v].recovery_floor for v in t.vendors])
    assert not (t.valid & (CANDIDATES[None, :] < floor[:, None])).any()


def test_four_device_chunked_fleet_matches_one_device_and_direct():
    """4 forced host devices: an 8-DIMM resampled fleet streamed as
    chunks over the ("batch",) mesh equals, bit for bit, the same stream
    on one device and the direct exact-shape call; the counters record
    the lanes, the padding and the mesh."""
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \\
            "--xla_force_host_platform_device_count=4"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import sys
        sys.path.insert(0, "src")
        import numpy as np
        import jax
        from repro import engine
        from repro.core import perf_model
        from repro.dram import chips
        from repro.engine import controller, dispatch, fleet
        from repro.launch import mesh as mesh_lib
        from repro.memsim import workloads

        assert len(jax.devices()) == 4
        mesh4 = mesh_lib.make_batch_mesh(jax.devices()[:4])
        mesh1 = mesh_lib.make_batch_mesh(jax.devices()[:1])
        grid = engine.DimmGrid.from_dimms(
            chips.resampled_population(8, {RESAMPLE_SEED}))
        cand = np.array({CANDIDATES.tolist()})
        tables = fleet.build_tables(grid, cand,
                                    policies=fleet.ecc_policies(),
                                    mesh=mesh4)
        model = perf_model.fit()
        wb = engine.WorkloadBatch.from_workloads(
            workloads.homogeneous_workloads()[:5])
        t = 6
        phases = 1 + 0.15 * np.random.default_rng(0).uniform(
            -1, 1, (t, 5 * 8))
        args = (wb, tables, phases, model.coef_low, model.coef_high, 5.0)
        # chunks of 16 lanes: 40 lanes stream as 3 chunks, 8 of them dead
        kw = dict(max_elements_resident=controller.element_cost(t) * 16)
        dispatch.reset_stats()
        four = fleet.run_fleet_batched(*args, mesh=mesh4, **kw)
        s = dispatch.stats("fleet")
        assert s["chunked_calls"] == 1, s
        assert (s["lanes_total"], s["padded_lanes_total"],
                s["devices"]) == (40, 8, 4), s
        put = [r for r in dispatch.spans()[0] if r.name == "repro.fleet.put"]
        assert put[-1].attrs["chunks"] == 3, put[-1].attrs
        one = fleet.run_fleet_batched(*args, mesh=mesh1, **kw)
        assert dispatch.stats("fleet")["devices"] == 1
        direct = fleet.run_fleet_batched(*args, dispatch="direct")
        for f in ("selected_voltages", "perf_loss_pct",
                  "dram_power_savings_pct", "dram_energy_savings_pct",
                  "system_energy_savings_pct", "perf_per_watt_gain_pct",
                  "base_component_j", "pt_component_j"):
            got = getattr(four, f)
            for other in (one, direct):
                want = getattr(other, f)
                assert got.dtype == want.dtype, f
                assert np.array_equal(got.view(np.uint8),
                                      want.view(np.uint8)), f
        print("OPFLEET_MESH_OK")
    """)
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=600, cwd=os.path.dirname(os.path.dirname(__file__)),
        env=dict(os.environ))
    assert "OPFLEET_MESH_OK" in out.stdout, out.stderr[-3000:]
