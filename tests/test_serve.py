"""Streaming fleet service: coalescing parity, admission control and
mid-stream failure injection (:mod:`repro.engine.service`).

The service contract under test: requests coalesced into one megabatch are
bit-exact per lane against the direct single-request path
(``dispatch="direct"`` through the batch APIs), admission never passes the
queue budget, and dropping a DIMM's table mid-stream fails exactly that
DIMM's requests — typed, fast — while every other lane completes.
"""
from __future__ import annotations

import asyncio
import functools

import numpy as np
import pytest

from _hypothesis_compat import given, settings, strategies as st
from repro.engine import dispatch, fleet, population, service as svc
from repro.engine import test1 as engine_test1
from repro.engine.batch import WorkloadBatch
from repro.launch import fleet_serve

MODULES = ("A1", "B2", "C2")
N_INTERVALS = 4
LANE_COST = 8 * 5 * 5       # min-latency element cost at the default G=5
ATOL = 1e-12


@functools.lru_cache(maxsize=1)
def _env():
    """Shared grid / tables / workloads / perf model (built once; plain
    cached helper rather than a fixture so the property tests — which the
    hypothesis shim wraps with an opaque signature — can reach it too)."""
    from repro.core import perf_model, voltron
    from repro.memsim import workloads

    grid = population.DimmGrid.from_population(MODULES)
    tables = voltron.fleet_tables(grid)
    wls = tuple(workloads.homogeneous_workloads()[:4])
    return grid, tables, wls, perf_model.fit()


def make_service(**cfg_kw) -> svc.EngineService:
    grid, tables, wls, model = _env()
    return svc.EngineService(grid, tables=tables, workloads=wls,
                             model=model, config=svc.ServiceConfig(**cfg_kw))


def serve_all(service, requests):
    """Submit every request concurrently (one batching window) and return
    per-request results — exceptions kept in place.  Drains but does not
    close the service, so a test can keep using it across calls."""
    async def run():
        out = await asyncio.gather(*(service.submit(r) for r in requests),
                                   return_exceptions=True)
        await service.drain()
        return out
    return asyncio.run(run())


def fleet_reference(req: svc.FleetRequest):
    """The direct single-request path for a FleetRequest."""
    from repro.core import voltron

    _, tables, wls, model = _env()
    by_name = dict(wls)
    wb = WorkloadBatch.from_workloads(
        [(n, by_name[n]) for n in req.workloads])
    phases = voltron._phase_matrix(
        wb.names, req.n_intervals, voltron.DEFAULT_INTERVAL_CYCLES,
        req.phase_seed, req.phase_amplitude)
    return fleet.run_fleet_batched(
        wb, tables.select(list(req.modules)), phases, model.coef_low,
        model.coef_high, req.target_loss_pct, dispatch="direct")


def check_parity(req, result):
    grid = _env()[0]
    if isinstance(req, svc.MinLatencyRequest):
        ref = engine_test1.find_min_latency_batch(
            grid.select([req.module]), np.asarray(req.voltages),
            step=req.step, max_latency=req.max_latency, temp_c=req.temp_c,
            dispatch="direct")[0]
        np.testing.assert_array_equal(result, ref)
    elif isinstance(req, svc.CharacterizeRequest):
        ref = population.characterize_batch(
            grid.select([req.module]), np.asarray(req.voltages), req.temps,
            req.patterns, req.retention_ms, req.t_rcd, req.t_rp,
            dispatch="direct")
        for key, ref_a in (
                ("line_error_fraction", ref.line_error_fraction[0]),
                ("ber", ref.ber[0]),
                ("t_rcd_min", ref.t_rcd_min[0]),
                ("t_rp_min", ref.t_rp_min[0]),
                ("row_error_prob", ref.row_error_prob[0]),
                ("line_error_prob", ref.line_error_prob[0]),
                ("expected_weak_cells", ref.expected_weak_cells)):
            np.testing.assert_array_equal(result[key], ref_a, err_msg=key)
    elif isinstance(req, svc.FleetRequest):
        ref = fleet_reference(req)
        # voltage selections are bit-exact; the f32 derived metrics carry
        # XLA's shape-dependent vectorization drift (~1e-6 relative) when
        # the lane runs at a different bucket rung — the batch API shows
        # the identical drift across compositions, coalescing adds none
        np.testing.assert_array_equal(result.selected_voltages,
                                      ref.selected_voltages)
        for field in ("perf_loss_pct", "dram_power_savings_pct",
                      "dram_energy_savings_pct",
                      "system_energy_savings_pct",
                      "perf_per_watt_gain_pct"):
            np.testing.assert_allclose(getattr(result, field),
                                       getattr(ref, field), rtol=1e-5,
                                       atol=1e-8, err_msg=field)
    else:
        raise TypeError(req)


# --------------------------------------------------------------------------
# Coalescing parity (one dispatch per window) per entry point
# --------------------------------------------------------------------------
def test_min_latency_coalescing_parity():
    service = make_service(window_s=0.05)
    reqs = [svc.MinLatencyRequest("A1", (1.05, 1.2)),
            svc.MinLatencyRequest("B2", (0.95,)),
            svc.MinLatencyRequest("C2", (1.0, 1.1, 1.3))]
    calls0 = dispatch.stats("min_latency")["calls"]
    results = serve_all(service, reqs)
    # one shared window -> one megabatch -> one dispatch call
    assert dispatch.stats("min_latency")["calls"] == calls0 + 1
    assert service.stats()["flushes"] == 1
    for req, res in zip(reqs, results):
        assert not isinstance(res, Exception), res
        check_parity(req, res)


def test_characterize_coalescing_parity():
    service = make_service(window_s=0.05)
    reqs = [svc.CharacterizeRequest("A1", (1.1, 1.25), temps=(20.0, 45.0)),
            svc.CharacterizeRequest("B2", (1.05,))]
    calls0 = dispatch.stats("characterize")["calls"]
    results = serve_all(service, reqs)
    assert dispatch.stats("characterize")["calls"] == calls0 + 1
    for req, res in zip(reqs, results):
        assert not isinstance(res, Exception), res
        check_parity(req, res)


def test_worker_thread_runs_float64_entries_under_x64():
    """JAX's x64 switch is a thread-local context: the service's worker
    thread enters it for the float64 entry points itself, and the caller's
    thread is left as it was."""
    import jax
    service = make_service(window_s=0.01)
    dispatch.clear_cache()
    serve_all(service, [svc.CharacterizeRequest("A1", (1.1,)),
                        svc.MinLatencyRequest("B2", (1.0,))])
    for entry in ("characterize", "min_latency"):
        exes = dispatch.executables(entry)
        assert exes and all("f64" in c.as_text() for c in exes), entry
    assert not jax.config.jax_enable_x64


def test_fleet_coalescing_parity():
    service = make_service(window_s=0.05)
    names = service.workload_names
    reqs = [svc.FleetRequest((names[0], names[1]), ("A1", "C2"),
                             n_intervals=N_INTERVALS),
            svc.FleetRequest((names[2],), ("B2",),
                             n_intervals=N_INTERVALS)]
    calls0 = dispatch.stats("fleet")["calls"]
    results = serve_all(service, reqs)
    assert dispatch.stats("fleet")["calls"] == calls0 + 1
    for req, res in zip(reqs, results):
        assert not isinstance(res, Exception), res
        check_parity(req, res)


def test_size_trigger_flushes_before_window():
    # a deliberately unreachable window with a 4-lane size trigger: the
    # flushes must come from the size trigger, never the timer
    service = make_service(window_s=60.0, max_batch_lanes=4)
    reqs = [svc.MinLatencyRequest(MODULES[i % 3], (1.0 + 0.02 * i,))
            for i in range(8)]

    async def run():
        return await asyncio.wait_for(
            asyncio.gather(*(service.submit(r) for r in reqs)),
            timeout=60.0)

    results = asyncio.run(run())
    st_ = service.stats()
    assert st_["flushes"] == 2 and st_["max_flush_lanes"] == 4
    for req, res in zip(reqs, results):
        check_parity(req, res)


# --------------------------------------------------------------------------
# Admission control against the queue budget
# --------------------------------------------------------------------------
def test_admission_sheds_past_budget():
    budget = 3 * LANE_COST
    service = make_service(window_s=60.0, admission="shed",
                           max_queue_elements=budget)
    big = svc.MinLatencyRequest("A1", tuple(np.linspace(0.9, 1.3, 9)))
    results = serve_all(service, [
        svc.MinLatencyRequest("A1", (1.0, 1.1)),    # 2 lanes: admitted
        svc.MinLatencyRequest("B2", (1.0, 1.1)),    # would exceed: shed
        big,                                        # > whole budget: refused
    ])
    assert not isinstance(results[0], Exception), results[0]
    assert isinstance(results[1], svc.AdmissionError)
    assert isinstance(results[2], svc.AdmissionError)
    st_ = service.stats()
    assert st_["shed"] >= 1
    assert st_["max_queued_elements"] <= budget


def test_admission_queue_mode_suspends_and_completes():
    # each request costs exactly the whole budget: queue mode must
    # serialize them (suspend, not shed) and still complete every one
    budget = 2 * LANE_COST
    service = make_service(window_s=0.01, admission="queue",
                           max_queue_elements=budget)
    reqs = [svc.MinLatencyRequest(m, (1.0 + 0.05 * i, 1.3))
            for i, m in enumerate(MODULES * 2)]
    results = serve_all(service, reqs)
    for req, res in zip(reqs, results):
        assert not isinstance(res, Exception), res
        check_parity(req, res)
    st_ = service.stats()
    # zero admission past the budget, ever
    assert st_["max_queued_elements"] <= budget
    assert st_["completed"] == len(reqs)
    assert st_["shed"] == 0
    assert st_["flushes"] >= 3       # the budget forces several batches


# --------------------------------------------------------------------------
# Mid-stream failure injection: drop + re-derive a DIMM table
# --------------------------------------------------------------------------
def test_midstream_table_drop_and_rederive():
    grid, tables, wls, _ = _env()
    service = make_service(window_s=0.05)
    names = service.workload_names
    ok_req = svc.FleetRequest((names[0],), ("A1", "C2"),
                              n_intervals=N_INTERVALS)
    bad_req = svc.FleetRequest((names[1],), ("B2",),
                               n_intervals=N_INTERVALS)

    async def run():
        # both requests enter the same batching window...
        f_ok = asyncio.ensure_future(service.submit(ok_req))
        f_bad = asyncio.ensure_future(service.submit(bad_req))
        await asyncio.sleep(0)
        # ...then B2's table drops before the flush fires
        service.drop_table("B2")
        out = await asyncio.gather(f_ok, f_bad, return_exceptions=True)
        await service.drain()
        return out

    res_ok, res_bad = asyncio.run(run())
    # the unaffected DIMMs complete bit-exact
    assert not isinstance(res_ok, Exception), res_ok
    check_parity(ok_req, res_ok)
    # the dropped DIMM fails fast with the typed error
    assert isinstance(res_bad, svc.TableUnavailableError)
    assert res_bad.module == "B2"

    # a fresh request for the dropped DIMM also fails fast...
    assert isinstance(serve_all(service, [bad_req])[0],
                      svc.TableUnavailableError)
    # ...until the table is re-derived through the engine and reinstalled
    service.install_tables(
        fleet.build_tables(grid.select(["B2"]), tables.cand_v))
    res_again = serve_all(service, [bad_req])[0]
    assert not isinstance(res_again, Exception), res_again
    check_parity(bad_req, res_again)


def test_midstream_rederive_with_hammer_skewed_tables():
    """Mid-stream drop + re-derive with hammer-*aware* tables: the
    reinstalled row carries a skewed disturbance threshold, the service
    serves against the raised safety floor, and the reported per-candidate
    hammer margin is the reinstalled one."""
    grid, tables, wls, model = _env()
    di = tables.modules.index("B2")
    k_low = np.where(tables.valid[di])[0][0]
    scale = 0.9 / tables.hammer_margin[di, k_low]
    skewed = fleet.build_tables(grid.select(["B2"]), tables.cand_v,
                                hammer_scale={"B2": scale})
    assert skewed.valid.sum() < tables.valid[di].sum()   # the floor bit

    service = make_service(window_s=0.01)
    name = service.workload_names[0]
    req = svc.FleetRequest((name,), ("B2",), n_intervals=N_INTERVALS)
    service.drop_table("B2")
    assert isinstance(serve_all(service, [req])[0],
                      svc.TableUnavailableError)
    service.install_tables(skewed)
    res = serve_all(service, [req])[0]
    assert not isinstance(res, Exception), res

    # reference: the direct batch path on the same skewed tables
    by_name = dict(wls)
    wb = WorkloadBatch.from_workloads([(name, by_name[name])])
    from repro.core import voltron
    phases = voltron._phase_matrix(wb.names, N_INTERVALS,
                                   voltron.DEFAULT_INTERVAL_CYCLES,
                                   None, 0.15)
    ref = fleet.run_fleet_batched(wb, skewed, phases, model.coef_low,
                                  model.coef_high, req.target_loss_pct,
                                  dispatch="direct")
    np.testing.assert_array_equal(res.selected_voltages,
                                  ref.selected_voltages)
    np.testing.assert_array_equal(res.hammer_margin, skewed.hammer_margin)
    # the served selections respect the hammer-raised floor
    chosen = set(np.unique(res.selected_voltages))
    assert chosen <= set(skewed.cand_v[skewed.valid[0]])
    # restore the shared _env tables for the tests that follow
    service.install_tables(tables)
    restored = serve_all(service, [req])[0]
    assert not isinstance(restored, Exception), restored
    check_parity(req, restored)


def test_fleet_decorrelated_phases_parity():
    """FleetRequest(decorrelate_phases=True): each (workload, DIMM) lane
    draws its own phase column; the coalesced result matches the direct
    batch path on the same [T, W*D] matrix."""
    from repro.core import voltron
    _, tables, wls, model = _env()
    service = make_service(window_s=0.01)
    names = service.workload_names[:2]
    req = svc.FleetRequest(names, ("A1", "B2"), n_intervals=N_INTERVALS,
                           decorrelate_phases=True)
    res = serve_all(service, [req])[0]
    assert not isinstance(res, Exception), res

    by_name = dict(wls)
    wb = WorkloadBatch.from_workloads([(n, by_name[n]) for n in names])
    phases = voltron.fleet_phase_matrix(
        wb.names, req.modules, N_INTERVALS,
        voltron.DEFAULT_INTERVAL_CYCLES, None, 0.15)
    ref = fleet.run_fleet_batched(
        wb, tables.select(list(req.modules)), phases, model.coef_low,
        model.coef_high, req.target_loss_pct, dispatch="direct")
    np.testing.assert_array_equal(res.selected_voltages,
                                  ref.selected_voltages)
    np.testing.assert_allclose(res.perf_loss_pct, ref.perf_loss_pct,
                               rtol=1e-5, atol=1e-8)
    # and it genuinely decorrelates: differs from the shared-phase result
    shared = serve_all(service, [svc.FleetRequest(
        names, ("A1", "B2"), n_intervals=N_INTERVALS)])[0]
    assert not np.allclose(res.perf_loss_pct, shared.perf_loss_pct)


def test_unknown_module_and_workload_fail_typed():
    service = make_service(window_s=0.01)
    with pytest.raises(svc.ServiceError):
        service.run_request(svc.MinLatencyRequest("Z9", (1.0,)))
    with pytest.raises(svc.ServiceError):
        service.run_request(svc.FleetRequest(("no-such-workload",), ("A1",)))


# --------------------------------------------------------------------------
# Property: random interleavings == direct single-request results
# --------------------------------------------------------------------------
@settings(max_examples=4, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000))
def test_property_interleaved_stream_parity(seed):
    rng = np.random.default_rng(seed)
    service = make_service(window_s=0.005)
    reqs = fleet_serve.request_mix(rng, 8, MODULES, service.workload_names,
                                   n_intervals=N_INTERVALS,
                                   characterize_frac=0.25)
    results = serve_all(service, reqs)
    for req, res in zip(reqs, results):
        assert not isinstance(res, Exception), res
        check_parity(req, res)
    assert service.stats()["completed"] == len(reqs)


def test_chunked_megabatch_straddle_parity():
    # a resident budget of 4 min-latency lanes with two 3-lane requests:
    # the first request leaves the group below the size trigger, the second
    # overshoots it, so one 6-lane megabatch streams through the chunked
    # path — and the second request's lanes straddle the 4-lane chunk
    # boundary.  Still bit-exact per lane.
    service = make_service(window_s=0.05,
                           max_elements_resident=4 * LANE_COST,
                           max_queue_elements=1 << 30)
    reqs = [svc.MinLatencyRequest("A1", (1.0, 1.1, 1.25)),
            svc.MinLatencyRequest("B2", (0.95, 1.2, 1.3))]
    chunked0 = dispatch.stats("min_latency")["chunked_calls"]
    results = serve_all(service, reqs)
    assert dispatch.stats("min_latency")["chunked_calls"] == chunked0 + 1
    assert service.stats()["max_flush_lanes"] == 6
    for req, res in zip(reqs, results):
        assert not isinstance(res, Exception), res
        check_parity(req, res)


# --------------------------------------------------------------------------
# Observability: dispatch wall-time counters + service gauges
# --------------------------------------------------------------------------
def test_dispatch_us_counters_and_service_gauges():
    dispatch.reset_stats()
    service = make_service(window_s=0.01)
    service.run_request(svc.MinLatencyRequest("A1", (1.0, 1.2)))
    s = dispatch.stats("min_latency")
    assert s["calls"] == 1
    assert s["dispatch_us_total"] > 0.0
    assert s["dispatch_us_last"] > 0.0
    assert s["dispatch_us_total"] >= s["dispatch_us_last"]

    serve_all(service, [svc.MinLatencyRequest("B2", (1.1,))])
    gauges = dispatch.stats("service")
    assert gauges["queue_depth"] == 0 and gauges["queue_elements"] == 0
    # cumulative time grows call over call
    s2 = dispatch.stats("min_latency")
    assert s2["calls"] == 2
    assert s2["dispatch_us_total"] > s["dispatch_us_total"]

    dispatch.reset_stats()
    assert "queue_depth" not in dispatch.stats("service")
    assert dispatch.stats("min_latency")["dispatch_us_total"] == 0.0
