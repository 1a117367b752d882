"""Host spans of the dispatch layer (:func:`repro.engine.dispatch.span`).

Every dispatched entry point times its call, its operand lowering, the
copies to and from the device and the blocking execution as spans: each
a ``jax.profiler.TraceAnnotation`` named ``repro.<entry>[.<stage>]``, a
record in a bounded log, and a ``<stage>_us_total`` counter on the
entry's ``stats()`` row.  These tests pin the names and nesting, the
counters against the log, the log against the profiler's own trace, the
bound of the log, and the parent of work handed to the service's
executor thread.
"""
from __future__ import annotations

import asyncio
import collections
import glob
import os

import numpy as np
import pytest

from repro.engine import dispatch, fleet, population
from repro.engine import service as svc
from repro.engine import test1 as engine_test1

MODULES = ("A1", "B2", "C2")
CAND_V = (0.9, 1.0, 1.1, 1.2, 1.3, 1.35)


@pytest.fixture(scope="module")
def grid():
    return population.DimmGrid.from_population(MODULES)


def _characterize(grid):
    return population.characterize_batch(grid, [1.0, 1.2], (20.0, 50.0),
                                         ("0xaa",))


def _by_name(records) -> dict:
    out = collections.defaultdict(list)
    for r in records:
        out[r.name].append(r)
    return out


def _stage_totals_match_log(records) -> None:
    """Each entry's ``<stage>_us_total`` equals its spans' summed time."""
    want = collections.Counter()
    for r in records:
        entry, _, stage = r.name[len("repro."):].partition(".")
        want[entry, stage or "call"] += (r.end_ns - r.start_ns) / 1e3
    for (entry, stage), us in want.items():
        got = dispatch.stats(entry)[stage + "_us_total"]
        assert got == pytest.approx(us, rel=1e-9), (entry, stage)


def _children(records, parent) -> set:
    return {r.name for r in records if r.parent_id == parent.id}


def test_characterize_spans_nest_under_the_entry(grid):
    dispatch.reset_stats()
    _characterize(grid)
    records, dropped = dispatch.spans()
    assert dropped == 0
    names = _by_name(records)
    (top,) = names["repro.characterize"]
    assert top.parent_id is None
    stages = {"repro.characterize." + s
              for s in ("lower", "put", "dispatch", "fetch")}
    assert stages <= _children(records, top) <= stages | {
        "repro.characterize.compile"}
    assert all(top.start_ns <= r.start_ns <= r.end_ns <= top.end_ns
               for r in records)
    for stage in ("put", "fetch"):
        (r,) = names["repro.characterize." + stage]
        assert r.attrs["bytes"] > 0
    _stage_totals_match_log(records)
    assert dispatch.stats("characterize")["dispatch_us_last"] == pytest.approx(
        (names["repro.characterize.dispatch"][0].end_ns
         - names["repro.characterize.dispatch"][0].start_ns) / 1e3)


def test_test1_spans_nest_under_the_entry(grid):
    dispatch.reset_stats()
    engine_test1.run_batch(grid, [1.0, 1.2], [("0xaa", "0x55")], rows=8,
                           row_bytes=256, inject_impl="reference")
    records, _ = dispatch.spans()
    (top,) = _by_name(records)["repro.test1"]
    stages = {"repro.test1." + s for s in ("lower", "put", "dispatch",
                                           "fetch")}
    assert stages <= _children(records, top) <= stages | {
        "repro.test1.compile"}
    _stage_totals_match_log(records)


@pytest.mark.parametrize("entry", ["characterize", "test1"])
def test_fetch_counts_the_float64_words(grid, entry):
    """Every float64 output crosses as words: all of characterize's
    fetched bytes, none of Test 1's (integer counts)."""
    dispatch.reset_stats()
    if entry == "characterize":
        _characterize(grid)
    else:
        engine_test1.run_batch(grid, [1.0, 1.2], [("0xaa", "0x55")], rows=8,
                               row_bytes=256, inject_impl="reference")
    (fetch,) = _by_name(dispatch.spans()[0])[f"repro.{entry}.fetch"]
    assert fetch.attrs["bytes"] > 0
    want = fetch.attrs["bytes"] if entry == "characterize" else 0
    assert fetch.attrs["wire_bytes"] == want
    assert dispatch.stats(entry)["wire_bytes_total"] == want


def test_table_policies_nest_under_tables(grid):
    dispatch.reset_stats()
    fleet.build_tables(grid, np.array(CAND_V), policies=fleet.ecc_policies())
    records, _ = dispatch.spans()
    names = _by_name(records)
    (top,) = names["repro.tables"]
    assert top.parent_id is None
    assert _children(records, top) == {"repro.tables.MinLatencyFloor",
                                       "repro.tables.EccAdmission",
                                       "repro.tables.HammerFloor"}
    (minlat,) = names["repro.min_latency"]
    (beat,) = names["repro.beat_error"]
    assert minlat.parent_id == names["repro.tables.MinLatencyFloor"][0].id
    assert beat.parent_id == names["repro.tables.EccAdmission"][0].id
    assert "repro.min_latency.lower" in _children(records, minlat)
    assert "repro.beat_error.lower" in _children(records, beat)
    _stage_totals_match_log(records)


def test_spans_and_trace_events_differ_by_one_offset(grid, tmp_path):
    import jax
    from jax.profiler import ProfileData

    _characterize(grid)                    # compile outside the trace
    dispatch.reset_stats()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _characterize(grid)
        _characterize(grid)
    finally:
        jax.profiler.stop_trace()
    records, _ = dispatch.spans()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    events = sorted(
        ((e.name, e.start_ns, e.end_ns)
         for plane in ProfileData.from_file(path).planes
         for line in plane.lines for e in line.events
         if e.name.startswith("repro.")), key=lambda e: e[1])
    logged = sorted(records, key=lambda r: r.start_ns)
    assert [e[0] for e in events] == [r.name for r in logged]
    offsets = [t - r.start_ns for (_, t, _), r in zip(events, logged)] + [
        t - r.end_ns for (_, _, t), r in zip(events, logged)]
    assert max(offsets) - min(offsets) < 50_000


def test_bounded_log_drops_the_oldest(monkeypatch):
    monkeypatch.setattr(dispatch, "_SPANS", collections.deque(maxlen=3))
    dispatch.reset_stats()
    for i in range(5):
        with dispatch.span(f"bounded.s{i}"):
            pass
    records, dropped = dispatch.spans()
    assert [r.name for r in records] == [f"repro.bounded.s{i}"
                                         for i in (2, 3, 4)]
    assert dropped == 2
    # the counters keep every span
    assert set(dispatch.stats("bounded")) >= {f"s{i}_us_total"
                                              for i in range(5)}
    dispatch.reset_stats()
    assert dispatch.spans() == ([], 0)


def test_span_records_on_error_and_restores_the_parent():
    dispatch.reset_stats()
    with dispatch.span("outer"):
        with pytest.raises(ValueError):
            with dispatch.span("outer.inner"):
                raise ValueError("boom")
        with dispatch.span("outer.after"):
            pass
    records, _ = dispatch.spans()
    by = {r.name: r for r in records}
    outer = by["repro.outer"]
    assert by["repro.outer.inner"].parent_id == outer.id
    assert by["repro.outer.after"].parent_id == outer.id
    assert outer.parent_id is None


def test_service_flush_keeps_its_parent(grid):
    service = svc.EngineService(grid, config=svc.ServiceConfig(
        window_s=0.01))
    requests = [svc.MinLatencyRequest("A1", (1.0, 1.2)),
                svc.MinLatencyRequest("B2", (1.1,))]

    async def run():
        with dispatch.span("client"):
            out = await asyncio.gather(*(service.submit(r)
                                         for r in requests))
            await service.drain()
        await service.aclose()
        return out

    dispatch.reset_stats()
    out = asyncio.run(run())
    assert len(out) == 2
    records, _ = dispatch.spans()
    names = _by_name(records)
    (client,) = names["repro.client"]
    flushed = [r for r in records if r.name.startswith("repro.min_latency.")
               and not r.name.endswith(".lower")]
    # put / dispatch / fetch ran on the executor thread, under the client
    assert {r.name for r in flushed} >= {"repro.min_latency.put",
                                         "repro.min_latency.dispatch",
                                         "repro.min_latency.fetch"}
    assert all(r.parent_id == client.id for r in flushed)
    assert all(r.parent_id == client.id
               for r in names["repro.min_latency.lower"])


def _scaled(x, *, factor):
    return x * factor


def test_resident_executables_are_named_after_their_function():
    import functools

    import jax.numpy as jnp
    fn = functools.partial(functools.partial(_scaled), factor=2.0)
    dispatch.aot_call("named_entry", fn, (jnp.ones(4),),
                      statics_key=("named",))
    (compiled,) = dispatch.executables("named_entry")
    assert compiled.as_text().startswith("HloModule jit__scaled")
