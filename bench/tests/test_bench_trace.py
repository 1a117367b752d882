import json
import os

import numpy as np
import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "trace_stress_slice.json")


@pytest.fixture(scope="module")
def rec():
    with open(DATA) as f:
        r = json.load(f)
    r["devices"] = {int(k): v for k, v in r["devices"].items()}
    return r


def _mask_busy(ops, lo, hi):
    """Busy nanoseconds by brute force: a boolean per nanosecond."""
    lo, hi = int(lo), int(hi)
    m = np.zeros(hi - lo, bool)
    for _, s, e in ops:
        a, b = max(int(s), lo), min(int(e), hi)
        if b > a:
            m[a - lo:b - lo] = True
    return int(m.sum()), m


def test_busy_is_the_union_of_operations(rec):
    ops = rec["devices"][0]["ops"]
    lo, hi = trace.span(rec, "bench.window")
    want, _ = _mask_busy(ops, lo, hi)
    assert trace.busy_ns(ops, lo, hi) == pytest.approx(want, abs=len(ops))
    # nested operations (the chunk loop around its body) count once
    assert trace.busy_ns(ops, lo, hi) < sum(e - s for _, s, e in ops)
    share = trace.idle_share(ops, lo, hi)
    assert share == pytest.approx(1 - want / (hi - lo), abs=1e-4)
    assert 0 < share < 1


def test_overlap_counts_once_and_clips_to_the_window():
    ops = [["a", 0, 10], ["b", 5, 15], ["c", 20, 30], ["d", 28, 50]]
    assert trace.busy_ns(ops, 0, 40) == 15 + 20
    assert trace.idle_share(ops, 0, 40) == pytest.approx(5 / 40)
    with pytest.raises(trace.TraceError):
        trace.idle_share(ops, 5, 5)


def test_module_time_by_name_inside_entry_spans(rec):
    mods = rec["devices"][0]["modules"]
    spans = [(s, e) for n, s, e in rec["spans"] if n == "bench.entry.test1"]
    got = trace.module_ns(mods, [r"jit_fn\("], spans)
    want = sum(min(e, spans[0][1]) - max(s, spans[0][0])
               for n, s, e in mods if n.startswith("jit_fn("))
    assert got == want > 0
    # a module outside the entry's spans does not count
    extra = mods + [["jit_fn(1)", -5e6, -4e6]]
    assert trace.module_ns(extra, [r"jit_fn\("], spans) == got


def test_missing_module_name_fails(rec):
    mods = rec["devices"][0]["modules"]
    with pytest.raises(trace.TraceError):
        trace.module_ns(mods, [r"jit__test1_flat_fn\("], [(0, 1e12)])


def test_idle_gaps_are_named_by_the_innermost_span(rec):
    ops = rec["devices"][0]["ops"]
    lo, hi = trace.span(rec, "bench.window")
    gaps = trace.idle_gaps(ops, rec["spans"], lo, hi)
    _, mask = _mask_busy(ops, lo, hi)
    assert len(gaps) <= 10
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    assert sum(g[1] for g in gaps) <= (hi - lo - mask.sum()) / 1e9 + 1e-6
    names = {g[0] for g in gaps}
    assert names <= {"bench.job", "bench.entry.test1"}
    # the entry span opens 0.5 ms into the job: a gap whose midpoint lies
    # past that is the entry's, one before it the job's
    toy = trace.idle_gaps([["op", 1000, 2000]], rec["spans"], 0, 1_200_000)
    assert toy[0] == ["bench.entry.test1", (1_200_000 - 2000) / 1e9]
    assert toy[1] == ["bench.job", 1000 / 1e9]


def test_top_ops_leave_out_enclosing_loops(rec):
    ops = rec["devices"][0]["ops"]
    lo, hi = trace.span(rec, "bench.window")
    top = trace.top_ops(ops, lo, hi)
    assert len(top) <= 10
    assert not any(n.startswith("while") for n, _ in top)
    assert "inject_pallas" in [n for n, _ in top]
    assert sum(d for _, d in top) <= trace.busy_ns(ops, lo, hi) / 1e9 + 1e-9


def test_span_must_be_unique(rec):
    with pytest.raises(trace.TraceError):
        trace.span(rec, "bench.nothing")


def test_op_name():
    assert trace.op_name("%inject_pallas.7 = u32[8,2]{1,0} custom-call(x)") \
        == "inject_pallas"
    assert trace.op_name("%while.5 = (s32[]) while(x)") == "while"


def test_window_idle_pct_averages_the_cell_chips(rec):
    lo, hi = trace.span(rec, "bench.window")
    one = 100 * trace.idle_share(rec["devices"][0]["ops"], lo, hi)
    assert trace.window_idle_pct(rec, 1) == pytest.approx(one)
    two = dict(rec, devices={0: rec["devices"][0],
                             1: {"ops": [["op", lo, hi]], "modules": []}})
    assert trace.window_idle_pct(two, 2) == pytest.approx(one / 2)


def test_every_listed_reader_reads_the_recorded_trace(rec):
    """Each per-layer reader of BENCHMARK.json loads, and on the recorded
    stress slice returns a number where its spans are there, else None."""
    from types import SimpleNamespace

    from bench import spec
    lo, hi = trace.span(rec, "bench.window")
    job = SimpleNamespace(plane_bytes=lambda: 558 * 8 * 1365 * 8192)
    ctx = {"job": job, "trace": rec, "chips": 1,
           "peaks": {"hbm_bytes_per_s": 819e9},
           "window": {"jobs": [{"start": 0.0, "end": 3.2,
                                "dispatch_s": 2.9}]}}
    read = {m["name"]: spec.load_module("metrics", m["name"]).read(ctx)
            for m in spec.load_benchmark()["per_layer"]}
    assert read["host_ms.job"] == read["host_ms.requalify"] == \
        pytest.approx(300.0)
    assert read["dispatch_ms.job"] == pytest.approx(2900.0)
    assert read["device_idle_pct.job"] == read["device_idle_pct.requalify"] \
        == pytest.approx(trace.window_idle_pct(rec, 1))
    assert 0 < read["test1_plane_roofline"] < 100 * (hi - lo)
    assert read["device_ms.controller_scan"] is None
