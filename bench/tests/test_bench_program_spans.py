"""The program's span log on the trace's clock (``bench/program_spans.py``),
on a synthetic log laid over the recorded stress slice: the alignment by
the ``bench.job`` spans, the stage sums, the attribution of each idle
stretch to the innermost program span, and the refusals."""
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from bench import program_spans, spec, trace
from repro.engine import dispatch

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "trace_stress_slice.json")
JOB_START_S = 1000.0            # the job record's perf_counter start
MS = 1_000_000


def _rec(i, parent, name, start_ms, end_ms, **attrs):
    """A span record ``start_ms``..``end_ms`` into the recorded job."""
    base = int(JOB_START_S * 1e9)
    return dispatch.SpanRecord(i, parent, "repro." + name,
                               base + int(start_ms * MS),
                               base + int(end_ms * MS), attrs)


# one Test-1 call inside the recorded job (0-15 ms; the device starts at
# 3 ms); the warm job's spans, long before the window, are left out
LOG = [
    _rec(1, None, "test1", -900.0, -800.0),
    _rec(3, 2, "test1.lower", 0.7, 1.8),
    _rec(4, 2, "test1.put", 1.8, 2.9, bytes=24_000_000),
    _rec(5, 2, "test1.dispatch", 2.9, 14.5),
    _rec(6, 2, "test1.fetch", 14.5, 14.8, bytes=7_000_000),
    _rec(2, None, "test1", 0.6, 14.9),
]


@pytest.fixture(scope="module")
def rec():
    with open(DATA) as f:
        r = json.load(f)
    r["devices"] = {int(k): v for k, v in r["devices"].items()}
    return r


def _ctx(rec, jobs=None):
    jobs = jobs or [{"start": JOB_START_S, "end": JOB_START_S + 0.015,
                     "dispatch_s": 0.0116}]
    return {"trace": rec, "chips": 1, "window": {"jobs": jobs},
            "job": SimpleNamespace()}


@pytest.fixture
def log(monkeypatch):
    state = {"records": list(LOG), "dropped": 0}
    monkeypatch.setattr(dispatch, "spans",
                        lambda: (list(state["records"]), state["dropped"]))
    return state


def _brute_idle(rec) -> dict:
    """Idle ns per innermost span name, one boolean per nanosecond."""
    lo, hi = (int(t) for t in trace.span(rec, "bench.window"))
    busy = np.zeros(hi - lo, bool)
    for _, s, e in rec["devices"][0]["ops"]:
        a, b = max(int(s), lo), min(int(e), hi)
        if b > a:
            busy[a - lo:b - lo] = True
    label = np.full(hi - lo, -1)
    spans = sorted(LOG[1:], key=lambda r: r.start_ns)   # inner ones later
    base = int(JOB_START_S * 1e9)
    for k, r in enumerate(spans):
        label[r.start_ns - base - lo:r.end_ns - base - lo] = k
    counts = np.bincount(label[~busy] + 1, minlength=len(spans) + 1)
    out = {None: int(counts[0])}
    for k, r in enumerate(spans):
        out[r.name] = out.get(r.name, 0) + int(counts[k + 1])
    return out


def test_idle_is_put_down_to_the_innermost_span(rec, log):
    got = program_spans.idle_ms(_ctx(rec))
    want = _brute_idle(rec)
    assert set(got) == {k for k, v in want.items() if v}
    for name, ns in want.items():
        assert got.get(name, 0.0) == pytest.approx(ns / 1e6, abs=1e-5), name
    # one idle stretch (0-3 ms) splits into five pieces
    assert got[None] == pytest.approx(0.6)
    assert got["repro.test1"] == pytest.approx(0.1)
    assert got["repro.test1.lower"] == pytest.approx(1.1)
    assert got["repro.test1.put"] == pytest.approx(1.1)
    assert got["repro.test1.dispatch"] == pytest.approx(0.1, abs=1e-3)
    host = program_spans.idle_host_ms(_ctx(rec))
    assert host == pytest.approx(0.1 + 1.1 + 1.1)


def test_stage_self_times(rec, log):
    ctx = _ctx(rec)
    assert program_spans.stage_ms(ctx, ("lower",)) == pytest.approx(1.1)
    assert program_spans.stage_ms(ctx, ("put", "fetch")) == \
        pytest.approx(1.4)
    # the entry's own time is what its stages leave
    assert program_spans.stage_ms(ctx, ("call",)) == pytest.approx(
        14.3 - 1.1 - 1.1 - 11.6 - 0.3)
    names = {r.name for r in program_spans.window_spans(ctx)}
    assert len(program_spans.window_spans(ctx)) == 5
    assert names == {r.name for r in LOG}


def test_readers_of_the_six_metrics(rec, log):
    ctx = _ctx(rec)
    read = {name: spec.load_module("metrics", name).read(ctx)
            for name in ("lower_ms.job", "lower_ms.requalify",
                         "transfer_ms.job", "transfer_ms.requalify",
                         "idle_host_ms.job", "idle_host_ms.requalify")}
    assert read["lower_ms.job"] == read["lower_ms.requalify"] == \
        pytest.approx(1.1)
    assert read["transfer_ms.job"] == read["transfer_ms.requalify"] == \
        pytest.approx(1.4)
    assert read["idle_host_ms.job"] == read["idle_host_ms.requalify"] == \
        pytest.approx(2.3)


def test_offset_is_the_median_over_jobs(rec):
    two = dict(rec, spans=rec["spans"] + [["bench.job", 20 * MS, 30 * MS]])
    jobs = [{"start": 5.0, "end": 5.015},
            {"start": 5.020 + 40e-6, "end": 5.030}]
    ctx = _ctx(two, jobs)
    assert program_spans.clock_offsets(ctx) == pytest.approx(
        [-5e9, -5e9 - 40e3])
    assert program_spans.clock_offset_ns(ctx) == pytest.approx(-5e9 - 20e3)
    jobs[1]["start"] = 5.020 + 150e-6
    with pytest.raises(trace.TraceError):
        program_spans.clock_offset_ns(ctx)
    with pytest.raises(trace.TraceError):
        program_spans.clock_offsets(_ctx(two, jobs[:1]))


def test_dropped_records_inside_the_window_refuse(rec, log):
    log["dropped"] = 3            # older than the oldest kept: harmless
    log["records"] = LOG[:1] + LOG[1:]
    assert program_spans.stage_ms(_ctx(rec), ("lower",)) == \
        pytest.approx(1.1)
    log["records"] = LOG[1:]      # the oldest kept closes in the window
    with pytest.raises(trace.TraceError):
        program_spans.stage_ms(_ctx(rec), ("lower",))
    with pytest.raises(trace.TraceError):
        program_spans.idle_host_ms(_ctx(rec))


def test_a_program_without_the_log_reads_none(rec, monkeypatch):
    monkeypatch.delattr(dispatch, "spans")
    ctx = _ctx(rec)
    assert program_spans.window_spans(ctx) is None
    for name in ("lower_ms.job", "transfer_ms.requalify",
                 "idle_host_ms.job"):
        assert spec.load_module("metrics", name).read(ctx) is None


def test_stage_names():
    assert program_spans.stage("repro.fleet.lower") == "lower"
    assert program_spans.stage("repro.test1/chunked.dispatch") == "dispatch"
    assert program_spans.stage("repro.tables.EccAdmission") == \
        "EccAdmission"
    assert program_spans.stage("repro.characterize") == "call"
