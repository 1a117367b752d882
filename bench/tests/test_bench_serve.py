"""The voltron77.serve cell at a small fleet on the CPU: the harness's
run with the look for chips skipped, the open-loop arithmetic, the
precision control, and faults planted in the served path."""
import numpy as np
import pytest

from bench import loadgen, run, spec

SEED = 2**31 + 4242


def small(**kw):
    cfg = dict(spec.load_config("chang17-voltron77"),
               modules=["A1", "B2", "C5"], n_workloads=4)
    cell = spec.load_cell("voltron77.serve")
    cell = dict(cell, params=dict(cell["params"], rate=40.0,
                                  check_requests=24, prewarm_lanes=32, **kw))
    return cfg, cell


def bench_with_serve() -> dict:
    """``BENCHMARK.json`` with the served cell and its end-to-end metrics,
    which the benchmark does not list yet (PERF.md, Open questions)."""
    bench = spec.load_benchmark()
    bench["workloads"].append({"name": "voltron77.serve",
                               "config": "chang17-voltron77",
                               "traffic": "open_loop_mix", "chips": 1})
    for name, unit in (("serve_p95_ms", "ms"), ("serve_rps", "req/s")):
        bench["end_to_end"].append({"name": name, "unit": unit,
                                    "workloads": ["voltron77.serve"]})
    return bench


def run_small():
    cfg, cell = small()
    return run.run_cell(bench_with_serve(), "voltron77.serve", SEED, 1.0,
                        False, cell=cell, config=cfg,
                        log=lambda *a, **k: None)


def test_arrivals_are_bursts_at_the_offered_rate():
    t = loadgen.arrivals(20, rate=40.0, burst=8)
    assert t.tolist() == [0.0] * 8 + [0.2] * 8 + [0.4] * 4
    assert len(loadgen.arrivals(400, 40.0, 8)) / 40.0 == pytest.approx(
        (loadgen.arrivals(400, 40.0, 8)[-1] + 0.2))
    with pytest.raises(ValueError):
        loadgen.arrivals(4, 0.0, 8)


def test_request_mix_is_seeded_and_shaped():
    mods, wls = ["A1", "B2"], ["w0", "w1", "w2"]
    a = loadgen.request_mix(np.random.default_rng(5), 400, mods, wls,
                            characterize_frac=0.25)
    b = loadgen.request_mix(np.random.default_rng(5), 400, mods, wls,
                            characterize_frac=0.25)
    assert a == b
    kinds = [r[0] for r in a]
    assert 0.18 < kinds.count("characterize") / 400 < 0.32
    assert 0.38 < kinds.count("min_latency") / 400 < 0.52
    for r in a:
        assert 1 <= len(r[2]) <= 2
        if r[0] == "fleet":
            assert 1 <= len(r[1]) <= 2 and set(r[1]) <= set(wls)


def test_open_loop_times_from_the_schedule_and_counts_errors():
    import asyncio
    import time

    async def submit(r):
        if r == "bad":
            raise RuntimeError("shed")
        await asyncio.sleep(0.01)
        return r

    recs = asyncio.run(loadgen.open_loop(
        submit, ["a", "bad", "c"], np.array([0.0, 0.0, 0.05]),
        time.perf_counter))
    assert [r[3] for r in recs] == ["a", None, "c"]
    assert isinstance(recs[1][4], RuntimeError)
    assert recs[2][0] - recs[0][0] == pytest.approx(0.05)
    assert all(r[2] >= r[0] for r in recs)


def test_sound_run_is_correct():
    out = run_small()
    assert out["correct"] is True
    assert set(out["metrics"]) == {"serve_p95_ms", "serve_rps", "setup_s"}
    assert out["notes"]["backend_compiles_in_window"] == 0
    assert out["notes"]["compiles_in_window"] == 0
    assert out["attempted"] == 40 and out["failed"] == 0


def test_precision_control_fails():
    cfg, cell = small()
    jm = spec.load_module("jobs", "serve")
    job = jm.Job(cfg, cell["params"], SEED)
    job.warm()
    res = jm.window(job, 1.0)
    assert all(v <= lim for _, v, lim in job.check(res["outputs"]))
    assert any(v > lim for _, v, lim in job.control(res["outputs"]))


def _alter_answer(out):
    """Every lane of every flush altered where the dispatch produces it."""
    return {k: (v + 1 if np.issubdtype(np.asarray(v).dtype, np.number)
                and k not in ("selected_idx",) else v)
            for k, v in out.items()}


def _drop_half(out):
    n = next(iter(out.values())).shape[0]
    keep = np.arange(n) < n // 2
    return {k: np.where(keep.reshape((n,) + (1,) * (np.ndim(v) - 1)), v,
                        np.zeros_like(v)) for k, v in out.items()}


@pytest.mark.parametrize("fault", [_alter_answer, _drop_half])
def test_planted_fault_is_caught(monkeypatch, fault):
    from repro.engine import service
    real = service.EngineService._run_dispatch
    monkeypatch.setattr(service.EngineService, "_run_dispatch",
                        lambda self, *a, **k: fault(real(self, *a, **k)))
    assert run_small()["correct"] is False
