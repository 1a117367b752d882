"""The window's reservoir: which job outputs outlive their job for the
check, on the CPU.  Kept jobs depend on the seed and the job count alone,
every job is kept at the same rate, no more than ``k`` are ever held, and
a fault in the kept jobs still makes the check fail."""
import gc
import weakref

import numpy as np
import pytest

from bench import run, spec

SEED = 2**31 + 4242


def kept_indices(seed: int, k: int, n: int) -> list:
    res = run.Reservoir(k, run.keep_rng(seed))
    for i in range(n):
        res.offer(i, object())
    return sorted(i for i, _ in res.items)


@pytest.mark.parametrize("k,n", [(1, 1), (1, 60), (2, 3), (16, 75),
                                 (64, 40)])
def test_same_seed_and_count_keep_the_same_jobs(k, n):
    a = kept_indices(SEED, k, n)
    assert a == kept_indices(SEED, k, n)
    assert len(a) == min(k, n) == len(set(a))
    assert all(0 <= i < n for i in a)
    if n <= k:
        assert a == list(range(n))


def test_other_seeds_keep_other_jobs():
    draws = {tuple(kept_indices(SEED + s, 2, 50)) for s in range(20)}
    assert len(draws) > 10


@pytest.mark.parametrize("k,n", [(1, 10), (3, 20)])
def test_every_job_is_kept_at_the_same_rate(k, n):
    seeds = 4000
    counts = np.zeros(n)
    for s in range(seeds):
        counts[kept_indices(SEED + s, k, n)] += 1
    rate = counts / seeds
    # binomial sd at p = k/n over 4,000 seeds is at most 0.0075
    assert np.abs(rate - k / n).max() < 0.035, rate


class _Output:
    pass


def test_never_more_than_k_are_held():
    k = 3
    res = run.Reservoir(k, run.keep_rng(SEED))
    refs = []
    for i in range(40):
        out = _Output()
        refs.append(weakref.ref(out))
        res.offer(i, out)
        del out
        gc.collect()
        assert len(res.items) <= k
        assert sum(r() is not None for r in refs) <= k
    assert [i for i, r in enumerate(refs) if r() is not None] \
        == sorted(i for i, _ in res.items)


def test_reservoir_refuses_to_keep_nothing():
    with pytest.raises(ValueError):
        run.Reservoir(0, run.keep_rng(SEED))


class _Counting:
    """A job whose outputs carry their index; ``keep_jobs`` of them."""

    metric = "job_s"

    def __init__(self, k):
        self.keep_jobs, self.seed, self.live = k, SEED, []

    def run(self, i):
        out = _Output()
        out.job = i
        self.live.append(weakref.ref(out))
        return out


def test_window_keeps_what_the_reservoir_draws():
    job = _Counting(4)
    res = run.batch_window(job, 0.05)
    n = res["attempted"]
    assert n == len(res["jobs"]) == len(job.live) == res["notes"]["jobs"]
    assert [o.job for o in res["outputs"]] == kept_indices(SEED, 4, n)
    gc.collect()
    assert sum(r() is not None for r in job.live) == len(res["outputs"])
    notes = res["notes"]
    assert 0 < notes["job_p50_s"] <= notes["job_p90_s"] <= notes["job_max_s"]
    assert notes["rss_growth_mb"] is None or np.isfinite(
        notes["rss_growth_mb"])


def _small(kind):
    """A small configuration and cell of each one-chip kind."""
    if kind == "tables":
        cfg = dict(spec.load_config("chang17-char31"),
                   modules=["A1", "B2", "C1", "C5"])
        cell = spec.load_cell("char31.tables")
        params = dict(cell["params"], temps=[20.0, 70.0], check_dimms=2)
    elif kind == "stress":
        cfg = dict(spec.load_config("chang17-char31"),
                   modules=["A1", "B2", "C5"], rows_per_bank=16,
                   row_bytes=256)
        cell = spec.load_cell("char31.stress")
        params = dict(cell["params"], voltages=[1.0, 1.2],
                      pattern_groups=[["0xaa", "0x55"]],
                      hammer_voltages=[1.1], hammer_counts=[5e5],
                      inject_impl="reference")
    else:
        cfg = dict(spec.load_config("chang17-voltron77"),
                   modules=["A1", "B2", "C5"], n_workloads=4)
        cell = spec.load_cell("voltron77.fleet")
        params = dict(cell["params"], impl="reference", check_lanes=6)
    return cfg, params


def _fault(kind, out):
    import dataclasses
    if kind == "tables":
        t = np.array(out["tables"].timings)
        t[..., 0] += 2.5
        return dict(out, tables=dataclasses.replace(out["tables"], timings=t))
    if kind == "stress":
        t1 = out["test1"]
        bits = np.array(t1.bit_errors)
        bits.flat[-1] += 1
        return dict(out, test1=dataclasses.replace(t1, bit_errors=bits))
    r = out["result"]
    return dict(out, result=dataclasses.replace(
        r, dram_energy_savings_pct=r.dram_energy_savings_pct + 0.01))


@pytest.mark.parametrize("kind", ["tables", "stress", "fleet"])
def test_fault_in_the_kept_jobs_is_caught(kind):
    """Jobs offered to the reservoir as the window offers them, with a
    fault in exactly the jobs it keeps: the check fails.  The same jobs
    with the fault in the others only: the check passes, so it reads the
    kept jobs and nothing else."""
    cfg, params = _small(kind)
    job = spec.load_module("jobs", kind).Job(cfg, params, SEED)
    n = job.keep_jobs + 2
    kept = set(kept_indices(SEED, job.keep_jobs, n))
    runs = [job.run(i) for i in range(n)]
    for faulty, fails in ((kept, True), (set(range(n)) - kept, False)):
        res = run.Reservoir(job.keep_jobs, run.keep_rng(SEED))
        for i, out in enumerate(runs):
            res.offer(i, _fault(kind, out) if i in faulty else out)
        checks = job.check(res.kept())
        assert any(v > lim for _, v, lim in checks) is fails, checks
