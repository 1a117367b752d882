"""The opfleet.x4 cell at a small fleet on four forced CPU devices: the
harness's run with the look for chips skipped, the precision control, and
faults planted in the timed path, each of which ``correct`` has to catch.
The check's picks are read at the cell's own size."""
import os
import subprocess
import sys
import textwrap
import types

import numpy as np
import pytest

from bench import spec

SEED = 2**31 + 1234
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = ["src", "."]
    import dataclasses
    import types
    import numpy as np
    import jax
    from bench import run, spec
    from repro.engine import fleet

    SEED, SCENARIO = int(sys.argv[1]), sys.argv[2]
    assert len(jax.devices()) == 4
    cfg = dict(spec.load_config("chang17-opfleet1024"), n_dimms=8,
               n_workloads=8)
    cell = spec.load_cell("opfleet.x4")
    cell = dict(cell, params=dict(cell["params"], impl="reference"))
    jobmod = spec.load_module("jobs", "opfleet")

    def picks():
        # what check draws: a window of --seconds 0 holds one job
        probe = jobmod.Job.__new__(jobmod.Job)
        probe.config, probe.params = cfg, cell["params"]
        probe.mesh = types.SimpleNamespace(devices=np.empty(4))
        probe.check_rng = np.random.default_rng(
            np.random.default_rng(SEED).integers(2**63))
        return probe, probe.picks(1)

    if SCENARIO == "swapped_row":
        real = fleet.build_tables

        def swapped(*a, **k):
            t = real(*a, **k)
            d = next(j for j in range(1, t.n_dimms)
                     if not np.array_equal(t.valid[0], t.valid[j]))
            order = np.arange(t.n_dimms)
            order[[0, d]] = order[[d, 0]]
            rows = {f: getattr(t, f)[order] for f in
                    ("timings", "valid", "lat_feat", "hammer_margin",
                     "correctable", "detectable", "silent")}
            return dataclasses.replace(t, **rows)
        fleet.build_tables = swapped
    elif SCENARIO == "wrong_selection":
        probe, chosen = picks()
        job, lane = next((j, n) for j, n in chosen
                         if probe.shard_of(n) > 0)
        w, d = divmod(lane, cfg["n_dimms"])
        real = fleet.run_fleet_batched

        def wrong(*a, **k):
            r = real(*a, **k)
            sel = r.selected_voltages.copy()
            v = sel[w, d, 3]
            sel[w, d, 3] = r.cand_v[-1] if v != r.cand_v[-1] else r.cand_v[0]
            return dataclasses.replace(r, selected_voltages=sel)
        fleet.run_fleet_batched = wrong
    if SCENARIO == "control":
        job = jobmod.Job(cfg, cell["params"], SEED)
        outs = [job.run(0)]
        print("PROGRAM", all(v <= lim for _, v, lim in job.check(outs)))
        print("CONTROL", all(v <= lim for _, v, lim in job.control(outs)))
    else:
        out = run.run_cell(spec.load_benchmark(), "opfleet.x4", SEED, 0.0,
                           False, cell=cell, config=cfg,
                           log=lambda *a, **k: None)
        print("CHECKED", out["checked"])
        print("CORRECT", out["correct"])
""")


def run_scenario(scenario: str) -> str:
    out = subprocess.run([sys.executable, "-c", SCRIPT, str(SEED), scenario],
                         capture_output=True, text=True, timeout=900,
                         cwd=ROOT, env=dict(os.environ))
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_picks_cover_every_chip_at_the_cell_size():
    """64 picks from the seed over the cell's 78,848 lanes in chunks of
    4,096: at least 8 from each chip's 1,024 positions of a chunk, in
    every job of a window."""
    cfg = spec.load_config("chang17-opfleet1024")
    cell = spec.load_cell("opfleet.x4")
    jobmod = spec.load_module("jobs", "opfleet")
    job = jobmod.Job.__new__(jobmod.Job)
    job.config, job.params = cfg, cell["params"]
    job.mesh = types.SimpleNamespace(devices=np.empty(4))
    job.check_rng = np.random.default_rng(SEED)
    assert job.lanes() == 78_848
    # the last chunk's 1,024 valid lanes all lie on the first chip
    assert list(job.shard_of([0, 1023, 1024, 4095, 4096, 77_823,
                              78_847])) == [0, 0, 1, 3, 0, 3, 0]
    for n_jobs in (1, 3):
        picks = job.picks(n_jobs)
        assert len(picks) == 64 == len(set(picks))
        assert all(0 <= j < n_jobs and 0 <= n < 78_848 for j, n in picks)
        counts = np.bincount(job.shard_of([n for _, n in picks]),
                             minlength=4)
        assert counts.min() >= 8, counts


def test_sound_run_is_correct():
    out = run_scenario("sound")
    assert "CORRECT True" in out, out


def test_precision_control_fails():
    out = run_scenario("control")
    assert "PROGRAM True" in out and "CONTROL False" in out, out


@pytest.mark.parametrize("fault", ["swapped_row", "wrong_selection"])
def test_planted_fault_is_caught(fault):
    out = run_scenario(fault)
    assert "CORRECT False" in out, out
