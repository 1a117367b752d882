"""The voltron77.fleet cell at a small fleet on the CPU: the harness's
run with the look for chips skipped, the precision control, and faults
planted in the timed path, each of which ``correct`` has to catch."""
import dataclasses

import numpy as np
import pytest

from bench import run, spec

SEED = 2**31 + 777


def small():
    cfg = dict(spec.load_config("chang17-voltron77"),
               modules=["A1", "B2", "C5"], n_workloads=4)
    cell = spec.load_cell("voltron77.fleet")
    cell = dict(cell, params=dict(cell["params"], impl="reference",
                                  check_lanes=12))
    return cfg, cell


def run_small():
    cfg, cell = small()
    return run.run_cell(spec.load_benchmark(), "voltron77.fleet", SEED, 0.2,
                        False, cell=cell, config=cfg,
                        log=lambda *a, **k: None)


def test_phases_are_seeded_and_piecewise_constant():
    jm = spec.load_module("jobs", "fleet")
    a = jm.phases(np.random.default_rng([SEED, 1]), 25, 6, 0.15, 5)
    b = jm.phases(np.random.default_rng([SEED, 1]), 25, 6, 0.15, 5)
    assert a.shape == (25, 6) and np.array_equal(a, b)
    assert np.all(np.abs(a - 1.0) <= 0.15)
    assert np.array_equal(a[0:5], np.repeat(a[:1], 5, axis=0))
    c = jm.phases(np.random.default_rng([SEED, 2]), 25, 6, 0.15, 5)
    assert not np.array_equal(a, c)


def test_sound_run_is_correct():
    out = run_small()
    assert out["correct"] is True
    assert set(out["metrics"]) == {"job_s", "setup_s"}
    assert out["checked"]["lanes_with_other_voltages"]["value"] == 0
    assert out["checked"]["metric_gap_pp"]["value"] < 1e-3


def test_precision_control_fails():
    cfg, cell = small()
    job = spec.load_module("jobs", "fleet").Job(cfg, cell["params"], SEED)
    outs = [job.run(i) for i in range(2)]
    assert all(v <= lim for _, v, lim in job.check(outs))
    assert any(v > lim for _, v, lim in job.control(outs))


def _alter_answer(res):
    """Every lane's energy saving moved by 0.01 pp where it is produced."""
    return dataclasses.replace(
        res, dram_energy_savings_pct=res.dram_energy_savings_pct + 0.01)


def _drop_half(res):
    w = res.perf_loss_pct.shape[0]
    keep = (np.arange(w) < w // 2)[:, None]
    fields = {f: np.where(keep, getattr(res, f), 0.0) for f in
              ("perf_loss_pct", "dram_power_savings_pct",
               "dram_energy_savings_pct", "system_energy_savings_pct",
               "perf_per_watt_gain_pct")}
    return dataclasses.replace(res, **fields)


def _state_unchanged(res):
    """The controller never leaves its starting (nominal) voltage."""
    z = np.zeros_like(res.perf_loss_pct)
    return dataclasses.replace(
        res, selected_voltages=np.full_like(res.selected_voltages, 1.35),
        perf_loss_pct=z, dram_power_savings_pct=z,
        dram_energy_savings_pct=z, system_energy_savings_pct=z,
        perf_per_watt_gain_pct=z)


@pytest.mark.parametrize("fault", [_alter_answer, _drop_half, _state_unchanged])
def test_planted_fault_is_caught(monkeypatch, fault):
    from repro.engine import fleet
    real = fleet.run_fleet_batched
    monkeypatch.setattr(fleet, "run_fleet_batched",
                        lambda *a, **k: fault(real(*a, **k)))
    assert run_small()["correct"] is False
