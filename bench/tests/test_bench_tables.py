"""The char31.tables cell on four DIMMs and two temperatures on the CPU:
the harness's run with the look for chips skipped, the precision control,
and faults planted in the timed path."""
import dataclasses

import numpy as np
import pytest

from bench import run, spec

SEED = 2**31 + 31


def small():
    cfg = dict(spec.load_config("chang17-char31"),
               modules=["A1", "B2", "C1", "C5"])
    cell = spec.load_cell("char31.tables")
    cell = dict(cell, params=dict(cell["params"], temps=[20.0, 70.0],
                                  check_dimms=2))
    return cfg, cell


def run_small():
    cfg, cell = small()
    return run.run_cell(spec.load_benchmark(), "char31.tables", SEED, 0.5,
                        False, cell=cell, config=cfg,
                        log=lambda *a, **k: None)


def test_dimm_order_is_a_seeded_permutation():
    cfg, cell = small()
    job = spec.load_module("jobs", "tables").Job(cfg, cell["params"], SEED)
    assert sorted(job.order(0)) == sorted(cfg["modules"])
    assert job.order(0) == job.order(0)
    assert job.voltages.size == 19 and job.voltages[0] == 1.35


def test_sound_run_is_correct():
    out = run_small()
    assert out["correct"] is True
    assert set(out["metrics"]) == {"requalify_s", "setup_s"}
    assert out["notes"]["compiles_in_window"] == 0


def test_precision_control_fails():
    cfg, cell = small()
    job = spec.load_module("jobs", "tables").Job(cfg, cell["params"], SEED)
    outs = [job.run(0)]
    assert all(v <= lim for _, v, lim in job.check(outs))
    assert any(v > lim for _, v, lim in job.control(outs))


def _alter_char(res):
    return dataclasses.replace(res, ber=res.ber * (1 + 1e-3))


def _drop_half_char(res):
    d = res.line_error_fraction.shape[0]
    keep = np.arange(d) >= d // 2
    z = lambda a: np.where(keep.reshape((d,) + (1,) * (a.ndim - 1)), a, 0.0)
    return dataclasses.replace(
        res, line_error_fraction=z(res.line_error_fraction), ber=z(res.ber),
        row_error_prob=z(res.row_error_prob),
        line_error_prob=z(res.line_error_prob))


def _alter_table(res):
    t = np.array(res.timings)
    t[..., 0] += 2.5
    return dataclasses.replace(res, timings=t)


@pytest.mark.parametrize("where,fault", [
    ("characterize_batch", _alter_char), ("characterize_batch",
                                          _drop_half_char),
    ("build_tables", _alter_table)])
def test_planted_fault_is_caught(monkeypatch, where, fault):
    from repro import engine
    from repro.engine import fleet
    mod = engine if where == "characterize_batch" else fleet
    real = getattr(mod, where)
    monkeypatch.setattr(mod, where, lambda *a, **k: fault(real(*a, **k)))
    assert run_small()["correct"] is False
