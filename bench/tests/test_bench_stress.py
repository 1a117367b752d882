"""The char31.stress cell at a tiny geometry on the CPU: the harness's
run with the look for chips skipped, the precision control, and faults
planted in the timed path, each of which ``correct`` has to catch."""
import dataclasses

import numpy as np
import pytest

from bench import run, spec

SEED = 2**31 + 12345


def tiny():
    cfg = dict(spec.load_config("chang17-char31"), modules=["A1", "B2", "C5"],
               rows_per_bank=16, row_bytes=256)
    cell = spec.load_cell("char31.stress")
    cell = dict(cell, params=dict(
        cell["params"], voltages=[1.0, 1.2], pattern_groups=[["0xaa", "0x55"]],
        hammer_voltages=[1.1], hammer_counts=[5e5], inject_impl="reference"))
    return cfg, cell


def run_tiny():
    cfg, cell = tiny()
    return run.run_cell(spec.load_benchmark(), "char31.stress", SEED, 0.2,
                        False, cell=cell, config=cfg,
                        log=lambda *a, **k: None)


def test_plane_bytes_from_the_cell_shapes():
    jm = spec.load_module("jobs", "stress")
    cfg, cell = spec.load_config("chang17-char31"), spec.load_cell(
        "char31.stress")
    job = jm.Job.__new__(jm.Job)
    job.config, job.params = cfg, cell["params"]
    assert job.lanes() == (31 * 4 * 3, 31 * 2 * 3)
    assert job.plane_bytes() == 558 * 8 * 1365 * 8192


def test_sound_run_is_correct():
    out = run_tiny()
    assert out["correct"] is True
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "notes", "checked"]
    assert set(out["metrics"]) == {"job_s", "setup_s"}
    assert out["notes"]["compiles_in_window"] == 0
    assert all(c["value"] == 0 for c in out["checked"].values())


def test_precision_control_fails():
    from bench import control
    cfg, cell = tiny()
    out = control.readings("char31.stress", SEED, 0.2, True, cell=cell,
                           config=cfg)
    assert all(v == 0 for v in out["program"].values())
    assert any(v > 0 for v in out["control"].values())


def _alter_one(res):
    bits = np.array(res.bit_errors)
    bits.flat[-1] += 1
    return dataclasses.replace(res, bit_errors=bits)


def _drop_half(res):
    n = res.bit_errors.shape[0]
    keep = (np.arange(n) < n // 2).reshape((n,) + (1,) * 3)
    return dataclasses.replace(
        res, bit_errors=np.where(keep, res.bit_errors, 0),
        erroneous_lines=np.where(keep, res.erroneous_lines, 0),
        error_rows=np.where(keep[..., None, None], res.error_rows, False))


@pytest.mark.parametrize("entry", ["run_batch", "run_hammer_batch"])
@pytest.mark.parametrize("fault", [_alter_one, _drop_half])
def test_planted_fault_is_caught(monkeypatch, entry, fault):
    from repro.engine import test1
    real = getattr(test1, entry)
    monkeypatch.setattr(test1, entry,
                        lambda *a, **k: fault(real(*a, **k)))
    assert run_tiny()["correct"] is False
