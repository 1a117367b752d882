"""``chip_smoke.py``'s phases at tiny sizes on the CPU, references on.

Each phase function runs through the same public entry points and the
same reference comparison as on the chip (Pallas in interpret mode here);
the entry must refuse to run any phase without a TPU.
"""
import dataclasses
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke  # noqa: E402

from repro import engine  # noqa: E402
from repro.core import perf_model  # noqa: E402
from repro.engine import dispatch  # noqa: E402

MODULES = ("A1", "B2", "C1", "C3")


@pytest.fixture(scope="module")
def grid():
    return engine.DimmGrid.from_population(MODULES)


@pytest.fixture(scope="module")
def tables(grid):
    res = chip_smoke.phase_tables(grid, rng=np.random.default_rng(1),
                                  n_sample=2)
    assert res["max_abs_diff"] <= chip_smoke.CHAR_ATOL
    return res["result"]


@pytest.fixture(scope="module")
def model():
    return perf_model.fit()


def test_lane_rows_fill_the_dispatch_budget():
    rows = chip_smoke.lane_rows()
    words = chip_smoke.ROW_BYTES // 4
    per_row = (chip_smoke.NPLANES + 4) * 8 * words
    assert chip_smoke.ROW_BYTES == 8192
    assert rows * per_row <= dispatch.DEFAULT_MAX_ELEMENTS_RESIDENT \
        < (rows + 1) * per_row


def test_characterize_phase_matches_scalar(grid):
    res = chip_smoke.phase_characterize(
        grid, voltages=chip_smoke.CHAR_VOLTAGES[::6], temps=(20.0, 70.0),
        rng=np.random.default_rng(0), n_sample=3)
    assert res["lanes"] == len(MODULES) * 4 * 2
    assert res["max_abs_diff"] <= chip_smoke.CHAR_ATOL


def test_stress_phase_matches_scalar(grid):
    res = chip_smoke.phase_stress(
        grid.select(["A1", "C1"]), voltages=(1.35, 1.0),
        hammer_voltages=(1.1,), rows=8, row_bytes=1024,
        inject_impl="pallas_interpret", rng=np.random.default_rng(0),
        n_sample=2)
    t1, ham = res["test1"], res["hammer"]
    assert t1.bit_errors[:, 0].sum() == 0 < t1.bit_errors[:, 1].sum()
    assert ham.bit_errors.shape == (2, 1, len(chip_smoke.HAMMER_COUNTS), 1)


def test_tables_phase_builds_the_ecc_stack(tables):
    assert tables.stack_name == "min_latency+ecc+hammer"
    assert tables.valid[:, -1].all()


def test_fleet_phase_matches_reference(tables, model):
    wls = chip_smoke.fleet_workloads()
    res = chip_smoke.phase_fleet(
        tables, wls[:2] + wls[-1:], n_intervals=4, impl="pallas_interpret",
        model=model, rng=np.random.default_rng(0), n_sample=3)
    assert res["lanes"] == 3 * len(MODULES)
    assert res["max_abs_diff_pct"] <= chip_smoke.METRIC_ATOL_PCT


def test_service_phase_answers_every_kind(tables, model):
    res = chip_smoke.phase_service(tables, n_workloads=3, n_requests=6,
                                   rng=np.random.default_rng(0), model=model)
    assert res["kinds"] == ["CharacterizeRequest", "FleetRequest",
                            "MinLatencyRequest"]
    assert res["stats"]["completed"] == res["lanes"]
    assert res["stats"]["failed"] == 0


def test_mesh_comparison_catches_a_flipped_bit(grid):
    kw = dict(voltages=(1.0,), hammer_voltages=(1.1,), rows=8,
              row_bytes=1024, inject_impl="reference")
    a = chip_smoke.phase_stress(grid.select(["A1"]), **kw)
    b = chip_smoke.phase_stress(grid.select(["A1"]), **kw)
    chip_smoke.compare_meshes(a, b, ("test1", "hammer"))
    flipped = b["test1"].error_rows.copy()
    flipped.flat[0] ^= True
    b["test1"] = dataclasses.replace(b["test1"], error_rows=flipped)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.compare_meshes(a, b, ("test1", "hammer"))


def test_pallas_check_refuses_an_executable_without_the_kernel(grid):
    chip_smoke.phase_stress(grid.select(["A1"]), voltages=(1.0,),
                            hammer_voltages=(1.1,), rows=8, row_bytes=1024,
                            inject_impl="reference")
    with pytest.raises(chip_smoke.SmokeFailure, match="tpu_custom_call"):
        chip_smoke.assert_pallas_compiled(("test1",))


@pytest.fixture
def cache_config(monkeypatch, tmp_path):
    """Route the persistent cache to ``tmp_path``; restore JAX's cache
    settings afterwards."""
    import jax
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    yield tmp_path
    for n, v in before.items():
        jax.config.update(n, v)


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_entry_refuses_without_a_tpu(argv, capsys, monkeypatch,
                                     cache_config):
    monkeypatch.setattr(chip_smoke, "run_one_chip",
                        lambda *a: pytest.fail("a phase ran"))
    monkeypatch.setattr(chip_smoke, "run_four_chips",
                        lambda *a: pytest.fail("a phase ran"))
    assert chip_smoke.main(argv) != 0
    out, err = capsys.readouterr()
    assert out == ""
    assert "no TPU" in err


@pytest.mark.parametrize("alone", [False, True])
def test_script_fails_without_a_tpu_or_without_the_repo(alone, tmp_path):
    """Run as a program on the CPU, or copied into a directory that holds
    nothing else of the repo, the script exits nonzero and prints no
    result line."""
    script = os.path.join(os.path.dirname(chip_smoke.__file__),
                          "chip_smoke.py")
    if alone:
        shutil.copy(script, tmp_path / "chip_smoke.py")
        script = str(tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, script], cwd=tmp_path, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
