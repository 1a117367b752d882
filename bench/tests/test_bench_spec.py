import json
import os

import pytest

from bench import spec


def test_benchmark_loads_and_every_file_exists():
    bench = spec.load_benchmark()
    for cfg in bench["configs"]:
        assert os.path.isfile(os.path.join(spec.ROOT, cfg["file"]))
        spec.load_config(cfg["name"])
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell["config"] == w["config"]
        assert cell["chips"] == w["chips"]
        assert cell["why"] == w["why"] and len(w["why"]) <= 200
        assert os.path.isfile(os.path.join(spec.BENCH_DIR, "jobs",
                                           cell["kind"] + ".py"))
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(spec.BENCH_DIR, "metrics",
                                           m["name"] + ".py"))
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])


@pytest.mark.parametrize("name", ["a b", "a,b", "a/b", ".x", "-x", "", "x" * 65,
                                  "café", 7])
def test_malformed_name_is_refused(name):
    with pytest.raises(spec.SpecError):
        spec.check_name(name)


@pytest.mark.parametrize("name", ["job_s", "char31.stress", "chang17-char31",
                                  "_x", "9a", "x" * 64])
def test_name_is_accepted(name):
    assert spec.check_name(name) == name


@pytest.mark.parametrize("unit", ["tokens per second", "", "µs",
                                  "x" * 17, "ms,s"])
def test_malformed_unit_is_refused(unit):
    with pytest.raises(spec.SpecError):
        spec.check_unit(unit)


@pytest.mark.parametrize("unit", ["s", "ms", "%", "req/s", "tokens/s"])
def test_unit_is_accepted(unit):
    assert spec.check_unit(unit) == unit


def _write_bench(tmp_path, mutate):
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mutate(bench)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)


@pytest.mark.parametrize("mutate", [
    lambda b: b["workloads"][0].update(name="bad name"),
    lambda b: b["workloads"][0].update(config="no-such-config"),
    lambda b: b["workloads"][0].update(chips=2),
    lambda b: b["end_to_end"][0].update(unit="per second"),
    lambda b: b["per_layer"][0].update(better="up"),
    lambda b: b["per_layer"][0].update(source="guess"),
    lambda b: b["per_layer"][0].update(workloads=["no.such.cell"]),
])
def test_malformed_benchmark_is_refused(tmp_path, mutate):
    root = _write_bench(tmp_path, mutate)
    with pytest.raises(spec.SpecError):
        spec.load_benchmark(root)


def test_cell_and_config_files_need_their_keys(tmp_path):
    (tmp_path / "bench" / "workloads").mkdir(parents=True)
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "workloads" / "c.json").write_text(
        json.dumps({"config": "k", "kind": "stress", "chips": 1}))
    (tmp_path / "bench" / "configs" / "k.json").write_text(
        json.dumps({"source": "s", "reduced": ["bad key"], "assumed": {}}))
    with pytest.raises(spec.SpecError):
        spec.load_cell("c", str(tmp_path))
    with pytest.raises(spec.SpecError):
        spec.load_config("k", str(tmp_path))
    with pytest.raises(spec.SpecError):
        spec.load_module("jobs", "nothing", str(tmp_path))


def test_cell_metrics_follow_the_workloads_key():
    bench = {"per_layer": [{"name": "a"}, {"name": "b", "workloads": ["x"]},
                           {"name": "c", "workloads": ["y"]}]}
    assert [m["name"] for m in spec.cell_metrics(bench, "x", "per_layer")] \
        == ["a", "b"]
