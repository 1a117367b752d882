"""The main path's programs compile for a TPU v5e, with no chip attached.

Each test lowers a kernel or a whole jitted engine program at the shapes
``chip_smoke.py`` runs and compiles it for a described ``v5e:2x2``
topology: what Mosaic or XLA:TPU would refuse on the chip (tiling, casts,
memory, partitioning) fails here.  The two Pallas kernels must appear as a
``tpu_custom_call``; on the four-chip mesh the dispatch layer's
``shard_map`` must keep every operand local (no all-gather).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke  # noqa: E402

from repro import engine  # noqa: E402
from repro.core import perf_model, voltron  # noqa: E402
from repro.engine import controller, dispatch, population  # noqa: E402
from repro.engine import solve as engine_solve  # noqa: E402
from repro.engine import test1  # noqa: E402
from repro.kernels import autotune  # noqa: E402
from repro.kernels.sweep_solve import ops as ss_ops  # noqa: E402
from repro.kernels.voltage_inject import ops as vi_ops  # noqa: E402

FLEET_BUCKET = 4096          # 77 workloads x 31 DIMMs = 2,387 lanes
CHAR_BUCKET = 4096           # 31 DIMMs x 19 voltages x 6 temperatures
OPFLEET_CHUNKS = 20          # 77 workloads x 1,024 DIMMs in chunks of 4,096


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), np.dtype(dtype),
                                sharding=sharding)


def _stress_plane_rows() -> int:
    # one lane per chunk: banks x rows/bank rows of the flattened plane
    return chip_smoke.hw.BANKS_PER_RANK * chip_smoke.lane_rows()


def test_voltage_inject_compiles_at_the_stress_plane(one_chip, no_cache):
    rows, words = _stress_plane_rows(), chip_smoke.ROW_BYTES // 4
    fn = jax.jit(functools.partial(vi_ops.inject, impl="pallas"))
    compiled = fn.lower(
        _sds((rows, words), jnp.uint32, one_chip),
        _sds((rows,), jnp.float32, one_chip),
        _sds((rows, words), jnp.uint32, one_chip),
        _sds((chip_smoke.NPLANES, rows, words), jnp.uint32, one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sweep_solve_compiles_at_the_fleet_bucket(one_chip, no_cache):
    c = 4
    per_core = _sds((FLEET_BUCKET, c), jnp.float32, one_chip)
    per_lane = _sds((FLEET_BUCKET,), jnp.float32, one_chip)
    fn = jax.jit(lambda *a: ss_ops.solve(*a, impl="pallas"))
    compiled = fn.lower(*([per_core] * 3 + [per_lane] * 8)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _characterize_specs(b, sharding):
    """The characterization kernel's operands at ``b`` lanes (under x64)."""
    lane = _sds((b,), jnp.float64, sharding)
    return ([lane] * 8
            + [_sds((b, population.FIELD_SIZE), jnp.float64, sharding),
               _sds((len(chip_smoke.CHAR_PATTERNS),), jnp.float64, sharding),
               _sds((len(population.RETENTION_GRID_MS),), jnp.float64,
                    sharding),
               _sds((b,), jnp.bool_, sharding)])


def test_characterize_float64_compiles_at_4096_lanes(one_chip, no_cache):
    b = CHAR_BUCKET
    with jax.enable_x64(True):
        compiled = jax.jit(population._characterize_flat_fn).lower(
            *_characterize_specs(b, one_chip)).compile()
    out = compiled.memory_analysis().output_size_in_bytes
    assert out >= 2 * b * population.FIELD_SIZE * 8     # two f64 maps


def test_characterize_fetches_float64_as_words_at_4096_lanes(one_chip,
                                                             no_cache):
    """The kernel as the dispatch layer runs it: every float64 output
    bitcast to uint32 words inside the executable, which XLA:TPU must
    accept; the maps keep their size on the wire."""
    b = CHAR_BUCKET
    fn = dispatch._f64_as_words(population._characterize_flat_fn)
    with jax.enable_x64(True):
        compiled = jax.jit(fn).lower(*_characterize_specs(b, one_chip)
                                     ).compile()
    assert compiled.as_text().startswith("HloModule jit__characterize_flat_fn")
    same, rows, lanes = compiled.out_info
    assert not same                 # every characterization output is f64
    assert set(rows) == {"ber", "row_map", "line_map", "weak"}
    assert set(lanes) == {"frac", "tmin_rcd", "tmin_rp"}
    maps = [rows[k] for k in ("row_map", "line_map")]
    assert all(m.dtype == jnp.uint32
               and m.shape == (b, 2 * population.FIELD_SIZE) for m in maps)
    wire = sum(int(np.prod(m.shape)) * m.dtype.itemsize for m in maps)
    assert wire == 2 * b * population.FIELD_SIZE * 8    # two f64 maps
    out = compiled.memory_analysis().output_size_in_bytes
    assert out >= wire


def _stress_operands():
    """The (b) Test-1 plane's per-lane operands for one chunk of lanes."""
    grid = engine.DimmGrid.from_population(("A1", "B1", "C1"))
    rows = chip_smoke.lane_rows()
    v = np.asarray(chip_smoke.STRESS_VOLTAGES[:1])
    p_word = test1._word_probs(grid, v, 10.0, 10.0, 20.0, rows)
    kd = test1._bank_key_data([d.index for d in grid.dimms], 1, 0, 8)
    statics = dict(banks=8, rows=rows, words=chip_smoke.ROW_BYTES // 4,
                   nplanes=chip_smoke.NPLANES, inject_impl="pallas",
                   inject_cfg=autotune.DEFAULTS["voltage_inject"])
    return [p_word.reshape(3, 8, rows), kd.reshape(3, 8, 2, 2),
            np.zeros(3, np.int32)], statics


def _fleet_operands(n_lanes: int):
    """The (d) controller scan's operands, ``n_lanes`` fleet lanes."""
    wls = chip_smoke.fleet_workloads()
    wb = engine.WorkloadBatch.from_workloads(wls)
    feats = {k: np.resize(np.asarray(a), (n_lanes,) + np.shape(a)[1:])
             for k, a in engine_solve._wb_feats(wb).items()}
    k = len(voltron.CANDIDATE_VOLTAGES) + 1
    phases = voltron._phase_matrix(wb.names, chip_smoke.N_INTERVALS,
                                   voltron.DEFAULT_INTERVAL_CYCLES, None,
                                   0.15)
    model = perf_model.fit()
    timings = {op: np.full((n_lanes, k), 12.5) for op in
               ("t_rcd", "t_rp", "t_ras")}
    return controller.flat_operands(
        feats, np.resize(phases, (phases.shape[0], n_lanes)),
        model.coef_low, model.coef_high, 5.0,
        chip_smoke.candidate_voltages(), np.full((n_lanes, k - 1), 50.0),
        timings, np.ones((n_lanes, k), bool))


def _lane_specs(batched, replicated, sharding_of, rep_sharding, lead=()):
    args = [_sds(lead + a.shape, a.dtype, sharding_of(len(lead) + a.ndim))
            for a in batched]
    valid = _sds(lead + (batched[0].shape[0],), np.bool_,
                 sharding_of(len(lead) + 1))
    rep = [_sds(np.shape(a), np.asarray(a).dtype, rep_sharding)
           for a in replicated]
    return args, valid, rep


def test_test1_plane_holds_the_kernel(one_chip, no_cache):
    """The whole jitted Test-1 plane, chunked one lane at a time as the
    dispatch budget streams it at 8 KiB rows."""
    batched, statics = _stress_operands()
    kernel = functools.partial(test1._test1_flat_fn, **statics)
    one = [a[:1] for a in batched]
    args, valid, rep = _lane_specs(one, [np.zeros((3, 2), np.uint32)],
                                   lambda nd: one_chip, one_chip, lead=(3,))
    fn = dispatch._chunk_fn(kernel, len(args))
    compiled = jax.jit(fn).lower(*args, valid, *rep).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_controller_scan_holds_the_kernel(one_chip, no_cache):
    batched, replicated = _fleet_operands(FLEET_BUCKET)
    kernel = functools.partial(controller._controller_flat_fn,
                               impl="pallas",
                               solve_cfg=autotune.DEFAULTS["sweep_solve"])
    args, valid, rep = _lane_specs(batched, replicated, lambda nd: one_chip,
                                   one_chip)
    compiled = jax.jit(kernel).lower(*args, *rep, valid).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("path", ["test1_chunked", "test1_direct",
                                  "fleet_bucket", "fleet_chunked"])
def test_four_chip_mesh_keeps_lanes_local(topo, no_cache, path):
    """On the ("batch",) mesh the dispatch layer and the direct Test-1
    reference run each kernel under shard_map: the Pallas call stays in,
    and nothing is gathered.  ``fleet_chunked`` is the operator fleet's
    stream as the dispatch layer builds it: 20 donated chunks of 4,096
    lanes, 1,024 a chip, float64 outputs as words."""
    mesh = Mesh(np.array(topo.devices), ("batch",))
    rep_sh = NamedSharding(mesh, PartitionSpec())
    donate = ()
    if path == "test1_direct":
        batched, statics = _stress_operands()
        four = [np.resize(a, (4,) + a.shape[1:]) for a in batched]
        kernel = functools.partial(test1._test1_flat_fn, **statics)
        lanes = lambda nd: NamedSharding(
            mesh, PartitionSpec("batch", *([None] * (nd - 1))))
        args, valid, rep = _lane_specs(four, [np.zeros((3, 2), np.uint32)],
                                       lanes, rep_sh)
        fn = dispatch.lane_sharded(kernel, mesh, len(args), len(rep), 0)
        full = (*args, *rep, valid)
    elif path == "test1_chunked":
        batched, statics = _stress_operands()
        four = [np.resize(a, (4,) + a.shape[1:]) for a in batched]
        kernel = functools.partial(test1._test1_flat_fn, **statics)
        lanes = lambda nd: NamedSharding(
            mesh, PartitionSpec(None, "batch", *([None] * (nd - 2))))
        args, valid, rep = _lane_specs(four, [np.zeros((3, 2), np.uint32)],
                                       lanes, rep_sh, lead=(2,))
        fn = dispatch.lane_sharded(dispatch._chunk_fn(kernel, len(args)),
                                    mesh, len(args), len(rep), 1)
        full = (*args, valid, *rep)
    elif path == "fleet_bucket":
        batched, replicated = _fleet_operands(FLEET_BUCKET)
        kernel = functools.partial(
            controller._controller_flat_fn, impl="pallas",
            solve_cfg=autotune.DEFAULTS["sweep_solve"])
        lanes = lambda nd: NamedSharding(
            mesh, PartitionSpec("batch", *([None] * (nd - 1))))
        args, valid, rep = _lane_specs(batched, replicated, lanes, rep_sh)
        fn = dispatch.lane_sharded(kernel, mesh, len(args), len(rep), 0)
        full = (*args, *rep, valid)
    else:
        batched, replicated = _fleet_operands(FLEET_BUCKET)
        kernel = dispatch._f64_as_words(functools.partial(
            controller._controller_flat_fn, impl="pallas",
            solve_cfg=autotune.DEFAULTS["sweep_solve"]))
        lanes = lambda nd: NamedSharding(
            mesh, PartitionSpec(None, "batch", *([None] * (nd - 2))))
        args, valid, rep = _lane_specs(batched, replicated, lanes, rep_sh,
                                       lead=(OPFLEET_CHUNKS,))
        fn = dispatch.lane_sharded(dispatch._chunk_fn(kernel, len(args)),
                                    mesh, len(args), len(rep), 1)
        full = (*args, valid, *rep)
        donate = tuple(range(len(full)))
    text = jax.jit(fn, donate_argnums=donate).lower(*full).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" not in text
