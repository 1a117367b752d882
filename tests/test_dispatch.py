"""Shape-stable dispatch layer: bucketing/chunking parity + retrace bounds.

The dispatched paths (bucketed padding with a validity mask, chunked
``lax.map`` streaming) must be *bit-exact* per element against the direct
exact-shape jit calls for Test 1 and within 1e-12 for the characterization
and system sweeps (observed: exactly 0.0 — the padded lanes are masked,
never reduced), and the number of retraces must be bounded by the bucket
ladder rather than the request stream.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro import engine
from repro.engine import dispatch, population, test1
from repro.launch import mesh as mesh_lib

ATOL = 1e-12
CHAR_QUANTITIES = ("line_error_fraction", "ber", "t_rcd_min", "t_rp_min",
                   "row_error_prob", "line_error_prob",
                   "expected_weak_cells")
T1_QUANTITIES = ("bit_errors", "erroneous_lines", "error_rows")


class TestBuckets:
    def test_ladder_is_mesh_divisible_powers_of_two(self):
        for nd in (1, 2, 3, 8):
            ladder = dispatch.bucket_ladder(nd)
            assert ladder[0] == nd
            assert all(b % nd == 0 for b in ladder)
            assert all(b == ladder[0] * 2 ** i for i, b in enumerate(ladder))
            assert ladder[-1] >= dispatch.DEFAULT_MAX_BUCKET

    def test_pick_bucket(self):
        ladder = dispatch.bucket_ladder(1, max_bucket=8)
        assert dispatch.pick_bucket(1, ladder) == 1
        assert dispatch.pick_bucket(3, ladder) == 4
        assert dispatch.pick_bucket(8, ladder) == 8
        assert dispatch.pick_bucket(9, ladder) is None

    def test_pad_axis(self):
        a = np.arange(6, dtype=np.float64).reshape(3, 2)
        p = dispatch.pad_axis(a, 5)
        assert p.shape == (5, 2)
        np.testing.assert_array_equal(p[:3], a)
        np.testing.assert_array_equal(p[3:], np.tile(a[:1], (2, 1)))
        assert dispatch.pad_axis(a, 3) is not None
        np.testing.assert_array_equal(dispatch.pad_axis(a, 3), a)
        p1 = dispatch.pad_axis(np.arange(8).reshape(2, 4), 6, axis=1)
        assert p1.shape == (2, 6)
        np.testing.assert_array_equal(p1[:, 4:], [[0, 0], [4, 4]])


class TestRetraceRegression:
    """Two different-sized requests in the same bucket => exactly one
    trace (the AOT executable cache is the jit cache made observable)."""

    def test_characterize_same_bucket_single_trace(self):
        grid = engine.DimmGrid.from_population()
        dispatch.clear_cache()
        dispatch.reset_stats()
        # N = 3*3*1 = 9 and N = 2*5*1 = 10 both pad to bucket 16
        engine.characterize_batch(grid.select(("A1", "B2", "C2")),
                                  [1.2, 1.15, 1.1])
        engine.characterize_batch(grid.select(("A1", "C4")),
                                  [1.3, 1.25, 1.2, 1.15, 1.1])
        s = dispatch.stats("characterize")
        assert s["calls"] == 2
        assert s["compiles"] == 1
        assert s["hits"] == 1

    def test_test1_same_bucket_single_trace(self):
        grid = engine.DimmGrid.from_population(("A1", "B2"))
        dispatch.clear_cache()
        dispatch.reset_stats()
        kw = dict(rows=8, row_bytes=1024, seed=3)
        test1.run_batch(grid, [1.2, 1.15], **kw)        # N = 12 -> 16
        test1.run_batch(grid, [1.25, 1.2, 1.15], rounds=1, **kw)  # 18 -> 32
        test1.run_batch(grid, [1.1], rounds=2, **kw)    # N = 12 -> 16 again
        s = dispatch.stats("test1")
        assert s["calls"] == 3
        assert s["compiles"] == 2
        assert s["hits"] == 1

    def test_stream_of_shapes_bounded_by_ladder(self):
        """A stream of distinct system-sweep shapes compiles at most once
        per (W-bucket, P-bucket) pair, far below one per shape."""
        from repro.memsim import workloads
        wls = workloads.homogeneous_workloads()
        dispatch.clear_cache()
        dispatch.reset_stats()
        v_grids = ([1.2], [1.2, 1.15], [1.3, 1.25, 1.2],
                   [1.35, 1.3, 1.25, 1.2])
        for w_count, v in zip((3, 5, 7, 8), v_grids):
            wb = engine.WorkloadBatch.from_workloads(wls[:w_count])
            pg = engine.PointGrid.from_voltages(v)
            engine.simulate_batch(wb, pg)
        s = dispatch.stats("grid_sim")
        assert s["calls"] == 4
        # W buckets {4, 8}, P buckets {1, 2, 4}: at most 4 distinct keys
        assert s["compiles"] <= 4 < 8   # 8 = one trace per request shape
        assert s["hits"] == s["calls"] - s["compiles"]


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 2**30), n=st.integers(1, 6))
def test_property_characterize_bucket_boundary_parity(seed, n):
    """Random subsets with flat sizes straddling bucket boundaries:
    bucketed == direct to <= 1e-12 on every Fig. 4/6/8/11 quantity."""
    grid = engine.DimmGrid.from_population()
    rng = np.random.default_rng(seed)
    mods = tuple(rng.choice(np.asarray(grid.modules), size=n, replace=False))
    # voltage count chosen so N = n * v hugs a power of two +- 1
    b = int(rng.choice([4, 8, 16]))
    v_count = max(1, min(14, (b + int(rng.integers(-1, 2))) // n))
    v = np.round(rng.uniform(1.0, 1.35, size=v_count), 4)
    sub = grid.select(mods)
    got = engine.characterize_batch(sub, v)
    ref = engine.characterize_batch(sub, v, dispatch="direct")
    for f in CHAR_QUANTITIES:
        np.testing.assert_allclose(getattr(got, f), getattr(ref, f),
                                   atol=ATOL, err_msg=f)


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 2**30), n=st.integers(1, 3),
       rounds=st.integers(1, 2), rows=st.sampled_from([8, 16]))
def test_property_test1_bucketed_bit_exact(seed, n, rounds, rows):
    """Random Test-1 grids: bucketed dispatch is bit-exact vs direct."""
    grid = engine.DimmGrid.from_population()
    rng = np.random.default_rng(seed)
    mods = tuple(rng.choice(np.asarray(grid.modules), size=n, replace=False))
    v = np.round(rng.uniform(1.05, 1.3, size=int(rng.integers(1, 4))), 4)
    sub = grid.select(mods)
    kw = dict(rounds=rounds, rows=rows, row_bytes=1024, seed=seed % 1000)
    got = test1.run_batch(sub, v, **kw)
    ref = test1.run_batch(sub, v, dispatch="direct", **kw)
    for f in T1_QUANTITIES:
        np.testing.assert_array_equal(getattr(got, f), getattr(ref, f),
                                      err_msg=f)


class TestChunked:
    def test_characterize_chunked_matches_direct(self):
        grid = engine.DimmGrid.from_population()
        v = population.SWEEP_VOLTAGES[:7]          # N = 31*7 = 217
        ref = engine.characterize_batch(grid, v, dispatch="direct")
        # budget of 32 elements -> 7 chunks of 32
        got = engine.characterize_batch(
            grid, v, dispatch="chunked",
            max_elements_resident=32 * 8 * population.FIELD_SIZE)
        for f in CHAR_QUANTITIES:
            np.testing.assert_allclose(getattr(got, f), getattr(ref, f),
                                       atol=ATOL, err_msg=f)
        assert dispatch.stats("characterize/chunked")["max_resident"] <= 32

    def test_test1_chunked_bit_exact_and_bounded(self):
        grid = engine.DimmGrid.from_population(("A1", "B2", "C2"))
        v = [1.25, 1.2, 1.15, 1.1]                 # N = 3*4*3*2 = 72
        kw = dict(rounds=2, rows=16, row_bytes=1024, seed=0)
        ref = test1.run_batch(grid, v, dispatch="direct", **kw)
        cost = 6 * 8 * 16 * 256                    # (nplanes+4)*B*R*W
        dispatch.reset_stats()
        got = test1.run_batch(grid, v, dispatch="chunked",
                              max_elements_resident=16 * cost, **kw)
        for f in T1_QUANTITIES:
            np.testing.assert_array_equal(getattr(got, f), getattr(ref, f),
                                          err_msg=f)
        s = dispatch.stats("test1/chunked")
        assert s["max_resident"] == 16             # 5 chunks of 16, O(chunk)

    def test_auto_overflow_routes_to_chunks(self):
        """A request over the budget streams automatically (no forcing)."""
        grid = engine.DimmGrid.from_population(("A1", "B2"))
        v = [1.25, 1.2, 1.15]
        kw = dict(rounds=2, rows=8, row_bytes=1024, seed=1)
        cost = 6 * 8 * 8 * 256
        dispatch.reset_stats()
        got = test1.run_batch(grid, v, max_elements_resident=8 * cost, **kw)
        ref = test1.run_batch(grid, v, dispatch="direct", **kw)
        assert dispatch.stats("test1")["chunked_calls"] == 1
        for f in T1_QUANTITIES:
            np.testing.assert_array_equal(getattr(got, f), getattr(ref, f))


def _special_f64(n_lanes: int) -> np.ndarray:
    """[n_lanes, 8] float64 with NaN, +-inf, +-0.0, subnormals, the
    neighbours of 1.0, the extremes and random bit patterns."""
    f = np.finfo(np.float64)
    special = np.array([np.nan, np.inf, -np.inf, 0.0, -0.0,
                        f.smallest_subnormal, -3 * f.smallest_subnormal,
                        f.tiny - f.smallest_subnormal, f.tiny, 1.0,
                        np.nextafter(1.0, 2.0), np.nextafter(1.0, 0.0),
                        -np.nextafter(1.0, 2.0), f.max, -f.max, 1 / 3])
    bits = np.random.default_rng(5).integers(0, 2**64, n_lanes * 8,
                                             dtype=np.uint64)
    out = bits.view(np.float64).copy()
    out[:special.size] = special
    return out.reshape(n_lanes, 8)


def _typed_kernel(x, k, valid):
    """Outputs of every dtype the dispatch layer carries; dead lanes
    zeroed as the engine kernels do."""
    live = valid[:, None]
    return {"f64": jnp.where(live, x, 0.0), "f64_sum": x[:, 0] + x[:, 1],
            "f32": jnp.where(live, x.astype(jnp.float32), 0.0),
            "i32": k * 3, "b": valid & (k[:, 0] > 0)}


@pytest.mark.parametrize("mode,n", [("bucketed", 13), ("bucketed", 16),
                                    ("chunked", 6), ("chunked", 13)])
def test_float64_outputs_cross_as_words_bit_exact(mode, n):
    """Float64 outputs come back bit for bit as the direct call returns
    them (dead lanes in the bucket or the last chunk); other dtypes keep
    dtype and values; ``wire_bytes_total`` counts the float64 bytes."""
    x = _special_f64(n)
    k = np.arange(n * 2, dtype=np.int32).reshape(n, 2) - 5
    name = f"words-{mode}-{n}"
    dispatch.reset_stats()
    with jax.enable_x64(True):
        ref = jax.jit(_typed_kernel)(x, k, np.ones(n, bool))
        got = dispatch.dispatch_flat(
            name, _typed_kernel, [x, k], mode=mode,
            config=dispatch.DispatchConfig(max_elements_resident=4))
    assert sorted(got) == sorted(ref)
    for key, want in ref.items():
        want = np.asarray(want)
        assert got[key].dtype == want.dtype and got[key].shape == want.shape
        if want.dtype == np.float64:
            np.testing.assert_array_equal(got[key].view(np.uint64),
                                          want.view(np.uint64), err_msg=key)
        else:
            np.testing.assert_array_equal(got[key], want, err_msg=key)
    lanes = 8 if n <= 8 else 16     # the bucket, or chunks of 4 lanes
    assert dispatch.stats(name)["wire_bytes_total"] == lanes * (8 + 1) * 8


@pytest.mark.parametrize("mode,n,lanes,chunks", [("bucketed", 13, 16, 1),
                                                  ("bucketed", 16, 16, 1),
                                                  ("chunked", 13, 16, 4),
                                                  ("chunked", 6, 8, 2)])
def test_lane_counters(mode, n, lanes, chunks):
    """Each dispatch adds its true lanes and its dead ones (the bucket, or
    ``chunks x chunk``, less N) to the entry's row, records the mesh size,
    and puts all of it, with the chunk count, on its ``put`` span."""
    x = np.ones((n, 2), np.float32)
    name = f"lanes-{mode}-{n}"
    dispatch.reset_stats()
    for _ in range(2):
        dispatch.dispatch_flat(
            name, lambda x, valid: {"y": x * 2}, [x], mode=mode,
            config=dispatch.DispatchConfig(max_elements_resident=4))
    s = dispatch.stats(name)
    assert (s["lanes_total"], s["padded_lanes_total"], s["devices"]) == (
        2 * n, 2 * (lanes - n), 1)
    puts = [r for r in dispatch.spans()[0] if r.name == f"repro.{name}.put"]
    assert len(puts) == 2
    assert {k: puts[-1].attrs[k] for k in ("lanes", "padded_lanes",
                                           "devices", "chunks")} == {
        "lanes": n, "padded_lanes": lanes - n, "devices": 1,
        "chunks": chunks}


def test_emulated_float64_words_exact_off_subnormals():
    """The arithmetic a TPU runs in place of the bitcast gives the IEEE
    bits of every normal value and infinity (subnormals are flushed by
    XLA:CPU, so this host cannot check them); NaN stays NaN, and a zero
    comes back as +0.0, as the chip's own copy returns most -0.0s."""
    x = _special_f64(4096).ravel()
    x = x[(np.abs(x) >= np.finfo(np.float64).tiny) | ~np.isfinite(x)
          | (x == 0)]
    with jax.enable_x64(True):
        words = np.asarray(jax.jit(dispatch._emulated_f64_words)(x))
    assert words.dtype == np.uint32 and words.shape == x.shape + (2,)
    got = words.view(np.float64)[..., 0]
    nan = np.isnan(x)
    assert np.isnan(got[nan]).all()
    want = np.where(x == 0, 0.0, x)                 # -0.0 -> +0.0
    np.testing.assert_array_equal(got[~nan].view(np.uint64),
                                  want[~nan].view(np.uint64))


class TestSystemSweepParity:
    def test_simulate_and_evaluate_bucketed_match_direct(self):
        from repro.core.perf_model import TRAIN_VOLTAGES
        from repro.memsim import workloads
        wls = workloads.homogeneous_workloads()[:5]
        wb = engine.WorkloadBatch.from_workloads(wls)
        pg = engine.PointGrid.from_voltages(TRAIN_VOLTAGES)
        got, ref = (engine.simulate_batch(wb, pg, dispatch=d)
                    for d in ("auto", "direct"))
        for f in ("ipc", "alone_ipc", "ws", "stall_frac", "runtime_s",
                  "avg_latency_ns", "bus_utilization"):
            np.testing.assert_allclose(getattr(got, f), getattr(ref, f),
                                       atol=ATOL, err_msg=f)
        e_got, e_ref = (engine.evaluate_batch(wb, pg, dispatch=d)
                        for d in ("auto", "direct"))
        for f in ("perf_loss_pct", "dram_power_savings_pct",
                  "system_energy_savings_pct", "perf_per_watt_gain_pct"):
            np.testing.assert_allclose(getattr(e_got, f), getattr(e_ref, f),
                                       atol=ATOL, err_msg=f)

    def test_controller_bucketed_matches_direct(self):
        from repro.core import perf_model, voltron
        from repro.memsim import workloads
        wls = workloads.homogeneous_workloads()[:3]
        model = perf_model.fit()
        wb = engine.WorkloadBatch.from_workloads(wls)
        phases = voltron._phase_matrix(
            wb.names, 10, voltron.DEFAULT_INTERVAL_CYCLES, None, 0.15)
        cand_v, lat_feat, timings = voltron._candidate_grid(False)
        args = (wb, phases, model.coef_low, model.coef_high, 5.0, cand_v,
                lat_feat, timings)
        got = engine.run_batched(*args)
        ref = engine.run_batched(*args, dispatch="direct")
        np.testing.assert_array_equal(got.selected_voltages,
                                      ref.selected_voltages)
        for f in ("perf_loss_pct", "dram_power_savings_pct",
                  "dram_energy_savings_pct", "system_energy_savings_pct",
                  "perf_per_watt_gain_pct"):
            np.testing.assert_allclose(getattr(got, f), getattr(ref, f),
                                       atol=ATOL, err_msg=f)


class TestValidation:
    def test_unknown_dispatch_rejected(self):
        grid = engine.DimmGrid.from_population(("A1",))
        with pytest.raises(ValueError):
            engine.characterize_batch(grid, [1.2], dispatch="banana")
        with pytest.raises(ValueError):
            test1.run_batch(grid, [1.2], dispatch="banana")

    def test_forced_bucketed_overflow_rejected(self):
        """dispatch='bucketed' must refuse (not silently chunk) a batch
        over the top ladder rung."""
        n = dispatch.DEFAULT_MAX_BUCKET + 1
        with pytest.raises(ValueError, match="bucketed"):
            dispatch.dispatch_flat("overflow-test", lambda *a: {},
                                   [np.zeros((n, 1), np.float32)],
                                   mode="bucketed")

    @pytest.fixture
    def cache_config(self):
        """Restore JAX's persistent-cache settings (and drop its
        initialized cache) after a test that points them elsewhere."""
        import jax
        from jax.experimental.compilation_cache import compilation_cache
        names = ("jax_compilation_cache_dir",
                 "jax_persistent_cache_min_compile_time_secs",
                 "jax_persistent_cache_min_entry_size_bytes")
        before = {n: getattr(jax.config, n) for n in names}
        yield
        for n, v in before.items():
            jax.config.update(n, v)
        compilation_cache.reset_cache()

    def test_persistent_cache_round_trips(self, tmp_path, monkeypatch,
                                          cache_config):
        import jax
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
        path = dispatch.enable_persistent_cache()
        assert path == str(tmp_path / "jc") and os.path.isdir(path)
        assert jax.config.jax_compilation_cache_dir == path

    def test_persistent_cache_writes_only_to_env_dir(self, tmp_path,
                                                     monkeypatch,
                                                     cache_config):
        """With $JAX_COMPILATION_CACHE_DIR set, a compile lands there and
        nowhere under the working directory."""
        import jax
        import jax.numpy as jnp
        from jax.experimental.compilation_cache import compilation_cache
        env_dir, cwd = tmp_path / "env_cache", tmp_path / "cwd"
        cwd.mkdir()
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(env_dir))
        monkeypatch.chdir(cwd)
        assert dispatch.enable_persistent_cache() == str(env_dir)
        compilation_cache.reset_cache()
        jax.jit(lambda x: jnp.sin(x) * 3.25 + 0.125).lower(
            jnp.zeros((7, 3))).compile()
        assert any(env_dir.iterdir())
        assert not any(cwd.iterdir())

    def test_persistent_cache_default_ignores_cwd(self, tmp_path,
                                                  monkeypatch, cache_config):
        """Without the variable the cache is the checkout's
        artifacts/jax_cache, whatever the working directory."""
        import jax
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(repo, "artifacts", "jax_cache")
        for cwd in (tmp_path, repo):
            monkeypatch.chdir(cwd)
            assert dispatch.enable_persistent_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
        assert not (tmp_path / "artifacts").exists()


@pytest.mark.slow
def test_multidevice_sharded_dispatch_matches_direct():
    """8 forced host devices: bucketed AND chunked dispatch (bucket/chunk
    sizes mesh-divisible by construction) match the direct sharded call."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count=8"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import sys
        sys.path.insert(0, "src")
        import numpy as np
        import jax
        from repro import engine
        from repro.engine import dispatch, population, test1

        assert len(jax.devices()) == 8
        grid = engine.DimmGrid.from_population(("A1", "B2", "C2"))
        v = np.asarray([1.35, 1.2, 1.15, 1.1, 1.05])     # N = 15 -> 16
        b = engine.characterize_batch(grid, v)
        s = engine.characterize_batch(grid, v, dispatch="direct")
        for f in ("line_error_fraction", "ber", "t_rcd_min", "t_rp_min",
                  "row_error_prob", "line_error_prob",
                  "expected_weak_cells"):
            np.testing.assert_allclose(getattr(b, f), getattr(s, f),
                                       atol=1e-12, err_msg=f)
        kw = dict(rounds=2, rows=8, row_bytes=1024, seed=0)
        t_direct = test1.run_batch(grid, v, dispatch="direct", **kw)
        t_chunk = test1.run_batch(
            grid, v, dispatch="chunked",
            max_elements_resident=16 * 6 * 8 * 8 * 256, **kw)
        for f in ("bit_errors", "erroneous_lines", "error_rows"):
            np.testing.assert_array_equal(getattr(t_chunk, f),
                                          getattr(t_direct, f), err_msg=f)
        assert dispatch.stats("test1/chunked")["max_resident"] % 8 == 0
        print("DISPATCH_SHARDED_OK")
    """)
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=600, cwd=os.path.dirname(os.path.dirname(__file__)),
        env=dict(os.environ))
    assert "DISPATCH_SHARDED_OK" in out.stdout, out.stderr[-3000:]
