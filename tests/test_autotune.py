"""Measured kernel autotuning: config parity, roofline pruning, the
tuning-file round-trip and the dispatch-visible config key.

The contract under test (see ``repro.kernels.autotune``):

- the default config reproduces today's module constants bit-for-bit;
- every ``voltage_inject`` config (Pallas blocks, oracle chunks) is
  bit-exact on random non-tile-aligned geometries — the math is integer
  elementwise, so no config may change a single bit;
- ``sweep_solve`` oracle variants (scan unroll, batch chunking) change
  only XLA's fusion boundaries, never the step math: an unroll that keeps
  a loop of two or more trips is bit-exact, while a loop XLA inlines (an
  unroll over half the trip count) or the ``lax.map`` of a batch chunk
  moves each output by at most a few float32 ulps (relative 1e-6);
- candidates failing parity (or failing to build) are ``ineligible`` and
  can never win; candidates whose padded-traffic roofline bound cannot
  beat the incumbent are ``pruned`` unmeasured;
- winners persist to a JSON tuning file, reload across enable(), and the
  engine's dispatched paths pick the persisted config up — observable via
  ``dispatch.stats()`` (``config_last`` / ``kernel_configs``) without a
  retrace on warm calls.
"""
from __future__ import annotations

import dataclasses
import os

import numpy as np
import pytest

from _hypothesis_compat import given, settings, strategies as st

from repro.kernels import autotune
from repro.kernels.sweep_solve import kernel as ss_kernel
from repro.kernels.sweep_solve import ops as ss_ops
from repro.kernels.sweep_solve import ref as ss_ref
from repro.kernels.voltage_inject import kernel as vi_kernel
from repro.kernels.voltage_inject import ops as vi_ops


@pytest.fixture(autouse=True)
def _tuning_disabled():
    """Every test starts and ends with tuning off (the suite default)."""
    autotune.disable()
    yield
    autotune.disable()


def test_default_configs_match_module_constants():
    vi = autotune.DEFAULTS["voltage_inject"]
    assert (vi.row_block, vi.lane_block) == (vi_kernel.ROW_BLOCK,
                                             vi_kernel.WORD_BLOCK)
    assert (vi.oracle_chunk, vi.unroll) == (0, 1)
    ss = autotune.DEFAULTS["sweep_solve"]
    assert (ss.row_block, ss.lane_block) == (ss_kernel.ROW_BLOCK,
                                             ss_kernel.LANES)
    assert (ss.oracle_chunk, ss.unroll) == (0, 1)
    # disabled tuning serves exactly the default at any shape
    assert autotune.active_config("sweep_solve", (4096, 4)) == ss
    assert autotune.active_config("voltage_inject", (512, 8192)) == vi


class TestInjectConfigParity:
    """Bit-exactness of every voltage_inject config on random
    non-tile-aligned geometries."""

    @settings(max_examples=8, deadline=None)
    @given(rows=st.integers(min_value=1, max_value=70),
           words=st.integers(min_value=1, max_value=1200),
           row_block=st.sampled_from([4, 8, 16]),
           word_block=st.sampled_from([256, 512, 1024]),
           chunk=st.sampled_from([1, 3, 16, 64]))
    def test_bit_exact(self, rows, words, row_block, word_block, chunk):
        args = autotune.inject_inputs(rows, words, 2,
                                      seed=rows * 1201 + words)
        ref = np.asarray(vi_ops.inject(*args, impl="reference"))
        chunked = dataclasses.replace(autotune.DEFAULTS["voltage_inject"],
                                      oracle_chunk=chunk)
        got = vi_ops.inject(*args, impl="reference", config=chunked)
        assert np.array_equal(np.asarray(got), ref), \
            f"oracle_chunk={chunk} not bit-exact at {(rows, words)}"
        blocks = dataclasses.replace(autotune.DEFAULTS["voltage_inject"],
                                     row_block=row_block,
                                     lane_block=word_block)
        got = vi_ops.inject(*args, impl="pallas_interpret", config=blocks)
        assert np.array_equal(np.asarray(got), ref), \
            f"blocks {(row_block, word_block)} not bit-exact at " \
            f"{(rows, words)}"


# float32 units in the last place an oracle variant may move an output by
MAX_ULPS = 4


def _ulps(got, ref) -> int:
    """Largest distance between two same-signed float32 arrays, in ulps."""
    g = np.asarray(got, np.float32).view(np.int32).astype(np.int64)
    r = np.asarray(ref, np.float32).view(np.int32).astype(np.int64)
    return int(np.abs(g - r).max())


def _assert_within_ulps(got: dict, ref: dict, label: str) -> None:
    for k in ref:
        g, r = np.asarray(got[k]), np.asarray(ref[k])
        if k == "stall_frac":
            # 1 - ipc / ipc_base cancels: the ratio's ulps (at most those
            # of 1.0) land as absolute error on a value near zero
            np.testing.assert_allclose(
                g, r, rtol=0, atol=MAX_ULPS * np.finfo(np.float32).eps,
                err_msg=f"{k} @ {label}")
        else:
            assert _ulps(g, r) <= MAX_ULPS, (k, label, _ulps(g, r))
            np.testing.assert_allclose(g, r, rtol=1e-6,
                                       err_msg=f"{k} @ {label}")


class TestSolveConfigParity:
    """sweep_solve oracle variants vs the default oracle.

    Neither knob touches the step math; both move XLA's fusion boundaries.
    ``lax.scan`` runs ``iters // unroll`` trips of the unrolled body: while
    that is two or more, the loop body is the same fused step and the
    results are bit-exact.  At one trip XLA inlines the loop and fuses
    across steps; ``oracle_chunk`` wraps the whole solve in a ``lax.map``
    (every chunk size gives the same bits).  Those move outputs by a few
    ulps."""

    @settings(max_examples=8, deadline=None)
    @given(b=st.integers(min_value=1, max_value=40),
           c=st.sampled_from([1, 2, 4]),
           unroll=st.sampled_from([2, 5, 25]),
           chunk=st.sampled_from([0, 1, 7, 16]))
    def test_oracle_variants_within_1e6(self, b, c, unroll, chunk):
        args = autotune.solve_inputs(b, c, seed=b * 13 + c)
        ref = ss_ops.solve(*args, impl="reference")
        cfg = dataclasses.replace(autotune.DEFAULTS["sweep_solve"],
                                  unroll=unroll, oracle_chunk=chunk)
        got = ss_ops.solve(*args, impl="reference", config=cfg)
        label = f"unroll={unroll} chunk={chunk} b={b} c={c}"
        if chunk == 0 and ss_ref.DEFAULT_ITERS // unroll >= 2:
            for k in ref:
                assert np.array_equal(np.asarray(got[k]),
                                      np.asarray(ref[k])), (k, label)
        else:
            _assert_within_ulps(got, ref, label)

    def test_unroll_alone_is_bit_exact(self):
        """unroll changes only the loop lowering, never the step math: bit
        exact while the loop keeps two or more trips, within a few ulps
        once XLA inlines its single trip."""
        args = autotune.solve_inputs(29, 4, seed=5)
        ref = ss_ops.solve(*args, impl="reference")
        iters = ss_ref.DEFAULT_ITERS
        for unroll in (2, 5, iters // 2, iters // 2 + 1, iters):
            cfg = dataclasses.replace(autotune.DEFAULTS["sweep_solve"],
                                      unroll=unroll)
            got = ss_ops.solve(*args, impl="reference", config=cfg)
            if iters // unroll >= 2:
                for k in ref:
                    assert np.array_equal(np.asarray(got[k]),
                                          np.asarray(ref[k])), (k, unroll)
            else:
                _assert_within_ulps(got, ref, f"unroll={unroll}")

    def test_interpret_row_block_variant(self):
        args = autotune.solve_inputs(11, 4, seed=9)
        ref = ss_ops.solve(*args, impl="reference")
        cfg = dataclasses.replace(autotune.DEFAULTS["sweep_solve"],
                                  row_block=16)
        got = ss_ops.solve(*args, impl="pallas_interpret", config=cfg)
        for k in ref:
            np.testing.assert_allclose(np.asarray(got[k]),
                                       np.asarray(ref[k]), rtol=1e-6,
                                       err_msg=k)


class TestSweepSolveSearch:
    """The lane-dense ``sweep_solve`` search space and its roofline cost."""

    def test_candidates_vary_rows_at_128_lanes(self):
        cands = autotune.candidate_configs("sweep_solve", "tpu")
        assert {c.row_block for c in cands} == \
            {8, 16, 32, 64} - {ss_kernel.ROW_BLOCK}
        assert {c.lane_block for c in cands} == {ss_kernel.LANES}
        smoke = autotune.candidate_configs("sweep_solve", "tpu", smoke=True)
        assert len(smoke) == 1 and smoke[0] in cands

    @pytest.mark.parametrize("b,c,row_block,padded", [
        (5000, 4, 16, 3 * 16 * 128),     # three blocks of 2,048 samples
        (100, 1, 64, 8 * 128),           # under one block: 8 rows
        (4096, 4, 32, 4096),             # one whole block
    ])
    def test_cost_counts_lane_dense_padding(self, monkeypatch, b, c,
                                            row_block, padded):
        monkeypatch.setattr(autotune.jax, "default_backend", lambda: "tpu")
        cfg = dataclasses.replace(autotune.DEFAULTS["sweep_solve"],
                                  row_block=row_block)
        flops, bytes_ = autotune.candidate_cost(cfg, (b, c))
        assert bytes_ == padded * (3 * c + 8 + c + 2) * 4
        assert flops == 40.0 * padded * c * ss_ref.DEFAULT_ITERS


class TestTuner:
    def test_roofline_prunes_oversized_candidate(self):
        """A chunk far above the batch pads the whole plane up — its bound
        exceeds both the incumbent's bound and measured time, so the tuner
        skips it unmeasured."""
        huge = dataclasses.replace(autotune.DEFAULTS["voltage_inject"],
                                   oracle_chunk=65536)
        r = autotune.tune_kernel("voltage_inject", (64, 1024),
                                 candidates=[huge], n=1)
        assert [c.status for c in r.candidates] == ["pruned"]
        assert r.best == autotune.DEFAULTS["voltage_inject"]

    def test_parity_failure_is_ineligible_and_cannot_win(self, monkeypatch):
        """A candidate that fails the parity gate is recorded ineligible
        and the incumbent default stays the winner."""
        def fail(kernel, got, ref, label):
            raise AssertionError(f"{label}: forced parity failure")
        monkeypatch.setattr(autotune, "_assert_parity", fail)
        cand = dataclasses.replace(autotune.DEFAULTS["sweep_solve"],
                                   unroll=5)
        r = autotune.tune_kernel("sweep_solve", (32, 4),
                                 candidates=[cand], n=1)
        (c,) = r.candidates
        assert c.status == "ineligible"
        assert "forced parity failure" in c.note
        assert r.best == autotune.DEFAULTS["sweep_solve"]

    def test_measured_candidate_recorded(self):
        cand = dataclasses.replace(autotune.DEFAULTS["sweep_solve"],
                                   unroll=5)
        r = autotune.tune_kernel("sweep_solve", (64, 4),
                                 candidates=[cand], n=1)
        (c,) = r.candidates
        assert c.status == "measured" and np.isfinite(c.measured_us)
        assert r.best in (cand, autotune.DEFAULTS["sweep_solve"])
        assert r.default_us > 0 and r.best_us > 0


class TestPersistenceAndDispatch:
    def test_shape_bucket_and_fallback(self, tmp_path):
        path = str(tmp_path / "TUNE_cpu_test.json")
        tuned = dataclasses.replace(autotune.DEFAULTS["sweep_solve"],
                                    unroll=5)
        autotune.save_configs({"sweep_solve:n1024.t4": tuned}, path)
        autotune.enable(path)
        # exact bucket, nearest-bucket fallback, other-kernel default
        assert autotune.active_config("sweep_solve", (1000, 4)) == tuned
        assert autotune.active_config("sweep_solve", (9000, 4)) == tuned
        assert autotune.active_config("voltage_inject", (1024, 4)) \
            == autotune.DEFAULTS["voltage_inject"]
        autotune.disable()
        assert autotune.active_config("sweep_solve", (1000, 4)) \
            == autotune.DEFAULTS["sweep_solve"]

    def test_save_merges_existing_entries(self, tmp_path):
        path = str(tmp_path / "TUNE_cpu_test.json")
        a = dataclasses.replace(autotune.DEFAULTS["sweep_solve"], unroll=2)
        b = dataclasses.replace(autotune.DEFAULTS["voltage_inject"],
                                oracle_chunk=64)
        autotune.save_configs({"sweep_solve:n64.t4": a}, path)
        autotune.save_configs({"voltage_inject:n64.t1024": b}, path)
        table = autotune.load_configs(path)
        assert table == {"sweep_solve:n64.t4": a,
                         "voltage_inject:n64.t1024": b}

    def test_roundtrip_reaches_dispatch_stats(self, tmp_path):
        """write -> reload -> the dispatched engine path picks the
        persisted config: visible in dispatch.stats(), warm on the second
        call, and numerically identical for a pure-unroll config."""
        from repro.core.perf_model import TRAIN_VOLTAGES
        from repro.engine import dispatch
        from repro.engine import solve as engine_solve
        from repro.engine.batch import PointGrid, WorkloadBatch
        from repro.memsim import workloads

        wb = WorkloadBatch.from_workloads(
            workloads.homogeneous_workloads()[:3])
        pg = PointGrid.from_voltages(TRAIN_VOLTAGES[:2])
        base = engine_solve.simulate_batch(wb, pg)   # tuning disabled

        tuned = dataclasses.replace(autotune.DEFAULTS["sweep_solve"],
                                    unroll=5)
        path = str(tmp_path / "TUNE_cpu_test.json")
        autotune.save_configs(
            {f"sweep_solve:{autotune.shape_bucket('sweep_solve', (64, 4))}":
             tuned}, path)
        assert os.path.exists(path)

        autotune.enable(path)                        # reload from disk
        try:
            dispatch.reset_stats()
            r1 = engine_solve.simulate_batch(wb, pg)
            first = dispatch.stats("grid_sim")
            r2 = engine_solve.simulate_batch(wb, pg)
            second = dispatch.stats("grid_sim")
        finally:
            autotune.disable()
        assert first["config_last"] == tuned.key()
        assert tuned.key() in second["kernel_configs"]
        assert second["compiles"] == first["compiles"], \
            "warm second run must not retrace"
        assert second["hits"] == first["hits"] + 1
        # pure unroll: tuned results match the untuned run bit-for-bit
        np.testing.assert_array_equal(r1.ws, base.ws)
        np.testing.assert_array_equal(r2.ws, r1.ws)

    def test_direct_dispatch_ignores_tuning(self, tmp_path):
        """dispatch='direct' is the pinned parity reference: it must run
        the default config even while tuning is enabled."""
        from repro.core.perf_model import TRAIN_VOLTAGES
        from repro.engine import solve as engine_solve
        from repro.engine.batch import PointGrid, WorkloadBatch
        from repro.memsim import workloads

        wb = WorkloadBatch.from_workloads(
            workloads.homogeneous_workloads()[:2])
        pg = PointGrid.from_voltages(TRAIN_VOLTAGES[:2])
        ref = engine_solve.simulate_batch(wb, pg, dispatch="direct")
        tuned = dataclasses.replace(autotune.DEFAULTS["sweep_solve"],
                                    oracle_chunk=8, unroll=5)
        path = str(tmp_path / "TUNE_cpu_test.json")
        autotune.save_configs({"sweep_solve:n8.t4": tuned}, path)
        autotune.enable(path)
        try:
            got = engine_solve.simulate_batch(wb, pg, dispatch="direct")
        finally:
            autotune.disable()
        np.testing.assert_array_equal(got.ws, ref.ws)
        np.testing.assert_array_equal(got.ipc, ref.ipc)
