"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.ssd_scan import ops as ssd_ops
from repro.kernels.sweep_solve import kernel as sweep_kernel
from repro.kernels.sweep_solve import ops as sweep_ops
from repro.kernels.voltage_inject import ops as inject_ops
from repro.models.ssm import ssd_ref

FA_CASES = [
    # (b, sq, sk, h, kv, hd, causal, window, softcap, dtype)
    (2, 128, 128, 4, 2, 64, True, None, None, jnp.float32),
    (1, 256, 256, 8, 4, 64, True, 64, 50.0, jnp.float32),
    (2, 128, 128, 4, 4, 128, False, None, None, jnp.float32),
    (1, 128, 128, 2, 1, 256, True, None, 30.0, jnp.float32),
    (1, 128, 128, 4, 2, 64, True, None, None, jnp.bfloat16),
]


@pytest.mark.parametrize("case", FA_CASES)
def test_flash_attention_vs_ref(case):
    b, sq, sk, h, kv, hd, causal, window, cap, dt = case
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, sq, h, hd), dt)
    k = jax.random.normal(ks[1], (b, sk, kv, hd), dt)
    v = jax.random.normal(ks[2], (b, sk, kv, hd), dt)
    ref = fa_ops.flash_attention(q, k, v, causal=causal, window=window,
                                 softcap=cap, impl="reference")
    pal = fa_ops.flash_attention(q, k, v, causal=causal, window=window,
                                 softcap=cap, impl="pallas_interpret",
                                 bq=64, bk=64)
    tol = 2e-2 if dt == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(pal, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_decode_shape():
    """Single-token decode via the same kernel (Sq=1 specialization)."""
    b, sk, h, kv, hd = 2, 256, 4, 2, 64
    q = jax.random.normal(jax.random.key(0), (b, 1, h, hd))
    k = jax.random.normal(jax.random.key(1), (b, sk, kv, hd))
    v = jax.random.normal(jax.random.key(2), (b, sk, kv, hd))
    ref = fa_ops.flash_attention(q, k, v, causal=False, impl="reference")
    pal = fa_ops.flash_attention(q, k, v, causal=False,
                                 impl="pallas_interpret", bq=1, bk=64)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def _solve_args(b, c, seed=0):
    """Random-but-benign solve inputs for a [B, C] sample batch."""
    rng = np.random.default_rng(seed)
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    return (f32(rng.uniform(0.5, 40.0, (b, c))),        # mpki
            f32(rng.uniform(0.8, 3.0, (b, c))),         # ipc_base
            f32(rng.uniform(1.0, 3.0, (b, c))),         # mlp
            f32(rng.uniform(0.2, 0.95, (b,))),          # row_hit
            f32(rng.uniform(1.0, 8.0, (b,))),           # eff_banks
            f32(rng.uniform(1.0, 1.5, (b,))),           # write_mult
            f32(rng.uniform(10.0, 22.0, (b,))),         # t_rcd
            f32(rng.uniform(10.0, 22.0, (b,))),         # t_rp
            f32(rng.uniform(30.0, 50.0, (b,))),         # t_ras
            f32(rng.uniform(4.0, 8.0, (b,))),           # transfer_ns
            f32(rng.uniform(15.0, 30.0, (b,))))         # peak_bw_gbps


class TestSweepSolveEdges:
    """Interpret-mode edge cases of the lane-dense feature layout."""

    @pytest.mark.parametrize("b", [1, 5, 13])
    def test_batch_not_multiple_of_row_block(self, b):
        """W*P that does not fill a row of 128 lanes (and the W=P=1 case,
        b=1) pads with benign samples that must not leak into results."""
        args = _solve_args(b, 4, seed=b)
        ref = sweep_ops.solve(*args, impl="reference")
        pal = sweep_ops.solve(*args, impl="pallas_interpret")
        for k in ref:
            assert np.isfinite(np.asarray(pal[k])).all(), k
            np.testing.assert_allclose(np.asarray(pal[k]),
                                       np.asarray(ref[k]), rtol=1e-6,
                                       err_msg=k)

    def test_single_core(self):
        """C=1 workloads (the alone-IPC solve path)."""
        args = _solve_args(6, 1, seed=42)
        ref = sweep_ops.solve(*args, impl="reference")
        pal = sweep_ops.solve(*args, impl="pallas_interpret")
        np.testing.assert_allclose(np.asarray(pal["ipc"]),
                                   np.asarray(ref["ipc"]), rtol=1e-6)

    @pytest.mark.parametrize("b,c,row_block", [
        (5, 1, None),                        # under one block (C = 4
                                             # at B = 5: the test above)
        (1000, 4, None), (1000, 1, None),    # not a multiple of 128
        (2 * 16 * 128 + 77, 4, 16),          # 3 grid steps of 16 rows
        (2 * 8 * 128 + 77, 1, 8),            # 3 grid steps of 8 rows
    ])
    def test_lane_dense_matches_ref(self, b, c, row_block):
        """The lane-dense kernel against the oracle at rtol 1e-6 on every
        output.  ``stall_frac`` is ``1 - ipc / ipc_base``: near zero the
        subtraction turns an ipc ulp into a large relative error, so it
        also gets an absolute 1e-6 (ipc's relative tolerance)."""
        args = _solve_args(b, c, seed=b + c)
        ref = sweep_ops.solve(*args, impl="reference")
        if row_block is None:
            pal = sweep_ops.solve(*args, impl="pallas_interpret")
        else:
            assert sweep_kernel.block_rows(b, row_block) == row_block
            assert -(-b // (row_block * 128)) >= 3
            pal = sweep_ops._solve_padded(*args, interpret=True,
                                          row_block=row_block)
        for k in ref:
            assert pal[k].shape == ref[k].shape, k
            np.testing.assert_allclose(
                np.asarray(pal[k]), np.asarray(ref[k]), rtol=1e-6,
                atol=1e-6 if k == "stall_frac" else 0.0, err_msg=k)

    def test_pack_features_pads_to_lane_block(self):
        """Samples lie along the 128 lanes, one plane a feature; a batch
        under one block pads to a single 8-row sublane tile of samples."""
        args = _solve_args(5, 4)
        feat = sweep_ops.pack_features(*args)
        assert feat.shape == (3 * 4 + 8, 8, sweep_kernel.LANES)
        planes = np.asarray(feat).reshape(feat.shape[0], -1)
        np.testing.assert_array_equal(planes[:4, :5], np.asarray(args[0]).T)
        np.testing.assert_array_equal(planes[12, :5], np.asarray(args[3]))
        # benign pad samples keep the fixed point stable (no NaN/inf)
        out = sweep_kernel.solve_pallas(feat, 4, interpret=True, row_block=8)
        assert out.shape == (4 + 2, 8, sweep_kernel.LANES)
        assert np.isfinite(np.asarray(out)).all()

    def test_solve_rejects_unknown_impl(self):
        with pytest.raises(ValueError):
            sweep_ops.solve(*_solve_args(2, 4), impl="banana")

    def test_solve_pallas_rejects_untiled_shape(self):
        """The batch axis must be tiled by row_block x 128 samples, the
        features must be the 3C + 8 planes, and the lanes stay 128."""
        feat = jnp.zeros((20, 8, 128), jnp.float32)
        with pytest.raises(ValueError):        # 8 rows, blocks of 16
            sweep_kernel.solve_pallas(feat, 4, row_block=16)
        with pytest.raises(ValueError):        # not a sublane multiple
            sweep_kernel.solve_pallas(jnp.zeros((20, 12, 128), jnp.float32),
                                      4, row_block=12)
        with pytest.raises(ValueError):        # 64 lanes of samples
            sweep_kernel.solve_pallas(jnp.zeros((20, 8, 64), jnp.float32),
                                      4, row_block=8)
        with pytest.raises(ValueError):        # C = 1 planes for C = 4
            sweep_kernel.solve_pallas(jnp.zeros((11, 8, 128), jnp.float32),
                                      4, row_block=8)

    def test_block_rows_adapts_to_the_batch(self):
        """A batch under one block takes its own rows of 128 rounded up
        to 8; a larger one takes the full block."""
        rb = sweep_kernel.ROW_BLOCK
        assert sweep_kernel.block_rows(1) == 8
        assert sweep_kernel.block_rows(1000) == min(rb, 8)
        assert sweep_kernel.block_rows(1025) == min(rb, 16)
        assert sweep_kernel.block_rows(rb * 128 * 3) == rb
        assert sweep_kernel.block_rows(16384, 64) == 64
        assert sweep_kernel.block_rows(4096, 64) == 32

    def test_empty_candidate_fallback_to_nominal(self):
        """Algorithm 1 with an unreachable loss target selects the 1.35 V
        fallback in every interval, in both controller implementations."""
        from repro.core import voltron
        from repro.memsim import workloads
        name, cores = workloads.homogeneous_workloads()[0]
        runs = {impl: voltron.run_controller(name, cores, -1e6,
                                             n_intervals=3, impl=impl)
                for impl in ("engine", "scalar")}
        for impl, r in runs.items():
            assert (r.selected_voltages == 1.35).all(), impl
        np.testing.assert_array_equal(runs["engine"].selected_voltages,
                                      runs["scalar"].selected_voltages)


class TestVoltageInjectEdges:
    def test_full_probability_corrupts_every_word(self):
        """row_prob=1: every word takes the plane-AND flip mask exactly."""
        data = jnp.zeros((8, 1024), jnp.uint32)
        prob = jnp.ones((8,), jnp.float32)
        rw = jax.random.bits(jax.random.key(0), (8, 1024), dtype=jnp.uint32)
        pls = jax.random.bits(jax.random.key(1), (1, 8, 1024),
                              dtype=jnp.uint32)
        ref = inject_ops.inject(data, prob, rw, pls, impl="reference")
        pal = inject_ops.inject(data, prob, rw, pls,
                                impl="pallas_interpret")
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(pls[0]))
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(pal))

    def test_single_plane_density(self):
        """nplanes=1 flips ~half the bits of corrupted words."""
        data = jnp.zeros((8, 1024), jnp.uint32)
        prob = jnp.ones((8,), jnp.float32)
        rw = jax.random.bits(jax.random.key(2), (8, 1024), dtype=jnp.uint32)
        pls = jax.random.bits(jax.random.key(3), (1, 8, 1024),
                              dtype=jnp.uint32)
        out = np.asarray(inject_ops.inject(data, prob, rw, pls,
                                           impl="reference"))
        density = np.unpackbits(out.view(np.uint8)).mean()
        assert 0.45 < density < 0.55

    def test_raw_kernel_rejects_untiled_shape(self):
        """The bare kernel still demands tile-aligned planes; only the
        dispatch wrapper pads (test_untiled_shapes_pad_and_slice below)."""
        from repro.kernels.voltage_inject import kernel as inject_kernel
        data = jnp.zeros((7, 1024), jnp.uint32)
        with pytest.raises(ValueError):
            inject_kernel.inject_pallas(data, jnp.zeros((7,), jnp.float32),
                                        data, data[None], interpret=True)

    @pytest.mark.parametrize("shape", [(7, 1024), (8, 512), (12, 640)])
    def test_untiled_shapes_pad_and_slice(self, shape):
        """Reduced geometries (2 KiB rows = 512 words, odd row counts) run
        through the Pallas path via pad-and-slice, bit-identical to the
        oracle."""
        rows, words = shape
        data = jax.random.bits(jax.random.key(10), shape, dtype=jnp.uint32)
        prob = jax.random.uniform(jax.random.key(11), (rows,), jnp.float32,
                                  0, 1)
        rw = jax.random.bits(jax.random.key(12), shape, dtype=jnp.uint32)
        pls = jax.random.bits(jax.random.key(13), (2, *shape),
                              dtype=jnp.uint32)
        ref = inject_ops.inject(data, prob, rw, pls, impl="reference")
        pal = inject_ops.inject(data, prob, rw, pls, impl="pallas_interpret")
        assert pal.shape == shape
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(pal))

    @pytest.mark.parametrize("rows,words,row_block,word_block", [
        (7, 1000, 4, 256),         # neither axis tile-aligned: 2 x 4 steps
        (70, 1200, 8, 512),        # the default rows: 9 x 3 steps
        (33, 640, 16, 1024),       # words under one block
        (20, 2048, 4, 512),        # words aligned, rows padded: 5 x 4 steps
        (1, 1, 8, 1024),           # a single word
    ])
    def test_block_tilings_bit_exact(self, rows, words, row_block,
                                     word_block):
        """Any (row_block, word_block) tiling of the padded plane is
        bit-identical to the oracle: the math is integer elementwise, so
        the tile changes which grid step computes a word, never a bit."""
        ks = jax.random.split(jax.random.key(rows * 1201 + words), 4)
        shape = (rows, words)
        data = jax.random.bits(ks[0], shape, dtype=jnp.uint32)
        prob = jax.random.uniform(ks[1], (rows,), jnp.float32, 0, 1)
        rw = jax.random.bits(ks[2], shape, dtype=jnp.uint32)
        pls = jax.random.bits(ks[3], (2, *shape), dtype=jnp.uint32)
        ref = inject_ops.inject(data, prob, rw, pls, impl="reference")
        pal = inject_ops._inject_padded(data, prob, rw, pls, interpret=True,
                                        row_block=row_block,
                                        word_block=word_block)
        assert pal.shape == shape
        np.testing.assert_array_equal(np.asarray(pal), np.asarray(ref))

    def test_inject_rejects_unknown_impl(self):
        data = jnp.zeros((8, 1024), jnp.uint32)
        with pytest.raises(ValueError):
            inject_ops.inject(data, jnp.zeros((8,), jnp.float32), data,
                              data[None], impl="banana")


SSD_CASES = [
    (2, 64, 4, 32, 16, 16), (1, 128, 2, 16, 8, 32), (2, 96, 3, 64, 32, 16),
]


@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_kernel_vs_sequential(case):
    b, s, h, p, n, chunk = case
    ks = jax.random.split(jax.random.key(0), 5)
    x = jax.random.normal(ks[0], (b, s, h, p)) * 0.5
    a = -jnp.exp(jax.random.normal(ks[1], (h,)) * 0.3)
    bm = jax.random.normal(ks[2], (b, s, n)) * 0.4
    cm = jax.random.normal(ks[3], (b, s, n)) * 0.4
    dt = jax.nn.softplus(jax.random.normal(ks[4], (b, s, h)))
    y_ref, _ = ssd_ref(x, a, bm, cm, dt, jnp.ones((h,)))
    y_pal = ssd_ops.ssd(x, a, bm, cm, dt, jnp.ones((h,)), chunk,
                        impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               atol=5e-5, rtol=5e-5)
