"""Error injection, spatial locality (Fig. 8), Test 1, data patterns.

Covers both the scalar Test 1 (:mod:`repro.dram.test1`) and the batched
engine substrate (:mod:`repro.engine.test1`), whose error counts must be
bit-exact against the scalar per-bank loop on matched PRNG keys.
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
from repro.dram import chips, errors, test1
from repro.engine import test1 as engine_test1
from repro.kernels.voltage_inject import ops as inject_ops

BATCH_FIELDS = ("bit_errors", "erroneous_lines", "error_rows")


def _dimm(module):
    return [d for d in chips.population() if d.module == module][0]


class TestSpatialLocality:
    def test_vendor_c_bank_clustering(self):
        """Fig. 8b: Vendor C errors concentrate in a subset of banks."""
        d = _dimm("C2")
        prob = errors.error_probability_map(d, d.vmin - 0.025)
        per_bank = prob.max(axis=1)
        assert (per_bank > 1e-6).sum() < 8      # not all banks affected
        assert (per_bank > 1e-6).sum() >= 1

    def test_vendor_b_row_clustering(self):
        """Fig. 8a: Vendor B errors cluster in row bands across banks."""
        d = _dimm("B5")
        prob = errors.error_probability_map(d, d.vmin - 0.025)
        per_group = prob.mean(axis=0)
        hot = per_group > per_group.mean() + 3 * per_group.std() * 0 + 1e-9
        # hot row-groups exist and are a minority
        assert 0 < hot.sum() < prob.shape[1] / 2

    def test_error_free_regions_allow_standard_latency(self):
        """Section 6.5 premise: some banks have zero error probability at
        one step below V_min."""
        d = _dimm("C2")
        prob = errors.error_probability_map(d, d.vmin - 0.025)
        assert (prob.max(axis=1) == 0).any()


class TestSecded:
    def test_secded_insufficient(self):
        d = _dimm("C2")
        assert not errors.secded_is_sufficient(d, d.vmin - 0.05)

    def test_outcome_fractions_sum(self):
        d = _dimm("B2")
        o = errors.secded_outcomes(d, d.vmin - 0.05)
        total = o.clean + o.corrected + o.detected + o.undetected_or_mis
        np.testing.assert_allclose(total, 1.0, atol=1e-9)

    def test_temp_threads_into_ecc_analysis(self):
        """Regression: secded_outcomes/secded_is_sufficient silently pinned
        temp_c=20 — the ECC analysis must compose with the Section 5.3
        temperature scenarios.  C2 at 1.275 V is clean at 20 C but failing
        at 70 C (Fig. 10)."""
        d = _dimm("C2")
        cold = errors.secded_outcomes(d, 1.275)
        hot = errors.secded_outcomes(d, 1.275, temp_c=70.0)
        assert cold.clean == 1.0 and cold.still_erroneous == 0.0
        assert hot.clean < 1.0 and hot.still_erroneous > 0.0
        assert errors.secded_is_sufficient(d, 1.275)
        assert not errors.secded_is_sufficient(d, 1.275, temp_c=70.0)
        # default unchanged
        explicit = errors.secded_outcomes(d, 1.275, temp_c=20.0)
        assert explicit == cold


class TestPatternGroups:
    def test_groups_are_true_inverses(self):
        """Section 3: the second pattern of each Test-1 group must be the
        bitwise inverse of the first (the shortened precharge leaves the
        bitlines biased toward the previous row's values)."""
        for a, b in test1.PATTERN_GROUPS:
            assert test1.DATA_PATTERNS[a] ^ test1.DATA_PATTERNS[b] \
                == 0xFFFFFFFF, (a, b)

    def test_groups_cover_every_pattern_once(self):
        names = [p for g in test1.PATTERN_GROUPS for p in g]
        assert sorted(names) == sorted(test1.DATA_PATTERNS)


class TestTest1:
    def test_no_errors_at_vmin(self):
        d = _dimm("A1")
        r = test1.run(d, d.vmin, rows=32)
        assert r.bit_errors == 0

    def test_errors_below_vmin(self):
        d = _dimm("C2")
        r = test1.run(d, d.vmin - 0.075, rows=32)
        assert r.bit_errors > 0

    def test_latency_recovery(self):
        d = _dimm("C2")
        best = test1.find_min_latency(d, d.vmin - 0.025)
        assert best is not None
        assert max(best) >= 12.5                 # needs a real increase
        r = test1.run(d, d.vmin - 0.025, t_rcd=best[0], t_rp=best[1], rows=32)
        assert r.bit_errors == 0

    def test_below_recovery_floor_unfixable(self):
        """Section 4.2: very low voltage is unrecoverable by latency."""
        d = _dimm("A1")
        assert test1.find_min_latency(d, 1.05) is None

    def test_find_min_latency_tie_break_documented_order(self):
        """The returned pair is the (sum, tRCD, tRP)-lexicographic minimum
        of all zero-error grid pairs — not an iteration-order accident."""
        grid = np.arange(10.0, 20.0 + 1e-9, 2.5)
        for module, v in (("C2", 1.225), ("B2", 1.125), ("A1", 1.0875)):
            d = _dimm(module)
            ok = [(float(a), float(b)) for a in grid for b in grid
                  if float(d.line_error_fraction(v, float(a), float(b))[0])
                  <= 0.0]
            best = test1.find_min_latency(d, v)
            if not ok or v < chips.circuit.VENDORS[d.vendor].recovery_floor:
                assert best is None, (module, v)
            else:
                expect = min(ok, key=lambda p: (p[0] + p[1], p[0], p[1]))
                assert best == expect, (module, v)

    def test_voltage_sweep_accepts_seed_kwarg(self):
        """Regression: seed= used to raise 'multiple values for seed'."""
        d = _dimm("C2")
        out = test1.voltage_sweep(d, [1.2], rounds=2, seed=5, rows=8)
        assert len(out) == 2

    def test_voltage_sweep_rounds_derive_from_base_seed(self):
        d = _dimm("C2")
        out = test1.voltage_sweep(d, [1.2], rounds=2, seed=5, rows=8)
        ref = test1.run(d, 1.2, seed=6, rows=8)
        assert out[1].bit_errors == ref.bit_errors
        np.testing.assert_array_equal(out[1].error_rows, ref.error_rows)

    def test_data_pattern_no_significant_effect(self):
        """Appendix B: data pattern does not consistently change the BER."""
        d = _dimm("C2")
        v = d.vmin - 0.05
        bers = [test1.run(d, v, pattern_group=g, rows=32, seed=7).ber
                for g in test1.PATTERN_GROUPS]
        assert max(bers) < 3 * max(min(bers), 1e-12) + 1e-6


class TestBatchedTest1:
    """engine.test1.run_batch vs the scalar dram.test1 loop: bit-exact."""

    V_GRID = np.asarray([1.30, 1.20, 1.15, 1.10])
    KW = dict(rounds=2, rows=16, row_bytes=4096, seed=3)

    @pytest.fixture(scope="class")
    def sub_grid(self):
        return engine.DimmGrid.from_population(("A1", "B2", "C2"))

    @pytest.fixture(scope="class")
    def batched(self, sub_grid):
        return engine_test1.run_batch(sub_grid, self.V_GRID, **self.KW)

    @pytest.fixture(scope="class")
    def scalar(self, sub_grid):
        return engine_test1.run_batch(sub_grid, self.V_GRID, impl="scalar",
                                      **self.KW)

    def test_shapes(self, batched):
        d, v, p, r = 3, self.V_GRID.size, len(test1.PATTERN_GROUPS), 2
        assert batched.bit_errors.shape == (d, v, p, r)
        assert batched.erroneous_lines.shape == (d, v, p, r)
        assert batched.error_rows.shape == (d, v, p, r, 8, 16)
        assert batched.total_bits == 8 * 16 * 1024 * 32
        assert batched.total_lines == 8 * 16 * 64

    def test_bit_exact_vs_scalar(self, batched, scalar):
        for f in BATCH_FIELDS:
            np.testing.assert_array_equal(getattr(batched, f),
                                          getattr(scalar, f), err_msg=f)
        assert batched.total_bits == scalar.total_bits
        assert batched.total_lines == scalar.total_lines

    def test_matches_dram_test1_directly(self, sub_grid, batched):
        """Spot-check one element straight against dram.test1.run (not the
        wrapped scalar impl): same counts, same BER, same row map."""
        d = sub_grid.dimms[2]
        r = test1.run(d, float(self.V_GRID[1]),
                      pattern_group=test1.PATTERN_GROUPS[1], rows=16,
                      seed=3 + 1)
        assert batched.bit_errors[2, 1, 1, 1] == r.bit_errors
        assert batched.erroneous_lines[2, 1, 1, 1] == r.erroneous_lines
        np.testing.assert_array_equal(batched.error_rows[2, 1, 1, 1],
                                      r.error_rows)
        np.testing.assert_allclose(batched.ber[2, 1, 1, 1], r.ber)
        np.testing.assert_allclose(batched.line_error_fraction[2, 1, 1, 1],
                                   r.line_error_fraction)

    def test_zero_errors_at_vmin(self, sub_grid):
        res = engine_test1.run_batch(sub_grid, sub_grid.vmin.max(), rows=8)
        assert (res.bit_errors == 0).all()

    def test_nplanes_forwarded_to_scalar_path(self, sub_grid):
        """nplanes=1 (per-bit flip density 1/2 instead of 1/4) must reach
        both implementations — parity stays bit-exact."""
        kw = dict(rows=8, nplanes=1, seed=2)
        b = engine_test1.run_batch(sub_grid, [1.1], **kw)
        s = engine_test1.run_batch(sub_grid, [1.1], impl="scalar", **kw)
        for f in BATCH_FIELDS:
            np.testing.assert_array_equal(getattr(b, f), getattr(s, f),
                                          err_msg=f)

    def test_requires_real_dimms(self):
        synth = engine.DimmGrid.from_vendor_z("A", [0.0])
        with pytest.raises(ValueError):
            engine_test1.run_batch(synth, [1.2])

    def test_unknown_impl_rejected(self, sub_grid):
        with pytest.raises(ValueError):
            engine_test1.run_batch(sub_grid, [1.2], impl="banana")

    def test_pallas_interpret_non_tile_aligned_geometry(self, sub_grid):
        """2 KiB rows (512 words) and 12 rows don't tile the kernel's
        (8, 1024) blocks: the pad-and-slice dispatch keeps the Pallas path
        bit-identical to the oracle and to the scalar loop."""
        one = sub_grid.select(("C2",))
        kw = dict(rows=12, row_bytes=2048, seed=1)
        pal = engine_test1.run_batch(one, [1.2, 1.15],
                                     inject_impl="pallas_interpret", **kw)
        ref = engine_test1.run_batch(one, [1.2, 1.15], **kw)
        sca = engine_test1.run_batch(one, [1.2, 1.15], impl="scalar",
                                     inject_impl="pallas_interpret", **kw)
        for f in BATCH_FIELDS:
            np.testing.assert_array_equal(getattr(pal, f), getattr(ref, f),
                                          err_msg=f)
            np.testing.assert_array_equal(getattr(pal, f), getattr(sca, f),
                                          err_msg=f)


class TestBatchedMinLatency:
    def test_matches_scalar_across_population_sample(self):
        grid = engine.DimmGrid.from_population(
            ("A1", "A9", "B2", "B5", "C2", "C5"))
        v = [1.25, 1.15, 1.075, 1.05]     # spans recovery floors -> NaNs
        b = engine_test1.find_min_latency_batch(grid, v)
        s = engine_test1.find_min_latency_batch(grid, v, impl="scalar")
        np.testing.assert_array_equal(b, s)
        assert np.isnan(b).any()          # the unrecoverable corner exists
        assert np.isfinite(b).any()

    def test_matches_dram_test1_directly(self):
        grid = engine.DimmGrid.from_population(("C2",))
        b = engine_test1.find_min_latency_batch(grid, [1.225])
        assert tuple(b[0, 0]) == test1.find_min_latency(_dimm("C2"), 1.225)

    def test_scalar_impl_requires_real_dimms(self):
        synth = engine.DimmGrid.from_vendor_z("A", [0.0])
        with pytest.raises(ValueError):
            engine_test1.find_min_latency_batch(synth, [1.2], impl="scalar")


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 2**30), n=st.integers(1, 3),
       rows=st.sampled_from([8, 16]),
       row_bytes=st.sampled_from([2048, 4096]), rounds=st.integers(1, 2))
def test_property_batched_test1_matches_scalar(seed, n, rows, row_bytes,
                                               rounds):
    """Random DIMM/voltage/pattern/geometry subsets: batched == scalar,
    bit-exact, because both draw the same per-(DIMM, round, bank) keys."""
    rng = np.random.default_rng(seed)
    pop = engine.DimmGrid.from_population()
    mods = tuple(rng.choice(np.asarray(pop.modules), size=n, replace=False))
    sub = pop.select(mods)
    v = np.round(rng.uniform(1.05, 1.3, size=int(rng.integers(1, 3))), 4)
    groups = [test1.PATTERN_GROUPS[i] for i in
              rng.choice(3, size=int(rng.integers(1, 4)), replace=False)]
    kw = dict(rounds=rounds, rows=rows, row_bytes=row_bytes,
              seed=int(rng.integers(0, 100)))
    b = engine_test1.run_batch(sub, v, tuple(groups), **kw)
    s = engine_test1.run_batch(sub, v, tuple(groups), impl="scalar", **kw)
    for f in BATCH_FIELDS:
        np.testing.assert_array_equal(getattr(b, f), getattr(s, f),
                                      err_msg=f)


@pytest.mark.slow
def test_multidevice_sharded_test1_matches_scalar():
    """8 forced host devices: the flat D*V*P*R axis (27 elements, not a
    multiple of 8 — exercising the pad path) sharded over a real
    ("batch",) mesh, dispatched and direct, still matches the scalar loop
    bit-exactly."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import sys
        sys.path.insert(0, "src")
        import numpy as np
        import jax
        from repro import engine
        from repro.engine import test1 as engine_test1
        from repro.launch import mesh as mesh_lib

        assert len(jax.devices()) == 8
        grid = engine.DimmGrid.from_population(("A1", "B2", "C2"))
        v = np.asarray([1.3, 1.15, 1.1])
        mesh = mesh_lib.make_batch_mesh()
        b = engine_test1.run_batch(grid, v, rows=8, mesh=mesh)
        d = engine_test1.run_batch(grid, v, rows=8, mesh=mesh,
                                   dispatch="direct")
        s = engine_test1.run_batch(grid, v, rows=8, impl="scalar")
        for f in ("bit_errors", "erroneous_lines", "error_rows"):
            np.testing.assert_array_equal(getattr(b, f), getattr(s, f),
                                          err_msg=f)
            np.testing.assert_array_equal(getattr(d, f), getattr(s, f),
                                          err_msg=f)
        fm = engine_test1.find_min_latency_batch(grid, v, mesh=mesh)
        fs = engine_test1.find_min_latency_batch(grid, v, impl="scalar")
        np.testing.assert_array_equal(fm, fs)
        print("SHARDED_TEST1_OK")
    """)
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=600, cwd=os.path.dirname(os.path.dirname(__file__)),
        env=dict(os.environ))
    assert "SHARDED_TEST1_OK" in out.stdout, out.stderr[-3000:]


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**30), rows=st.sampled_from([8, 16]),
       words=st.sampled_from([1024, 2048]))
def test_property_inject_kernel_bitexact(seed, rows, words):
    key = jax.random.key(seed)
    data = jax.random.bits(key, (rows, words), dtype=jnp.uint32)
    prob = jax.random.uniform(jax.random.key(seed + 1), (rows,),
                              jnp.float32, 0, 0.4)
    rw = jax.random.bits(jax.random.key(seed + 2), (rows, words),
                         dtype=jnp.uint32)
    pls = jax.random.bits(jax.random.key(seed + 3), (2, rows, words),
                          dtype=jnp.uint32)
    a = inject_ops.inject(data, prob, rw, pls, impl="reference")
    b = inject_ops.inject(data, prob, rw, pls, impl="pallas_interpret")
    assert bool((a == b).all())


def test_inject_zero_prob_identity():
    data = jnp.arange(8 * 1024, dtype=jnp.uint32).reshape(8, 1024)
    zero = jnp.zeros((8,), jnp.float32)
    rw = jax.random.bits(jax.random.key(0), (8, 1024), dtype=jnp.uint32)
    pls = jax.random.bits(jax.random.key(1), (2, 8, 1024), dtype=jnp.uint32)
    out = inject_ops.inject(data, zero, rw, pls, impl="reference")
    assert bool((out == data).all())
