"""Batched Test 1: the D x voltage x pattern-group x round sweep, one jit.

The scalar Test 1 (:mod:`repro.dram.test1`) walks every (DIMM, voltage,
pattern group, round) through a Python loop over banks, paying one
``voltage_inject`` dispatch plus a NumPy popcount per bank per operating
point.  This module runs the whole sweep the way the engine runs every other
sweep (:mod:`repro.engine.population` for the characterization grid,
``simulate_batch`` for the system grid):

- the per-bank probability mapping of ``errors.inject_row_errors`` is
  resolved **eagerly and vectorized** into one ``[D, V, banks, rows]``
  float32 table (same float32 threshold rounding as the scalar chain, so
  the injected masks are bit-identical);
- the per-(DIMM, round, bank) PRNG key chain of ``dram.test1.run`` is
  reproduced with vmapped splits, so the batched sweep draws **exactly the
  same random bits** as the scalar loop on matched seeds;
- the full D x V x P x R grid flattens into one leading batch axis, the
  random planes are generated in-jit from the carried key data, and the
  corruption runs as **one** ``voltage_inject`` dispatch over the flattened
  ``[N * banks * rows, words]`` plane, with popcount / line reduction in
  jnp;
- the flat axis is padded to the device count and sharded with a
  ``NamedSharding`` over :func:`repro.launch.mesh.make_batch_mesh` — the
  same transparent-on-one-device convention as ``characterize_batch`` —
  and reaches the kernel through :mod:`repro.engine.dispatch`
  (``dispatch="auto"``): bucketed padding with a lane mask for warm AOT
  executable reuse, or chunked ``lax.map`` streaming (random planes
  generated per chunk in-jit, O(chunk) peak memory) for megabatches over
  the resident budget; ``dispatch="direct"`` keeps the exact-shape jit
  call as the bit-exact parity reference.

``find_min_latency_batch`` replaces the Section 4.2 O(grid^2) Python loop
of closed-form error evaluations with one vectorized evaluation: a latency
pair is error-free iff the *most susceptible* cell clears the truncation
threshold for both operations (``_trunc_phi`` is monotone in x, so only
``max(field)`` matters), which turns the grid search into two [N, G]
threshold tables and a masked argmin.

The original per-bank path survives as ``impl="scalar"`` (a loop over
``dram.test1.run``) and is the parity reference:
``tests/test_errors_and_test1.py`` asserts the batched error counts, line
counts and row maps are bit-exact against it.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import hw
from repro.dram import chips, circuit, errors
from repro.dram import test1 as scalar_test1
from repro.engine import dispatch as dispatch_lib
from repro.engine import population
from repro.engine.population import DimmGrid
from repro.kernels.voltage_inject import ops as inject_ops
from repro.launch import mesh as mesh_lib

WORDS_PER_LINE = hw.CACHE_LINE_BYTES // 4          # 64 B line = 16 words


@dataclasses.dataclass(frozen=True)
class Test1Batch:
    """Results of one D x V x pattern-group x round Test-1 sweep.

    Array axes: D DIMMs, V voltages, P pattern groups, R rounds,
    [B, rows] = the reduced simulated geometry.
    """

    modules: tuple
    v_grid: np.ndarray              # [V]
    pattern_groups: tuple           # [P] of (data, ~data) label pairs
    rounds: int
    t_rcd: float
    t_rp: float
    banks: int
    rows: int
    row_bytes: int
    bit_errors: np.ndarray          # [D, V, P, R] int64
    erroneous_lines: np.ndarray     # [D, V, P, R] int64
    error_rows: np.ndarray          # [D, V, P, R, banks, rows] bool
    total_bits: int                 # per grid element
    total_lines: int                # per grid element

    @property
    def ber(self) -> np.ndarray:
        return self.bit_errors / self.total_bits

    @property
    def line_error_fraction(self) -> np.ndarray:
        return self.erroneous_lines / self.total_lines


# --------------------------------------------------------------------------
# Eager, vectorized input resolution (bit-identical to the scalar chain)
# --------------------------------------------------------------------------
def _word_probs(grid: DimmGrid, v: np.ndarray, t_rcd: float, t_rp: float,
                temp_c: float, rows: int) -> np.ndarray:
    """float32 [D, V, banks, rows] per-word corruption probabilities.

    This is ``errors.row_line_probs`` -> ``inject_row_errors``'s word-prob
    mapping vectorized over the whole (DIMM, voltage) grid: the float32
    threshold (``errors._x_threshold``) and the float64 word-probability
    arithmetic are reproduced operation-for-operation, so the float32 table
    matches the scalar per-bank values bit-for-bit.
    """
    req = population.required_latency32(grid, v, temp_c)
    field = grid.susceptibility                        # [D, B, G] float64
    sigma32 = grid.cell_sigma.astype(np.float32)
    p_ok = np.ones((grid.n_dimms, v.size) + field.shape[1:])
    for op, t_prog in (("rcd", t_rcd), ("rp", t_rp)):
        x32 = (t_prog / req[op] - 1.0) / sigma32[:, None]   # [D, V] float32
        p_ok = p_ok * chips._trunc_phi(x32[:, :, None, None]
                                       - field[:, None])
    probs = 1.0 - p_ok                                  # [D, V, B, G]
    groups = field.shape[2]
    idx = (np.arange(rows) * groups) // rows
    p_line = probs[..., idx]                            # [D, V, B, rows]
    p_word = 1.0 - (1.0 - p_line) ** (1.0 / WORDS_PER_LINE)
    p_word = np.clip(p_word * 0.55 * WORDS_PER_LINE / 2, 0.0, 1.0)
    return p_word.astype(np.float32)


def _bank_key_data(indices, rounds: int, seed: int, banks: int) -> np.ndarray:
    """uint32 [D, R, banks, 2, 2] PRNG key data reproducing the scalar
    chain of ``dram.test1.run``: per (DIMM, round) the base key is
    ``jax.random.key(seed_r * 1000003 + index)`` and each bank consumes one
    sequential split; ``[..., 0, :]`` / ``[..., 1, :]`` are the word / plane
    subkeys (``k1``/``k2`` of ``errors.inject_row_errors``)."""
    idx = np.asarray(indices, np.int64)
    seeds = ((seed + np.arange(rounds, dtype=np.int64))[None, :] * 1000003
             + idx[:, None])                            # [D, R]
    base = jax.vmap(jax.random.key)(jnp.asarray(seeds.reshape(-1)))
    k1s, k2s = [], []
    for _ in range(banks):
        pair = jax.vmap(jax.random.split)(base)         # [D*R, 2] keys
        base = pair[:, 0]
        sub = jax.vmap(jax.random.split)(pair[:, 1])
        k1s.append(sub[:, 0])
        k2s.append(sub[:, 1])
    kd = np.stack([np.asarray(jax.random.key_data(jnp.stack(ks, axis=1)))
                   for ks in (k1s, k2s)], axis=2)       # [D*R, B, 2, 2]
    return kd.reshape(idx.size, rounds, banks, 2, 2)


# --------------------------------------------------------------------------
# The flat-batch kernel
# --------------------------------------------------------------------------
def _test1_flat_fn(p_word, key_data, p_idx, patterns, valid, *, banks, rows,
                   words, nplanes, inject_impl):
    """One Test-1 evaluation of the flat N = D*V*P*R batch.

    ``p_word`` float32 [N, banks, rows]; ``key_data`` uint32 [N, banks, 2, 2];
    ``p_idx`` int32 [N] pattern-group index; ``patterns`` uint32 [P, 2]
    (data, ~data) words; ``valid`` bool [N] masks padded lanes (their
    counts/maps land on zero).  The random planes are generated in-jit from
    the carried key data — under chunked dispatch that means one chunk's
    planes at a time — and the corruption runs as a single
    ``voltage_inject`` dispatch over the flattened [N*banks*rows, words]
    plane.
    """
    n = p_word.shape[0]
    # write data into even rows, ~data into odd rows (Test 1 lines 4-5)
    row_sel = (jnp.arange(rows) % 2).astype(jnp.int32)
    vals = patterns[p_idx][:, row_sel]                  # [N, rows]
    data = jnp.broadcast_to(vals[:, None, :, None], (n, banks, rows, words))

    keys = jax.random.wrap_key_data(key_data)           # [N, banks, 2]
    flat_keys = keys.reshape(n * banks, 2)
    rand_word = jax.vmap(
        lambda k: jax.random.bits(k, (rows, words), dtype=jnp.uint32))(
        flat_keys[:, 0])
    rand_planes = jax.vmap(
        lambda k: jax.random.bits(k, (nplanes, rows, words),
                                  dtype=jnp.uint32))(flat_keys[:, 1])

    plane_rows = n * banks * rows
    got = inject_ops.inject(
        data.reshape(plane_rows, words),
        p_word.reshape(plane_rows),
        rand_word.reshape(plane_rows, words),
        jnp.moveaxis(rand_planes, 1, 0).reshape(nplanes, plane_rows, words),
        impl=inject_impl)

    flips = jax.lax.population_count(got ^ data.reshape(plane_rows, words))
    flips = flips.reshape(n, banks, rows, words).astype(jnp.int32)
    line_bad = flips.reshape(n, banks, rows, words // WORDS_PER_LINE,
                             WORDS_PER_LINE).sum(-1) > 0
    return {
        "bit_errors": jnp.where(valid, flips.sum(axis=(1, 2, 3)), 0),
        "erroneous_lines": jnp.where(
            valid, line_bad.sum(axis=(1, 2, 3)), 0).astype(jnp.int32),
        "error_rows": valid[:, None, None] & (flips.sum(axis=3) > 0),
    }


_test1_flat = jax.jit(_test1_flat_fn,
                      static_argnames=("banks", "rows", "words", "nplanes",
                                       "inject_impl"))


def _dispatch_test1_plane(entry, inputs, patterns, statics, mesh,
                          dispatch_mode, max_elements_resident):
    """Run ``_test1_flat_fn`` over a flattened stress batch — shared by the
    Test-1 pattern sweep (entry ``"test1"``) and the hammer sweep (entry
    ``"hammer"``): one ``voltage_inject`` dispatch per call, bucketed /
    chunked through the dispatch layer, or the exact-shape jit for
    ``dispatch="direct"`` (the bit-exact parity reference)."""
    mesh = mesh_lib.make_batch_mesh() if mesh is None else mesh
    n_devices = int(mesh.devices.size)
    if dispatch_mode == "direct":
        inputs, n_pad = population._pad_flat(inputs, n_devices)
        args = [jnp.asarray(a) for a in inputs]
        valid = jnp.ones((args[0].shape[0],), bool)
        pat = jnp.asarray(patterns)
        if n_devices > 1:
            args = [jax.device_put(a, mesh_lib.batch_sharding(mesh, a.ndim))
                    for a in args]
            valid = jax.device_put(valid, mesh_lib.batch_sharding(mesh, 1))
            pat = jax.device_put(pat, jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec()))
            # GSPMD cannot partition the Pallas inject: one slice per device
            kernel = dispatch_lib.lane_sharded(
                functools.partial(_test1_flat_fn, **statics), mesh,
                len(args), 1, 0)
            out = jax.jit(kernel)(*args, pat, valid)
        else:
            out = _test1_flat(*args, pat, valid, **statics)
        out = {k: np.asarray(a) for k, a in out.items()}
        if n_pad:
            out = {k: a[:-n_pad] for k, a in out.items()}
        return out
    # the [banks, rows, words] data/random planes plus popcounts are
    # the resident footprint each flat element drags through the jit
    cfg = None if max_elements_resident is None else \
        dispatch_lib.DispatchConfig(
            max_elements_resident=int(max_elements_resident))
    banks, rows, words, nplanes = (statics["banks"], statics["rows"],
                                   statics["words"], statics["nplanes"])
    out = dispatch_lib.dispatch_flat(
        entry, functools.partial(_test1_flat_fn, **statics),
        inputs, (patterns,), statics_key=tuple(sorted(statics.items())),
        mesh=mesh, element_cost=(nplanes + 4) * banks * rows * words,
        mode=dispatch_mode, config=cfg)
    return {k: np.asarray(a) for k, a in out.items()}


def _run_batched(grid, v, pattern_groups, rounds, t_rcd, t_rp, banks, rows,
                 row_bytes, temp_c, seed, nplanes, mesh, inject_impl,
                 dispatch_mode: str = "auto",
                 max_elements_resident: int | None = None):
    words = row_bytes // 4
    d_, v_, p_ = grid.n_dimms, v.size, len(pattern_groups)
    shape4 = (d_, v_, p_, rounds)

    with dispatch_lib.span("test1.lower"):
        p_word = _word_probs(grid, v, t_rcd, t_rp, temp_c, rows)
        kd = _bank_key_data([d.index for d in grid.dimms], rounds, seed,
                            banks)
        patterns = np.array([[scalar_test1.DATA_PATTERNS[a],
                              scalar_test1.DATA_PATTERNS[b]]
                             for a, b in pattern_groups], np.uint32)

        # flatten D x V x P x R into the leading batch axis
        flat = lambda a, trail: np.ascontiguousarray(
            np.broadcast_to(a, shape4 + trail).reshape((-1,) + trail))
        inputs = [
            flat(p_word[:, :, None, None], (banks, rows)),
            flat(kd[:, None, None], (banks, 2, 2)),
            flat(np.arange(p_, dtype=np.int32)[None, None, :, None], ()),
        ]

    statics = dict(banks=banks, rows=rows, words=words, nplanes=nplanes,
                   inject_impl=inject_impl)
    out = _dispatch_test1_plane("test1", inputs, patterns, statics, mesh,
                                dispatch_mode, max_elements_resident)

    return Test1Batch(
        grid.modules, v, tuple(tuple(g) for g in pattern_groups), rounds,
        t_rcd, t_rp, banks, rows, row_bytes,
        out["bit_errors"].reshape(shape4).astype(np.int64),
        out["erroneous_lines"].reshape(shape4).astype(np.int64),
        out["error_rows"].reshape(shape4 + (banks, rows)),
        banks * rows * words * 32,
        banks * rows * (words // WORDS_PER_LINE))


# --------------------------------------------------------------------------
# Scalar reference implementation (loop over dram.test1.run)
# --------------------------------------------------------------------------
def _run_scalar(grid, v, pattern_groups, rounds, t_rcd, t_rp, banks, rows,
                row_bytes, temp_c, seed, nplanes, inject_impl):
    d_, v_, p_ = grid.n_dimms, v.size, len(pattern_groups)
    shape4 = (d_, v_, p_, rounds)
    bit_errors = np.zeros(shape4, np.int64)
    bad_lines = np.zeros(shape4, np.int64)
    err_rows = np.zeros(shape4 + (banks, rows), bool)
    res = None
    for di, d in enumerate(grid.dimms):
        for vi, vv in enumerate(v):
            for pi, g in enumerate(pattern_groups):
                for ri in range(rounds):
                    res = scalar_test1.run(
                        d, float(vv), t_rcd, t_rp, pattern_group=tuple(g),
                        banks=banks, rows=rows, row_bytes=row_bytes,
                        temp_c=temp_c, seed=seed + ri, nplanes=nplanes,
                        impl=inject_impl)
                    bit_errors[di, vi, pi, ri] = res.bit_errors
                    bad_lines[di, vi, pi, ri] = res.erroneous_lines
                    err_rows[di, vi, pi, ri] = res.error_rows
    return Test1Batch(
        grid.modules, v, tuple(tuple(g) for g in pattern_groups), rounds,
        t_rcd, t_rp, banks, rows, row_bytes, bit_errors, bad_lines,
        err_rows, res.total_bits, res.total_lines)


@dispatch_lib.span("test1")
def run_batch(grid: DimmGrid, v_grid,
              pattern_groups=tuple(scalar_test1.PATTERN_GROUPS), *,
              rounds: int = 1, t_rcd: float = 10.0, t_rp: float = 10.0,
              banks: int = 8, rows: int = 64, row_bytes: int = 4096,
              temp_c: float = 20.0, seed: int = 0, nplanes: int = 2,
              mesh=None, impl: str = "auto",
              inject_impl: str | None = None, dispatch: str = "auto",
              max_elements_resident: int | None = None) -> Test1Batch:
    """Run Test 1 on every (DIMM, voltage, pattern group, round) at once.

    The D x V x P x R grid flattens into one batch axis evaluated by a
    single jit-compiled call (one ``voltage_inject`` dispatch over the
    flattened plane), sharded over ``mesh`` (default: the 1-D ``("batch",)``
    mesh — a no-op on one device).  ``seed`` is the base seed; round ``r``
    injects with ``seed + r``, matching ``dram.test1.voltage_sweep``.
    ``impl="scalar"`` runs the original per-bank loop over
    ``dram.test1.run`` instead (parity reference and benchmark baseline);
    ``inject_impl`` picks the ``voltage_inject`` implementation for either
    path (default: the ops-level auto choice).

    ``dispatch``: "auto" routes the flat axis through
    :mod:`repro.engine.dispatch` — padded to a canonical bucket (warm AOT
    executable per bucket, bit-exact: padded lanes are masked out) or, when
    the sweep overflows the resident-element budget, streamed chunk by
    chunk with the random planes generated per chunk in-jit (peak memory
    O(chunk)).  "bucketed"/"chunked" force a path; "direct" keeps the
    exact-shape jit call (the dispatched paths' bit-exact parity
    reference).  ``max_elements_resident`` overrides the dispatch layer's
    resident-footprint budget (in element-cost units) — the knob that
    decides when a megabatch starts streaming.
    """
    if grid.dimms is None:
        raise ValueError("Test 1 needs a grid built from real DIMMs "
                         "(DimmGrid.from_population / from_dimms)")
    v = np.atleast_1d(np.asarray(v_grid, np.float64))
    t_rcd, t_rp, temp_c = float(t_rcd), float(t_rp), float(temp_c)
    if impl == "auto":
        impl = "batched"
    if impl == "scalar":
        return _run_scalar(grid, v, pattern_groups, rounds, t_rcd, t_rp,
                           banks, rows, row_bytes, temp_c, seed, nplanes,
                           inject_impl or "auto")
    if impl != "batched":
        raise ValueError(f"unknown impl {impl!r}")
    if dispatch not in ("auto", "bucketed", "chunked", "direct"):
        raise ValueError(f"unknown dispatch {dispatch!r}")
    if inject_impl is None:
        inject_impl = ("pallas" if jax.default_backend() == "tpu"
                       else "reference")
    return _run_batched(grid, v, pattern_groups, rounds, t_rcd, t_rp, banks,
                        rows, row_bytes, temp_c, seed, nplanes, mesh,
                        inject_impl, dispatch, max_elements_resident)


# --------------------------------------------------------------------------
# Batched RowHammer stress (the hammer pattern-group on the Test-1 axis)
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class HammerBatch:
    """Results of one D x V x hammer-count x round disturbance sweep.

    The hammer-count axis H rides the Test-1 flat axis in the
    pattern-group slot: the grid flattens to ``N = D * V * H * R`` and runs
    through the same ``voltage_inject`` dispatch plane as ``run_batch``
    (entry ``"hammer"``).  Even rows are aggressors (never flip), odd rows
    the blast-radius-1 victims.
    """

    modules: tuple
    v_grid: np.ndarray              # [V]
    hammer_counts: np.ndarray       # [H]
    rounds: int
    pattern: str                    # aggressor/victim (data, ~data) labels
    banks: int
    rows: int
    row_bytes: int
    bit_errors: np.ndarray          # [D, V, H, R] int64 (victim flips)
    erroneous_lines: np.ndarray     # [D, V, H, R] int64
    error_rows: np.ndarray          # [D, V, H, R, banks, rows] bool
    total_bits: int                 # per grid element
    total_lines: int                # per grid element

    @property
    def ber(self) -> np.ndarray:
        return self.bit_errors / self.total_bits

    @property
    def line_error_fraction(self) -> np.ndarray:
        return self.erroneous_lines / self.total_lines

    @property
    def victim_row_fraction(self) -> np.ndarray:
        """[D, V, H, R] fraction of victim (odd) rows with >= 1 flip."""
        victims = self.error_rows[..., 1::2]
        return victims.mean(axis=(-2, -1))


def _hammer_word_probs(grid: DimmGrid, v: np.ndarray, hammer_counts,
                       rows: int) -> np.ndarray:
    """float32 [D, V, H, banks, rows] hammer corruption probabilities —
    :func:`repro.dram.errors.hammer_word_probs` broadcast over the whole
    (DIMM, voltage, hammer-count) grid.  The scalar reference calls the
    identical elementwise function, so the tables match bit-for-bit."""
    h = np.asarray(hammer_counts, np.float64)
    field = grid.susceptibility[:, None, None]           # [D, 1, 1, B, G]
    return errors.hammer_word_probs(
        field, v[None, :, None, None, None],
        h[None, None, :, None, None], rows)


def _run_hammer_scalar(grid, v, h, rounds, pattern_group, banks, rows,
                       row_bytes, seed, nplanes, inject_impl):
    shape4 = (grid.n_dimms, v.size, h.size, rounds)
    bit_errors = np.zeros(shape4, np.int64)
    bad_lines = np.zeros(shape4, np.int64)
    err_rows = np.zeros(shape4 + (banks, rows), bool)
    res = None
    for di, d in enumerate(grid.dimms):
        for vi, vv in enumerate(v):
            for hi, hh in enumerate(h):
                for ri in range(rounds):
                    res = scalar_test1.run_hammer(
                        d, float(vv), float(hh),
                        pattern_group=tuple(pattern_group), banks=banks,
                        rows=rows, row_bytes=row_bytes, seed=seed + ri,
                        nplanes=nplanes, impl=inject_impl)
                    bit_errors[di, vi, hi, ri] = res.bit_errors
                    bad_lines[di, vi, hi, ri] = res.erroneous_lines
                    err_rows[di, vi, hi, ri] = res.error_rows
    return HammerBatch(
        grid.modules, v, h, rounds, "/".join(pattern_group), banks, rows,
        row_bytes, bit_errors, bad_lines, err_rows, res.total_bits,
        res.total_lines)


@dispatch_lib.span("hammer")
def run_hammer_batch(grid: DimmGrid, v_grid, hammer_counts, *,
                     rounds: int = 1, pattern_group=("0xaa", "0x55"),
                     banks: int = 8, rows: int = 64, row_bytes: int = 4096,
                     seed: int = 0, nplanes: int = 2, mesh=None,
                     impl: str = "auto", inject_impl: str | None = None,
                     dispatch: str = "auto",
                     max_elements_resident: int | None = None
                     ) -> HammerBatch:
    """RowHammer stress on every (DIMM, voltage, hammer count, round) at
    once — the hammer pattern-group on the Test-1 flat batch axis.

    Aggressor (even) rows hold the data pattern and are toggled
    ``hammer_counts[h]`` times; victim (odd) rows hold the inverse and are
    read back through the same flat ``voltage_inject`` dispatch plane as
    ``run_batch`` — the D x V x H x R grid flattens into one leading batch
    axis (no Python loop over DIMMs or voltages), the per-element PRNG key
    data reproduces the scalar split chain of ``dram.test1.run_hammer``
    bit-exactly, and the per-element probability table encodes the
    aggressor/victim structure (aggressors at exactly 0).  Dispatch
    semantics (bucketing, chunking, ``dispatch="direct"`` parity reference)
    are identical to ``run_batch``; stats land under entry ``"hammer"``.
    ``impl="scalar"`` loops ``dram.test1.run_hammer`` instead (the parity
    reference and benchmark baseline).
    """
    if grid.dimms is None:
        raise ValueError("the hammer sweep needs a grid built from real "
                         "DIMMs (DimmGrid.from_population / from_dimms)")
    v = np.atleast_1d(np.asarray(v_grid, np.float64))
    h = np.atleast_1d(np.asarray(hammer_counts, np.float64))
    if impl == "auto":
        impl = "batched"
    if impl == "scalar":
        return _run_hammer_scalar(grid, v, h, rounds, pattern_group, banks,
                                  rows, row_bytes, seed, nplanes,
                                  inject_impl or "auto")
    if impl != "batched":
        raise ValueError(f"unknown impl {impl!r}")
    if dispatch not in ("auto", "bucketed", "chunked", "direct"):
        raise ValueError(f"unknown dispatch {dispatch!r}")
    if inject_impl is None:
        inject_impl = ("pallas" if jax.default_backend() == "tpu"
                       else "reference")

    words = row_bytes // 4
    shape4 = (grid.n_dimms, v.size, h.size, rounds)
    with dispatch_lib.span("hammer.lower"):
        p_word = _hammer_word_probs(grid, v, h, rows)    # [D, V, H, B, rows]
        kd = _bank_key_data([d.index for d in grid.dimms], rounds, seed,
                            banks)
        patterns = np.array([[scalar_test1.DATA_PATTERNS[pattern_group[0]],
                              scalar_test1.DATA_PATTERNS[pattern_group[1]]]],
                            np.uint32)                   # [1, 2]

        flat = lambda a, trail: np.ascontiguousarray(
            np.broadcast_to(a, shape4 + trail).reshape((-1,) + trail))
        inputs = [
            flat(p_word[:, :, :, None], (banks, rows)),
            flat(kd[:, None, None], (banks, 2, 2)),
            flat(np.zeros((1, 1, 1, 1), np.int32), ()),
        ]
    statics = dict(banks=banks, rows=rows, words=words, nplanes=nplanes,
                   inject_impl=inject_impl)
    out = _dispatch_test1_plane("hammer", inputs, patterns, statics, mesh,
                                dispatch, max_elements_resident)
    return HammerBatch(
        grid.modules, v, h, rounds, "/".join(pattern_group), banks, rows,
        row_bytes,
        out["bit_errors"].reshape(shape4).astype(np.int64),
        out["erroneous_lines"].reshape(shape4).astype(np.int64),
        out["error_rows"].reshape(shape4 + (banks, rows)),
        banks * rows * words * 32,
        banks * rows * (words // WORDS_PER_LINE))


# --------------------------------------------------------------------------
# Batched Section 4.2 latency grid search
# --------------------------------------------------------------------------
def _min_latency_flat_fn(x_rcd, x_rp, field_max, v, recovery_floor,
                         fail_floor, lat_grid, valid):
    """Masked-argmin latency search over the flat N = D*V batch.

    ``x_rcd``/``x_rp`` [N, G] are the cell-threshold z-scores of each
    candidate latency; a candidate is error-free iff the most susceptible
    cell clears the truncated support (``x - max(field) >= CELL_XMAX`` —
    ``_trunc_phi`` is monotone, so the worst cell decides).  Ties resolve by
    flat row-major argmin: min (tRCD + tRP), then min tRCD, then min tRP —
    the documented ``dram.test1.find_min_latency`` order.  ``valid`` [N] is
    the dispatch lane mask (dead lanes land on 0.0 — NaN is a *real*
    "unrecoverable" result, so padded lanes must not fake one).
    """
    ok_rcd = x_rcd - field_max[:, None] >= chips.CELL_XMAX      # [N, G]
    ok_rp = x_rp - field_max[:, None] >= chips.CELL_XMAX
    usable = (v >= recovery_floor) & (v >= fail_floor)          # [N]
    ok = ok_rcd[:, :, None] & ok_rp[:, None, :] & usable[:, None, None]
    sums = lat_grid[:, None] + lat_grid[None, :]                # [G, G]
    g = lat_grid.shape[0]
    score = jnp.where(ok, sums[None], jnp.inf).reshape(-1, g * g)
    best = jnp.argmin(score, axis=1)
    found = jnp.isfinite(jnp.min(score, axis=1))
    t_rcd = jnp.where(found, lat_grid[best // g], jnp.nan)
    t_rp = jnp.where(found, lat_grid[best % g], jnp.nan)
    out = jnp.stack([t_rcd, t_rp], axis=-1)
    return {"lat": jnp.where(valid[:, None], out, 0.0)}


_min_latency_flat = jax.jit(_min_latency_flat_fn)


@dispatch_lib.span("min_latency.lower")
def min_latency_inputs(grid: DimmGrid, v_grid, *, step: float = 2.5,
                       max_latency: float = 20.0,
                       temp_c: float = 20.0) -> tuple:
    """Eager per-lane operands of ``_min_latency_flat_fn`` for the
    flattened D x V grid: ``(inputs, lat_grid)``.

    Every array's values depend only on its own (DIMM, voltage) lane —
    never on the batch composition — which is what lets the serving
    front-end concatenate lanes from different requests and stay bit-exact
    against the per-request path (``find_min_latency_batch`` shares this
    exact lowering).
    """
    v = np.atleast_1d(np.asarray(v_grid, np.float64))
    lat = np.arange(10.0, float(max_latency) + 1e-9, float(step))
    req = population.required_latency32(grid, v, float(temp_c))
    # the scalar path passes the float64 grid latency into
    # line_error_fraction, so the threshold is float64 of a float32 req —
    # mirror that promotion exactly
    x = {op: ((lat[None, None, :] / req[op][:, :, None].astype(np.float64)
               - 1.0) / grid.cell_sigma[:, None, None])
         for op in ("rcd", "rp")}
    floors = np.array([circuit.VENDORS[vd].recovery_floor
                       for vd in grid.vendors])
    field_max = grid.susceptibility.reshape(grid.n_dimms, -1).max(axis=1)

    d_, v_ = grid.n_dimms, v.size
    flat = lambda a: np.ascontiguousarray(
        np.broadcast_to(a, (d_, v_) + a.shape[2:]).reshape(
            (-1,) + a.shape[2:]))
    inputs = [
        flat(x["rcd"]), flat(x["rp"]),
        flat(np.broadcast_to(field_max[:, None], (d_, v_))),
        flat(np.broadcast_to(v[None, :], (d_, v_))),
        flat(np.broadcast_to(floors[:, None], (d_, v_))),
        flat(np.broadcast_to(grid.fail_floor[:, None], (d_, v_))),
    ]
    return inputs, lat


@dispatch_lib.span("min_latency")
def find_min_latency_batch(grid: DimmGrid, v_grid, *, step: float = 2.5,
                           max_latency: float = 20.0, temp_c: float = 20.0,
                           mesh=None, impl: str = "auto",
                           dispatch: str = "auto") -> np.ndarray:
    """Smallest error-free (tRCD, tRP) per (DIMM, voltage): float64
    [D, V, 2], NaN pairs where no latency <= ``max_latency`` recovers
    correct operation (or the voltage is below the vendor recovery floor).

    One vectorized closed-form evaluation replaces the scalar O(grid^2)
    loop of ``line_error_fraction`` calls: the float32/float64 threshold
    arithmetic of the scalar path is reproduced eagerly, and the candidate
    grid is resolved by a single jit-compiled masked argmin, sharded over
    the flat D x V axis.  Tie-breaking matches the documented
    ``dram.test1.find_min_latency`` order (min sum, then min tRCD, then
    min tRP).

    ``dispatch="auto"`` routes the flat D x V axis through
    :mod:`repro.engine.dispatch` — the fleet layer issues one request per
    candidate-table build, with D and V varying per request, so warm AOT
    executable reuse (``dispatch.stats("min_latency")``) replaces the
    retrace-per-shape behavior of the old private exact-shape jit;
    ``"direct"`` keeps the exact-shape call as the parity reference.
    """
    v = np.atleast_1d(np.asarray(v_grid, np.float64))
    if impl == "scalar":
        if grid.dimms is None:
            raise ValueError("impl='scalar' needs a grid built from real "
                             "DIMMs")
        out = np.full((grid.n_dimms, v.size, 2), np.nan)
        for di, d in enumerate(grid.dimms):
            for vi, vv in enumerate(v):
                best = scalar_test1.find_min_latency(
                    d, float(vv), step=step, max_latency=max_latency,
                    temp_c=temp_c)
                if best is not None:
                    out[di, vi] = best
        return out
    if impl not in ("auto", "batched"):
        raise ValueError(f"unknown impl {impl!r}")
    if dispatch not in ("auto", "bucketed", "chunked", "direct"):
        raise ValueError(f"unknown dispatch {dispatch!r}")

    inputs, lat = min_latency_inputs(grid, v, step=step,
                                     max_latency=max_latency, temp_c=temp_c)
    d_, v_ = grid.n_dimms, v.size
    mesh = mesh_lib.make_batch_mesh() if mesh is None else mesh
    n_devices = int(mesh.devices.size)
    # float64 end to end (like characterize_batch): the scalar decision is
    # made on float64 thresholds, so the batched one must not round to f32
    with jax.enable_x64(True):
        if dispatch == "direct":
            inputs, n_pad = population._pad_flat(inputs, n_devices)
            args = [jnp.asarray(a) for a in inputs]
            valid = jnp.ones((args[0].shape[0],), bool)
            if n_devices > 1:
                args = [jax.device_put(a,
                                       mesh_lib.batch_sharding(mesh, a.ndim))
                        for a in args]
                valid = jax.device_put(valid,
                                       mesh_lib.batch_sharding(mesh, 1))
            out = np.asarray(
                _min_latency_flat(*args, jnp.asarray(lat), valid)["lat"],
                np.float64)
            if n_pad:
                out = out[:-n_pad]
        else:
            res = dispatch_lib.dispatch_flat(
                "min_latency", _min_latency_flat_fn, inputs, (lat,),
                mesh=mesh, element_cost=8 * lat.size * lat.size,
                mode=dispatch)
            out = np.asarray(res["lat"], np.float64)
    return out.reshape(d_, v_, 2)
