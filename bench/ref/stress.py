"""Plain reference of the Test-1 and RowHammer stress rounds.

Follows the paper's Test 1 (Section 3) as the per-bank scalar loop does:
every bank of a DIMM gets its own key from one sequential split chain
(base key ``seed * 1000003 + dimm.index``), even rows hold the data
pattern and odd rows its inverse, a word is corrupted iff its uniform
24-bit draw lies under the row's word-corruption probability, and a
corrupted word flips the AND of ``nplanes`` random bit planes.  The
probabilities come from this directory's copy of the DIMM population
model, evaluated in float64 on the host and rounded to ``p_dtype``.

The bit-level part (draws, injection, popcounts) is integer arithmetic
and a comparison of exactly representable floats, so it runs on the
default device in blocks of lanes; nothing here imports the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import chips, hw

DATA_PATTERNS = {
    "0x00": 0x00000000, "0xff": 0xFFFFFFFF,
    "0xaa": 0xAAAAAAAA, "0x33": 0x33333333,
    "0xcc": 0xCCCCCCCC, "0x55": 0x55555555,
}
PATTERN_GROUPS = (("0x00", "0xff"), ("0xaa", "0x55"), ("0xcc", "0x33"))
HAMMER_GROUP = ("0xaa", "0x55")
WORDS_PER_LINE = hw.CACHE_LINE_BYTES // 4

# RowHammer disturbance model constants (arxiv 2206.09999 shape)
HAMMER_HC0 = 200_000.0
HAMMER_V_SENS = 0.5
HAMMER_FIELD_SENS = 0.3
HAMMER_SIGMA = 0.15


def dimms() -> dict:
    """The 31 Table 7 DIMMs by module name."""
    return {d.module: d for d in chips.population()}


def _rows_to_words(p_line: np.ndarray, rows: int) -> np.ndarray:
    """Line-error probability per row-group -> per-word corruption
    probability per row (16 words per line, concentrated by the beat
    density factor), float64."""
    groups = p_line.shape[-1]
    idx = (np.arange(rows) * groups) // rows
    p_line = p_line[..., idx]
    p_word = 1.0 - (1.0 - p_line) ** (1.0 / WORDS_PER_LINE)
    return np.clip(p_word * 0.55 * WORDS_PER_LINE / 2, 0.0, 1.0)


def test1_word_probs(dimm, v: float, rows: int, t_rcd: float = 10.0,
                     t_rp: float = 10.0, temp_c: float = 20.0) -> np.ndarray:
    """float64 [banks, rows] word-corruption probabilities of one Test-1
    round at ``v`` and the programmed latencies."""
    field = dimm.susceptibility                       # [banks, groups]
    p_ok = np.ones_like(field)
    for op, t_prog in (("rcd", t_rcd), ("rp", t_rp)):
        req = dimm.required_latency(op, v, temp_c)    # float32
        x_thr = (t_prog / req - 1.0) / dimm.cell_sigma
        p_ok = p_ok * chips._trunc_phi(x_thr - field)
    return _rows_to_words(1.0 - p_ok, rows)


def hammer_word_probs(dimm, v: float, hammer_count: float,
                      rows: int) -> np.ndarray:
    """float64 [banks, rows] victim word-corruption probabilities after
    ``hammer_count`` activations of every aggressor (even) row."""
    field = np.asarray(dimm.susceptibility, np.float64)
    exponent = (HAMMER_V_SENS * (v - hw.VDD_NOMINAL) / chips.DEFICIT_RANGE_V
                - HAMMER_FIELD_SENS * field)
    threshold = HAMMER_HC0 * np.power(10.0, exponent)
    h = max(float(hammer_count), 1.0)
    x = (np.log10(h) - np.log10(threshold)) / HAMMER_SIGMA - chips.CELL_XMAX
    p_word = _rows_to_words(chips._trunc_phi(x), rows)
    return np.where(np.arange(rows) % 2 == 0, 0.0, p_word)


def bank_keys(dimm_index: int, seed: int, banks: int) -> np.ndarray:
    """uint32 [banks, 2, 2]: the (k1, k2) key data of every bank."""
    key = jax.random.key(seed * 1000003 + dimm_index)
    out = []
    for _ in range(banks):
        key, sub = jax.random.split(key)
        k1, k2 = jax.random.split(sub)
        out.append(np.stack([np.asarray(jax.random.key_data(k1)),
                             np.asarray(jax.random.key_data(k2))]))
    return np.stack(out).astype(np.uint32)


@functools.partial(jax.jit, static_argnames=("words", "nplanes"))
def _lanes(p_word, keys, pats, *, words: int, nplanes: int):
    """Counts of a block of lanes: ``p_word`` float32 [L, banks, rows],
    ``keys`` uint32 [L, banks, 2, 2], ``pats`` uint32 [L, 2]."""
    rows = p_word.shape[2]

    def bank(p, kd, pat):
        k1 = jax.random.wrap_key_data(kd[0])
        k2 = jax.random.wrap_key_data(kd[1])
        data = jnp.where(jnp.arange(rows)[:, None] % 2 == 0, pat[0], pat[1])
        data = jnp.broadcast_to(data, (rows, words)).astype(jnp.uint32)
        rand_word = jax.random.bits(k1, (rows, words), dtype=jnp.uint32)
        planes = jax.random.bits(k2, (nplanes, rows, words), dtype=jnp.uint32)
        u = (rand_word >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)
        bad = u < p[:, None]
        flip = planes[0]
        for i in range(1, nplanes):
            flip = flip & planes[i]
        flips = jax.lax.population_count(jnp.where(bad, flip, 0))
        flips = flips.astype(jnp.int32)
        lines = flips.reshape(rows, words // WORDS_PER_LINE,
                              WORDS_PER_LINE).sum(-1) > 0
        return flips.sum(), lines.sum(), flips.sum(axis=1) > 0

    def lane(x):
        p, kd, pat = x
        bits, lines, rows_bad = jax.vmap(bank, in_axes=(0, 0, None))(
            p, kd, pat)
        return bits.sum(), lines.sum(), rows_bad

    return jax.lax.map(lane, (p_word, keys, pats))


def run_lanes(p_word: np.ndarray, keys: np.ndarray, pats: np.ndarray, *,
              words: int, nplanes: int, p_dtype=np.float32,
              block: int = 16) -> dict:
    """Reference counts of every lane: ``p_word`` float64 [N, banks,
    rows] rounded to ``p_dtype`` (float32 as the configuration states;
    the precision control passes a lower one), ``keys`` [N, banks, 2, 2],
    ``pats`` [N, 2].  Runs in blocks of ``block`` lanes."""
    p = np.asarray(np.asarray(p_word).astype(p_dtype), np.float32)
    n = p.shape[0]
    outs = []
    for s in range(0, n, block):
        sl = slice(s, min(s + block, n))
        pad = block - (sl.stop - sl.start)
        blk = [np.asarray(a[sl]) for a in (p, keys, pats)]
        if pad:
            blk = [np.concatenate([a, np.repeat(a[:1], pad, axis=0)])
                   for a in blk]
        res = _lanes(*blk, words=words, nplanes=nplanes)
        outs.append([np.asarray(r)[:block - pad] for r in res])
    bits, lines, rows = (np.concatenate(c) for c in zip(*outs))
    return {"bit_errors": bits.astype(np.int64),
            "erroneous_lines": lines.astype(np.int64), "error_rows": rows}
