"""Pallas TPU kernel for voltage-error bit injection.

The Test-1 characterization sweep touches every cache line of a DIMM for
every (voltage, latency, data-pattern, round) combination — on the real
FPGA platform this is hours of wall time, and in simulation it is the hot
loop of the characterization substrate.  The kernel tiles the (rows x words)
data plane into VMEM blocks and applies the corruption mask with pure
integer ops (compare / AND / XOR), which map onto the TPU VPU lanes.

Tiling: rows x words blocks of (8, 1024) uint32 = 32 KiB per operand block,
five operands resident -> ~160 KiB of VMEM per grid step, well inside the
~16 MiB VMEM budget while keeping the lane dimension (1024 words = 8 x 128
lanes) MXU/VPU aligned.  ``ROW_BLOCK`` / ``WORD_BLOCK`` are the default
tile; the ``row_block`` / ``word_block`` statics take another (the tests
run several grid steps at small sizes that way).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROW_BLOCK = 8
WORD_BLOCK = 1024


def _inject_kernel(nplanes: int, data_ref, prob_ref, rand_ref, planes_ref,
                   out_ref):
    data = data_ref[...]
    prob = prob_ref[...]                       # [ROW_BLOCK, 1]
    # Mosaic casts no uint32 to float32; the top 24 bits fit int32 exactly,
    # so going through int32 gives the oracle's float bit for bit.
    top = (rand_ref[...] >> jnp.uint32(8)).astype(jnp.int32)
    u = top.astype(jnp.float32) * jnp.float32(2.0 ** -24)
    bad = (u < prob).astype(jnp.uint32)
    flip = planes_ref[0]
    for i in range(1, nplanes):
        flip = flip & planes_ref[i]
    out_ref[...] = data ^ (flip * bad)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "row_block", "word_block"))
def inject_pallas(data, row_prob, rand_word, rand_planes, *, interpret=False,
                  row_block: int = ROW_BLOCK, word_block: int = WORD_BLOCK):
    r, w = data.shape
    p = rand_planes.shape[0]
    if r % row_block or w % word_block:
        raise ValueError(f"shape {(r, w)} must tile by "
                         f"({row_block}, {word_block})")
    grid = (r // row_block, w // word_block)
    # Mosaic tiles a rank-1 block only at the array's full length or a
    # multiple of 128, so the per-row probabilities ride as a [rows, 1]
    # column: a (row_block, 1) block spans the array's whole last axis.
    return pl.pallas_call(
        functools.partial(_inject_kernel, p),
        grid=grid,
        in_specs=[
            pl.BlockSpec((row_block, word_block), lambda i, j: (i, j)),
            pl.BlockSpec((row_block, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((row_block, word_block), lambda i, j: (i, j)),
            pl.BlockSpec((p, row_block, word_block), lambda i, j: (0, i, j)),
        ],
        out_specs=pl.BlockSpec((row_block, word_block), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, w), jnp.uint32),
        interpret=interpret,
    )(data, row_prob.reshape(r, 1), rand_word, rand_planes)
