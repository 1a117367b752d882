"""jit'd public wrapper for voltage-error injection.

On CPU (this container) the Pallas kernel runs in interpret mode, which is
slower than plain jnp — so the default implementation is the oracle, and the
kernel is selected with ``impl='pallas'`` (TPU) or ``impl='pallas_interpret'``
(validation).  All three paths are bit-identical.

The Pallas kernel tiles the plane into (ROW_BLOCK, WORD_BLOCK) VMEM blocks;
planes that do not tile evenly (reduced geometries like 2 KiB rows = 512
words) are padded up to the tile grid here and the output sliced back —
the kernel is elementwise, so the in-bounds region is unaffected and all
impls stay bit-identical (the same pad-and-slice convention as
``sweep_solve.ops.pack_features``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.voltage_inject import kernel as _kernel
from repro.kernels.voltage_inject import ref as _ref


def _inject_padded(data, row_prob, rand_word, rand_planes, *, interpret,
                   row_block: int = _kernel.ROW_BLOCK,
                   word_block: int = _kernel.WORD_BLOCK):
    """Pad every operand's plane up to the kernel tile grid, run the Pallas
    kernel, slice the result back to the original shape."""
    r, w = data.shape
    pad_r = (-r) % row_block
    pad_w = (-w) % word_block
    if pad_r or pad_w:
        plane_pad = ((0, pad_r), (0, pad_w))
        data = jnp.pad(data, plane_pad)
        rand_word = jnp.pad(rand_word, plane_pad)
        rand_planes = jnp.pad(rand_planes, ((0, 0), *plane_pad))
        row_prob = jnp.pad(row_prob, (0, pad_r))
    out = _kernel.inject_pallas(data, row_prob, rand_word, rand_planes,
                                interpret=interpret, row_block=row_block,
                                word_block=word_block)
    if pad_r or pad_w:
        out = out[:r, :w]
    return out


def inject(data, row_prob, rand_word, rand_planes, impl: str = "auto"):
    """Flip bits in ``data`` per the voltage-error model.  See ref.py."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "reference"
    if impl == "reference":
        return jax.jit(_ref.inject_ref)(data, row_prob, rand_word, rand_planes)
    if impl not in ("pallas", "pallas_interpret"):
        raise ValueError(f"unknown impl {impl!r}")
    return _inject_padded(data, row_prob, rand_word, rand_planes,
                          interpret=(impl == "pallas_interpret"))
