"""Pallas TPU kernel for the batched fixed-point latency/CPI inner loop.

The design-space sweep (workloads x voltages x intervals) flattens into one
batch axis B of independent fixed-point solves; each sample is tiny (C=4
cores, ~20 scalar features) but the batch is large, so the kernel lays the
samples along the 128 lanes and the features on a leading axis: the packed
batch is float32[F, Bp/128, 128], and a grid step takes ``row_block`` rows
of 128 samples, so every feature is one dense ``(row_block, 128)`` slab and
the damped iteration is a ``fori_loop`` of elementwise vector ops entirely
in VMEM (the per-core request rate is a sum of C slabs, no cross-lane
reduction).

Feature planes (see ``ops.pack_features``): per-core planes first (mpki,
ipc_base, mlp: C planes each), then the per-sample planes (row_hit,
eff_banks, write_mult, t_rcd, t_rp, t_ras, transfer_ns, peak_bw_gbps).
Output planes: [0:C) = converged IPC, C = loaded latency (ns), C+1 =
binding-resource utilization.

On this container (CPU) the kernel is exercised in interpret mode; the
numerical contract with ``ref.solve_ref`` is asserted by the parity tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import hw
from repro.kernels.sweep_solve.ref import N_CHANNELS
from repro.memsim.core import (CONFLICT_FRAC, CPU_FREQ_GHZ, ROB_HIDE_CYCLES,
                               STALL_AMPLIFY)

ROW_BLOCK = 32       # rows of 128 samples per grid step (largest block)
LANES = 128          # samples per row (one VPU register row)
SUBLANES = 8         # f32 sublane tile: the smallest block
N_SCALARS = 8        # per-sample feature planes after the 3C per-core ones


def block_rows(b: int, row_block: int = ROW_BLOCK) -> int:
    """Rows of 128 samples a grid step takes for a batch of ``b``:
    ``row_block``, or for a batch under one such block its own rows
    rounded up to the sublane tile, so a small batch pads to no more than
    the next 8 x 128 samples."""
    rows = max(1, -(-int(b) // LANES))
    return min(int(row_block), -(-rows // SUBLANES) * SUBLANES)


def _solve_kernel(c: int, iters: int, t_cl: float, feat_ref, out_ref):
    mpki = [feat_ref[k] for k in range(c)]
    ipc_base = [feat_ref[c + k] for k in range(c)]
    mlp = [feat_ref[2 * c + k] for k in range(c)]
    (row_hit, eff_banks, write_mult, t_rcd, t_rp, t_ras, transfer,
     peak_bw) = (feat_ref[3 * c + k] for k in range(N_SCALARS))

    miss = 1.0 - row_hit
    t_rc = t_ras + t_rp
    hit = t_cl + transfer
    closed = t_rcd + t_cl + transfer
    conflict = t_rp + t_rcd + t_cl + transfer
    svc = row_hit * hit + miss * ((1.0 - CONFLICT_FRAC) * closed
                                  + CONFLICT_FRAC * conflict)
    bank_limit = (eff_banks / jnp.maximum(miss * t_rc, 1e-12)
                  * hw.CACHE_LINE_BYTES * N_CHANNELS)
    bw = jnp.where(miss > 0.0, jnp.minimum(peak_bw, bank_limit), peak_bw)
    cpi_bw = [(m / 1000.0) * hw.CACHE_LINE_BYTES / (bw / c) * CPU_FREQ_GHZ
              for m in mpki]
    bank_svc = miss * t_rc / eff_banks
    queued_svc = jnp.maximum(jnp.maximum(transfer, bank_svc), 0.5 * svc)

    def body(_, carry):
        ipc, _, _ = carry
        read_rate = ipc[0] * CPU_FREQ_GHZ * mpki[0] / 1000.0
        for k in range(1, c):
            read_rate = read_rate + ipc[k] * CPU_FREQ_GHZ * mpki[k] / 1000.0
        req_rate = jnp.maximum(read_rate * write_mult, 1e-9)
        rate_per_ch = req_rate / N_CHANNELS
        util_bus = jnp.clip(rate_per_ch * transfer, 0.0, 0.999)
        util_bank = jnp.clip(rate_per_ch * miss * t_rc / eff_banks,
                             0.0, 0.999)
        util = jnp.maximum(util_bus, util_bank)
        wait = 0.5 * util / (1.0 - util) * queued_svc
        loaded = svc + wait
        stall = (jnp.maximum(loaded * CPU_FREQ_GHZ - ROB_HIDE_CYCLES, 0.0)
                 * STALL_AMPLIFY)
        new_ipc = []
        for k in range(c):
            stall_per_miss = stall / mlp[k]
            cpi_lat = 1.0 / ipc_base[k] + (mpki[k] / 1000.0) * stall_per_miss
            cpi = jnp.maximum(cpi_lat, cpi_bw[k])
            new_ipc.append(0.5 * ipc[k] + 0.5 / cpi)
        return (tuple(new_ipc), loaded, util)

    zero = jnp.zeros_like(row_hit)
    ipc, loaded, util = jax.lax.fori_loop(0, iters, body,
                                          (tuple(ipc_base), zero, zero))
    for k in range(c):
        out_ref[k] = ipc[k]
    out_ref[c] = loaded
    out_ref[c + 1] = util


@functools.partial(jax.jit,
                   static_argnames=("n_cores", "iters", "t_cl", "interpret",
                                    "row_block"))
def solve_pallas(feat, n_cores: int, iters: int = 25,
                 t_cl: float = hw.T_CL_STD, *, interpret: bool = False,
                 row_block: int = ROW_BLOCK):
    """Run the lane-dense fixed-point solve.  ``feat``: float32[3C + 8, R,
    128] with R a multiple of ``row_block``, itself a multiple of the
    8-row sublane tile (``ops.solve`` picks it with ``block_rows``).
    Returns float32[C + 2, R, 128] (see the planes above)."""
    f, rows, lanes = feat.shape
    if (f != 3 * n_cores + N_SCALARS or lanes != LANES
            or row_block % SUBLANES or rows % row_block):
        raise ValueError(f"feat shape {feat.shape} must be "
                         f"[{3 * n_cores + N_SCALARS}, k*{row_block}, "
                         f"{LANES}] with {row_block} a multiple of "
                         f"{SUBLANES}")
    return pl.pallas_call(
        functools.partial(_solve_kernel, n_cores, iters, t_cl),
        grid=(rows // row_block,),
        in_specs=[pl.BlockSpec((f, row_block, LANES),
                               lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((n_cores + 2, row_block, LANES),
                               lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_cores + 2, rows, LANES),
                                       jnp.float32),
        interpret=interpret,
    )(feat)
