"""Public dispatch for the batched fixed-point sweep solve.

``solve`` takes the struct-of-arrays sample batch (see ``ref.solve_ref`` for
shapes/semantics) and dispatches to the pure-jnp oracle or the Pallas kernel.
As with the other kernel packages, the oracle is the default off-TPU: the
Pallas path exists for TPU deployment and is validated in interpret mode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import hw
from repro.kernels.sweep_solve import kernel as _kernel
from repro.kernels.sweep_solve import ref as _ref


def pack_features(mpki, ipc_base, mlp, row_hit, eff_banks, write_mult,
                  t_rcd, t_rp, t_ras, transfer_ns, peak_bw_gbps,
                  row_block=None):
    """Lay the SoA sample batch out lane-dense for the kernel:
    float32[3C + 8, Bp/128, 128], one plane a feature (mpki, ipc_base and
    mlp as C planes each, then the 8 per-sample scalars), samples along
    the lanes.  B pads up to whole blocks of ``row_block`` rows of 128
    (default: ``kernel.block_rows(B)``) with benign samples (ipc_base, mlp,
    banks, write multiplier = 1, the standard channel timings: the named
    ``hw`` constants)."""
    b, c = mpki.shape
    row_block = row_block or _kernel.block_rows(b)
    planes = [jnp.asarray(x, jnp.float32).T for x in (mpki, ipc_base, mlp)]
    planes += [jnp.asarray(x, jnp.float32)[None]
               for x in (row_hit, eff_banks, write_mult, t_rcd, t_rp, t_ras,
                         transfer_ns, peak_bw_gbps)]
    feat = jnp.concatenate(planes, axis=0)               # [3C + 8, B]
    block = row_block * _kernel.LANES
    pad = (-b) % block
    if pad:
        fill = np.array([0.0] * c + [1.0] * (2 * c)
                        + [0.0, 1.0, 1.0] + [hw.T_RCD_STD] * 3
                        + [hw.LINE_TRANSFER_NS, hw.PEAK_BW_GBPS], np.float32)
        benign = jnp.broadcast_to(jnp.asarray(fill)[:, None],
                                  (fill.size, pad))
        feat = jnp.concatenate([feat, benign], axis=1)
    return feat.reshape(feat.shape[0], -1, _kernel.LANES)


def _solve_ref_chunked(mpki, ipc_base, mlp, row_hit, eff_banks, write_mult,
                       t_rcd, t_rp, t_ras, transfer_ns, peak_bw_gbps,
                       *, t_cl, iters, unroll, chunk):
    """Oracle with a tunable batch chunk: the flat axis runs through
    ``lax.map`` over ``chunk``-sample slabs.  The pad samples are the same
    benign samples ``pack_features`` appends (ipc_base/mlp/banks/write = 1,
    standard channel timings), every sample solves independently, and the
    pads are sliced back off — so chunking changes XLA's working-set shape
    only.  Per-sample values can drift <=1e-6 from the unchunked oracle
    (shape-dependent vectorization of the float reductions)."""
    b = mpki.shape[0]
    chunk = max(1, int(chunk))
    pad = (-b) % chunk
    per_core = [jnp.asarray(x, jnp.float32) for x in (mpki, ipc_base, mlp)]
    scalars = [jnp.asarray(x, jnp.float32)
               for x in (row_hit, eff_banks, write_mult, t_rcd, t_rp, t_ras,
                         transfer_ns, peak_bw_gbps)]
    if pad:
        fills_pc = (0.0, 1.0, 1.0)                       # mpki, ipc_base, mlp
        fills_sc = (0.0, 1.0, 1.0, hw.T_RCD_STD, hw.T_RP_STD, hw.T_RAS_STD,
                    hw.LINE_TRANSFER_NS, hw.PEAK_BW_GBPS)
        per_core = [jnp.pad(x, ((0, pad), (0, 0)), constant_values=v)
                    for x, v in zip(per_core, fills_pc)]
        scalars = [jnp.pad(x, (0, pad), constant_values=v)
                   for x, v in zip(scalars, fills_sc)]
    k = (b + pad) // chunk
    xs = tuple(x.reshape(k, chunk, *x.shape[1:])
               for x in per_core + scalars)
    out = jax.lax.map(
        lambda s: _ref.solve_ref(*s, t_cl=t_cl, iters=iters, unroll=unroll),
        xs)
    out = {key: v.reshape(k * chunk, *v.shape[2:]) for key, v in out.items()}
    return {key: v[:b] for key, v in out.items()} if pad else out


def solve(mpki, ipc_base, mlp, row_hit, eff_banks, write_mult,
          t_rcd, t_rp, t_ras, transfer_ns, peak_bw_gbps,
          t_cl: float = hw.T_CL_STD, iters: int = _ref.DEFAULT_ITERS,
          impl: str = "auto", config=None):
    """Batched fixed-point CPI/latency solve.  Returns the dict documented
    in ``ref.solve_ref``.

    ``config`` is an optional ``autotune.KernelConfig``: ``unroll`` and a
    nonzero ``oracle_chunk`` retune the reference path; ``row_block`` is the
    Pallas path's largest block (rows of 128 samples a grid step, see
    ``kernel.block_rows``) and ``lane_block`` must be 128.  ``None`` is the
    default config.
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "reference"
    if impl == "reference":
        unroll = config.unroll if config is not None else 1
        if config is not None and config.oracle_chunk:
            return _solve_ref_chunked(
                mpki, ipc_base, mlp, row_hit, eff_banks, write_mult,
                t_rcd, t_rp, t_ras, transfer_ns, peak_bw_gbps,
                t_cl=t_cl, iters=iters, unroll=unroll,
                chunk=config.oracle_chunk)
        return _ref.solve_ref(mpki, ipc_base, mlp, row_hit, eff_banks,
                              write_mult, t_rcd, t_rp, t_ras, transfer_ns,
                              peak_bw_gbps, t_cl=t_cl, iters=iters,
                              unroll=unroll)
    if impl not in ("pallas", "pallas_interpret"):
        raise ValueError(f"unknown impl {impl!r}")
    b, c = mpki.shape
    if config is None:
        row_block, lanes = _kernel.block_rows(b), _kernel.LANES
    else:
        row_block = _kernel.block_rows(b, config.row_block)
        lanes = config.lane_block
    feat = pack_features(mpki, ipc_base, mlp, row_hit, eff_banks, write_mult,
                         t_rcd, t_rp, t_ras, transfer_ns, peak_bw_gbps,
                         row_block=row_block)
    out = _kernel.solve_pallas(feat, c, iters, t_cl,
                               interpret=(impl == "pallas_interpret"),
                               row_block=row_block, lanes=lanes)
    out = out.reshape(c + 2, -1)
    return _ref.finalize(out[:c, :b].T, out[c, :b], out[c + 1, :b],
                         jnp.asarray(mpki, jnp.float32),
                         jnp.asarray(ipc_base, jnp.float32),
                         jnp.asarray(row_hit, jnp.float32))
