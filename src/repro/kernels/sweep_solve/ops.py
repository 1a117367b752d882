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


def _solve_padded(mpki, ipc_base, mlp, row_hit, eff_banks, write_mult,
                  t_rcd, t_rp, t_ras, transfer_ns, peak_bw_gbps, *,
                  interpret, row_block: int = _kernel.ROW_BLOCK,
                  t_cl: float = hw.T_CL_STD,
                  iters: int = _ref.DEFAULT_ITERS):
    """Lay the batch out lane-dense, run the Pallas kernel with blocks of
    at most ``row_block`` rows of 128 samples (``kernel.block_rows``
    adapts it to a smaller batch) and unpack the planes to
    ``ref.solve_ref``'s dict."""
    b, c = mpki.shape
    row_block = _kernel.block_rows(b, row_block)
    feat = pack_features(mpki, ipc_base, mlp, row_hit, eff_banks, write_mult,
                         t_rcd, t_rp, t_ras, transfer_ns, peak_bw_gbps,
                         row_block=row_block)
    out = _kernel.solve_pallas(feat, c, iters, t_cl, interpret=interpret,
                               row_block=row_block)
    out = out.reshape(c + 2, -1)
    return _ref.finalize(out[:c, :b].T, out[c, :b], out[c + 1, :b],
                         jnp.asarray(mpki, jnp.float32),
                         jnp.asarray(ipc_base, jnp.float32),
                         jnp.asarray(row_hit, jnp.float32))


def solve(mpki, ipc_base, mlp, row_hit, eff_banks, write_mult,
          t_rcd, t_rp, t_ras, transfer_ns, peak_bw_gbps,
          t_cl: float = hw.T_CL_STD, iters: int = _ref.DEFAULT_ITERS,
          impl: str = "auto"):
    """Batched fixed-point CPI/latency solve.  Returns the dict documented
    in ``ref.solve_ref``."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "reference"
    if impl == "reference":
        return _ref.solve_ref(mpki, ipc_base, mlp, row_hit, eff_banks,
                              write_mult, t_rcd, t_rp, t_ras, transfer_ns,
                              peak_bw_gbps, t_cl=t_cl, iters=iters)
    if impl not in ("pallas", "pallas_interpret"):
        raise ValueError(f"unknown impl {impl!r}")
    return _solve_padded(mpki, ipc_base, mlp, row_hit, eff_banks, write_mult,
                         t_rcd, t_rp, t_ras, transfer_ns, peak_bw_gbps,
                         interpret=(impl == "pallas_interpret"),
                         t_cl=t_cl, iters=iters)
