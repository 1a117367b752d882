"""Pure-jnp oracle for the batched fixed-point latency/CPI solve.

This is the vectorized form of ``repro.memsim.core.simulate_cores`` +
``repro.memsim.dram_timing.access_latency`` / ``sustainable_bandwidth_gbps``:
one flat batch axis B of simulation samples, each a multiprogrammed C-core
workload at one DRAM operating point.  The damped fixed-point iteration that
couples the aggregate request rate to the loaded memory latency runs as a
``lax.scan`` over ``iters`` steps, identical in structure (and, up to f32
rounding, in value) to the scalar NumPy loop it replaces.

Inputs (all jnp arrays; ``[B, C]`` per-core, ``[B]`` per-sample):

- ``mpki``, ``ipc_base``, ``mlp``            float[B, C]
- ``row_hit``, ``eff_banks``, ``write_mult`` float[B]
- ``t_rcd``, ``t_rp``, ``t_ras``             float[B]  (ns)
- ``transfer_ns``, ``peak_bw_gbps``          float[B]  (channel-rate derived)

Returns a dict:

- ``ipc`` float[B, C]             converged per-core IPC
- ``stall_frac`` float[B, C]      fraction of cycles stalled on memory
- ``req_rate_per_ns`` float[B]    aggregate read-line rate
- ``avg_loaded_ns`` float[B]      loaded memory latency (last iteration)
- ``utilization`` float[B]        binding-resource utilization
- ``acts_per_ns`` float[B]        activation rate (for energy)
- ``reads_per_ns`` float[B]       line-transfer rate (for energy)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import hw
from repro.memsim.core import (CONFLICT_FRAC, CPU_FREQ_GHZ, ROB_HIDE_CYCLES,
                               STALL_AMPLIFY)

N_CHANNELS = 2          # ChannelConfig default; fixed across the sweep
DEFAULT_ITERS = 25


def solve_ref(mpki, ipc_base, mlp, row_hit, eff_banks, write_mult,
              t_rcd, t_rp, t_ras, transfer_ns, peak_bw_gbps,
              t_cl: float = hw.T_CL_STD, iters: int = DEFAULT_ITERS,
              unroll: int = 1):
    n_cores = mpki.shape[-1]
    miss = 1.0 - row_hit
    t_rc = t_ras + t_rp

    # unloaded service latency (per sample)
    hit = t_cl + transfer_ns
    closed = t_rcd + t_cl + transfer_ns
    conflict = t_rp + t_rcd + t_cl + transfer_ns
    svc = row_hit * hit + miss * ((1.0 - CONFLICT_FRAC) * closed
                                  + CONFLICT_FRAC * conflict)

    # bandwidth bound (iteration-invariant): min(bus, bank row-cycle limit)
    bank_limit = (eff_banks / jnp.maximum(miss * t_rc, 1e-12)
                  * hw.CACHE_LINE_BYTES * N_CHANNELS)
    bw = jnp.where(miss > 0.0, jnp.minimum(peak_bw_gbps, bank_limit),
                   peak_bw_gbps)
    bw_share = bw / n_cores
    cpi_bw = (mpki / 1000.0) * hw.CACHE_LINE_BYTES / bw_share[..., None] \
        * CPU_FREQ_GHZ

    bank_svc = miss * t_rc / eff_banks
    queued_svc = jnp.maximum(jnp.maximum(transfer_ns, bank_svc), 0.5 * svc)

    def step(carry, _):
        ipc, _, _ = carry
        inst_per_ns = ipc * CPU_FREQ_GHZ
        read_rate = jnp.sum(inst_per_ns * mpki / 1000.0, axis=-1)
        req_rate = jnp.maximum(read_rate * write_mult, 1e-9)
        rate_per_ch = req_rate / N_CHANNELS
        util_bus = jnp.clip(rate_per_ch * transfer_ns, 0.0, 0.999)
        util_bank = jnp.clip(rate_per_ch * miss * t_rc / eff_banks,
                             0.0, 0.999)
        util = jnp.maximum(util_bus, util_bank)
        wait = 0.5 * util / (1.0 - util) * queued_svc
        loaded = svc + wait
        lat_cycles = loaded * CPU_FREQ_GHZ
        stall_per_miss = (jnp.maximum(lat_cycles - ROB_HIDE_CYCLES, 0.0)
                          [..., None] * STALL_AMPLIFY / mlp)
        cpi_lat = 1.0 / ipc_base + (mpki / 1000.0) * stall_per_miss
        cpi = jnp.maximum(cpi_lat, cpi_bw)
        new_ipc = 0.5 * ipc + 0.5 / cpi                  # damped fixed point
        return (new_ipc, loaded, util), None

    # ``unroll`` is an autotuner knob (repro.kernels.autotune): it changes
    # only how XLA lowers the loop, never the step sequence.  While the
    # loop keeps two or more trips the result is bit-identical to
    # unroll=1; at one trip XLA inlines the loop, fuses across steps and
    # the float32 results can move by a few ulps.
    init = (ipc_base, jnp.zeros_like(svc), jnp.zeros_like(svc))
    (ipc, loaded, util), _ = jax.lax.scan(step, init, None, length=iters,
                                          unroll=max(1, int(unroll)))
    return finalize(ipc, loaded, util, mpki, ipc_base, row_hit)


def finalize(ipc, loaded, util, mpki, ipc_base, row_hit):
    """Derived quantities shared by the oracle and the Pallas kernel path."""
    stall = jnp.clip(1.0 - ipc / ipc_base, 0.0, 1.0)
    req_rate = jnp.sum(ipc * CPU_FREQ_GHZ * mpki / 1000.0, axis=-1)
    return {
        "ipc": ipc,
        "stall_frac": stall,
        "req_rate_per_ns": req_rate,
        "avg_loaded_ns": loaded,
        "utilization": util,
        "acts_per_ns": req_rate * (1.0 - row_hit),
        "reads_per_ns": req_rate,
    }
