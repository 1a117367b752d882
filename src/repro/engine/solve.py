"""Batched (workload x operating-point) simulation: the engine core.

``simulate_batch`` evaluates every (workload w, point p) pair of a
``WorkloadBatch`` x ``PointGrid`` grid in one jit-compiled call: the grid is
flattened to a single batch axis, pushed through the vmapped fixed-point
CPI solve (``repro.kernels.sweep_solve``), and finished with vectorized
weighted-speedup / power / energy math (the jnp form of
``repro.memsim.energy``).  ``evaluate_batch`` layers the Fig. 13-19 /
Table 5 comparisons (loss, power/energy savings, perf-per-watt) on top.

The per-core "alone" IPCs that anchor weighted speedup are solved in the
same way: a [W*C] batch of single-core samples at the nominal point.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import power as power_lib
from repro.engine import dispatch as dispatch_lib
from repro.engine.batch import PointGrid, WorkloadBatch
from repro.kernels.sweep_solve import ops as sweep_ops
from repro.memsim.core import CPU_FREQ_GHZ
from repro.memsim.energy import CONST
from repro.memsim.system import INSTR_PER_CORE

CPU_FREQ_HZ = CPU_FREQ_GHZ * 1e9
N_CPU_CORES = CONST.n_cores      # = hw.CPU_CORES (one source of truth)


def _wb_feats(wb: WorkloadBatch) -> dict:
    return {"mpki": jnp.asarray(wb.mpki, jnp.float32),
            "ipc_base": jnp.asarray(wb.ipc_base, jnp.float32),
            "mlp": jnp.asarray(wb.mlp, jnp.float32),
            "row_hit": jnp.asarray(wb.row_hit, jnp.float32),
            "eff_banks": jnp.asarray(wb.eff_banks, jnp.float32),
            "write_mult": jnp.asarray(wb.write_mult, jnp.float32),
            "alone_row_hit": jnp.asarray(wb.row_hit_core, jnp.float32),
            "alone_eff_banks": jnp.asarray(wb.alone_eff_banks, jnp.float32),
            "alone_write_mult": jnp.asarray(wb.alone_write_mult, jnp.float32)}


def _pg_points(pg: PointGrid) -> dict:
    return {k: jnp.asarray(getattr(pg, k), jnp.float32)
            for k in ("v_array", "v_periph", "freq_ratio", "t_rcd", "t_rp",
                      "t_ras", "transfer_ns", "peak_bw_gbps")}


NOMINAL_POINT = _pg_points(PointGrid.nominal())


def alone_solve(feats: dict, mpki=None,
                impl: str = "reference") -> jnp.ndarray:
    """Single-core IPC of every (workload, core) at the nominal point
    -> [W, C].  ``mpki`` overrides the batch's (for phased workloads)."""
    mpki = feats["mpki"] if mpki is None else mpki
    w, c = mpki.shape
    flat = lambda x: x.reshape(w * c, 1)
    scal = lambda x: x.reshape(w * c)
    n = {k: jnp.broadcast_to(v, (w * c,)) for k, v in NOMINAL_POINT.items()}
    out = sweep_ops.solve(
        flat(mpki), flat(feats["ipc_base"]), flat(feats["mlp"]),
        scal(feats["alone_row_hit"]), scal(feats["alone_eff_banks"]),
        scal(feats["alone_write_mult"]),
        n["t_rcd"], n["t_rp"], n["t_ras"], n["transfer_ns"],
        n["peak_bw_gbps"], impl=impl)
    return out["ipc"].reshape(w, c)


def _power_energy(points: dict, acts, reads, total_ipc, runtime_s,
                  coeffs=None):
    """Vectorized ``energy.system_power`` + ``system_energy`` (broadcasts
    over any leading batch shape) — a thin sum over the per-component
    device-model breakdown (:func:`repro.power.component_power`).

    ``coeffs`` selects the device model: ``None`` (the default ``ddr3l``),
    a model's hashable ``coeffs()`` tuple (the jit-static form the grid
    path uses), or a per-lane ``[..., NCOEFF]`` array riding the batch
    axis (the heterogeneous-fleet form the controller scan uses).  The
    stacked ``dram_comp_w`` / ``dram_comp_j`` outputs carry the
    :data:`repro.power.COMPONENTS` axis last.
    """
    comp = power_lib.component_power(
        points, {"acts_per_ns": acts, "lines_per_ns": reads}, coeffs)
    dyn, static = power_lib.power_totals(comp)
    cpu_w = (CONST.n_cores * CONST.p_core_static_w
             + total_ipc * CPU_FREQ_HZ * CONST.e_per_inst_nj * 1e-9)
    cpu_static_j = CONST.n_cores * CONST.p_core_static_w * runtime_s
    cpu_dyn_j = (total_ipc * CPU_FREQ_HZ * runtime_s
                 * CONST.e_per_inst_nj * 1e-9)
    dram_j = (dyn + static) * runtime_s
    comp_w = jnp.stack([comp[k] for k in power_lib.COMPONENTS], axis=-1)
    rt = jnp.asarray(runtime_s)[..., None]
    return {"dram_dynamic_w": dyn, "dram_static_w": static,
            "dram_w": dyn + static, "cpu_w": cpu_w,
            "system_w": dyn + static + cpu_w,
            "cpu_j": cpu_static_j + cpu_dyn_j,
            "dram_dynamic_j": dyn * runtime_s,
            "dram_static_j": static * runtime_s, "dram_j": dram_j,
            "system_j": cpu_static_j + cpu_dyn_j + dram_j,
            "dram_comp_w": comp_w, "dram_comp_j": comp_w * rt}


def _grid_sim_fn(feats: dict, points: dict, impl: str = "reference",
                 coeffs: tuple | None = None) -> dict:
    """The full [W, P] grid simulation; returns a dict of jnp arrays.
    ``coeffs``: optional device-model coefficient tuple (hashable, rides as
    a jit-static argument — one model per grid; per-lane mixes go through
    the controller/fleet path)."""
    w, c = feats["mpki"].shape
    p = points["t_rcd"].shape[0]
    per_core = lambda x: jnp.broadcast_to(x[:, None, :], (w, p, c)) \
        .reshape(w * p, c)
    per_wl = lambda x: jnp.broadcast_to(x[:, None], (w, p)).reshape(w * p)
    per_pt = lambda x: jnp.broadcast_to(x[None, :], (w, p)).reshape(w * p)

    out = sweep_ops.solve(
        per_core(feats["mpki"]), per_core(feats["ipc_base"]),
        per_core(feats["mlp"]), per_wl(feats["row_hit"]),
        per_wl(feats["eff_banks"]), per_wl(feats["write_mult"]),
        per_pt(points["t_rcd"]), per_pt(points["t_rp"]),
        per_pt(points["t_ras"]), per_pt(points["transfer_ns"]),
        per_pt(points["peak_bw_gbps"]), impl=impl)

    ipc = out["ipc"].reshape(w, p, c)
    alone = alone_solve(feats, impl=impl)                 # [W, C]
    ws = jnp.sum(ipc / alone[:, None, :], axis=-1)
    runtime_s = jnp.max(INSTR_PER_CORE / (ipc * CPU_FREQ_HZ), axis=-1)
    total_ipc = jnp.sum(ipc, axis=-1)
    grid_points = {k: jnp.broadcast_to(v[None, :], (w, p))
                   for k, v in points.items()}
    pe = _power_energy(grid_points,
                       out["acts_per_ns"].reshape(w, p),
                       out["reads_per_ns"].reshape(w, p),
                       total_ipc, runtime_s, coeffs)
    return {"ipc": ipc, "alone_ipc": alone, "ws": ws,
            "stall_frac": out["stall_frac"].reshape(w, p, c),
            "runtime_s": runtime_s,
            "avg_latency_ns": out["avg_loaded_ns"].reshape(w, p),
            "bus_utilization": out["utilization"].reshape(w, p), **pe}


_grid_sim = jax.jit(_grid_sim_fn, static_argnames=("impl", "coeffs"))


def _grid_sim_dispatched(feats: dict, points: dict, impl: str,
                         coeffs: tuple | None = None) -> dict:
    """``_grid_sim`` through the shape-stable dispatch layer: the W and P
    axes are padded up to canonical buckets so any workload x point grid
    hits a warm AOT executable (the kernel reduces only over the core axis,
    so padded lanes are dead rows sliced off here — no mask needed)."""
    w, p = feats["mpki"].shape[0], points["t_rcd"].shape[0]
    ladder = dispatch_lib.bucket_ladder(1)
    bw = dispatch_lib.pick_bucket(w, ladder) or w
    bp = dispatch_lib.pick_bucket(p, ladder) or p
    pf = {k: jnp.asarray(dispatch_lib.pad_axis(a, bw))
          for k, a in feats.items()}
    pp = {k: jnp.asarray(dispatch_lib.pad_axis(a, bp))
          for k, a in points.items()}
    r = dispatch_lib.aot_call("grid_sim",
                              functools.partial(_grid_sim_fn, impl=impl,
                                                coeffs=coeffs),
                              (pf, pp), statics_key=(impl, coeffs),
                              resident=bw * bp)
    return {k: (a[:w] if k == "alone_ipc" else a[:w, :p])
            for k, a in r.items()}


@dataclasses.dataclass(frozen=True)
class BatchResult:
    """Grid simulation results; every array is [W, P] unless noted."""

    names: tuple
    ipc: np.ndarray              # [W, P, C]
    alone_ipc: np.ndarray        # [W, C] (nominal point)
    ws: np.ndarray
    stall_frac: np.ndarray       # [W, P, C]
    runtime_s: np.ndarray
    avg_latency_ns: np.ndarray
    bus_utilization: np.ndarray
    power: dict                  # *_w entries, each [W, P]
    energy: dict                 # *_j entries, each [W, P]
    # per-component DRAM breakdown (repro.power.COMPONENTS keys), each
    # [W, P]; components_w sums to power["dram_w"], components_j to
    # energy["dram_j"] (float rounding aside)
    components_w: dict | None = None
    components_j: dict | None = None
    device_model: str = "ddr3l"  # the model the whole grid was run under


@dataclasses.dataclass(frozen=True)
class ComparisonBatch:
    """Vectorized ``system.Comparison``; every array is [W, P]."""

    names: tuple
    perf_loss_pct: np.ndarray
    dram_power_savings_pct: np.ndarray
    dram_energy_savings_pct: np.ndarray
    system_energy_savings_pct: np.ndarray
    perf_per_watt_gain_pct: np.ndarray
    cpu_energy_increase_pct: np.ndarray


def simulate_batch(wb: WorkloadBatch, pg: PointGrid, impl: str = "auto",
                   dispatch: str = "auto",
                   device_model=None) -> BatchResult:
    """Simulate every (workload, operating point) pair in one batched call.

    ``dispatch="auto"`` pads W and P to canonical buckets and reuses a warm
    AOT executable per bucket (see :mod:`repro.engine.dispatch`);
    ``"direct"`` keeps the exact-shape jit call (one retrace per new grid
    shape — the bucketed path's parity reference).  ``device_model``
    (name or :class:`repro.power.DeviceModel`) selects the DRAM power
    model for the whole grid (default ``ddr3l``)."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "reference"
    model = power_lib.get(device_model if device_model is not None
                          else "ddr3l")
    coeffs = None if model is power_lib.DDR3L else model.coeffs()
    if dispatch == "direct":
        r = _grid_sim(_wb_feats(wb), _pg_points(pg), impl=impl,
                      coeffs=coeffs)
    elif dispatch in ("auto", "bucketed"):
        r = _grid_sim_dispatched(_wb_feats(wb), _pg_points(pg), impl, coeffs)
    else:
        raise ValueError(f"unknown dispatch {dispatch!r}")
    a = {k: np.asarray(v, np.float64) for k, v in r.items()}
    comp = lambda key: {name: a[key][..., i] for i, name
                        in enumerate(power_lib.COMPONENTS)}
    return BatchResult(
        wb.names, a["ipc"], a["alone_ipc"], a["ws"], a["stall_frac"],
        a["runtime_s"], a["avg_latency_ns"], a["bus_utilization"],
        power={k: a[k] for k in ("dram_dynamic_w", "dram_static_w", "dram_w",
                                 "cpu_w", "system_w")},
        energy={k: a[k] for k in ("cpu_j", "dram_dynamic_j", "dram_static_j",
                                  "dram_j", "system_j")},
        components_w=comp("dram_comp_w"), components_j=comp("dram_comp_j"),
        device_model=model.name)


def evaluate_batch(wb: WorkloadBatch, pg: PointGrid,
                   base_pg: PointGrid | None = None,
                   impl: str = "auto",
                   dispatch: str = "auto") -> ComparisonBatch:
    """Fig. 13-19 / Table 5 comparisons of every grid point against the
    (per-workload) baseline point — [W, P] arrays in one batched call."""
    base_pg = base_pg or PointGrid.nominal()
    if base_pg.n_points != 1:
        raise ValueError("base_pg must hold exactly one baseline point")
    pt = simulate_batch(wb, pg, impl=impl, dispatch=dispatch)
    base = simulate_batch(wb, base_pg, impl=impl, dispatch=dispatch)
    b_ws = base.ws[:, :1]
    ppw_base = b_ws / base.power["system_w"][:, :1]
    return ComparisonBatch(
        wb.names,
        100.0 * (1.0 - pt.ws / b_ws),
        100.0 * (1.0 - pt.power["dram_w"] / base.power["dram_w"][:, :1]),
        100.0 * (1.0 - pt.energy["dram_j"] / base.energy["dram_j"][:, :1]),
        100.0 * (1.0 - pt.energy["system_j"] / base.energy["system_j"][:, :1]),
        100.0 * ((pt.ws / pt.power["system_w"]) / ppw_base - 1.0),
        100.0 * (pt.energy["cpu_j"] / base.energy["cpu_j"][:, :1] - 1.0))
