"""Plain reference of the Voltron fleet controller (Section 5, Algorithm 1)
over per-DIMM safe-voltage tables.

Three steps, each the paper's semantics written out once, with nothing
taken from the program:

1. ``tables``: for every DIMM and Algorithm-1 candidate voltage, the
   smallest error-free (tRCD, tRP) on the 2.5 ns platform grid up to
   20 ns (Section 4.2) at the circuit model's tRAS, then the RowHammer
   floor (worst-cell threshold over the refresh-window exposure >= 1).
2. ``fit``: the piecewise-linear loss predictor of Eq. 1, fitted by least
   squares on 27 workloads x 8 voltages with the 151/65 split.
3. ``controller``: per lane (workload, DIMM), 25 profiling intervals:
   simulate the baseline and the current candidate, profile MPKI and
   stall, pick the smallest valid candidate whose predicted loss is
   within the target, else the 1.35 V fallback.

The simulation is the memory-system model's damped fixed point (25
iterations) in float32, the precision the configuration states; it runs
in jnp on whichever device is current (the caller places it on the host).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from . import chips, circuit, hw, workloads

CANDIDATES = [round(0.90 + 0.05 * i, 2) for i in range(9)]   # 0.90..1.30
TARGET_PCT = 5.0
INTERVAL_CYCLES = 4_000_000

# memory-system model constants
CPU_FREQ_GHZ = 2.0
ROB_HIDE_CYCLES = 0.0
STALL_AMPLIFY = 5.0
MLP_SCALE = 0.62
CONFLICT_FRAC = 0.90
N_CHANNELS = 2
N_BANKS = 8.0
ITERS = 25
INSTR_PER_CORE = 500e6
CPU_FREQ_HZ = CPU_FREQ_GHZ * 1e9
# CPU energy (4 Cortex-A9-class cores) and the DDR3L component model
N_CORES = 4
P_CORE_STATIC_W = 0.55
E_PER_INST_NJ = 0.32
DDR3L = dict(v_nom_array=1.35, v_nom_periph=1.35, e_act_pre_nj=30.0,
             e_rw_array_nj=5.0, e_rw_periph_nj=10.0, p_bg_array_w=0.33,
             p_bg_periph_w=0.60, refresh_frac=0.18, bg_freq_floor=0.35,
             bg_freq_slope=0.65)
# the baseline: 1.35 V, 1600 MT/s, standard DDR3L timings (Table 2)
NOMINAL = dict(v_array=1.35, v_periph=1.35, freq_ratio=1.0, t_rcd=13.75,
               t_rp=13.75, t_ras=35.0, transfer_ns=5.0, peak_bw_gbps=25.6)
# RowHammer floor
HAMMER_HC0 = 200_000.0
HAMMER_V_SENS = 0.5
HAMMER_FIELD_SENS = 0.3
HAMMER_WINDOW_MS = 0.25
MEM_INTENSIVE_MPKI = 15.0


def fleet_workloads() -> list:
    """The paper's 27 homogeneous and 50 heterogeneous 4-core mixes."""
    return (workloads.homogeneous_workloads()
            + workloads.heterogeneous_workloads())


def candidate_voltages() -> np.ndarray:
    return np.array(CANDIDATES + [hw.VDD_NOMINAL])


# --------------------------------------------------------------------------
# 1. safe-voltage tables
# --------------------------------------------------------------------------
def min_latency(dimm, v: float, step: float = 2.5, max_latency: float = 20.0,
                temp_c: float = 20.0):
    """Smallest (tRCD, tRP) on the platform grid at which no cache line
    fails (Fig. 4's line-error fraction is exactly 0), ordered by (sum,
    tRCD, tRP); None below the vendor's recovery floor or when nothing up
    to ``max_latency`` recovers."""
    if v < circuit.VENDORS[dimm.vendor].recovery_floor:
        return None
    va = np.atleast_1d(np.asarray(v, dtype=np.float64))
    req = {op: dimm.required_latency(op, va, temp_c) for op in ("rcd", "rp")}
    field = dimm.susceptibility.reshape(-1)
    floor = circuit.VENDORS[dimm.vendor].fail_floor
    grid = np.arange(10.0, max_latency + 1e-9, step)
    best = None
    for t_rcd in grid:
        for t_rp in grid:
            p_ok = np.ones((1, field.size))
            for op, t_prog in (("rcd", t_rcd), ("rp", t_rp)):
                with np.errstate(divide="ignore"):
                    x_thr = (t_prog / req[op][:, None] - 1.0) / dimm.cell_sigma
                p_ok *= chips._trunc_phi(x_thr - field[None, :])
            frac = 1.0 - p_ok.mean(axis=1)
            frac = np.where(va < floor, np.maximum(frac, 0.5), frac)
            if float(frac[0]) <= 0.0:
                key = (t_rcd + t_rp, t_rcd, t_rp)
                if best is None or key < best:
                    best = key
    return None if best is None else (float(best[1]), float(best[2]))


def tables(modules) -> dict:
    """Per-DIMM candidate tables: ``timings`` [D, K, 3] (NaN where
    excluded), ``valid`` [D, K], ``lat_feat`` [D, K-1] (tRP + tRAS)."""
    pop = {d.module: d for d in chips.population()}
    cand = candidate_voltages()
    t_ras = circuit.timings_for_voltages(cand)[:, 2]
    d_, k_ = len(modules), cand.size
    timings = np.full((d_, k_, 3), np.nan)
    for i, m in enumerate(modules):
        dimm = pop[m]
        field_max = float(np.max(dimm.susceptibility))
        for k, v in enumerate(cand):
            lat = min_latency(dimm, float(v))
            if lat is None:
                continue
            threshold = HAMMER_HC0 * np.power(10.0,
                HAMMER_V_SENS * (v - hw.VDD_NOMINAL) / chips.DEFICIT_RANGE_V
                - HAMMER_FIELD_SENS * field_max)
            exposure = HAMMER_WINDOW_MS * 1e6 / (t_ras[k] + lat[1])
            if threshold / exposure >= 1.0:
                timings[i, k] = (lat[0], lat[1], t_ras[k])
    valid = np.isfinite(timings).all(axis=-1)
    if not valid[:, -1].all():
        raise ValueError("the 1.35 V fallback must be safe on every DIMM")
    return {"modules": tuple(modules), "cand_v": cand, "timings": timings,
            "valid": valid,
            "lat_feat": timings[:, :-1, 1] + timings[:, :-1, 2]}


# --------------------------------------------------------------------------
# the memory-system model
# --------------------------------------------------------------------------
def features(cores_list) -> dict:
    """float32 per-lane features of a list of 4-core mixes."""
    f = lambda attr: np.array([[getattr(b, attr) for b in cs]
                               for cs in cores_list], np.float64)
    mpki, ipc, rh, bp, wf = (f(a) for a in ("mpki", "ipc_base",
                                            "row_hit_rate",
                                            "bank_parallelism", "write_frac"))
    out = {"mpki": mpki, "ipc_base": ipc,
           "mlp": 1.0 + np.maximum(0.0, bp - 1.0) * MLP_SCALE,
           "row_hit": rh.mean(axis=-1),
           "eff_banks": np.minimum(bp.mean(axis=-1), N_BANKS),
           "write_mult": 1.0 + wf.mean(axis=-1),
           "alone_row_hit": rh, "alone_eff_banks": np.minimum(bp, N_BANKS),
           "alone_write_mult": 1.0 + wf}
    return {k: jnp.asarray(v, jnp.float32) for k, v in out.items()}


def solve(mpki, ipc_base, mlp, row_hit, eff_banks, write_mult, t_rcd, t_rp,
          t_ras, transfer_ns, peak_bw_gbps):
    """Loaded latency and CPI of B samples of C cores, damped fixed point."""
    t_cl, line = hw.T_CL_STD, hw.CACHE_LINE_BYTES
    n_cores = mpki.shape[-1]
    miss = 1.0 - row_hit
    t_rc = t_ras + t_rp
    hit = t_cl + transfer_ns
    closed = t_rcd + t_cl + transfer_ns
    conflict = t_rp + t_rcd + t_cl + transfer_ns
    svc = row_hit * hit + miss * ((1.0 - CONFLICT_FRAC) * closed
                                  + CONFLICT_FRAC * conflict)
    bank_limit = (eff_banks / jnp.maximum(miss * t_rc, 1e-12) * line
                  * N_CHANNELS)
    bw = jnp.where(miss > 0.0, jnp.minimum(peak_bw_gbps, bank_limit),
                   peak_bw_gbps)
    cpi_bw = ((mpki / 1000.0) * line / (bw / n_cores)[..., None]
              * CPU_FREQ_GHZ)
    bank_svc = miss * t_rc / eff_banks
    queued_svc = jnp.maximum(jnp.maximum(transfer_ns, bank_svc), 0.5 * svc)

    def step(carry, _):
        ipc, _, _ = carry
        read_rate = jnp.sum(ipc * CPU_FREQ_GHZ * mpki / 1000.0, axis=-1)
        req_rate = jnp.maximum(read_rate * write_mult, 1e-9)
        rate_per_ch = req_rate / N_CHANNELS
        util_bus = jnp.clip(rate_per_ch * transfer_ns, 0.0, 0.999)
        util_bank = jnp.clip(rate_per_ch * miss * t_rc / eff_banks,
                             0.0, 0.999)
        util = jnp.maximum(util_bus, util_bank)
        loaded = svc + 0.5 * util / (1.0 - util) * queued_svc
        stall_per_miss = (jnp.maximum(loaded * CPU_FREQ_GHZ
                                      - ROB_HIDE_CYCLES, 0.0)[..., None]
                          * STALL_AMPLIFY / mlp)
        cpi = jnp.maximum(1.0 / ipc_base + (mpki / 1000.0) * stall_per_miss,
                          cpi_bw)
        return (0.5 * ipc + 0.5 / cpi, loaded, util), None

    init = (ipc_base, jnp.zeros_like(svc), jnp.zeros_like(svc))
    (ipc, _, _), _ = jax.lax.scan(step, init, None, length=ITERS)
    req_rate = jnp.sum(ipc * CPU_FREQ_GHZ * mpki / 1000.0, axis=-1)
    return {"ipc": ipc, "stall_frac": jnp.clip(1.0 - ipc / ipc_base, 0., 1.),
            "acts_per_ns": req_rate * (1.0 - row_hit),
            "lines_per_ns": req_rate}


def _nominal(shape, dtype=jnp.float32) -> dict:
    return {k: jnp.full(shape, v, dtype) for k, v in NOMINAL.items()}


def alone_ipc(feats, mpki) -> jnp.ndarray:
    """Each core of each lane by itself at the nominal point -> [W, C]."""
    w, c = mpki.shape
    n = _nominal((w * c,), mpki.dtype)
    col = lambda x: x.reshape(w * c, 1)
    out = solve(col(mpki), col(feats["ipc_base"]), col(feats["mlp"]),
                feats["alone_row_hit"].reshape(-1),
                feats["alone_eff_banks"].reshape(-1),
                feats["alone_write_mult"].reshape(-1), n["t_rcd"],
                n["t_rp"], n["t_ras"], n["transfer_ns"], n["peak_bw_gbps"])
    return out["ipc"].reshape(w, c)


def power_energy(points, out, runtime_s) -> dict:
    """DRAM (six DRAMPower-style components) and CPU power and energy."""
    c = DDR3L
    sa = (points["v_array"] / c["v_nom_array"]) ** 2
    sp = (points["v_periph"] / c["v_nom_periph"]) ** 2
    acts, lines = out["acts_per_ns"], out["lines_per_ns"]
    dram_w = (c["p_bg_array_w"] * sa
              + acts * c["e_act_pre_nj"] * sa
              + lines * c["e_rw_array_nj"] * sa
              + c["p_bg_periph_w"] * sp * (c["bg_freq_floor"]
                                           + c["bg_freq_slope"]
                                           * points["freq_ratio"])
              + lines * c["e_rw_periph_nj"] * sp)
    total_ipc = jnp.sum(out["ipc"], axis=-1)
    cpu_w = (N_CORES * P_CORE_STATIC_W
             + total_ipc * CPU_FREQ_HZ * E_PER_INST_NJ * 1e-9)
    cpu_j = (N_CORES * P_CORE_STATIC_W * runtime_s
             + total_ipc * CPU_FREQ_HZ * runtime_s * E_PER_INST_NJ * 1e-9)
    return {"dram_w": dram_w, "system_w": dram_w + cpu_w,
            "dram_j": dram_w * runtime_s,
            "system_j": cpu_j + dram_w * runtime_s}


def _sim(feats, mpki, alone, points):
    out = solve(mpki, feats["ipc_base"], feats["mlp"], feats["row_hit"],
                feats["eff_banks"], feats["write_mult"], points["t_rcd"],
                points["t_rp"], points["t_ras"], points["transfer_ns"],
                points["peak_bw_gbps"])
    ws = jnp.sum(out["ipc"] / alone, axis=-1)
    runtime_s = jnp.max(INSTR_PER_CORE / (out["ipc"] * CPU_FREQ_HZ), axis=-1)
    return out, ws, power_energy(points, out, runtime_s)


# --------------------------------------------------------------------------
# 2. the loss predictor
# --------------------------------------------------------------------------
TRAIN_VOLTAGES = [1.30, 1.25, 1.20, 1.15, 1.10, 1.05, 1.00, 0.95]


@jax.jit
def _train_losses(feats, t3):
    """Weighted speedup at nominal, per-workload stall, and ws at each
    training voltage's Table-3 timings [V, W]."""
    w = feats["mpki"].shape[0]
    alone = alone_ipc(feats, feats["mpki"])
    base_out, base_ws, _ = _sim(feats, feats["mpki"], alone, _nominal((w,)))

    def at(t):
        pts = dict(_nominal((w,)), t_rcd=jnp.full((w,), t[0]),
                   t_rp=jnp.full((w,), t[1]), t_ras=jnp.full((w,), t[2]))
        return _sim(feats, feats["mpki"], alone, pts)[1]

    return base_ws, base_out["stall_frac"], jax.lax.map(at, t3)


def fit(seed: int = 0, train_frac: float = 0.70) -> tuple:
    """Eq. 1 coefficients ``(coef_low, coef_high)``, float64 [4] each."""
    wls = workloads.homogeneous_workloads()
    feats = features([cs for _, cs in wls])
    t3 = circuit.timings_for_voltages(TRAIN_VOLTAGES)
    base_ws, stall, ws = _train_losses(feats, jnp.asarray(t3, jnp.float32))
    stall = np.asarray(stall, np.float64).mean(axis=-1)
    loss = 100.0 * (1.0 - np.asarray(ws, np.float64).T
                    / np.asarray(base_ws, np.float64)[:, None])   # [W, V]
    lat = t3[:, 1] + t3[:, 2]
    mpki = np.array([cs[0].mpki for _, cs in wls], np.float64)
    data = np.array([(lat[j], mpki[i], stall[i], loss[i, j])
                     for i in range(len(wls))
                     for j in range(len(TRAIN_VOLTAGES))])
    idx = np.random.default_rng(seed).permutation(len(data))
    tr = data[idx[:int(round(train_frac * len(data)))]]

    def ols(mask):
        x = np.concatenate([np.ones((mask.sum(), 1)), tr[mask][:, :3]], 1)
        return np.linalg.lstsq(x, tr[mask][:, 3], rcond=None)[0]

    return (ols(tr[:, 1] < MEM_INTENSIVE_MPKI),
            ols(tr[:, 1] >= MEM_INTENSIVE_MPKI))


# --------------------------------------------------------------------------
# 3. the controller
# --------------------------------------------------------------------------
def phase_column(seed: int, n_intervals: int, amplitude: float = 0.15):
    """Piecewise-constant memory-intensity factors of one lane: a phase
    spans five 4M-cycle intervals."""
    rng = np.random.default_rng(seed)
    n_phases = max(1, int(np.ceil(n_intervals / 5)))
    f = 1.0 + amplitude * rng.uniform(-1.0, 1.0, n_phases)
    return np.repeat(f, 5)[:n_intervals]


@jax.jit
def _scan(feats, phases, coef_lo, coef_hi, cand_v, lat_feat, t_rcd, t_rp,
          t_ras, valid):
    w = feats["mpki"].shape[0]
    k_ = cand_v.shape[0]
    nom = _nominal((w,), feats["mpki"].dtype)
    take = lambda a, i: jnp.take_along_axis(a, i[:, None], axis=1)[:, 0]

    def step(carry, f):
        idx, sums = carry
        mpki = feats["mpki"] * f[:, None]
        alone = alone_ipc(feats, mpki)
        _, base_ws, base_pe = _sim(feats, mpki, alone, nom)
        pts = dict(nom, v_array=cand_v[idx], t_rcd=take(t_rcd, idx),
                   t_rp=take(t_rp, idx), t_ras=take(t_ras, idx))
        pt, pt_ws, pt_pe = _sim(feats, mpki, alone, pts)
        sums = {"base_ws": sums["base_ws"] + base_ws,
                "pt_ws": sums["pt_ws"] + pt_ws,
                **{f"{s}_{q}": sums[f"{s}_{q}"] + pe[q]
                   for s, pe in (("base", base_pe), ("pt", pt_pe))
                   for q in ("dram_w", "dram_j", "system_w", "system_j")}}
        m = jnp.mean(mpki, axis=-1)[:, None]
        st = jnp.mean(pt["stall_frac"], axis=-1)[:, None]
        x = jnp.stack(jnp.broadcast_arrays(jnp.ones_like(lat_feat), lat_feat,
                                           m, st), axis=-1)
        pred = jnp.where(m < MEM_INTENSIVE_MPKI, x @ coef_lo, x @ coef_hi)
        ok = (pred <= TARGET_PCT) & valid[:, :-1]
        new = jnp.where(ok.any(axis=-1), jnp.argmax(ok, axis=-1), k_ - 1)
        return (new.astype(jnp.int32), sums), new.astype(jnp.int32)

    zero = jnp.zeros((w,), feats["mpki"].dtype)
    keys = ["base_ws", "pt_ws"] + [f"{s}_{q}" for s in ("base", "pt")
                                   for q in ("dram_w", "dram_j", "system_w",
                                             "system_j")]
    init = (jnp.full((w,), k_ - 1, jnp.int32), {k: zero for k in keys})
    (_, s), chosen = jax.lax.scan(step, init, phases)
    return {
        "selected_idx": chosen.T,
        "perf_loss_pct": 100.0 * (1.0 - s["pt_ws"] / s["base_ws"]),
        "dram_power_savings_pct":
            100.0 * (1.0 - s["pt_dram_w"] / s["base_dram_w"]),
        "dram_energy_savings_pct":
            100.0 * (1.0 - s["pt_dram_j"] / s["base_dram_j"]),
        "system_energy_savings_pct":
            100.0 * (1.0 - s["pt_system_j"] / s["base_system_j"]),
        "perf_per_watt_gain_pct":
            100.0 * ((s["pt_ws"] / s["pt_system_w"])
                     / (s["base_ws"] / s["base_system_w"]) - 1.0),
    }


def controller(cores_list, table_rows: dict, phases: np.ndarray, coef_lo,
               coef_hi, dtype=jnp.float32) -> dict:
    """Run lanes: ``cores_list`` [W] 4-core mixes, ``table_rows`` the
    per-lane candidate rows (``timings`` [W, K, 3], ``valid`` [W, K],
    ``lat_feat`` [W, K-1], ``cand_v`` [K]), ``phases`` [T, W].  ``dtype``
    is the precision of every float operand (float32 as stated; the
    precision control passes a lower one)."""
    feats = {k: v.astype(dtype) for k, v in features(cores_list).items()}
    c = lambda a: jnp.asarray(np.asarray(a, np.float32), dtype)
    out = _scan(feats, c(phases), c(coef_lo), c(coef_hi),
                c(table_rows["cand_v"]), c(table_rows["lat_feat"]),
                c(table_rows["timings"][..., 0]),
                c(table_rows["timings"][..., 1]),
                c(table_rows["timings"][..., 2]),
                jnp.asarray(table_rows["valid"]))
    return {k: np.asarray(v.astype(jnp.float32) if v.dtype != jnp.int32
                          else v) for k, v in out.items()}
