"""The paper's Table 3 and Figures 4-19, computed through the engine.

  python -m repro.figures            # every figure
  python -m repro.figures fig14      # names containing "fig14"

Each function returns CSV rows ``(name, value, derived)``; ``main`` prints
them under a ``name,value,derived`` header and exits nonzero if any
function raised.
"""
from __future__ import annotations

import sys

import numpy as np


def fig04_error_rate():
    """Fraction of erroneous cache lines vs supply voltage, per DIMM —
    the whole population through one batched characterization call."""
    from repro import engine
    rows = []
    grid = engine.DimmGrid.from_population()
    res = engine.characterize_batch(grid, engine.population.SWEEP_VOLTAGES)
    v = res.v_grid
    for di, mod in enumerate(grid.modules):
        f = res.line_error_fraction[di, :, 0]
        first = v[f > 0].max() if (f > 0).any() else np.nan
        rows.append((f"fig4/{mod}", f"vmin={grid.vmin[di]}",
                     f"errors_from={first}"))
    return rows


def fig05_bitline():
    from repro.dram import circuit
    ts, vbl = circuit.bitline_waveform(np.array([1.35, 1.2, 1.1, 1.0, 0.9]))
    t_rcd, t_ras, t_rp = circuit.waveform_crossing_times(
        np.array([1.35, 1.2, 1.1, 1.0, 0.9]))
    return [(f"fig5/V={v}", f"t75={float(a):.2f}ns", f"tpre={float(c):.2f}ns")
            for v, a, c in zip([1.35, 1.2, 1.1, 1.0, 0.9],
                               np.asarray(t_rcd), np.asarray(t_rp))]


def fig06_latency_distribution():
    """tRCD_min / tRP_min distributions per vendor vs voltage: one batched
    call per vendor over a synthetic process-variation (z-score) grid."""
    from repro import engine
    rows = []
    zs = np.linspace(-2, 2, 21)
    voltages = [1.35, 1.25, 1.15, 1.10]
    for vendor in "ABC":
        grid = engine.DimmGrid.from_vendor_z(vendor, zs)
        res = engine.characterize_batch(grid, voltages)
        for vi, v in enumerate(voltages):
            for op, tmin in (("rcd", res.t_rcd_min), ("rp", res.t_rp_min)):
                vals = tmin[:, vi, 0]
                frac10 = float(np.mean(vals <= 10.0))
                rows.append((f"fig6/{vendor}/{op}/V={v}",
                             f"min={vals.min()}ns max={vals.max()}ns",
                             f"frac_ok_at_10ns={frac10:.2f}"))
    return rows


def fig07_spice_fit():
    """SPICE (base circuit) curve vs vendor-B measured range."""
    from repro.dram import circuit, timing
    rows = []
    for v in [1.35, 1.25, 1.15, 1.10, 1.05]:
        for op in ("rcd", "rp"):
            spice = float(np.asarray(circuit.raw_latency(op, v)))
            lo = circuit.measured_min_latency(op, v, "B", 20, -2.0)
            hi = circuit.measured_min_latency(op, v, "B", 20, 2.0)
            inside = (lo - 2.5) <= spice <= hi
            rows.append((f"fig7/{op}/V={v}", f"spice={spice:.2f}ns",
                         f"measured=({lo},{hi}) fit={'ok' if inside else 'off'}"))
    return rows


def fig08_spatial_locality():
    """Spatial error maps one step below V_min, from the batched sweep
    (each DIMM reads its own voltage off the shared V grid)."""
    from repro import engine
    rows = []
    grid = engine.DimmGrid.from_population(("B5", "C2"))
    res = engine.characterize_batch(grid, np.round(grid.vmin - 0.025, 4))
    for di, mod in enumerate(grid.modules):
        prob = res.row_error_prob[di, di, 0]
        hot_banks = int((prob.max(axis=1) > 1e-9).sum())
        hot_rows = int((prob.max(axis=0) > 1e-9).sum())
        rows.append((f"fig8/{mod}", f"banks_with_errors={hot_banks}/8",
                     f"rowgroups_with_errors={hot_rows}/256"))
    return rows


def fig09_beat_density():
    from repro.dram import chips
    rows = []
    d = [x for x in chips.population() if x.module == "C2"][0]
    for dv in (0.025, 0.05, 0.1):
        dist = d.beat_error_distribution(d.vmin - dv)
        one = float(np.atleast_1d(dist['one'])[0])
        two = float(np.atleast_1d(dist['two'])[0])
        many = float(np.atleast_1d(dist['many'])[0])
        rows.append((f"fig9/V=vmin-{dv}", f"1bit={one:.2e} 2bit={two:.2e}",
                     f"gt2bit={many:.2e} secded_helps={one > many}"))
    return rows


def fig10_temperature():
    from repro.dram import circuit
    rows = []
    for vendor in "ABC":
        for v in [1.35, 1.25, 1.15]:
            d20 = (circuit.measured_min_latency("rcd", v, vendor, 20),
                   circuit.measured_min_latency("rp", v, vendor, 20))
            d70 = (circuit.measured_min_latency("rcd", v, vendor, 70),
                   circuit.measured_min_latency("rp", v, vendor, 70))
            rows.append((f"fig10/{vendor}/V={v}",
                         f"20C=({d20[0]},{d20[1]})", f"70C=({d70[0]},{d70[1]})"))
    return rows


def fig11_retention():
    """Weak-cell counts over the (voltage, temperature, retention) grid in
    one batched call."""
    from repro import engine
    rows = []
    voltages, temps = (1.35, 1.15), (20.0, 70.0)
    ret = (64.0, 256.0, 512.0, 1024.0, 2048.0)
    grid = engine.DimmGrid.from_population(("A1",))
    res = engine.characterize_batch(grid, voltages, temps, retention_ms=ret)
    for ri, t in enumerate(ret):
        for ti, vi in ((0, 0), (0, 1), (1, 0), (1, 1)):
            n = res.expected_weak_cells[vi, ti, ri]
            rows.append((f"fig11/ret={t:.0f}ms/{temps[ti]:.0f}C"
                         f"/{voltages[vi]}V", f"weak_cells={n:.1f}", ""))
    return rows


def fig12_eq1_perf_model():
    from repro.core import perf_model
    m = perf_model.fit()
    return [
        ("fig12/eq1/low_mpki",
         f"coef={np.round(m.coef_low, 3).tolist()}",
         f"rmse={m.rmse_low:.2f} r2={m.r2_low:.3f} (paper 2.8/0.75)"),
        ("fig12/eq1/high_mpki",
         f"coef={np.round(m.coef_high, 3).tolist()}",
         f"rmse={m.rmse_high:.2f} r2={m.r2_high:.3f} (paper 2.5/0.90)"),
    ]


def table3_latencies():
    from repro.dram import circuit
    t3 = circuit.table3()
    rows = []
    for i, v in enumerate(circuit.TABLE3_VOLTAGES):
        match = all(t3[op][i] == circuit.TABLE3_PUBLISHED[op][i]
                    for op in ("rcd", "rp", "ras"))
        rows.append((f"table3/V={v:.2f}",
                     f"tRCD={t3['rcd'][i]} tRP={t3['rp'][i]} tRAS={t3['ras'][i]}",
                     f"exact_match={match}"))
    return rows


def fig13_table5_array_scaling():
    """The whole (group x voltage) grid in two batched engine calls."""
    from repro import engine
    from repro.memsim import workloads
    rows = []
    homog = workloads.homogeneous_workloads()
    voltages = (1.3, 1.2, 1.1, 1.0, 0.9)
    pg = engine.PointGrid.from_voltages(voltages)
    groups = {"mem": [w for w in homog if w[1][0].memory_intensive],
              "non": [w for w in homog if not w[1][0].memory_intensive]}
    targets = {("non", 1.2): (1.4, 10.4, 2.5), ("non", 0.9): (14.2, 29.0, 2.9)}
    for g, wls in groups.items():
        cmp_ = engine.evaluate_batch(
            engine.WorkloadBatch.from_workloads(wls), pg)     # [W, V]
        for vi, v in enumerate(voltages):
            loss = cmp_.perf_loss_pct[:, vi].mean()
            dp = cmp_.dram_power_savings_pct[:, vi].mean()
            se = cmp_.system_energy_savings_pct[:, vi].mean()
            t = targets.get((g, v))
            rows.append((f"fig13_table5/{g}/V={v}",
                         f"loss={loss:.1f}% dramP={dp:.1f}% sysE={se:.1f}%",
                         f"paper={t}" if t else ""))
    return rows


def fig14_15_voltron_vs_memdvfs():
    from repro.core import memdvfs, voltron
    from repro.memsim import workloads
    rows = []
    homog = workloads.homogeneous_workloads()
    for label, sel in (("non", False), ("mem", True)):
        grp = [(n, c) for n, c in homog if c[0].memory_intensive == sel]
        vr = voltron.run_suite(grp, 5.0, n_intervals=6)
        dr = [memdvfs.run(n, c, n_intervals=6) for n, c in grp]
        rows.append((
            f"fig14/voltron/{label}",
            f"loss={np.mean([r.perf_loss_pct for r in vr]):.1f}% "
            f"(max {np.max([r.perf_loss_pct for r in vr]):.1f}%)",
            f"sysE={np.mean([r.system_energy_savings_pct for r in vr]):.1f}% "
            f"(paper: mem 2.9%/7.0%, non 2.5%/3.2%)"))
        rows.append((
            f"fig14/memdvfs/{label}",
            f"loss={np.mean([r.perf_loss_pct for r in dr]):.1f}%",
            f"sysE={np.mean([r.system_energy_savings_pct for r in dr]):.1f}% "
            f"(paper: ~0 for mem)"))
        rows.append((f"fig15/{label}",
                     f"dram_energy_savings={np.mean([r.dram_energy_savings_pct for r in vr]):.1f}%",
                     ""))
    return rows


def fig16_bank_locality():
    from repro.core import voltron
    from repro.memsim import workloads
    homog = workloads.homogeneous_workloads()
    mem = [(n, c) for n, c in homog if c[0].memory_intensive]
    base = voltron.run_suite(mem, 5.0, n_intervals=6)
    bl = voltron.run_suite(mem, 5.0, n_intervals=6, bank_locality=True)
    return [
        ("fig16/voltron",
         f"loss={np.mean([r.perf_loss_pct for r in base]):.1f}%",
         f"sysE={np.mean([r.system_energy_savings_pct for r in base]):.1f}%"),
        ("fig16/voltron+BL",
         f"loss={np.mean([r.perf_loss_pct for r in bl]):.1f}%",
         f"sysE={np.mean([r.system_energy_savings_pct for r in bl]):.1f}% "
         "(paper: 2.9->1.8% loss, 7.0->7.3% energy)"),
    ]


def fig17_heterogeneous():
    from repro.core import voltron
    from repro.memsim import workloads
    rows = []
    wls = workloads.heterogeneous_workloads()
    by_cat = {}
    for n, c in wls:
        cat = n.split("-")[1]
        by_cat.setdefault(cat, []).append((n, c))
    for cat, grp in sorted(by_cat.items()):
        runs = voltron.run_suite(grp[:4], 5.0, n_intervals=4)
        rows.append((f"fig17/{cat}",
                     f"loss={np.mean([r.perf_loss_pct for r in runs]):.1f}%",
                     f"ppw={np.mean([r.perf_per_watt_gain_pct for r in runs]):.1f}%"))
    return rows


def fig18_target_sweep():
    from repro.core import voltron
    from repro.memsim import workloads
    homog = workloads.homogeneous_workloads()
    mem = [(n, c) for n, c in homog if c[0].memory_intensive][:4]
    rows = []
    for target in (1.0, 2.5, 5.0, 7.5, 10.0, 15.0):
        runs = voltron.run_suite(mem, target, n_intervals=4)
        rows.append((f"fig18/target={target}%",
                     f"loss={np.mean([r.perf_loss_pct for r in runs]):.1f}%",
                     f"sysE={np.mean([r.system_energy_savings_pct for r in runs]):.1f}%"))
    return rows


def fig19_interval_sweep():
    from repro.core import voltron
    from repro.memsim import workloads
    homog = workloads.homogeneous_workloads()
    mem = [(n, c) for n, c in homog if c[0].memory_intensive][:4]
    rows = []
    for interval in (1_000_000, 4_000_000, 16_000_000, 64_000_000):
        runs = voltron.run_suite(mem, 5.0, n_intervals=8,
                                 interval_cycles=interval,
                                 phase_amplitude=0.35)
        rows.append((f"fig19/interval={interval // 1_000_000}M",
                     f"ppw={np.mean([r.perf_per_watt_gain_pct for r in runs]):.2f}%",
                     f"sysE={np.mean([r.system_energy_savings_pct for r in runs]):.2f}%"))
    return rows


ALL = [
    table3_latencies, fig04_error_rate, fig05_bitline,
    fig06_latency_distribution, fig07_spice_fit, fig08_spatial_locality,
    fig09_beat_density, fig10_temperature, fig11_retention,
    fig12_eq1_perf_model, fig13_table5_array_scaling,
    fig14_15_voltron_vs_memdvfs, fig16_bank_locality, fig17_heterogeneous,
    fig18_target_sweep, fig19_interval_sweep,
]


def main() -> int:
    pattern = sys.argv[1] if len(sys.argv) > 1 else ""
    print("name,value,derived")
    failures = 0
    for fn in ALL:
        if pattern not in fn.__name__:
            continue
        try:
            rows = fn()
        except Exception as e:  # noqa: BLE001 — report and continue
            failures += 1
            print(f"{fn.__name__},ERROR,{type(e).__name__}: {e}")
            continue
        for name, value, derived in rows:
            print(f'{name},"{value}","{derived}"')
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
