"""Bring-up smoke run: the system's main path on one TPU chip.

Runs in one process, through the public entry points, in order:

  (a) characterization of the 31 Table 7 DIMMs over the paper's voltage
      grid (1.35 V down to 0.90 V) and 20-70 C (``characterize_batch``);
  (b) Test 1 and the RowHammer stress at the modelled part's 8 KiB rows
      on a few DIMMs (``test1.run_batch`` / ``run_hammer_batch``, Pallas
      ``voltage_inject``);
  (c) per-DIMM safe-voltage tables with the ECC-aware policy stack
      (``fleet.build_tables``);
  (d) the fleet Voltron controller over 77 workloads x 31 DIMMs
      (``fleet.run_fleet_batched``, Pallas ``sweep_solve``);
  (e) an ``EngineService`` over those tables answering coalesced
      min-latency, characterization and fleet requests via ``submit()``.

Every phase checks a seeded sample of its lanes against the engine's
parity reference (``impl="scalar"`` / ``dispatch="direct"``), run on the
host CPU so that no reference shares the chip's numerics: Test-1 and
hammer error counts, min-latency pairs, table masks and fleet selected
voltages bit-equal; float64 characterization values and table error rates
within ``CHAR_RTOL``/``CHAR_ATOL`` (float64 is emulated on the TPU, so the
largest difference seen is printed); fleet float32 metrics within
``METRIC_ATOL_PCT`` percentage points.  The executables of (b), (d) and (e)
must hold a Pallas ``tpu_custom_call``.  Any mismatch or error exits
nonzero; on success the last line of stdout is

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Usage (from the checkout root):

  python chip_smoke.py              # one chip: phases (a)-(e)
  python chip_smoke.py --chips 4    # phases (b) and (d) on the 4-chip
                                    # ("batch",) mesh vs a 1-chip mesh

It exits nonzero before any phase when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import asyncio
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import engine, hw  # noqa: E402
from repro.core import perf_model, voltron  # noqa: E402
from repro.dram import test1 as scalar_test1  # noqa: E402
from repro.engine import dispatch, fleet, service, test1  # noqa: E402
from repro.launch import fleet_serve  # noqa: E402
from repro.memsim import workloads  # noqa: E402

# the sampled reference lanes and requests
SEED = 0
N_SAMPLE = 8

# (a) the paper's characterization grid: 1.35 V down to 0.90 V in the
# Section 4.1 step, 20-70 C, every data pattern of Test 1
CHAR_VOLTAGES = np.round(np.arange(hw.VDD_NOMINAL, hw.VDD_SWEEP_FLOOR - 1e-9,
                                   -0.025), 4)
CHAR_TEMPS = (20.0, 30.0, 40.0, 50.0, 60.0, 70.0)
CHAR_PATTERNS = tuple(scalar_test1.DATA_PATTERNS)
# float64 characterization vs the host float64 loop:
# |got - ref| <= CHAR_ATOL + CHAR_RTOL * |ref|
CHAR_RTOL = 1e-6
CHAR_ATOL = 1e-6

# (b) the modelled part's row: 128 lines of 64 B
ROW_BYTES = hw.LINES_PER_ROW * hw.CACHE_LINE_BYTES
STRESS_VOLTAGES = (1.35, 1.20, 1.10, 1.00)
HAMMER_VOLTAGES = (1.35, 1.10)
HAMMER_COUNTS = (2e4, 1e5, 5e5)
NPLANES = 2

# (d) the fleet controller
N_INTERVALS = 25
TARGET_LOSS_PCT = voltron.DEFAULT_TARGET_PCT
# float32 engine metrics vs the reference, in percentage points
METRIC_ATOL_PCT = 5e-3
METRIC_FIELDS = ("perf_loss_pct", "dram_power_savings_pct",
                 "dram_energy_savings_pct", "system_energy_savings_pct",
                 "perf_per_watt_gain_pct")

PALLAS_ENTRIES = ("test1", "hammer", "fleet")


class SmokeFailure(AssertionError):
    """A phase's output disagrees with its reference."""


def lane_rows(row_bytes: int = ROW_BYTES, banks: int = hw.BANKS_PER_RANK,
              nplanes: int = NPLANES,
              budget: int = dispatch.DEFAULT_MAX_ELEMENTS_RESIDENT) -> int:
    """Rows per bank one Test-1 lane holds under the dispatch budget: a
    lane costs ``(nplanes + 4) * banks * rows * words`` resident
    elements (``test1._dispatch_test1_plane``)."""
    return budget // ((nplanes + 4) * banks * (row_bytes // 4))


def _equal(name: str, got, ref) -> None:
    got, ref = np.asarray(got), np.asarray(ref)
    if got.shape != ref.shape or not np.array_equal(got, ref,
                                                    equal_nan=True):
        raise SmokeFailure(f"{name}: not bit-equal to the reference "
                           f"(got {got.ravel()[:8]}, ref {ref.ravel()[:8]})")


def _close(name: str, got, ref, rtol: float, atol: float) -> float:
    """Largest |got - ref|; raises past ``atol + rtol * |ref|``."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if not np.array_equal(np.isnan(got), np.isnan(ref)):
        raise SmokeFailure(f"{name}: NaN pattern differs from the reference")
    ok = ~np.isnan(ref)
    diff = np.abs(got[ok] - ref[ok])
    if (diff > atol + rtol * np.abs(ref[ok])).any():
        raise SmokeFailure(f"{name}: differs from the reference by "
                           f"{diff.max()!r} (rtol {rtol}, atol {atol})")
    return float(diff.max()) if diff.size else 0.0


def host() -> contextlib.AbstractContextManager:
    """Place the JAX work of a reference on the host CPU."""
    return jax.default_device(jax.local_devices(backend="cpu")[0])


def _sample(rng, shape, n: int) -> list:
    """``n`` distinct seeded index tuples of an array of ``shape``."""
    flat = rng.choice(int(np.prod(shape)), size=min(n, int(np.prod(shape))),
                      replace=False)
    return [tuple(int(i) for i in np.unravel_index(f, shape))
            for f in sorted(flat)]


# --------------------------------------------------------------------------
# Phases
# --------------------------------------------------------------------------
def phase_characterize(grid, *, voltages=CHAR_VOLTAGES, temps=CHAR_TEMPS,
                       patterns=CHAR_PATTERNS, rng, n_sample: int) -> dict:
    """(a) The D x V x T characterization sweep; sampled lanes against the
    per-DIMM host loop (``impl="scalar"``)."""
    t0 = time.perf_counter()
    got = engine.characterize_batch(grid, voltages, temps, patterns)
    wall = time.perf_counter() - t0
    max_diff = 0.0
    for d, v, t in _sample(rng, got.line_error_fraction.shape, n_sample):
        with host():
            ref = engine.characterize_batch(
                grid.select([grid.modules[d]]), [voltages[v]], (temps[t],),
                patterns, impl="scalar")
        for field in ("line_error_fraction", "ber", "t_rcd_min", "t_rp_min",
                      "row_error_prob", "line_error_prob"):
            max_diff = max(max_diff, _close(
                f"characterize {field} {grid.modules[d]}@{voltages[v]}V,"
                f"{temps[t]}C", getattr(got, field)[d, v, t],
                getattr(ref, field)[0, 0, 0], CHAR_RTOL, CHAR_ATOL))
        max_diff = max(max_diff, _close(
            "characterize expected_weak_cells",
            got.expected_weak_cells[v, t], ref.expected_weak_cells[0, 0],
            CHAR_RTOL, CHAR_ATOL))
    return {"result": got, "wall_s": wall, "lanes": got.line_error_fraction.size,
            "max_abs_diff": max_diff}


def phase_stress(grid, *, voltages=STRESS_VOLTAGES,
                 hammer_voltages=HAMMER_VOLTAGES, hammer_counts=HAMMER_COUNTS,
                 rows: int, row_bytes: int = ROW_BYTES, inject_impl: str,
                 mesh=None, rng=None, n_sample: int = 0) -> dict:
    """(b) Test 1 over every pattern group and the RowHammer sweep on
    ``grid``'s DIMMs at ``rows`` x ``row_bytes`` per bank; ``n_sample``
    lanes of each against the per-bank ``dram.test1`` loop."""
    kw = dict(rows=rows, row_bytes=row_bytes, nplanes=NPLANES, mesh=mesh,
              inject_impl=inject_impl)
    t0 = time.perf_counter()
    t1 = test1.run_batch(grid, voltages, **kw)
    ham = test1.run_hammer_batch(grid, hammer_voltages, hammer_counts, **kw)
    wall = time.perf_counter() - t0
    ref_kw = dict(rows=rows, row_bytes=row_bytes, nplanes=NPLANES,
                  impl="scalar", inject_impl="reference")
    for d, v, p, r in (_sample(rng, t1.bit_errors.shape, n_sample)
                       if n_sample else ()):
        with host():
            ref = test1.run_batch(grid.select([grid.modules[d]]),
                                  [voltages[v]], [t1.pattern_groups[p]],
                                  **ref_kw)
        for field in ("bit_errors", "erroneous_lines", "error_rows"):
            _equal(f"test1 {field} {grid.modules[d]}@{voltages[v]}V",
                   getattr(t1, field)[d, v, p, r], getattr(ref, field)[0, 0, 0, r])
    for d, v, h, r in (_sample(rng, ham.bit_errors.shape, n_sample)
                       if n_sample else ()):
        with host():
            ref = test1.run_hammer_batch(grid.select([grid.modules[d]]),
                                         [hammer_voltages[v]],
                                         [hammer_counts[h]], **ref_kw)
        for field in ("bit_errors", "erroneous_lines", "error_rows"):
            _equal(f"hammer {field} {grid.modules[d]}@{hammer_voltages[v]}V",
                   getattr(ham, field)[d, v, h, r], getattr(ref, field)[0, 0, 0, r])
    return {"test1": t1, "hammer": ham, "wall_s": wall,
            "lanes": t1.bit_errors.size + ham.bit_errors.size}


def candidate_voltages() -> np.ndarray:
    """Algorithm 1's candidates, ascending, with the nominal fallback."""
    return np.array(voltron.CANDIDATE_VOLTAGES + [hw.VDD_NOMINAL])


def phase_tables(grid, *, rng, n_sample: int, mesh=None) -> dict:
    """(c) ECC-aware safe-voltage tables; sampled DIMMs against the scalar
    min-latency search and the exact-shape (``dispatch="direct"``) stack,
    both on the host."""
    cand_v = candidate_voltages()
    t0 = time.perf_counter()
    tables = fleet.build_tables(grid, cand_v, policies=fleet.ecc_policies(),
                                mesh=mesh)
    wall = time.perf_counter() - t0
    max_diff = 0.0
    if n_sample:
        picks = sorted(rng.choice(grid.n_dimms, min(n_sample, grid.n_dimms),
                                  replace=False))
        sub = grid.select([grid.modules[i] for i in picks])
        got_floor = test1.find_min_latency_batch(sub, cand_v)
        with host():
            ref_floor = test1.find_min_latency_batch(sub, cand_v,
                                                     impl="scalar")
            ref = fleet.build_tables(sub, cand_v,
                                     policies=fleet.ecc_policies(),
                                     dispatch="direct")
        _equal("min-latency floor", got_floor, ref_floor)
        got = tables.select(sub.modules)
        for field in ("valid", "timings", "lat_feat", "hammer_margin"):
            _equal(f"tables {field}", getattr(got, field),
                   getattr(ref, field))
        for field in ("correctable", "detectable", "silent"):
            max_diff = max(max_diff, _close(
                f"tables {field}", getattr(got, field), getattr(ref, field),
                CHAR_RTOL, CHAR_ATOL))
    return {"result": tables, "wall_s": wall, "lanes": tables.valid.size,
            "max_abs_diff": max_diff}


def fleet_workloads() -> list:
    """The paper's 27 homogeneous and 50 heterogeneous workloads."""
    return (workloads.homogeneous_workloads()
            + workloads.heterogeneous_workloads())


def _fleet_reference(wls, tables, n_intervals: int, model):
    """The exact-shape jnp-oracle controller on the host."""
    wb = engine.WorkloadBatch.from_workloads(wls)
    phases = voltron._phase_matrix(wb.names, n_intervals,
                                   voltron.DEFAULT_INTERVAL_CYCLES, None, 0.15)
    with host():
        return fleet.run_fleet_batched(wb, tables, phases, model.coef_low,
                                       model.coef_high, TARGET_LOSS_PCT,
                                       impl="reference", dispatch="direct")


def _check_fleet_lane(name: str, got, w: int, d: int, ref) -> float:
    """Lane (w, d) of ``got`` against the one-lane reference result."""
    _equal(f"{name} selected voltages", got.selected_voltages[w, d],
           ref.selected_voltages[0, 0])
    return max(_close(f"{name} {f}", getattr(got, f)[w, d],
                      getattr(ref, f)[0, 0], 0.0, METRIC_ATOL_PCT)
               for f in METRIC_FIELDS)


def phase_fleet(tables, wls, *, n_intervals: int = N_INTERVALS, impl: str,
                model, mesh=None, rng=None, n_sample: int = 0) -> dict:
    """(d) The W x D fleet controller; sampled lanes against the one-lane
    jnp-oracle reference (``impl="reference"``, ``dispatch="direct"``) on
    the host."""
    wb = engine.WorkloadBatch.from_workloads(wls)
    phases = voltron._phase_matrix(wb.names, n_intervals,
                                   voltron.DEFAULT_INTERVAL_CYCLES, None, 0.15)
    t0 = time.perf_counter()
    got = fleet.run_fleet_batched(wb, tables, phases, model.coef_low,
                                  model.coef_high, TARGET_LOSS_PCT,
                                  impl=impl, mesh=mesh)
    wall = time.perf_counter() - t0
    max_diff = 0.0
    for w, d in (_sample(rng, got.perf_loss_pct.shape, n_sample)
                 if n_sample else ()):
        ref = _fleet_reference([wls[w]], tables.select([tables.modules[d]]),
                               n_intervals, model)
        max_diff = max(max_diff, _check_fleet_lane(
            f"fleet {wb.names[w]} x {tables.modules[d]}", got, w, d, ref))
    return {"result": got, "wall_s": wall, "lanes": got.perf_loss_pct.size,
            "max_abs_diff_pct": max_diff}


async def _submit_all(svc, requests) -> list:
    async with svc:
        return await asyncio.gather(*(svc.submit(r) for r in requests))


def phase_service(tables, *, n_workloads: int, n_requests: int, rng,
                  model) -> dict:
    """(e) An ``EngineService`` over ``tables`` answering a seeded mix of
    concurrent requests; every answer against its reference."""
    grid = engine.DimmGrid.from_population(tables.modules)
    svc = service.EngineService(
        grid, tables=tables,
        workloads=workloads.homogeneous_workloads()[:n_workloads],
        model=model)
    mods = list(tables.modules)
    # one request of each kind, then the launcher's seeded mixed stream
    requests = [
        service.MinLatencyRequest(str(rng.choice(mods)), (0.90, 1.10, 1.30)),
        service.CharacterizeRequest(str(rng.choice(mods)), (1.00, 1.20),
                                    temps=(20.0, 70.0)),
        service.FleetRequest(tuple(svc.workload_names[:2]),
                             tuple(str(m) for m in rng.choice(mods, 3,
                                                              replace=False)),
                             n_intervals=4),
    ] + fleet_serve.request_mix(rng, n_requests, mods, svc.workload_names,
                                characterize_frac=0.25)
    kinds = {type(r).__name__ for r in requests}
    t0 = time.perf_counter()
    answers = asyncio.run(_submit_all(svc, requests))
    wall = time.perf_counter() - t0
    wl_by_name = dict(workloads.homogeneous_workloads())
    max_char, max_pct = 0.0, 0.0
    for req, got in zip(requests, answers):
        if isinstance(req, service.MinLatencyRequest):
            with host():
                ref = test1.find_min_latency_batch(
                    grid.select([req.module]), req.voltages, impl="scalar")
            _equal(f"service min-latency {req.module}", got, ref[0])
        elif isinstance(req, service.CharacterizeRequest):
            with host():
                ref = engine.characterize_batch(
                    grid.select([req.module]), req.voltages, req.temps,
                    req.patterns, impl="scalar")
            for field in ("line_error_fraction", "ber", "t_rcd_min",
                          "t_rp_min", "row_error_prob", "line_error_prob"):
                max_char = max(max_char, _close(
                    f"service characterize {field} {req.module}", got[field],
                    getattr(ref, field)[0], CHAR_RTOL, CHAR_ATOL))
            max_char = max(max_char, _close(
                "service characterize expected_weak_cells",
                got["expected_weak_cells"], ref.expected_weak_cells,
                CHAR_RTOL, CHAR_ATOL))
        else:
            ref = _fleet_reference([(n, wl_by_name[n]) for n in req.workloads],
                                   tables.select(req.modules),
                                   req.n_intervals, model)
            _equal("service fleet selected voltages", got.selected_voltages,
                   ref.selected_voltages)
            for f in METRIC_FIELDS:
                max_pct = max(max_pct, _close(
                    f"service fleet {f}", getattr(got, f), getattr(ref, f),
                    0.0, METRIC_ATOL_PCT))
    return {"result": answers, "wall_s": wall, "lanes": len(requests),
            "stats": svc.stats(), "kinds": sorted(kinds),
            "max_abs_diff": max_char, "max_abs_diff_pct": max_pct}


def assert_pallas_compiled(entries=PALLAS_ENTRIES) -> None:
    """Every executable cached for ``entries`` must hold a Pallas kernel."""
    for entry in entries:
        exes = dispatch.executables(entry)
        if not exes:
            raise SmokeFailure(f"no executable was compiled for {entry!r}")
        for compiled in exes:
            if "tpu_custom_call" not in compiled.as_text():
                raise SmokeFailure(f"an executable of {entry!r} holds no "
                                   "Pallas tpu_custom_call")


def compare_meshes(a: dict, b: dict, names) -> None:
    """Bit-equality of every per-element output of two runs of one phase."""
    for name in names:
        x, y = a[name], b[name]
        for field in ("bit_errors", "erroneous_lines", "error_rows",
                      "selected_voltages", "base_component_j",
                      "pt_component_j") + METRIC_FIELDS:
            if hasattr(x, field):
                _equal(f"{name} {field} (mesh vs one device)",
                       getattr(x, field), getattr(y, field))


# --------------------------------------------------------------------------
# Driver
# --------------------------------------------------------------------------
def _report(name: str, res: dict, entries) -> None:
    stats = {e: dispatch.stats(e) for e in entries}
    compile_s = sum(s["compile_us_total"] for s in stats.values()) / 1e6
    mem = jax.devices()[0].memory_stats() or {}
    peak = mem.get("peak_bytes_in_use", "not reported")
    extra = {k: res[k] for k in ("max_abs_diff", "max_abs_diff_pct")
             if k in res}
    print(f"[{name}] lanes={res['lanes']} wall_s={res['wall_s']!r} "
          f"compile_s={compile_s!r} peak_device_bytes={peak} "
          f"{json.dumps(extra)}")
    print(f"[{name}] dispatch.stats {json.dumps(stats, default=str)}")
    sys.stdout.flush()


def run_one_chip(rng, n_sample: int = N_SAMPLE) -> None:
    grid = engine.DimmGrid.from_population()
    model = perf_model.fit()
    print(f"characterization tolerance: |got - ref| <= {CHAR_ATOL} + "
          f"{CHAR_RTOL} * |ref|; fleet metrics within {METRIC_ATOL_PCT} "
          "percentage points; counts, latency pairs, masks and selected "
          "voltages bit-equal")

    dispatch.reset_stats()
    res = phase_characterize(grid, rng=rng, n_sample=n_sample)
    _report("a characterize", res, ("characterize",))

    rows = lane_rows()
    picks = [str(rng.choice([m for m, v in zip(grid.modules, grid.vendors)
                             if v == vendor])) for vendor in "ABC"]
    print(f"[b stress] modules={picks} row_bytes={ROW_BYTES} rows/bank={rows}"
          f" of the part's {hw.ROWS_PER_BANK} (cut {hw.ROWS_PER_BANK / rows:.2f}x;"
          " streaming rows within a lane is ROADMAP R1)")
    dispatch.reset_stats()
    res = phase_stress(grid.select(picks), rows=rows, inject_impl="pallas",
                       rng=rng, n_sample=max(1, n_sample // 4))
    assert_pallas_compiled(("test1", "hammer"))
    _report("b stress", res, ("test1", "test1/chunked", "hammer",
                              "hammer/chunked"))
    print(f"[b stress] test1 bit_errors by voltage "
          f"{res['test1'].bit_errors.sum(axis=(0, 2, 3)).tolist()}, hammer "
          f"by count {res['hammer'].bit_errors.sum(axis=(0, 1, 3)).tolist()}")

    dispatch.reset_stats()
    res = phase_tables(grid, rng=rng, n_sample=max(1, n_sample // 2))
    tables = res["result"]
    _report("c tables", res, ("min_latency", "beat_error"))
    print(f"[c tables] stack={tables.stack_name} safe_vmin by module "
          f"{dict(zip(tables.modules, tables.safe_vmin.tolist()))}")

    dispatch.reset_stats()
    res = phase_fleet(tables, fleet_workloads(), impl="pallas", model=model,
                      rng=rng, n_sample=n_sample)
    assert_pallas_compiled(("fleet",))
    _report("d fleet", res, ("fleet", "fleet/chunked"))
    print(f"[d fleet] mean dram_energy_savings_pct by vendor "
          f"{ {k: v['mean'] for k, v in res['result'].vendor_distribution().items()} }")

    dispatch.reset_stats()
    res = phase_service(tables, n_workloads=len(
        workloads.homogeneous_workloads()), n_requests=24, rng=rng,
        model=model)
    assert_pallas_compiled(("fleet",))
    _report("e service", res, ("min_latency", "characterize", "fleet",
                               "service"))
    print(f"[e service] kinds={res['kinds']} stats={json.dumps(res['stats'])}")


def run_four_chips(rng) -> None:
    """(b) and (d) on the 4-device ("batch",) mesh and on a one-device
    mesh; every per-element output must be bit-equal."""
    one = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("batch",))
    grid = engine.DimmGrid.from_population()
    model = perf_model.fit()
    rows = lane_rows()
    picks = [str(rng.choice([m for m, v in zip(grid.modules, grid.vendors)
                             if v == vendor])) for vendor in "ABC"]
    print(f"[b stress] modules={picks} row_bytes={ROW_BYTES} rows/bank={rows}")
    runs = {}
    for label, mesh in (("mesh4", None), ("one", one)):
        dispatch.reset_stats()
        runs[label] = phase_stress(grid.select(picks), rows=rows,
                                   inject_impl="pallas", mesh=mesh)
        _report(f"b stress {label}", runs[label],
                ("test1", "test1/chunked", "hammer", "hammer/chunked"))
    assert_pallas_compiled(("test1", "hammer"))
    compare_meshes(runs["mesh4"], runs["one"], ("test1", "hammer"))
    print("[b stress] 4-device mesh bit-equal to one device")

    t0 = time.perf_counter()
    tables = fleet.build_tables(grid, candidate_voltages(),
                                policies=fleet.ecc_policies(), mesh=one)
    print(f"[set-up] tables on one device in "
          f"{time.perf_counter() - t0!r} s")
    runs = {}
    for label, mesh in (("mesh4", None), ("one", one)):
        dispatch.reset_stats()
        runs[label] = phase_fleet(tables, fleet_workloads(), impl="pallas",
                                  model=model, mesh=mesh)
        _report(f"d fleet {label}", runs[label], ("fleet", "fleet/chunked"))
    assert_pallas_compiled(("fleet",))
    compare_meshes({"fleet": runs["mesh4"]["result"]},
                   {"fleet": runs["one"]["result"]}, ("fleet",))
    print("[d fleet] 4-device mesh bit-equal to one device")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded phases (b), (d) and their "
                         "one-device comparison")
    args = ap.parse_args(argv)

    cache = dispatch.enable_persistent_cache()
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: JAX found no TPU (default backend {backend!r}); "
              "no phase was run", file=sys.stderr)
        return 1
    devices = jax.devices()
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} needs exactly that many "
              f"devices; JAX sees {len(devices)}", file=sys.stderr)
        return 1
    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache}")
    rng = np.random.default_rng(SEED)
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(rng)
    else:
        run_one_chip(rng)
    print(f"total wall_s={time.perf_counter() - t0!r}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
