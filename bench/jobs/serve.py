"""Served traffic: an open loop against ``EngineService.submit``.

Set-up builds the service over the configuration's DIMMs (their
safe-voltage tables, the loss predictor, the workload mixes) and warms
the coalescer's buckets for the request shapes of the cell's mix.  The
window offers ``rate`` requests per second in bursts of ``burst`` for
``--seconds``, then waits for every request due in it.  ``serve_p95_ms``
is the 95th percentile of completion minus scheduled arrival over all of
them, a failed or shed request counting as infinitely late;
``serve_rps`` is the requests completed over the window's wall time,
drain included.

``check`` draws completed requests from the seed and recomputes each
with the plain references: min-latency pairs exactly, characterization
values within ``limit_char`` (absolute, float64), fleet slices with equal
selected voltages and metrics within ``limit_pp`` percentage points.
"""
from __future__ import annotations

import asyncio
import time
import zlib

import numpy as np

from bench import loadgen, stats

FLEET_METRICS = ("perf_loss_pct", "dram_power_savings_pct",
                 "dram_energy_savings_pct", "system_energy_savings_pct",
                 "perf_per_watt_gain_pct")
CHAR_FIELDS = ("line_error_fraction", "ber", "t_rcd_min", "t_rp_min",
               "row_error_prob", "line_error_prob", "expected_weak_cells")


class Job:
    entries = ("min_latency", "characterize", "fleet")

    def __init__(self, config: dict, params: dict, seed: int):
        import jax

        from repro import engine
        from repro.core import perf_model
        from repro.engine import fleet, service
        from repro.memsim import workloads

        self.jax, self.service_lib = jax, service
        self.config, self.params = config, params
        rng = np.random.default_rng(seed)
        self.mix_rng = np.random.default_rng(rng.integers(2**63))
        self.check_rng = np.random.default_rng(rng.integers(2**63))
        grid = engine.DimmGrid.from_population(config["modules"])
        tables = fleet.build_tables(grid,
                                    np.array(config["candidate_voltages"]),
                                    policies=fleet.legacy_policies())
        wls = (workloads.homogeneous_workloads()
               + workloads.heterogeneous_workloads())[:config["n_workloads"]]
        self.workload_names = [n for n, _ in wls]
        self.svc = service.EngineService(
            grid, tables=tables, workloads=wls, model=perf_model.fit(),
            config=service.ServiceConfig(**params.get("service", {})))

    def request(self, r):
        s = self.service_lib
        if r[0] == "min_latency":
            return s.MinLatencyRequest(r[1], r[2])
        if r[0] == "characterize":
            return s.CharacterizeRequest(r[1], r[2])
        return s.FleetRequest(r[1], r[2],
                              n_intervals=self.params["fleet_intervals"])

    def mix(self, rng, n: int) -> list:
        return loadgen.request_mix(
            rng, n, self.config["modules"], self.workload_names,
            characterize_frac=self.params["characterize_frac"])

    def warm(self) -> None:
        """Compile (or load) every bucket the mix's flushes can reach;
        fill the service's per-lane caches as a server that has been up
        a while has them (every DIMM at every voltage of the mix, every
        workload's features); then serve one short burst so that the
        event loop, the executor and every entry have run once."""
        p, s = self.params, self.service_lib
        shapes = self.mix(np.random.default_rng(0), 256)
        self.svc.prewarm([self.request(r) for r in shapes],
                         max_lanes=p["prewarm_lanes"])
        volts = tuple(float(v) for v in loadgen.VOLTAGES)
        for m in self.config["modules"]:
            self.svc.run_request(s.MinLatencyRequest(m, volts))
        self.svc.run_request(s.FleetRequest(
            tuple(self.workload_names), (self.config["modules"][0],),
            n_intervals=p["fleet_intervals"]))
        # every request shape of the mix once more through the lowering,
        # whose eager host operations compile per shape
        m, w = self.config["modules"], self.workload_names
        for n in (1, 2):
            self.svc.run_request(s.CharacterizeRequest(m[0], volts[:n]))
            for k in (1, 2):
                self.svc.run_request(s.FleetRequest(
                    tuple(w[:n]), tuple(m[:k]),
                    n_intervals=p["fleet_intervals"]))
        warm = self.mix(np.random.default_rng(1), 2 * p["burst"])
        asyncio.run(self._loop(warm, loadgen.arrivals(len(warm), p["rate"],
                                                      p["burst"])))

    def check(self, outputs: dict) -> list:
        """``[(name, value, limit), ...]`` over completed requests drawn
        from the seed."""
        done = [i for i, r in enumerate(outputs["records"]) if r[4] is None]
        n = min(self.params["check_requests"], len(done))
        picks = (sorted(self.check_rng.choice(done, n, replace=False))
                 if n else [])
        return _check(self, outputs, picks)

    def control(self, outputs: dict) -> list:
        """``check`` with the references one precision lower in the
        program's place: characterization rounded to float32 (float64 as
        stated), the fleet controller in bfloat16 (float32 as stated)."""
        done = [i for i, r in enumerate(outputs["records"]) if r[4] is None]
        n = min(self.params["check_requests"], len(done))
        picks = (sorted(self.check_rng.choice(done, n, replace=False))
                 if n else [])
        return _check(self, outputs, picks, control=True)

    async def _loop(self, reqs, offsets):
        async def submit(r):
            return await self.svc.submit(self.request(r))
        out = await loadgen.open_loop(submit, reqs, offsets,
                                      time.perf_counter)
        await self.svc.drain()
        return out


def window(job: Job, seconds: float) -> dict:
    """Offer the cell's rate for ``seconds``; every request due in that
    span is waited for and counted."""
    import jax

    from bench.run import dispatch_totals
    p = job.params
    n = max(p["burst"], int(round(p["rate"] * seconds)))
    reqs = job.mix(job.mix_rng, n)
    offsets = loadgen.arrivals(n, p["rate"], p["burst"])
    st0, (d0, _) = job.svc.stats(), dispatch_totals()
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        recs = asyncio.run(job._loop(reqs, offsets))
    window_s = time.perf_counter() - t0
    st1, (d1, _) = job.svc.stats(), dispatch_totals()
    lat = [None if err is not None else 1e3 * (done - due)
           for due, sent, done, res, err in recs]
    late = np.array([1e3 * (sent - due) for due, sent, *_ in recs])
    done_ms = [x for x in lat if x is not None]
    ok = sum(x is not None for x in lat)
    flushes = st1["flushes"] - st0["flushes"]
    return {
        "window_s": window_s,
        "outputs": {"requests": reqs, "records": recs},
        "end_to_end": {"serve_p95_ms": stats.percentile_with_misses(lat, 95),
                       "serve_rps": stats.completed_rate(ok, window_s)},
        "attempted": n, "failed": n - ok,
        "serve": {"flushes": flushes,
                  "flushed_lanes": st1["flushed_lanes"]
                  - st0["flushed_lanes"],
                  "dispatch_s": d1 - d0},
        "notes": {"requests": n, "offered_rps": p["rate"],
                  "p95_samples": n, "failed_or_shed": n - ok,
                  "p50_ms": stats.percentile_with_misses(done_ms, 50),
                  "p99_ms": stats.percentile_with_misses(lat, 99),
                  "generator_late_ms_p95": float(np.percentile(late, 95)),
                  "generator_late_ms_max": float(late.max()),
                  "flushes": flushes}}


# ---- correctness ----------------------------------------------------------
def _check(job: Job, res: dict, picks, control: bool = False) -> list:
    from bench.ref import characterize as ref_char
    from bench.ref import fleet as ref_fleet
    import jax

    p = job.params
    cpu = jax.local_devices(backend="cpu")[0]
    dimms = {d.module: d for d in ref_fleet.chips.population()}
    bad_minlat, char_gap, fleet_lanes, fleet_gap = 0, 0.0, 0, 0.0
    fleet_picks = []
    with jax.default_device(cpu):
        for i in picks:
            kind, a, b = res["requests"][i]
            got = res["records"][i][3]
            if kind == "min_latency":
                ref = np.array([ref_fleet.min_latency(dimms[a], v) or
                                (np.nan, np.nan) for v in b], np.float64)
                bad_minlat += int(not np.array_equal(
                    np.asarray(got, np.float64), ref, equal_nan=True))
            elif kind == "characterize":
                ref = ref_char.characterize(
                    dimms[a], b, (20.0,), ("0xaa",),
                    job.params["retention_ms"])
                if control:
                    got = {k: np.asarray(v, np.float32) for k, v in
                           ref.items()}
                char_gap = max(char_gap, _char_gap(got, ref))
            else:
                fleet_picks.append((a, b, got))
        if fleet_picks:
            lanes, gap = _fleet_check(job, fleet_picks, control)
            fleet_lanes, fleet_gap = lanes, gap
    return [("minlat_requests_differing", bad_minlat, 0),
            ("char_gap", char_gap, p["limit_char"]),
            ("fleet_lanes_with_other_voltages", fleet_lanes,
             p["limit_lanes"]),
            ("fleet_metric_gap_pp", fleet_gap, p["limit_pp"])]


def _char_gap(got: dict, ref: dict) -> float:
    gap = 0.0
    for k in CHAR_FIELDS:
        g = np.asarray(got[k], np.float64)
        r = np.asarray(ref[k], np.float64).reshape(g.shape)
        if not np.array_equal(np.isnan(g), np.isnan(r)):
            return float("inf")
        ok = ~np.isnan(r)
        if ok.any():
            gap = max(gap, float(np.max(np.abs(g[ok] - r[ok]))))
    return gap


def _fleet_check(job: Job, picks, control: bool = False) -> tuple:
    from bench.ref import fleet as ref

    if not hasattr(job, "_ref_setup"):
        wls = dict(ref.fleet_workloads()[:job.config["n_workloads"]])
        job._ref_setup = (ref.tables(job.config["modules"]), ref.fit(), wls)
    tab, (lo, hi), wls = job._ref_setup
    t_ = job.params["fleet_intervals"]
    mods = list(tab["modules"])
    cores, cols, rows, got = [], [], {"timings": [], "valid": [],
                                      "lat_feat": []}, []
    for names, modules, res in picks:
        for wi, name in enumerate(names):
            col = ref.phase_column(zlib.crc32(name.encode()), t_)
            for di, m in enumerate(modules):
                cores.append(wls[name])
                cols.append(col)
                for k in rows:
                    rows[k].append(tab[k][mods.index(m)])
                got.append({f: getattr(res, f)[wi, di]
                            for f in FLEET_METRICS}
                           | {"v": res.selected_voltages[wi, di]})
    table_rows = {k: np.stack(v) for k, v in rows.items()}
    table_rows["cand_v"] = tab["cand_v"]
    out = ref.controller(cores, table_rows, np.stack(cols, axis=1), lo, hi)
    ref_v = tab["cand_v"][out["selected_idx"]]
    if control:
        import jax.numpy as jnp
        low = ref.controller(cores, table_rows, np.stack(cols, axis=1), lo,
                             hi, dtype=jnp.bfloat16)
        got = [{f: low[f][i] for f in FLEET_METRICS}
               | {"v": tab["cand_v"][low["selected_idx"][i]]}
               for i in range(len(got))]
    lanes = sum(int(not np.allclose(g["v"], rv, rtol=0, atol=1e-9))
                for g, rv in zip(got, ref_v))
    gap = max(abs(g[f] - out[f][i]) for i, g in enumerate(got)
              for f in FLEET_METRICS)
    return lanes, float(gap)


