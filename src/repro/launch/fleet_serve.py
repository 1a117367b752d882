"""Asyncio driver for the streaming fleet service: open-loop bursty load
over :class:`repro.engine.service.EngineService`.

  PYTHONPATH=src python -m repro.launch.fleet_serve \
      --requests 96 --burst 8 --window-ms 2

The load generator is **open loop**: request arrival times are fixed up
front (bursts of ``--burst`` at the offered rate) and do not slow down when
the service falls behind — the production-faithful regime, where queueing
delay shows up as latency rather than as a politely throttled client.
Per-request latency is measured from the *scheduled* arrival, so a backlog
is charged to the service, not hidden in the generator.  With ``--rate 0``
(default) the offered rate is set to a multiple of the measured
request-at-a-time baseline, so the run demonstrates the coalescing
headroom directly.

The chip benchmark's served job runs a copy of this open loop and request
mix (``bench/loadgen.py``).
"""
from __future__ import annotations

import argparse
import asyncio
import collections
import time

import numpy as np

from repro.engine import service as service_lib

DEFAULT_MODULES = ("A1", "A3", "B1", "B2", "C1", "C2")


def default_service(modules=DEFAULT_MODULES, n_workloads: int = 6,
                    config: service_lib.ServiceConfig | None = None,
                    mesh=None) -> service_lib.EngineService:
    """An :class:`EngineService` over a characterized sub-fleet: Table 7
    DIMMs ``modules``, the first ``n_workloads`` homogeneous workloads,
    safe-voltage tables derived through the engine."""
    from repro.core import perf_model, voltron
    from repro.engine.population import DimmGrid
    from repro.memsim import workloads

    grid = DimmGrid.from_population(modules)
    wls = workloads.homogeneous_workloads()[:n_workloads]
    return service_lib.EngineService(
        grid, tables=voltron.fleet_tables(grid), workloads=wls,
        model=perf_model.fit(), config=config, mesh=mesh)


def request_mix(rng: np.random.Generator, n: int, modules,
                workload_names, *, n_intervals: int = 4,
                characterize_frac: float = 0.0) -> list:
    """A seeded stream of mixed-size requests across the entry points:
    ~60% min-latency (1-2 voltages), the rest fleet-controller slices
    (1-2 workloads x 1-2 DIMMs), optionally a ``characterize_frac``
    fraction of single-point characterization queries."""
    voltages = np.round(np.arange(0.90, 1.31, 0.05), 2)
    reqs = []
    for _ in range(n):
        u = rng.random()
        module = str(rng.choice(modules))
        if u < characterize_frac:
            reqs.append(service_lib.CharacterizeRequest(
                module, tuple(rng.choice(voltages, rng.integers(1, 3),
                                         replace=False))))
        elif u < characterize_frac + 0.6 * (1 - characterize_frac):
            reqs.append(service_lib.MinLatencyRequest(
                module, tuple(rng.choice(voltages, rng.integers(1, 3),
                                         replace=False))))
        else:
            w = list(rng.choice(workload_names,
                                rng.integers(1, 3), replace=False))
            d = list(rng.choice(modules, rng.integers(1, 3), replace=False))
            reqs.append(service_lib.FleetRequest(
                tuple(str(x) for x in w), tuple(str(x) for x in d),
                n_intervals=n_intervals))
    return reqs


def serial_loop(service: service_lib.EngineService, requests) -> dict:
    """The request-at-a-time baseline: one warm dispatch per request."""
    t0 = time.perf_counter()
    for req in requests:
        service.run_request(req)
    dt = time.perf_counter() - t0
    return {"n": len(requests), "duration_s": dt,
            "rps": len(requests) / dt}


async def open_loop(service: service_lib.EngineService, requests, *,
                    rate: float, burst: int = 8) -> dict:
    """Drive ``requests`` at a fixed offered ``rate`` (req/s) in bursts of
    ``burst``; returns sustained RPS and p50/p99 latency (ms, scheduled
    arrival -> completion) over the completed requests, plus typed-error
    counts for shed/failed ones."""
    loop = asyncio.get_running_loop()
    t0 = loop.time() + 0.005
    arrivals = [t0 + (i // burst) * (burst / rate)
                for i in range(len(requests))]
    latencies, errors = [], collections.Counter()

    async def one(req, at):
        await asyncio.sleep(max(0.0, at - loop.time()))
        try:
            await service.submit(req)
        except service_lib.ServiceError as e:
            errors[type(e).__name__] += 1
            return
        latencies.append(loop.time() - at)

    await asyncio.gather(*(one(r, a)
                           for r, a in zip(requests, arrivals)))
    await service.drain()
    duration = loop.time() - t0
    lat_ms = 1e3 * np.asarray(latencies if latencies else [np.nan])
    done = len(latencies)
    return {
        "n": len(requests), "completed": done,
        "offered_rps": rate, "duration_s": duration,
        "rps": done / duration,
        "p50_ms": float(np.percentile(lat_ms, 50)),
        "p99_ms": float(np.percentile(lat_ms, 99)),
        "max_ms": float(lat_ms.max()),
        "errors": dict(errors),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=96)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="offered req/s (0: 8x the serial baseline)")
    ap.add_argument("--burst", type=int, default=8)
    ap.add_argument("--window-ms", type=float, default=2.0)
    ap.add_argument("--max-batch-lanes", type=int, default=64)
    ap.add_argument("--admission", choices=("shed", "queue"),
                    default="queue")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.engine import dispatch
    dispatch.enable_persistent_cache()
    cfg = service_lib.ServiceConfig(
        window_s=args.window_ms * 1e-3,
        max_batch_lanes=args.max_batch_lanes, admission=args.admission)
    service = default_service(config=cfg)
    rng = np.random.default_rng(args.seed)
    reqs = request_mix(rng, args.requests, DEFAULT_MODULES,
                       service.workload_names)

    print("[fleet-serve] prewarming coalescer buckets...")
    service.prewarm(reqs)
    serial = serial_loop(service, reqs)
    print(f"[fleet-serve] serial baseline: {serial['rps']:.1f} req/s "
          f"({serial['duration_s']:.2f}s for {serial['n']})")
    rate = args.rate or 8.0 * serial["rps"]
    res = asyncio.run(open_loop(service, reqs, rate=rate,
                                burst=args.burst))
    print(f"[fleet-serve] open loop @ {rate:.1f} req/s offered "
          f"(bursts of {args.burst}): sustained {res['rps']:.1f} req/s, "
          f"p50 {res['p50_ms']:.1f} ms, p99 {res['p99_ms']:.1f} ms, "
          f"errors {res['errors'] or 'none'}")
    st = service.stats()
    print(f"[fleet-serve] coalescing: {st['flushes']} flushes for "
          f"{st['submitted']} requests "
          f"({st['flushed_lanes']} lanes, max {st['max_flush_lanes']}/flush;"
          f" peak queue {st['max_queued_elements']} elements)")
    print(f"[fleet-serve] speedup vs request-at-a-time: "
          f"{res['rps'] / serial['rps']:.1f}x")


if __name__ == "__main__":
    main()
