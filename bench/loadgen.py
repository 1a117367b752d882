"""Open-loop load: a seeded request mix on a fixed arrival schedule.

Arrivals come in bursts of ``burst`` at a fixed offered rate and do not
slow down when the system falls behind, so a backlog shows as latency.
Each request is timed from its scheduled arrival.  The mix follows the
service's launcher (``repro.launch.fleet_serve.request_mix``), copied
here so that the traffic stays fixed when the program changes: a share
of single-DIMM characterization queries, and of the rest 60% min-latency
searches over 1-2 voltages and 40% fleet slices of 1-2 workloads x 1-2
DIMMs.  Requests are plain tuples; the job turns them into the program's
request objects.
"""
from __future__ import annotations

import asyncio

import numpy as np

VOLTAGES = np.round(np.arange(0.90, 1.31, 0.05), 2)


def request_mix(rng: np.random.Generator, n: int, modules, workload_names,
                *, characterize_frac: float) -> list:
    """``n`` requests: ``("characterize", module, voltages)``,
    ``("min_latency", module, voltages)`` or ``("fleet", workloads,
    modules)``."""
    reqs = []
    for _ in range(n):
        u = rng.random()
        module = str(rng.choice(modules))
        if u < characterize_frac:
            reqs.append(("characterize", module, tuple(
                float(x) for x in rng.choice(VOLTAGES, rng.integers(1, 3),
                                             replace=False))))
        elif u < characterize_frac + 0.6 * (1 - characterize_frac):
            reqs.append(("min_latency", module, tuple(
                float(x) for x in rng.choice(VOLTAGES, rng.integers(1, 3),
                                             replace=False))))
        else:
            w = rng.choice(workload_names, rng.integers(1, 3), replace=False)
            d = rng.choice(modules, rng.integers(1, 3), replace=False)
            reqs.append(("fleet", tuple(str(x) for x in w),
                         tuple(str(x) for x in d)))
    return reqs


def arrivals(n: int, rate: float, burst: int) -> np.ndarray:
    """Scheduled arrival offsets (s) of ``n`` requests: bursts of
    ``burst`` every ``burst / rate`` seconds, the first at 0."""
    if rate <= 0 or burst < 1:
        raise ValueError("rate must be > 0 and burst >= 1")
    return (np.arange(n) // burst) * (burst / rate)


async def open_loop(submit, requests, offsets, clock) -> list:
    """Submit ``requests[i]`` at ``offsets[i]`` after the start, whatever
    the system's state.  Returns per request ``(due, sent, done, result,
    error)`` in ``clock()`` seconds; ``error`` is the exception of a
    request that failed or was shed."""
    loop = asyncio.get_running_loop()
    t0 = clock()
    out = [None] * len(requests)

    async def one(i):
        due = t0 + float(offsets[i])
        delay = due - clock()
        if delay > 0:
            await asyncio.sleep(delay)
        sent = clock()
        try:
            res, err = await submit(requests[i]), None
        except Exception as e:          # noqa: BLE001 - counted as a miss
            res, err = None, e
        out[i] = (due, sent, clock(), res, err)

    tasks = [loop.create_task(one(i)) for i in range(len(requests))]
    await asyncio.gather(*tasks)
    return out
