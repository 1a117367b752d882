"""Arithmetic of the end-to-end metrics, kept apart from the chip so that
it can be checked on made-up records."""
from __future__ import annotations

import math


def job_s(window_s: float, n_jobs: int) -> float:
    """Seconds per job: the whole window's wall time over the jobs it
    completed (the window ends at a job boundary)."""
    if n_jobs <= 0:
        raise ValueError("the window completed no job")
    return window_s / n_jobs


def percentile_with_misses(latencies, q: float) -> float:
    """The ``q``-th percentile (0-100, nearest rank) of ``latencies``,
    where ``None`` marks a request that failed or was shed: it counts as
    an infinite latency, so a tail that reaches one reads ``inf``."""
    vals = sorted(math.inf if x is None else float(x) for x in latencies)
    if not vals:
        raise ValueError("no request was due in the window")
    rank = max(1, math.ceil(q / 100.0 * len(vals)))
    return vals[rank - 1]


def completed_rate(n_ok: int, window_s: float) -> float:
    """Requests completed correctly per second of the window, drain
    included in ``window_s``."""
    if window_s <= 0:
        raise ValueError("empty window")
    return n_ok / window_s
