"""The program's own host spans, split by stage and laid on the trace.

The program's dispatch layer logs every span it opens
(``repro.engine.dispatch.spans()``): ``repro.<entry>`` for an entry
point's call and ``repro.<entry>.<stage>`` for its stages (``lower``,
``put``, ``compile``, ``dispatch``, ``fetch``, one per table policy),
stamped with ``time.perf_counter_ns``, the clock of the window's job
records.  Here:

- ``window_spans``: the logged spans inside the window's jobs;
- ``stage_ms``: their self time (a span's time less its children's) per
  job, summed over the stages asked for;
- ``idle_ms``: every device-idle stretch inside ``bench.window`` put
  down to the innermost program span open over it, per job.  The spans
  move onto the trace's clock by one offset: the median, over the
  window's jobs, of the ``bench.job`` span's start less the job
  record's ``start``, two stamps taken microseconds apart.

A program that logs no spans gives None throughout, so its metrics are
left out of the result line.  A log that dropped records inside the
window, or offsets that spread by more than ``MAX_OFFSET_SPREAD_NS``,
raise ``trace.TraceError``.
"""
from __future__ import annotations

import collections
import statistics

from bench import trace

PREFIX = "repro."
DEVICE_STAGES = ("dispatch", "compile")
MAX_OFFSET_SPREAD_NS = 100_000


def stage(name: str) -> str:
    """``repro.fleet.lower`` -> ``lower``; an entry's own span -> ``call``."""
    return name[len(PREFIX):].partition(".")[2] or "call"


def window_spans(ctx) -> list | None:
    """The program's span records that lie inside the window's jobs, or
    None where there is no job or the program logs no spans."""
    jobs = ctx["window"].get("jobs")
    try:
        from repro.engine.dispatch import spans
    except ImportError:
        return None
    if not jobs:
        return None
    records, dropped = spans()
    lo, hi = jobs[0]["start"] * 1e9, jobs[-1]["end"] * 1e9
    # records close in order, so every dropped one closed before the
    # oldest kept
    if dropped and (not records or records[0].end_ns >= lo):
        raise trace.TraceError(f"the span log dropped {dropped} records, "
                               "some inside the window")
    return [r for r in records if r.start_ns >= lo and r.end_ns <= hi]


def self_ns(spans) -> dict:
    """Self time of each span name, in ns: its spans' time less that of
    their children."""
    inner = collections.Counter()
    for r in spans:
        if r.parent_id is not None:
            inner[r.parent_id] += r.end_ns - r.start_ns
    out = collections.Counter()
    for r in spans:
        out[r.name] += r.end_ns - r.start_ns - inner[r.id]
    return dict(out)


def stage_ms(ctx, stages) -> float | None:
    """Self time per job of the spans of ``stages``, in ms."""
    spans = window_spans(ctx)
    if spans is None:
        return None
    total = sum(ns for name, ns in self_ns(spans).items()
                if stage(name) in stages)
    return total / 1e6 / len(ctx["window"]["jobs"])


def clock_offsets(ctx) -> list:
    """Per job, the ``bench.job`` span's start on the trace's clock less
    the job record's ``start`` on ``perf_counter``, in ns."""
    jobs = ctx["window"]["jobs"]
    starts = sorted(s for n, s, _ in ctx["trace"]["spans"] if n == "bench.job")
    if len(starts) != len(jobs):
        raise trace.TraceError(f"{len(starts)} bench.job spans for "
                               f"{len(jobs)} jobs")
    return [t - j["start"] * 1e9 for t, j in zip(starts, jobs)]


def clock_offset_ns(ctx) -> float:
    """The offset that moves a ``perf_counter_ns`` stamp onto the trace."""
    offs = clock_offsets(ctx)
    if max(offs) - min(offs) > MAX_OFFSET_SPREAD_NS:
        raise trace.TraceError(f"clock offsets spread by "
                               f"{max(offs) - min(offs):.0f} ns")
    return statistics.median(offs)


def _idle_stretches(ops, lo: float, hi: float) -> list:
    out, t = [], lo
    for s, e in trace._merged(ops, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _innermost(spans) -> list:
    """``[(start, end, name)]`` pieces of time, in order, each under the
    same innermost span (the latest to open) throughout."""
    cuts = sorted({t for _, s, e in spans for t in (s, e)})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        covering = [(s, -e, name) for name, s, e in spans if s <= a and e >= b]
        if covering:
            out.append((a, b, max(covering)[2]))
    return out


def idle_ns(ops, spans, lo: float, hi: float) -> dict:
    """Idle ns of ``ops`` inside [lo, hi] by the innermost of ``spans``
    (``[name, start, end]`` on the ops' clock) open over it; key None for
    idle time under no span."""
    pieces = _innermost(spans)
    out = collections.Counter()
    i = 0
    for a, b in _idle_stretches(ops, lo, hi):
        while i < len(pieces) and pieces[i][1] <= a:
            i += 1
        covered, j = 0, i
        while j < len(pieces) and pieces[j][0] < b:
            s, e, name = pieces[j]
            d = min(b, e) - max(a, s)
            if d > 0:
                out[name] += d
                covered += d
            j += 1
        out[None] += (b - a) - covered
    return dict(out)


def idle_ms(ctx) -> dict | None:
    """Device-idle time per job inside ``bench.window``, in ms, by the
    innermost program span open over it (key None: under none)."""
    spans = window_spans(ctx)
    if spans is None:
        return None
    off = clock_offset_ns(ctx)
    rec = ctx["trace"]
    lo, hi = trace.span(rec, "bench.window")
    ops = rec["devices"][min(rec["devices"])]["ops"]
    moved = [[r.name, r.start_ns + off, r.end_ns + off] for r in spans]
    n = len(ctx["window"]["jobs"])
    return {k: ns / 1e6 / n for k, ns in idle_ns(ops, moved, lo, hi).items()}


def idle_host_ms(ctx) -> float | None:
    """Device-idle ms per job whose innermost program span is a host
    stage: any but ``dispatch`` and ``compile``."""
    idle = idle_ms(ctx)
    if idle is None:
        return None
    return sum(ms for name, ms in idle.items()
               if name is not None and stage(name) not in DEVICE_STAGES)
