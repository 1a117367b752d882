"""Reduction of a JAX profiler trace to the benchmark's device numbers.

``extract`` turns the ``.xplane.pb`` the profiler writes into plain
records: per device, its operations and its executed XLA modules, and
the host spans that ``bench/`` opens with ``jax.profiler.TraceAnnotation``
(every such span's name starts with ``bench.``).  The rest works on those
records, so it can be checked on a small recorded trace without a chip:

- ``busy_ns``: the union of a device's operation intervals inside a
  window, so overlapping operations count once;
- ``idle_gaps``: the device's idle stretches inside a window, each
  named by the innermost host span open at its midpoint;
- ``module_ns``: the summed device time of the XLA modules whose names
  match, inside the host spans of one entry point; it fails when none
  does, so a renamed module never reads 0.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


class TraceError(RuntimeError):
    """The trace lacks what a metric needs."""


def xplane_path(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise TraceError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def extract(trace_dir: str) -> dict:
    """Plain records of the newest profile under ``trace_dir``:
    ``{"devices": {id: {"ops": [[name, start_ns, end_ns], ...],
    "modules": [[name, start_ns, end_ns], ...]}}, "spans": [[name,
    start_ns, end_ns], ...]}``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path(trace_dir))
    devices, spans = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(2)),
                                     {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key:
                    dev[key].extend([e.name, e.start_ns, e.end_ns]
                                    for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend([e.name, e.start_ns, e.end_ns]
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    if not devices:
        raise TraceError("the trace holds no device plane")
    return {"devices": devices, "spans": spans}


def _merged(intervals, lo: float, hi: float) -> list:
    """Sorted, merged ``[start, end]`` intervals clipped to [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for _, s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(ops, lo: float, hi: float) -> float:
    """Nanoseconds of [lo, hi] in which at least one operation ran."""
    return float(sum(e - s for s, e in _merged(ops, lo, hi)))


def idle_share(ops, lo: float, hi: float) -> float:
    """1 - busy / window over [lo, hi]."""
    if hi <= lo:
        raise TraceError("empty window")
    return 1.0 - busy_ns(ops, lo, hi) / (hi - lo)


def window_idle_pct(records: dict, chips: int) -> float:
    """Idle share of the ``bench.window`` span in %, averaged over the
    first ``chips`` devices."""
    lo, hi = span(records, "bench.window")
    ids = sorted(records["devices"])[:chips]
    return 100.0 * sum(idle_share(records["devices"][i]["ops"], lo, hi)
                       for i in ids) / len(ids)


def span(records: dict, name: str) -> tuple:
    """``(start_ns, end_ns)`` of the one host span called ``name``."""
    found = [(s, e) for n, s, e in records["spans"] if n == name]
    if len(found) != 1:
        raise TraceError(f"{len(found)} host spans named {name!r}")
    return found[0]


def _innermost(spans, t: float) -> str:
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else "outside bench spans"


def idle_gaps(ops, spans, lo: float, hi: float, top: int = 10) -> list:
    """The ``top`` longest idle stretches of [lo, hi] as ``[name,
    seconds]``, named by the innermost host span at each midpoint."""
    busy = _merged(ops, lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[_innermost(spans, (a + b) / 2), (b - a) / 1e9]
            for a, b in gaps[:top]]


def op_name(hlo: str) -> str:
    """``%inject_pallas.7 = u32[...] custom-call(...)`` -> ``inject_pallas``."""
    return re.sub(r"\.\d+$", "", hlo.split(" = ", 1)[0].lstrip("%"))


def top_ops(ops, lo: float, hi: float, top: int = 10) -> list:
    """The ``top`` operations by summed device time inside [lo, hi], as
    ``[name, seconds]``.  An operation that encloses others on its line
    (a loop around its body) is left out, so that time counts once."""
    ordered = sorted(ops, key=lambda o: (o[1], -o[2]))
    tot = {}
    for k, (name, s, e) in enumerate(ordered):
        if k + 1 < len(ordered) and ordered[k + 1][1] < e:
            continue
        d = min(e, hi) - max(s, lo)
        if d > 0:
            key = op_name(name)
            tot[key] = tot.get(key, 0.0) + d
    return [[n, d / 1e9] for n, d in
            sorted(tot.items(), key=lambda kv: -kv[1])[:top]]


def module_ns(modules, patterns, windows) -> float:
    """Summed device time, inside the ``[start, end]`` intervals of
    ``windows`` (host spans of one entry), of the XLA modules whose name
    matches any of ``patterns`` (regular expressions, ``re.match``).
    Raises when no such module ran there."""
    regs = [re.compile(p) for p in patterns]
    total, hits = 0.0, 0
    for name, s, e in modules:
        if not any(r.match(name) for r in regs):
            continue
        for lo, hi in windows:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                total += d
                hits += 1
    if not hits:
        raise TraceError(f"no XLA module matching {list(patterns)} ran in "
                         "the given spans")
    return total
