"""Lanes per flush of the service's coalescer: the delta of its
``flushed_lanes`` counter over the delta of ``flushes`` across the
window.  Served cells only."""


def read(ctx):
    s = ctx["window"].get("serve")
    if not s or not s["flushes"]:
        return None
    return s["flushed_lanes"] / s["flushes"]
