"""Dispatch time per flush of the service, in ms: the delta of
``dispatch_us_total`` summed over every dispatch entry, over the delta of
the service's ``flushes`` counter, across the window.  Served cells
only."""


def read(ctx):
    s = ctx["window"].get("serve")
    if not s or not s["flushes"]:
        return None
    return 1e3 * s["dispatch_s"] / s["flushes"]
