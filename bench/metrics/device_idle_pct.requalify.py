"""Device idle share of a batch window, in %: 1 - busy / window, where
busy is the union of the device's operation intervals inside the
``bench.window`` span (averaged over the cell's chips).  The re-qualification cell."""
from bench import trace


def read(ctx):
    if not ctx["window"].get("jobs"):
        return None
    return trace.window_idle_pct(ctx["trace"], ctx["chips"])
