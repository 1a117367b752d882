"""Device time of the fleet controller scan per job, in ms: the XLA
modules that run inside the benchmark's ``bench.entry.fleet`` spans under
the names the scan carries today (``jit__unknown`` for the resident
bucket of ``_controller_flat_fn``, ``jit_fn`` for its chunked stream,
``jit__controller_flat_fn`` should it be named after its function)."""
from bench import trace

MODULES = (r"jit__unknown\(", r"jit_fn\(", r"jit__controller_flat_fn\(")


def read(ctx):
    rec = ctx["trace"]
    spans = [(s, e) for n, s, e in rec["spans"] if n == "bench.entry.fleet"]
    if not spans:
        return None
    dev = rec["devices"][min(rec["devices"])]
    ns = trace.module_ns(dev["modules"], MODULES, spans)
    return ns / 1e6 / len(ctx["window"]["jobs"])
