"""Device time of the fleet's controller scan per valid lane a chip, in
us: the XLA modules of the scan inside the benchmark's
``bench.entry.fleet`` spans, summed per chip and averaged over the cell's
chips, per job, over the valid lanes each chip ran per job (the window's
``lanes`` on the ``repro.fleet.put`` spans over their ``devices``).  The
scan's modules are named after what ``dispatch_flat`` compiles:
``jit__controller_flat_fn`` for a resident bucket (``voltron77.fleet``,
one chip) and ``jit_fn`` for the chunked stream (``opfleet.x4``, the
``lax.map`` under ``shard_map`` on four chips).  None where the program's
spans carry no lane counts or no such module ran."""
from bench import spec, trace

MODULES = (r"jit__controller_flat_fn\(", r"jit_fn\(")


def read(ctx):
    found = spec.load_module("metrics", "padded_lane_pct.job").puts(ctx)
    rec = ctx["trace"]
    spans = [(s, e) for n, s, e in rec["spans"] if n == "bench.entry.fleet"]
    if not found or not spans:
        return None
    ids = sorted(rec["devices"])[:ctx["chips"]]
    try:
        ns = sum(trace.module_ns(rec["devices"][i]["modules"], MODULES, spans)
                 for i in ids) / len(ids)
    except trace.TraceError:
        return None
    per_chip = sum(r.attrs["lanes"] / r.attrs["devices"] for r in found)
    return ns / 1e3 / per_chip
