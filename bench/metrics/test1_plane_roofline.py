"""Share of the HBM roofline reached by the Test-1 plane, in %.

Least time: the bytes one read of every lane's data plane takes (lanes x
banks x rows x row_bytes, from the cell's shapes) at the chip's published
HBM bandwidth.  Measured time: the device time of the Test-1 and hammer
executables, the XLA modules that run inside the benchmark's
``bench.entry.test1`` / ``bench.entry.hammer`` spans under the names the
Test-1 plane carries (``jit_fn`` for the chunked stream, ``jit__test1_flat_fn``
for a resident bucket).  Both are per job; the random planes, the
injection and the popcounts all count as the plane's time."""
from bench import trace

MODULES = (r"jit_fn\(", r"jit__test1_flat_fn\(")


def read(ctx):
    job = ctx["job"]
    if not hasattr(job, "plane_bytes"):
        return None
    rec = ctx["trace"]
    spans = [(s, e) for n, s, e in rec["spans"]
             if n in ("bench.entry.test1", "bench.entry.hammer")]
    dev = rec["devices"][min(rec["devices"])]
    measured_s = trace.module_ns(dev["modules"], MODULES, spans) / 1e9
    n_jobs = len(ctx["window"]["jobs"])
    least_s = job.plane_bytes() / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s * n_jobs / measured_s
