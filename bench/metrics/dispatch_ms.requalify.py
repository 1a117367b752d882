"""Dispatch time per job: the delta of ``dispatch_us_total`` summed over
every dispatch entry, averaged over the window's jobs, in ms.  It is host
wall time around each compiled execution, ending in
``jax.block_until_ready``.  The re-qualification cell."""


def read(ctx):
    recs = ctx["window"].get("jobs")
    if not recs:
        return None
    return 1e3 * sum(r["dispatch_s"] for r in recs) / len(recs)
