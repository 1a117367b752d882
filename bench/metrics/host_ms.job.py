"""Host time per job: each job's wall time minus the time the program's
dispatch layer spent in compiled executions during it
(``dispatch.stats()["dispatch_us_total"]`` delta), averaged over the
window's jobs, in ms.  Batch cells only."""


def read(ctx):
    recs = ctx["window"].get("jobs")
    if not recs:
        return None
    host = sum(r["end"] - r["start"] - r["dispatch_s"] for r in recs)
    return 1e3 * host / len(recs)
