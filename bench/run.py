"""The benchmark: one cell of ``BENCHMARK.json`` on the chips of this host.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (imports, the cell's inputs from ``--seed``, one warm job that
compiles or loads every program the window uses) counts as ``setup_s``.
The window then runs jobs back to back until ``--seconds`` have passed
and ends at a job boundary.  With ``--trace 0`` the last line of standard
output holds the cell's end-to-end metrics; with ``--trace 1`` the window
runs under the JAX profiler and the line holds the per-layer metrics, read
by ``bench/metrics/<metric>.py``, with the device's busy time and a
breakdown.  Either way the outputs of the window are then checked against
the plain reference, and every number compared is printed beside its
limit, last on standard error and last in the result line.

The run fails, and prints no result, when JAX finds no TPU or fewer chips
than the cell asks for.  JAX's compilation cache lives in
``$JAX_COMPILATION_CACHE_DIR`` or else ``artifacts/jax_cache`` in the
checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
# bench/ itself stays off the path, so that bench/trace.py does not hide
# the standard library's module of that name
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != BENCH_DIR]
for path in (os.path.join(ROOT, "src"), ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)

from bench import spec, stats  # noqa: E402
from bench import trace as trace_lib  # noqa: E402


class _Compiles:
    """Backend compiles of the process, counted from JAX's monitoring
    events: the dispatch layer's executables and eager operations alike
    (a load from the persistent cache is no compile)."""

    count = 0
    seconds = 0.0
    registered = False

    @classmethod
    def start(cls) -> None:
        if not cls.registered:
            import jax.monitoring
            jax.monitoring.register_event_duration_secs_listener(cls._on)
            cls.registered = True

    @classmethod
    def _on(cls, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            cls.count += 1
            cls.seconds += duration


def dispatch_totals() -> tuple:
    """(dispatch seconds, compiles) summed over every dispatch entry."""
    from repro.engine import dispatch
    st = dispatch.stats()
    return (sum(s["dispatch_us_total"] for s in st.values()) / 1e6,
            sum(s["compiles"] for s in st.values()))


def batch_window(job, seconds: float) -> dict:
    """Jobs back to back until ``seconds`` have passed; the window ends
    at a job boundary.  Each job ends in ``block_until_ready`` on its
    outputs (the program's dispatch waits for them).  The window's time
    per job is reported under the job's ``metric`` (``job_s`` unless the
    job kind names another)."""
    import jax
    jobs, outputs = [], []
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            d0, _ = dispatch_totals()
            s = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.job"):
                outputs.append(job.run(len(outputs)))
            e = time.perf_counter()
            d1, _ = dispatch_totals()
            jobs.append({"start": s, "end": e, "dispatch_s": d1 - d0})
            if e - t0 >= seconds:
                break
    window_s = time.perf_counter() - t0
    return {"window_s": window_s, "jobs": jobs, "outputs": outputs,
            "end_to_end": {getattr(job, "metric", "job_s"):
                           stats.job_s(window_s, len(jobs))},
            "attempted": len(jobs), "failed": 0,
            "notes": {"jobs": len(jobs)}}


def _device_info(devices) -> dict:
    dev = devices[0]
    peaks = [d.memory_stats() or {} for d in devices]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(int(p.get("peak_bytes_in_use", 0))
                                     for p in peaks)}


def run_cell(bench: dict, name: str, seed: int, seconds: float, trace: bool,
             *, t_start: float = T_START, cell: dict | None = None,
             config: dict | None = None, log=print) -> dict:
    """Everything of a run after the look for chips: set-up, the window,
    the per-layer readings, the check.  Returns the result object."""
    import jax

    entry = next(w for w in bench["workloads"] if w["name"] == name)
    cell = cell or spec.load_cell(name)
    config = config or spec.load_config(cell["config"])
    jobmod = spec.load_module("jobs", cell["kind"])
    _Compiles.start()
    job = jobmod.Job(config, cell["params"], seed)
    job.warm()
    setup_s = time.perf_counter() - t_start
    _, compiles0 = dispatch_totals()
    jax_compiles0 = _Compiles.count
    window = getattr(jobmod, "window", batch_window)

    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        if trace:
            jax.profiler.start_trace(tdir)
        try:
            res = window(job, seconds)
        finally:
            if trace:
                jax.profiler.stop_trace()
        records = trace_lib.extract(tdir) if trace else None
    finally:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)
    _, compiles1 = dispatch_totals()
    devices = jax.devices()[:entry["chips"]]
    device = _device_info(devices)

    notes = dict(res["notes"], compiles_in_window=compiles1 - compiles0,
                 backend_compiles_in_window=_Compiles.count - jax_compiles0)
    if trace:
        ctx = {"job": job, "window": res, "trace": records,
               "device": device, "chips": entry["chips"],
               "peaks": _peaks(device["kind"])}
        metrics = {}
        for m in spec.cell_metrics(bench, name, "per_layer"):
            value = spec.load_module("metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        lo, hi = trace_lib.span(records, "bench.window")
        ops = _device_ops(records, entry["chips"])
        device["busy_s"] = sum(trace_lib.busy_ns(o, lo, hi)
                               for o in ops) / len(ops) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        breakdown = {
            "device_ops": trace_lib.top_ops(ops[0], lo, hi),
            "idle_gaps": trace_lib.idle_gaps(ops[0], records["spans"],
                                             lo, hi)}
    else:
        metrics = {}
        for m in spec.cell_metrics(bench, name, "end_to_end"):
            if m["name"] == "setup_s":
                metrics["setup_s"] = {"value": setup_s, "unit": m["unit"]}
            else:
                metrics[m["name"]] = {
                    "value": res["end_to_end"][m["name"]], "unit": m["unit"]}
        breakdown = None
    t_check = time.perf_counter()
    checks = job.check(res["outputs"])
    notes["check_s"] = time.perf_counter() - t_check
    for k, v in notes.items():
        log(f"[{name}] {k} = {v!r}", file=sys.stderr)
    correct = all(value <= limit for _, value, limit in checks)
    out = {"correct": correct, "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["notes"] = notes
    out["checked"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    return out


def _device_ops(records: dict, chips: int) -> list:
    ids = sorted(records["devices"])[:chips]
    return [records["devices"][i]["ops"] for i in ids]


def _peaks(kind: str) -> dict:
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r} in "
                       "bench/peaks.json")
    return table[kind]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = spec.load_benchmark(ROOT)
    entry = [w for w in bench["workloads"] if w["name"] == args.workload]
    if not entry:
        print(f"bench: no cell {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    import jax
    from repro.engine import dispatch
    cache = dispatch.enable_persistent_cache()
    if jax.default_backend() != "tpu":
        print(f"bench: JAX found no TPU (backend {jax.default_backend()!r});"
              " nothing was run", file=sys.stderr)
        return 1
    if len(jax.devices()) < entry[0]["chips"]:
        print(f"bench: the cell needs {entry[0]['chips']} chips, JAX sees "
              f"{len(jax.devices())}", file=sys.stderr)
        return 1
    print(f"bench: {args.workload} seed {args.seed} on "
          f"{jax.devices()[0].device_kind}; compile cache {cache}",
          file=sys.stderr)
    out = run_cell(bench, args.workload, args.seed, args.seconds,
                   bool(args.trace))
    for n, c in out["checked"].items():
        print(f"check {n} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
