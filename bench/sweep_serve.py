"""Rate sweep of a served cell, to find the highest rate it sustains.

  python3 bench/sweep_serve.py --workload voltron77.serve --seed 7 \
      --seconds 10 --rates 20,40,80,160

Builds the cell's service once, then offers each rate for ``--seconds``
in turn and prints one JSON line per rate: completed requests per second,
p50/p95 latency, failures, and how the latency of the last fifth of the
requests compares with the first fifth (a ratio far above 1 is a growing
backlog).  The sustained rate is the highest one whose completed rate
keeps up with the offer and whose backlog does not grow; the cell's rate
is written into its file by hand.  It needs a TPU, like ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != BENCH_DIR]
for path in (os.path.join(ROOT, "src"), ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)

import numpy as np  # noqa: E402

from bench import spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    import jax
    from repro.engine import dispatch
    dispatch.enable_persistent_cache()
    if jax.default_backend() != "tpu":
        print("sweep: JAX found no TPU", file=sys.stderr)
        return 1
    cell = spec.load_cell(args.workload)
    jobmod = spec.load_module("jobs", cell["kind"])
    job = jobmod.Job(spec.load_config(cell["config"]), cell["params"],
                     args.seed)
    job.warm()
    for rate in (float(r) for r in args.rates.split(",")):
        job.params = dict(cell["params"], rate=rate)
        res = jobmod.window(job, args.seconds)
        recs = res["outputs"]["records"]
        lat = np.array([1e3 * (done - due) for due, _, done, _, err in recs
                        if err is None])
        k = max(1, len(recs) // 5)
        head = np.mean([1e3 * (r[2] - r[0]) for r in recs[:k]])
        tail = np.mean([1e3 * (r[2] - r[0]) for r in recs[-k:]])
        print(json.dumps({
            "offered_rps": rate, **res["end_to_end"],
            "p50_ms": float(np.percentile(lat, 50)) if lat.size else None,
            "failed": res["failed"], "requests": res["attempted"],
            "backlog_ratio": float(tail / head),
            "lanes_per_flush": (res["serve"]["flushed_lanes"]
                                / max(1, res["serve"]["flushes"])),
            **{k: v for k, v in res["notes"].items()
               if k.startswith("generator")}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
