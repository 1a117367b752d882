"""Readings that the limits of a cell's correctness check are set from.

  python3 bench/control.py --workload char31.stress --seconds 10 \
      --seeds 1,2,...,12 --control-seeds 1,2,3

For every seed in ``--seeds`` it runs the cell's window as ``run.py``
does and prints the numbers ``check`` compares for the program (the
lower readings); for every seed in ``--control-seeds`` also the numbers
the precision control gives, the reference one precision lower put in
the program's place (the upper readings).  One JSON line per seed.  The
set-up's compiles are shared by all seeds of the process.  It needs a
TPU, like ``run.py``.
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

from bench import run, spec  # noqa: E402


def readings(cell_name: str, seed: int, seconds: float, control: bool,
             cell=None, config=None) -> dict:
    cell = cell or spec.load_cell(cell_name)
    config = config or spec.load_config(cell["config"])
    jobmod = spec.load_module("jobs", cell["kind"])
    job = jobmod.Job(config, cell["params"], seed)
    job.warm()
    res = getattr(jobmod, "window", run.batch_window)(job, seconds)
    out = {"seed": seed, "program": {n: v for n, v, _ in
                                     job.check(res["outputs"])}}
    if control:
        out["control"] = {n: v for n, v, _ in job.control(res["outputs"])}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    import jax
    from repro.engine import dispatch
    dispatch.enable_persistent_cache()
    if jax.default_backend() != "tpu":
        print("control: JAX found no TPU", file=sys.stderr)
        return 1
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    seeds = [int(s) for s in args.seeds.split(",")]
    for seed in seeds + sorted(ctl - set(seeds)):
        print(json.dumps(readings(args.workload, seed, args.seconds,
                                  seed in ctl)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
