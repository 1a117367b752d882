"""Kernel micro-benchmarks + the measured autotune smoke.

Timing discipline (shared with ``repro.kernels.autotune.measure``): every
perf number is median-of-n blocking wall time after explicit warmup calls
— the first call pays trace + compile and is never counted.  Pallas
interpret mode is exercised for *parity only* (bit-exact / <=1e-6 vs the
oracle), never timed: interpret-mode wall time is meaningless for perf, so
TPU projections come from the roofline math instead.

``kernels()`` (the ``benchmarks/run.py kernel`` entry) runs the full
roofline-pruned tuning search for ``voltage_inject`` and ``sweep_solve``
at the benchmark shapes and reports measured tuned-vs-default speedups.

``main(out_path)`` (the ``scripts/check.sh`` step) runs the tiny smoke
search, persists winners to ``artifacts/tuning/``, then proves the
round-trip: the tuned config is *reloaded from disk*, a warm second
``simulate_batch`` hits the same executable (retrace count unchanged),
and ``dispatch.stats()`` reports the tuned config label on the entry.
Exits nonzero if any acceptance step fails; writes
``artifacts/BENCH_kernel.json`` for ``scripts/bench_gate.py``.
"""
from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro import hw
from repro.kernels import autotune


def _parity_rows():
    """Interpret-mode parity of both Pallas kernels vs the oracle at
    reduced, non-tile-aligned shapes (never timed)."""
    from repro.kernels.sweep_solve import ops as ss
    from repro.kernels.voltage_inject import ops as vi
    rows = []
    args = autotune.inject_inputs(68, 1090, 2, seed=11)
    ref = vi.inject(*args, impl="reference")
    got = vi.inject(*args, impl="pallas_interpret")
    ok = np.array_equal(np.asarray(got), np.asarray(ref))
    rows.append(("kernel/voltage_inject/interpret_parity",
                 "bit-exact" if ok else "MISMATCH", "not timed"))
    sargs = autotune.solve_inputs(37, 4, seed=11)
    sref = ss.solve(*sargs, impl="reference")
    sgot = ss.solve(*sargs, impl="pallas_interpret")
    # the existing test-suite tolerance: relative 1e-6 per output
    rel = 0.0
    for k in sref:
        r = np.asarray(sref[k], np.float64)
        g = np.asarray(sgot[k], np.float64)
        denom = np.maximum(np.abs(r), 1e-30)
        rel = max(rel, float(np.max(np.abs(g - r) / denom)))
        np.testing.assert_allclose(g, r, rtol=1e-6, err_msg=k)
    rows.append(("kernel/sweep_solve/interpret_parity",
                 f"max_rel_diff={rel:.1e} (<=1e-6)", "not timed"))
    if not ok:
        raise AssertionError("voltage_inject interpret parity failed")
    return rows


def _tune_rows(kernel: str, n: int = 5):
    """Full measured tuning search at the benchmark shape; one row with the
    tuned-vs-default result plus the prune/measure accounting."""
    shape = autotune.TUNE_SHAPES[kernel]
    r = autotune.tune_kernel(kernel, shape, n=n)
    counts = r.counts()
    return r, (f"kernel/{kernel}/autotune",
               f"default={r.default_us:.0f}us tuned={r.best_us:.0f}us "
               f"speedup={r.speedup:.2f}x cfg={r.best.key()}",
               f"bucket={r.bucket} measured={counts['measured']} "
               f"roofline_pruned={counts['pruned']} "
               f"ineligible={counts['ineligible']}")


def kernels():
    rows = []
    from repro.kernels.flash_attention import ops as fa
    b, s, h, kv, hd = 2, 1024, 8, 4, 64
    q = jax.random.normal(jax.random.key(0), (b, s, h, hd), jnp.bfloat16)
    k = jax.random.normal(jax.random.key(1), (b, s, kv, hd), jnp.bfloat16)
    v = jax.random.normal(jax.random.key(2), (b, s, kv, hd), jnp.bfloat16)
    ref = jax.jit(lambda q, k, v: fa.flash_attention(q, k, v,
                                                     impl="reference"))
    t = autotune.measure(ref, (q, k, v), n=3)
    flops = 4 * b * h * s * s * hd
    rows.append(("kernel/flash_attention/ref_cpu",
                 f"{t * 1e3:.1f}ms for {flops / 1e9:.1f}GF",
                 f"tpu_roofline={flops / hw.TPU_V5E.peak_flops * 1e6:.1f}us"))

    from repro.kernels.ssd_scan import ops as ssd
    b2, s2, h2, p2, n2 = 2, 512, 8, 64, 64
    x = jax.random.normal(jax.random.key(0), (b2, s2, h2, p2)) * 0.3
    a = -jnp.exp(jax.random.normal(jax.random.key(1), (h2,)) * 0.2)
    bm = jax.random.normal(jax.random.key(2), (b2, s2, n2)) * 0.3
    cm = jax.random.normal(jax.random.key(3), (b2, s2, n2)) * 0.3
    dt = jax.nn.softplus(jax.random.normal(jax.random.key(4), (b2, s2, h2)))
    dsk = jnp.ones((h2,))
    f = jax.jit(lambda *xs: ssd.ssd(*xs, 128, impl="reference"))
    t = autotune.measure(f, (x, a, bm, cm, dt, dsk), n=3)
    chunk = 128
    fl = b2 * h2 * (s2 // chunk) * (2 * chunk * chunk * n2
                                    + 2 * chunk * chunk * p2)
    rows.append(("kernel/ssd_scan/ref_cpu",
                 f"{t * 1e3:.1f}ms for {fl / 1e9:.1f}GF intra-chunk",
                 f"tpu_roofline={fl / hw.TPU_V5E.peak_flops * 1e6:.1f}us"))

    # the two tuned kernels: full roofline-pruned measured search at the
    # benchmark shapes, plus the untimed interpret-parity checks
    rows.extend(_parity_rows())
    for kernel in autotune.KERNELS:
        _, row = _tune_rows(kernel)
        rows.append(row)
    return rows

# separates compile/steady internally; the harness must not run it twice
kernels.self_timed = True


def _reload_acceptance(path: str) -> dict:
    """Prove the tuning round-trip on the live engine: enable tuned
    configs *from the on-disk file*, run a warm second ``simulate_batch``,
    and require (a) no new retrace on the second call and (b) the tuned
    config label on the ``grid_sim`` stats row."""
    from repro.core.perf_model import TRAIN_VOLTAGES
    from repro.engine import dispatch
    from repro.engine import solve as engine_solve
    from repro.engine.batch import PointGrid, WorkloadBatch
    from repro.memsim import workloads

    wb = WorkloadBatch.from_workloads(workloads.homogeneous_workloads())
    pg = PointGrid.from_voltages(TRAIN_VOLTAGES)
    ladder = dispatch.bucket_ladder(1)
    bw = dispatch.pick_bucket(wb.n_workloads, ladder) or wb.n_workloads
    bp = dispatch.pick_bucket(pg.n_points, ladder) or pg.n_points
    autotune.enable(path)                      # reload table from disk
    try:
        expect = autotune.active_config("sweep_solve",
                                        (bw * bp, wb.mpki.shape[1]))
        if expect == autotune.DEFAULTS["sweep_solve"]:
            raise AssertionError(
                f"no tuned sweep_solve entry served from {path}")
        dispatch.reset_stats()
        engine_solve.simulate_batch(wb, pg)
        first = dispatch.stats("grid_sim")
        engine_solve.simulate_batch(wb, pg)
        second = dispatch.stats("grid_sim")
    finally:
        autotune.disable()
    if second["compiles"] != first["compiles"]:
        raise AssertionError(
            "warm second run retraced: compiles "
            f"{first['compiles']} -> {second['compiles']}")
    if second.get("config_last") != expect.key() \
            or expect.key() not in second.get("kernel_configs", ()):
        raise AssertionError(
            f"stats do not report the tuned config {expect.key()!r}: "
            f"{second}")
    return {"config": expect.key(), "tuning_file": os.path.basename(path),
            "compiles_first": int(first["compiles"]),
            "compiles_second": int(second["compiles"]),
            "retrace_delta": int(second["compiles"] - first["compiles"]),
            "hits_second": int(second["hits"])}


def main(out_path: str) -> None:
    from repro.engine import dispatch
    dispatch.enable_persistent_cache()

    _parity_rows()                             # parity gate, never timed
    path = autotune.tuning_path()
    results = autotune.tune(smoke=True, n=3, path=path)
    doc = {}
    for kernel, r in results.items():
        counts = r.counts()
        doc[kernel] = {"bucket": r.bucket,
                       "default_us": round(r.default_us, 3),
                       "tuned_us": round(r.best_us, 3),
                       "speedup": round(r.speedup, 4),
                       "config": r.best.key(), "candidates": counts}
        print(f"[kernel-bench] {kernel}: default={r.default_us:.0f}us "
              f"tuned={r.best_us:.0f}us speedup={r.speedup:.2f}x "
              f"cfg={r.best.key()} (measured={counts['measured']} "
              f"pruned={counts['pruned']} "
              f"ineligible={counts['ineligible']})")

    doc["reload"] = _reload_acceptance(path)
    print(f"[kernel-bench] reload acceptance: cfg={doc['reload']['config']} "
          f"from {doc['reload']['tuning_file']}, retrace_delta="
          f"{doc['reload']['retrace_delta']}")

    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as fh:
        json.dump(doc, fh, indent=1, sort_keys=True)
    print(f"[kernel-bench] wrote {out_path}")


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else
         os.path.join("artifacts", "BENCH_kernel.json"))
