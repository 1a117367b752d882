"""Accuracy of the chip's emulated float64 against the host.

  python3 bench/probe_f64.py [--n 1048576] [--seed 0]

Evaluates erfc, log, exp, division, square root and multiplication in
float64 on the default device (the TPU emulates float64) and on the host
CPU, over seeded inputs in the ranges the characterization kernels use,
and prints the largest relative difference of each as one JSON line.
It needs a TPU, like ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import sys


def probe(n: int, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import jax.scipy.special as jsp
    import numpy as np

    rng = np.random.default_rng(seed)
    x = {"erfc": rng.uniform(-6.0, 6.0, n),       # z-scores of the model
         "log": rng.uniform(1e-6, 1e3, n),
         "exp": rng.uniform(-30.0, 5.0, n),
         "div": rng.uniform(0.1, 50.0, n),
         "sqrt": rng.uniform(0.0, 1e3, n),
         "mul": rng.uniform(-1e3, 1e3, n)}
    y = rng.uniform(0.1, 50.0, n)
    fns = {"erfc": lambda a, b: jsp.erfc(a), "log": lambda a, b: jnp.log(a),
           "exp": lambda a, b: jnp.exp(a), "div": lambda a, b: a / b,
           "sqrt": lambda a, b: jnp.sqrt(a), "mul": lambda a, b: a * b}
    cpu = jax.local_devices(backend="cpu")[0]
    out = {}
    with jax.enable_x64(True):
        for name, fn in fns.items():
            f = jax.jit(fn)
            dev = np.asarray(f(jnp.asarray(x[name]), jnp.asarray(y)))
            with jax.default_device(cpu):
                host = np.asarray(f(jnp.asarray(x[name]), jnp.asarray(y)))
            if dev.dtype != np.float64:
                raise RuntimeError(f"{name} did not run in float64")
            ok = host != 0
            out[name] = float(np.max(np.abs(dev[ok] - host[ok])
                                     / np.abs(host[ok])))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n", type=int, default=1 << 20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    import jax
    if jax.default_backend() != "tpu":
        print("probe_f64: JAX found no TPU", file=sys.stderr)
        return 1
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "max_rel_diff": probe(args.n, args.seed)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
