"""Measured kernel autotuning: roofline-pruned config search for the
``voltage_inject`` / ``sweep_solve`` kernels, persisted per machine.

The paper's methodology is one giant sweep, and every engine layer above
the kernels is shape-stable (bucketed dispatch, AOT executable cache,
coalescing service) — so a per-kernel win multiplies across the whole
fleet.  This module closes the ROADMAP "real-hardware Pallas tuning" item
in a backend-portable way:

- :class:`KernelConfig` makes the kernels' tiling knobs explicit (the
  Pallas row/lane block sizes and feature-packing width that used to be
  module constants) *and* gives the jnp oracle paths analogous knobs
  (``oracle_chunk``: a ``lax.map`` chunk over the flat batch axis;
  ``unroll``: the fixed-point ``lax.scan`` unroll factor) — so there is
  something real to tune on CPU, where the oracle is the production path.
  ``DEFAULTS`` reproduce today's module constants bit-for-bit.
- :func:`tune_kernel` enumerates candidates (:func:`candidate_configs`),
  prunes them with the roofline cost terms
  (:func:`repro.roofline.analyze.kernel_roofline` — a candidate whose
  padded-traffic lower bound already exceeds the incumbent's *measured*
  time is skipped unmeasured), then measures the survivors with
  :func:`measure` (explicit warmup + median-of-n): compiled Pallas
  executables on TPU/GPU, the compiled oracle variants on CPU.
- **Parity before eligibility:** every Pallas candidate must pass
  interpret-mode parity against the oracle before it may be measured, and
  every oracle variant must match the default oracle on the tuning inputs
  (bit-exact for ``voltage_inject`` — integer elementwise math — and
  <=1e-6 for ``sweep_solve``, where XLA's shape-dependent vectorization
  reorders float reductions).  A candidate that fails parity (or cannot
  build) is recorded ``ineligible`` and can never win.
- Winners persist to ``artifacts/tuning/TUNE_<backend>_<device_kind>.json``
  keyed by ``"<kernel>:<shape bucket>"`` (pow2-bucketed leading axis —
  the same bucketing idea as the dispatch ladder, so one tuned entry
  serves every nearby sweep size).

Engine consumption: tuned configs apply only when tuning is explicitly
enabled (:func:`enable` / ``REPRO_KERNEL_TUNING=1`` or ``=<path>``).  The
dispatched engine paths resolve :func:`active_config` per call and thread
the config into their dispatch ``statics_key`` (plus ``config_label`` on
the stats row), so tuned executables persist across runs via the existing
``artifacts/jax_cache`` and ``dispatch.stats()`` reports which config each
entry compiled against.  ``dispatch="direct"`` always runs the default
config — the parity reference stays pinned to today's bit-exact behavior.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import hw
from repro.kernels.sweep_solve import kernel as _ss_kernel
from repro.kernels.sweep_solve import ref as _ss_ref
from repro.kernels.voltage_inject import kernel as _vi_kernel

KERNELS = ("voltage_inject", "sweep_solve")
DEFAULT_TUNING_DIR = os.path.join("artifacts", "tuning")
ENV_VAR = "REPRO_KERNEL_TUNING"

# Full-search tuning shapes (the kernel benchmark's) and the tiny smoke
# shapes scripts/check.sh exercises on every run.
TUNE_SHAPES = {"voltage_inject": (512, 8192), "sweep_solve": (4096, 4)}
SMOKE_SHAPES = {"voltage_inject": (128, 1024), "sweep_solve": (1024, 4)}


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """One point in a kernel's tuning space (hashable — rides jit statics
    and dispatch ``statics_key`` tuples).

    ``row_block`` / ``lane_block`` parameterize the Pallas tiling.  For
    ``voltage_inject`` they are the block's rows x words.  For
    ``sweep_solve``, whose samples lie along the lanes, ``row_block`` is
    the largest number of rows of 128 samples a grid step takes (a batch
    under one block takes its own rows rounded up to 8:
    ``sweep_solve.kernel.block_rows``) and ``lane_block`` is the 128
    samples of a row, the only width the kernel takes.  ``oracle_chunk``
    chunks the jnp oracle's flat batch axis through ``lax.map`` (0 = whole
    batch, today's behavior);
    ``unroll`` is the ``sweep_solve`` oracle's fixed-point scan unroll
    (1 = today's behavior).  The per-kernel :data:`DEFAULTS` reproduce the
    pre-tuning module constants bit-for-bit.
    """

    kernel: str
    row_block: int = 8
    lane_block: int = 1024
    oracle_chunk: int = 0
    unroll: int = 1

    def key(self) -> str:
        """Short stable label used in tuning files, dispatch statics keys
        and ``dispatch.stats()`` rows."""
        return (f"r{self.row_block}.l{self.lane_block}"
                f".c{self.oracle_chunk}.u{self.unroll}")

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @staticmethod
    def from_dict(d: dict) -> "KernelConfig":
        return KernelConfig(**{k: d[k] for k in
                               ("kernel", "row_block", "lane_block",
                                "oracle_chunk", "unroll")})


DEFAULTS = {
    "voltage_inject": KernelConfig("voltage_inject",
                                   row_block=_vi_kernel.ROW_BLOCK,
                                   lane_block=_vi_kernel.WORD_BLOCK),
    "sweep_solve": KernelConfig("sweep_solve",
                                row_block=_ss_kernel.ROW_BLOCK,
                                lane_block=_ss_kernel.LANES),
}


def shape_bucket(kernel: str, shape) -> str:
    """Tuning-table key for a kernel call shape: pow2-bucketed leading
    (flat batch) axis + exact trailing width — ``(rows, words)`` for
    ``voltage_inject``, ``(B, C)`` for ``sweep_solve``."""
    n = max(1, int(shape[0]))
    trail = int(shape[1]) if len(shape) > 1 else 0
    b = 1 if n <= 1 else 1 << (n - 1).bit_length()
    return f"n{b}.t{trail}"


_BUCKET_RE = re.compile(r"^n(\d+)\.t(\d+)$")


# --------------------------------------------------------------------------
# Active-config state (what the engine consults per dispatch)
# --------------------------------------------------------------------------
_STATE = {"enabled": False, "path": None, "table": {}, "env_checked": False}


def enable(path: str | None = None) -> str:
    """Turn tuned configs on, (re)loading the tuning table from ``path``
    (default: this machine's :func:`tuning_path`).  A missing file enables
    with an empty table — every lookup falls back to the default config."""
    path = path or tuning_path()
    _STATE.update(enabled=True, path=path, table=load_configs(path),
                  env_checked=True)
    return path


def disable() -> None:
    """Back to default configs everywhere (the test-suite state)."""
    _STATE.update(enabled=False, path=None, table={}, env_checked=True)


def is_enabled() -> bool:
    _maybe_env_enable()
    return bool(_STATE["enabled"])


def _maybe_env_enable() -> None:
    if _STATE["env_checked"]:
        return
    _STATE["env_checked"] = True
    val = os.environ.get(ENV_VAR, "").strip()
    if not val or val in ("0", "false", "off"):
        return
    enable(None if val in ("1", "true", "on") else val)


def active_config(kernel: str, shape) -> KernelConfig:
    """The config the engine should run ``kernel`` with at ``shape``.

    Returns the persisted winner for the shape bucket when tuning is
    enabled (exact bucket first, else the same-kernel entry with the
    nearest leading-axis bucket — preferring a matching trailing width),
    and ``DEFAULTS[kernel]`` otherwise."""
    _maybe_env_enable()
    default = DEFAULTS[kernel]
    if not _STATE["enabled"]:
        return default
    table = _STATE["table"]
    want = f"{kernel}:{shape_bucket(kernel, shape)}"
    hit = table.get(want)
    if hit is not None:
        return hit
    m = _BUCKET_RE.match(want.split(":", 1)[1])
    want_n, want_t = int(m.group(1)), int(m.group(2))
    best, best_rank = None, None
    for key, cfg in table.items():
        k_kernel, _, bucket = key.partition(":")
        mb = _BUCKET_RE.match(bucket)
        if k_kernel != kernel or not mb:
            continue
        n, t = int(mb.group(1)), int(mb.group(2))
        rank = (t != want_t, abs(math.log2(n) - math.log2(want_n)), -n)
        if best_rank is None or rank < best_rank:
            best, best_rank = cfg, rank
    return best if best is not None else default


# --------------------------------------------------------------------------
# Persistence: artifacts/tuning/TUNE_<backend>_<device_kind>.json
# --------------------------------------------------------------------------
def tuning_path(directory: str = DEFAULT_TUNING_DIR,
                backend: str | None = None,
                device_kind: str | None = None) -> str:
    backend = backend or jax.default_backend()
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    kind = re.sub(r"[^A-Za-z0-9_.-]+", "_", str(device_kind)).lower()
    return os.path.join(directory, f"TUNE_{backend}_{kind}.json")


def save_configs(configs: dict, path: str | None = None,
                 extras: dict | None = None) -> str:
    """Merge ``{"<kernel>:<bucket>": KernelConfig}`` winners into the
    tuning file (existing entries for other buckets are kept).  ``extras``
    maps the same keys to JSON-able measurement metadata."""
    path = path or tuning_path()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    doc = {"backend": jax.default_backend(),
           "device_kind": str(jax.devices()[0].device_kind),
           "entries": {}}
    if os.path.exists(path):
        try:
            with open(path) as f:
                old = json.load(f)
            doc["entries"] = dict(old.get("entries", {}))
        except (OSError, ValueError):
            pass
    for key, cfg in configs.items():
        entry = {"config": cfg.to_dict()}
        if extras and key in extras:
            entry.update(extras[key])
        doc["entries"][key] = entry
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    return path


def load_configs(path: str | None = None) -> dict:
    """``{"<kernel>:<bucket>": KernelConfig}`` from a tuning file (empty
    dict when the file is missing or unreadable)."""
    path = path or tuning_path()
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return {}
    out = {}
    for key, entry in doc.get("entries", {}).items():
        try:
            out[key] = KernelConfig.from_dict(entry["config"])
        except (KeyError, TypeError):
            continue
    return out


# --------------------------------------------------------------------------
# Measurement (the corrected timing idiom — shared with kernel_bench)
# --------------------------------------------------------------------------
def measure(fn, args: tuple, n: int = 5, warmup: int = 2) -> float:
    """Median-of-``n`` blocking wall seconds of ``fn(*args)`` after
    ``warmup`` explicit warmup calls (the first pays trace+compile)."""
    for _ in range(max(1, warmup)):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(max(1, n)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def inject_inputs(rows: int, words: int, nplanes: int = 2, seed: int = 0,
                  prob: float = 0.01) -> tuple:
    """Synthetic ``voltage_inject`` operands (shared by the tuner and
    ``benchmarks/kernel_bench.py``)."""
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.bits(ks[0], (rows, words), dtype=jnp.uint32),
            jnp.full((rows,), prob, jnp.float32),
            jax.random.bits(ks[1], (rows, words), dtype=jnp.uint32),
            jax.random.bits(ks[2], (nplanes, rows, words), dtype=jnp.uint32))


def solve_inputs(b: int, c: int, seed: int = 3) -> tuple:
    """Synthetic ``sweep_solve`` operands at the paper's standard channel
    rates (the hoisted ``hw`` constants — shared with the benchmark)."""
    ks = jax.random.split(jax.random.key(seed), 4)
    tns = jnp.full((b,), hw.T_RCD_STD, jnp.float32)
    return (jax.random.uniform(ks[0], (b, c), minval=0.1, maxval=60.0),
            jax.random.uniform(ks[1], (b, c), minval=0.8, maxval=2.4),
            jax.random.uniform(ks[2], (b, c), minval=1.0, maxval=5.0),
            jax.random.uniform(ks[3], (b,), minval=0.4, maxval=0.9),
            jnp.full((b,), 4.0, jnp.float32),
            jnp.full((b,), 1.3, jnp.float32),
            tns, tns, tns * 2.5,
            jnp.full((b,), hw.LINE_TRANSFER_NS, jnp.float32),
            jnp.full((b,), hw.PEAK_BW_GBPS, jnp.float32))


def _tuning_inputs(kernel: str, shape, nplanes: int) -> tuple:
    if kernel == "voltage_inject":
        return inject_inputs(shape[0], shape[1], nplanes)
    return solve_inputs(shape[0], shape[1])


def _compiled(kernel: str, config: KernelConfig, backend: str):
    """jit wrapper running ``kernel`` under ``config`` on ``backend``'s
    production path (compiled Pallas on TPU/GPU, the oracle elsewhere)."""
    impl = "pallas" if backend in ("tpu", "gpu") else "reference"
    if kernel == "voltage_inject":
        from repro.kernels.voltage_inject import ops as vi_ops
        return jax.jit(functools.partial(vi_ops.inject, impl=impl,
                                         config=config))
    from repro.kernels.sweep_solve import ops as ss_ops
    return jax.jit(functools.partial(ss_ops.solve, impl=impl, config=config))


def _assert_parity(kernel: str, got, ref, label: str) -> None:
    """Oracle-variant parity vs the default config on the tuning inputs:
    bit-exact for the integer ``voltage_inject``, <=1e-6 for the float
    ``sweep_solve`` (XLA's shape-dependent vectorization tolerance)."""
    if kernel == "voltage_inject":
        if not np.array_equal(np.asarray(got), np.asarray(ref)):
            raise AssertionError(f"{label}: output not bit-exact vs default")
        return
    for k in ref:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(ref[k]),
                                   rtol=1e-6, atol=1e-6,
                                   err_msg=f"{label}: {k} drifted")


def _interpret_parity(kernel: str, config: KernelConfig) -> None:
    """Parity-before-eligibility for Pallas candidates: interpret mode vs
    the oracle on a reduced shape (bit-exact / <=1e-6)."""
    if kernel == "voltage_inject":
        from repro.kernels.voltage_inject import ops as vi_ops
        args = inject_inputs(2 * config.row_block + 3,
                             config.lane_block + 17, 2, seed=7)
        ref = vi_ops.inject(*args, impl="reference")
        got = vi_ops.inject(*args, impl="pallas_interpret", config=config)
        if not np.array_equal(np.asarray(got), np.asarray(ref)):
            raise AssertionError(f"{config.key()}: interpret parity failed")
        return
    from repro.kernels.sweep_solve import ops as ss_ops
    args = solve_inputs(2 * config.row_block * _ss_kernel.LANES + 3, 4,
                        seed=7)
    ref = ss_ops.solve(*args, impl="reference")
    got = ss_ops.solve(*args, impl="pallas_interpret", config=config)
    _assert_parity(kernel, got, ref, f"{config.key()} interpret")


# --------------------------------------------------------------------------
# Candidate enumeration + roofline pruning
# --------------------------------------------------------------------------
def candidate_configs(kernel: str, backend: str | None = None,
                      smoke: bool = False) -> tuple:
    """Candidate configs for ``kernel`` on ``backend`` (the default config
    is the incumbent and is not re-listed).  TPU/GPU candidates vary the
    Pallas tiling; CPU candidates vary the oracle knobs the XLA CPU
    backend actually responds to (scan unroll, batch chunking)."""
    backend = backend or jax.default_backend()
    rep = functools.partial(dataclasses.replace, DEFAULTS[kernel])
    if backend in ("tpu", "gpu"):
        if kernel == "voltage_inject":
            grid = ([(8, 512), (16, 1024)] if smoke else
                    [(r, w) for r in (8, 16, 32) for w in (512, 1024, 2048)])
            return tuple(rep(row_block=r, lane_block=w) for r, w in grid
                         if (r, w) != (8, 1024))
        cands = tuple(rep(row_block=r, lane_block=_ss_kernel.LANES)
                      for r in (8, 16, 32, 64)
                      if r != DEFAULTS[kernel].row_block)
        return cands[:1] if smoke else cands
    if kernel == "voltage_inject":
        chunks = (64, 128) if smoke else (32, 64, 128, 256)
        return tuple(rep(oracle_chunk=c) for c in chunks)
    if smoke:
        return tuple(rep(unroll=u) for u in (2, 5))
    return tuple([rep(unroll=u) for u in (2, 5, 8)]
                 + [rep(unroll=5, oracle_chunk=1024),
                    rep(oracle_chunk=2048)])


def _ceil_to(n: int, mult: int) -> int:
    mult = max(1, int(mult))
    return -(-int(n) // mult) * mult


def candidate_cost(config: KernelConfig, shape, *, nplanes: int = 2,
                   iters: int = _ss_ref.DEFAULT_ITERS) -> tuple:
    """(flops, bytes) a candidate must move at minimum, after the padding
    its blocks/chunks force — the roofline-pruning inputs.  Oracle
    candidates pad only the leading axis (to the chunk); ``voltage_inject``
    Pallas candidates pad both axes to their tile grid, ``sweep_solve``'s
    the batch to whole blocks of rows of 128 samples."""
    if config.kernel == "voltage_inject":
        r, w = int(shape[0]), int(shape[1])
        if config.oracle_chunk:
            r2, w2 = _ceil_to(r, config.oracle_chunk), w
        else:
            r2 = _ceil_to(r, config.row_block)
            w2 = _ceil_to(w, config.lane_block)
        # data + rand_word + nplanes + output planes of u32, + the prob row
        return 8.0 * r2 * w2, float((nplanes + 3) * r2 * w2 * 4 + r2 * 4)
    b, c = int(shape[0]), int(shape[1])
    if jax.default_backend() in ("tpu", "gpu"):
        # the lane-dense kernel pads B to whole blocks of row_block x 128
        b2 = _ceil_to(b, _ss_kernel.block_rows(b, config.row_block)
                      * _ss_kernel.LANES)
    else:
        b2 = _ceil_to(b, config.oracle_chunk or 1)
    # ~40 vector ops per damped iteration over the padded [B2, C] batch;
    # the 3C + 8 feature planes in and the C + 2 output planes out
    return 40.0 * b2 * c * iters, float((3 * c + 8 + c + 2) * 4 * b2)


@dataclasses.dataclass(frozen=True)
class CandidateResult:
    config: KernelConfig
    status: str                  # "measured" | "pruned" | "ineligible"
    measured_us: float           # NaN unless measured
    bound_us: float              # roofline lower bound
    note: str = ""


@dataclasses.dataclass(frozen=True)
class TuneResult:
    kernel: str
    bucket: str
    default_us: float
    best: KernelConfig
    best_us: float
    candidates: tuple

    @property
    def speedup(self) -> float:
        return self.default_us / self.best_us if self.best_us else 1.0

    def counts(self) -> dict:
        c = {"measured": 0, "pruned": 0, "ineligible": 0}
        for r in self.candidates:
            c[r.status] = c.get(r.status, 0) + 1
        return c


def tune_kernel(kernel: str, shape, *, candidates=None, smoke: bool = False,
                n: int = 5, spec=None, nplanes: int = 2) -> TuneResult:
    """Roofline-pruned measured search for one kernel at one shape.

    The default config is measured first (the incumbent); a candidate is
    pruned when its roofline lower bound cannot beat the best measured
    time so far *and* it moves strictly more padded traffic than the
    default (a measured incumbent can legitimately beat its own bound on
    a host whose spec constants are pessimistic — same-traffic candidates
    must still be measured, not pruned on a miscalibrated bound).
    Survivors are checked for parity (see module docstring — failures are
    ``ineligible``), then measured with :func:`measure`.  Only parity-clean
    measured candidates can become ``best``.
    """
    backend = jax.default_backend()
    if spec is None:
        spec = (hw.HOST_CPU if backend == "cpu"
                else hw.device_spec(jax.devices()[0].device_kind))
    from repro.roofline import analyze
    args = _tuning_inputs(kernel, shape, nplanes)
    default = DEFAULTS[kernel]
    base_fn = _compiled(kernel, default, backend)
    ref_out = jax.block_until_ready(base_fn(*args))
    default_s = measure(base_fn, args, n=n)
    best, best_s = default, default_s
    d_flops, d_bytes = candidate_cost(default, shape, nplanes=nplanes)
    default_bound_s = analyze.kernel_roofline(d_flops, d_bytes, spec).bound_s

    results = []
    for cfg in (candidates if candidates is not None
                else candidate_configs(kernel, backend, smoke)):
        flops, bytes_ = candidate_cost(cfg, shape, nplanes=nplanes)
        bound_s = analyze.kernel_roofline(flops, bytes_, spec).bound_s
        if bound_s > best_s and bound_s > default_bound_s * 1.001:
            results.append(CandidateResult(
                cfg, "pruned", math.nan, bound_s * 1e6,
                f"bound {bound_s * 1e6:.0f}us > incumbent "
                f"{best_s * 1e6:.0f}us"))
            continue
        try:
            if backend in ("tpu", "gpu"):
                _interpret_parity(kernel, cfg)       # before eligibility
            fn = _compiled(kernel, cfg, backend)
            out = jax.block_until_ready(fn(*args))
            _assert_parity(kernel, out, ref_out, cfg.key())
        except Exception as e:  # noqa: BLE001 — candidate, not tuner, fault
            results.append(CandidateResult(
                cfg, "ineligible", math.nan, bound_s * 1e6,
                f"{type(e).__name__}: {e}"))
            continue
        t = measure(fn, args, n=n)
        results.append(CandidateResult(cfg, "measured", t * 1e6,
                                       bound_s * 1e6))
        if t < best_s:
            best, best_s = cfg, t
    return TuneResult(kernel, shape_bucket(kernel, shape), default_s * 1e6,
                      best, best_s * 1e6, tuple(results))


def tune(kernels=KERNELS, shapes: dict | None = None, *, smoke: bool = False,
         n: int = 5, path: str | None = None, save: bool = True) -> dict:
    """Tune every kernel in ``kernels`` and (by default) persist the
    winners to the machine's tuning file.  Returns
    ``{kernel: TuneResult}``."""
    shapes = shapes or (SMOKE_SHAPES if smoke else TUNE_SHAPES)
    results = {k: tune_kernel(k, shapes[k], smoke=smoke, n=n)
               for k in kernels}
    if save:
        configs = {f"{r.kernel}:{r.bucket}": r.best
                   for r in results.values()}
        extras = {f"{r.kernel}:{r.bucket}": {
            "default_us": round(r.default_us, 3),
            "tuned_us": round(r.best_us, 3),
            "speedup": round(r.speedup, 4),
            "counts": r.counts(),
        } for r in results.values()}
        save_configs(configs, path, extras)
    return results
