"""Shape-stable engine dispatch: bucketed padding, an AOT executable cache
and chunked megabatch execution.

Every engine entry point flattens its sweep grid into one leading batch
axis (the package convention) — but a *jit cache keyed on exact shapes*
means every new (D, V, T/P, R) grid retraces the kernel from scratch, and a
single resident ``[N, ...]`` plane bounds the population size by memory
rather than throughput.  This module gives every entry point
(``solve.simulate_batch``/``evaluate_batch``, ``population
.characterize_batch``, ``test1.run_batch``/``find_min_latency_batch``,
``controller.run_batched`` and the fleet cross-product
``fleet.run_fleet_batched``) one shared dispatch discipline:

1. **Shape bucketing** — the flat batch axis is padded up to the smallest
   canonical *bucket* (``n_devices * 2**k``, so every bucket stays divisible
   by the ``("batch",)`` mesh) and a boolean validity mask rides along so
   the kernels can zero the dead lanes in their reductions.  Arbitrary
   request shapes therefore hit a warm executable: the number of distinct
   traces is bounded by the bucket-ladder length, not the request stream.
2. **AOT executable cache** — kernels are compiled once per (entry point,
   bucket, static config) via ``jax.jit(...).lower(...).compile()`` and
   held in an explicit table with hit/compile counters (``stats()``), so
   retrace regressions are testable.  ``enable_persistent_cache()`` points
   JAX's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR`` (or
   the checkout's ``artifacts/jax_cache``) so repeated
   ``scripts/check.sh`` / benchmark runs pay XLA compilation once per
   machine.
3. **Chunked megabatch execution** — a request larger than the biggest
   bucket (or whose element footprint exceeds ``max_elements_resident``)
   streams through a ``lax.map`` over fixed-size chunks with the stacked
   inputs donated to the executable: per-chunk *in-jit intermediates*
   (e.g. the Test-1 random planes, generated in-jit from per-element key
   data — the dominant footprint of that sweep by ``words x (nplanes+4)``)
   never exist for more than one chunk at a time, so populations of
   thousands of simulated DIMMs become feasible.  Batched *inputs and
   outputs* still scale with N — they are carried/returned whole — so
   ``stats()["max_resident"]`` proxies the intermediate residency (the
   chunk), not total allocation; chunking pays off exactly where
   intermediates dwarf inputs/outputs (Test 1), and is asymptotically
   neutral where outputs dominate anyway (characterization's [N, F]
   maps).

Both dispatched paths are sliced back to the caller's N and are bit-exact
per element against the direct (unbucketed) calls, which every entry point
keeps as its parity reference (``dispatch="direct"``).  Their float64
outputs leave the executable as raw ``uint32`` words, which the host views
as float64 again: a TPU emulates float64, and converting it to IEEE bits
on the way to the host copies at a tenth of the float32 rate.

Host stages are timed by :func:`span`: each entry point's call, its
operand lowering, and here the host-to-device copies (``put``), the
compile, the blocking execution (``dispatch``) and the device-to-host
copies (``fetch``).  A span is a ``jax.profiler.TraceAnnotation`` named
``repro.<name>`` and a record in a bounded in-memory log
(:func:`spans`), stamped with ``time.perf_counter_ns`` and added to its
entry's :func:`stats` row, so a profiler trace and the counters tell the
same story without the profiler being on.

On a multi-device mesh both paths run the kernel under ``shard_map`` over
``"batch"``: every kernel is lane-local, so each device runs it on its own
slice of lanes and no operand is gathered (GSPMD refuses to partition the
Pallas custom calls inside the kernels).  Of the direct calls, the Test-1
/ hammer plane holds a Pallas kernel and runs under the same
:func:`lane_sharded` wrapper; the characterization, min-latency and
beat-error kernels are plain jnp, which GSPMD partitions, and the
controller's direct call runs unsharded on the default device.
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import dataclasses
import functools
import itertools
import os
import threading
import time
import typing
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch import mesh as mesh_lib

DEFAULT_MAX_BUCKET = 4096
# Footprint budget for one resident dispatch, in element-cost units (the
# caller's per-element word count): chunk * element_cost <= budget.
DEFAULT_MAX_ELEMENTS_RESIDENT = 1 << 27

# <checkout>/artifacts/jax_cache, from this file's location (src/repro/
# engine/dispatch.py), so every working directory shares one cache
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), "artifacts", "jax_cache")


@dataclasses.dataclass(frozen=True)
class DispatchConfig:
    """Per-call knobs; the defaults serve every in-repo sweep."""

    max_bucket: int = DEFAULT_MAX_BUCKET
    max_elements_resident: int = DEFAULT_MAX_ELEMENTS_RESIDENT


_LOCK = threading.Lock()
_EXECUTABLES: dict = {}
_KEY_LOCKS: dict = {}
_STATS: dict = {}

SPAN_PREFIX = "repro."
SPAN_LOG_SIZE = 65536


class SpanRecord(typing.NamedTuple):
    """One closed :func:`span`: ``name`` carries the ``repro.`` prefix of
    its trace event; times are ``time.perf_counter_ns()`` stamps."""

    id: int
    parent_id: int | None
    name: str
    start_ns: int
    end_ns: int
    attrs: dict


_SPANS: collections.deque = collections.deque(maxlen=SPAN_LOG_SIZE)
_SPANS_DROPPED = 0
_SPAN_IDS = itertools.count(1)
_SPAN_PARENT: contextvars.ContextVar = contextvars.ContextVar(
    "repro_span_parent", default=None)


# --------------------------------------------------------------------------
# Bucketing
# --------------------------------------------------------------------------
def bucket_ladder(n_devices: int = 1,
                  max_bucket: int = DEFAULT_MAX_BUCKET) -> tuple:
    """The canonical bucket sizes: ``n_devices * 2**k`` up to the smallest
    rung >= ``max_bucket``.  Every rung is divisible by the mesh, so the
    sharded flat axis never needs a device-count repad."""
    ladder, b = [], max(1, int(n_devices))
    while True:
        ladder.append(b)
        if b >= max_bucket:
            return tuple(ladder)
        b *= 2


def pick_bucket(n: int, ladder) -> int | None:
    """Smallest rung >= ``n``; None when ``n`` overflows the ladder (the
    chunked path takes over)."""
    for b in ladder:
        if b >= n:
            return b
    return None


def pad_axis(a: np.ndarray, n_to: int, axis: int = 0) -> np.ndarray:
    """Pad ``axis`` up to ``n_to`` by repeating the first slice (valid,
    finite values — padded lanes are masked/sliced off, never reduced)."""
    a = np.asarray(a)
    pad = n_to - a.shape[axis]
    if pad <= 0:
        return a
    first = np.take(a, [0], axis=axis)
    reps = [1] * a.ndim
    reps[axis] = pad
    return np.concatenate([a, np.tile(first, reps)], axis=axis)


# --------------------------------------------------------------------------
# AOT executable cache
# --------------------------------------------------------------------------
def _leaf_key(x):
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return (tuple(x.shape), str(x.dtype))
    return ("py", type(x).__name__, x)


def _stats_entry(entry: str) -> dict:
    return _STATS.setdefault(entry, {"calls": 0, "compiles": 0, "hits": 0,
                                     "chunked_calls": 0, "max_resident": 0,
                                     "lanes_total": 0,
                                     "padded_lanes_total": 0, "devices": 0,
                                     "compile_us_total": 0.0,
                                     "dispatch_us_total": 0.0,
                                     "dispatch_us_last": 0.0})


def stats(entry: str | None = None) -> dict:
    """Dispatch counters: per entry point ``calls`` / ``compiles`` (actual
    ``lower().compile()`` invocations = traces) / ``hits`` (warm-executable
    reuses) / ``chunked_calls`` / ``max_resident`` (largest resident flat
    batch actually materialized — the peak-memory proxy) /
    ``lanes_total`` (true lanes dispatched) / ``padded_lanes_total``
    (dead lanes added to fill the bucket or the last chunk) / ``devices``
    (the mesh size of the latest dispatch, a gauge) /
    ``compile_us_total`` (wall time of the ``lower().compile()`` calls) /
    ``dispatch_us_total`` and ``dispatch_us_last`` (blocking wall time of
    the compiled executions, cumulative and most-recent — compile time is
    excluded, so reuse *and* steady latency are separately inspectable).
    Every :func:`span` adds its wall time to ``<stage>_us_total`` and
    ``<stage>_us_last`` of its entry's row (``span("fleet.lower")`` ->
    ``stats("fleet")["lower_us_total"]``; the entry's own span is stage
    ``call``): ``lower`` (operand building on the host), ``put`` (padding
    and host-to-device copies), ``compile``, ``dispatch``, ``fetch``
    (device-to-host copies and the slice to N), and on row ``tables`` one
    stage per reliability policy.  ``wire_bytes_total`` counts the output
    bytes fetched as float64 words (:func:`dispatch_flat`).  :func:`spans`
    holds the spans themselves.
    Entries whose callers pass ``config_label`` (the engine paths that
    resolve an ``autotune.KernelConfig`` per dispatch) additionally report
    ``config_last`` (the label of the most recent call) and
    ``kernel_configs`` (every distinct label this entry has compiled
    against — the label also rides the caller's ``statics_key``, so each
    listed config corresponds to its own cached executable).  Gauges
    attached via :func:`record_gauge` (e.g. the serving front-end's
    queue depth) appear alongside the counters."""
    with _LOCK:
        if entry is not None:
            return dict(_stats_entry(entry))
        return {k: dict(v) for k, v in _STATS.items()}


def record_gauge(entry: str, **gauges) -> None:
    """Attach/update observability gauges on an entry's stats row (the
    serving front-end publishes ``queue_depth``/``queue_elements`` under
    entry ``"service"``).  ``reset_stats()`` clears gauges with everything
    else."""
    with _LOCK:
        _stats_entry(entry).update(gauges)


def reset_stats() -> None:
    """Clear every counter, gauge and the span log."""
    global _SPANS_DROPPED
    with _LOCK:
        _STATS.clear()
        _SPANS.clear()
        _SPANS_DROPPED = 0


@contextlib.contextmanager
def span(name: str, **attrs):
    """Time the ``with`` block as one host stage.

    ``name`` is ``<entry>`` or ``<entry>.<stage>``: the block runs under
    ``jax.profiler.TraceAnnotation("repro." + name, **attrs)``, its
    ``perf_counter_ns`` duration is added to ``<stage>_us_total`` (stage
    ``call`` for a bare entry) on the entry's :func:`stats` row, and a
    :class:`SpanRecord` goes to the log that :func:`spans` returns.  The
    parent is the span open in the caller's context (a
    ``contextvars.ContextVar``), so nesting holds per thread and per
    asyncio task; work handed to an executor keeps it only when run under
    ``contextvars.copy_context()``."""
    sid, parent, t0 = next(_SPAN_IDS), _SPAN_PARENT.get(), None
    token = _SPAN_PARENT.set(sid)
    try:
        # the stamps sit just inside the annotation: logging waits until
        # it has closed, so the trace event and the record differ by one
        # clock offset
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **attrs):
            t0 = time.perf_counter_ns()
            try:
                yield
            finally:
                t1 = time.perf_counter_ns()
    finally:
        _SPAN_PARENT.reset(token)
        if t0 is not None:
            _log(SpanRecord(sid, parent, SPAN_PREFIX + name, t0, t1, attrs))


def _log(record: SpanRecord) -> None:
    global _SPANS_DROPPED
    entry, _, stage = record.name[len(SPAN_PREFIX):].partition(".")
    key = (stage or "call") + "_us_"
    us = (record.end_ns - record.start_ns) / 1e3
    with _LOCK:
        if len(_SPANS) == _SPANS.maxlen:
            _SPANS_DROPPED += 1
        _SPANS.append(record)
        s = _stats_entry(entry)
        s[key + "total"] = s.get(key + "total", 0.0) + us
        s[key + "last"] = us


def spans() -> tuple:
    """``(records, dropped)``: the logged :class:`SpanRecord` s in the
    order they closed (at most :data:`SPAN_LOG_SIZE`, the oldest dropped
    first) and how many were dropped since :func:`reset_stats`."""
    with _LOCK:
        return list(_SPANS), _SPANS_DROPPED


def executables(entry: str) -> list:
    """The compiled executables cached for ``entry`` and its chunked form
    (``compiled.as_text()`` shows what each runs — e.g. whether a Pallas
    ``tpu_custom_call`` is inside)."""
    with _LOCK:
        return [c for k, c in _EXECUTABLES.items()
                if k[0] in (entry, entry + "/chunked")]


def clear_cache() -> None:
    """Drop every cached executable (tests use this to count fresh traces;
    the persistent on-disk cache, when enabled, still makes the recompiles
    cheap)."""
    with _LOCK:
        _EXECUTABLES.clear()
        _KEY_LOCKS.clear()


def aot_call(entry: str, fn, args: tuple, *, statics_key=(),
             donate: bool = False, resident: int | None = None,
             config_label: str | None = None):
    """Run ``fn(*args)`` through the AOT executable cache.

    ``fn`` must be jit-able with every static already closed over;
    ``statics_key`` distinguishes executables whose closed-over config
    differs at equal arg shapes.  The cache key is (entry, statics_key,
    arg treedef, every leaf's shape/dtype, x64 flag, donation) — exactly
    the trace key, so ``stats(entry)["compiles"]`` counts real retraces.

    ``config_label`` is observability only: callers that resolve a tuned
    kernel config per dispatch pass its label here so ``stats(entry)``
    reports which config each executable compiled against (the config must
    *also* ride ``statics_key`` — it changes the traced program).
    """
    flat, treedef = jax.tree.flatten(args)
    key = (entry, tuple(statics_key), treedef,
           tuple(_leaf_key(x) for x in flat),
           bool(jax.config.jax_enable_x64), bool(donate))
    with _LOCK:
        s = _stats_entry(entry)
        s["calls"] += 1
        if resident:
            s["max_resident"] = max(s["max_resident"], int(resident))
        if config_label is not None:
            s["config_last"] = config_label
            seen = s.setdefault("kernel_configs", ())
            if config_label not in seen:
                s["kernel_configs"] = seen + (config_label,)
        compiled = _EXECUTABLES.get(key)
        key_lock = _KEY_LOCKS.setdefault(key, threading.Lock())
    if compiled is None:
        # per-key lock: concurrent same-key callers wait for one compile
        # instead of duplicating it (and double-counting "compiles")
        with key_lock:
            with _LOCK:
                compiled = _EXECUTABLES.get(key)
            if compiled is None:
                jitted = jax.jit(_named(fn),
                                 donate_argnums=tuple(range(len(args)))
                                 if donate else ())
                with span(entry + ".compile"), warnings.catch_warnings():
                    warnings.filterwarnings(
                        "ignore",
                        message="Some donated buffers were not usable")
                    compiled = jitted.lower(*args).compile()
                with _LOCK:
                    _EXECUTABLES[key] = compiled
                    _stats_entry(entry)["compiles"] += 1
            else:
                with _LOCK:
                    _stats_entry(entry)["hits"] += 1
    else:
        with _LOCK:
            _stats_entry(entry)["hits"] += 1
    with span(entry + ".dispatch"):
        return jax.block_until_ready(compiled(*args))


def _named(fn):
    """``fn`` as jit will name its module: a ``functools.partial`` takes
    the name of the function it wraps (``jit__controller_flat_fn``, not
    ``jit__unknown``)."""
    if not isinstance(fn, functools.partial):
        return fn
    named = functools.partial(fn)         # flattens nested partials
    named.__name__ = named.func.__name__
    return named


# --------------------------------------------------------------------------
# The flat-batch dispatcher
# --------------------------------------------------------------------------
def _valid_mask(n: int, n_to: int) -> np.ndarray:
    return (np.arange(n_to) < n)


def lane_sharded(fn, mesh, n_batched: int, n_replicated: int,
                  lane_axis: int):
    """Run ``fn`` per device under ``shard_map``: the batched operands, the
    lane mask and every output split ``lane_axis`` over ``"batch"``; the
    replicated operands are whole on every device.  Argument order is the
    resident kernel's ``(*batched, *replicated, valid)`` for
    ``lane_axis == 0`` and the chunk stream's ``(*stacked, valid,
    *replicated)`` for ``lane_axis == 1``."""
    P = jax.sharding.PartitionSpec
    lanes = P(*([None] * lane_axis), "batch")
    if lane_axis == 0:
        specs = (lanes,) * n_batched + (P(),) * n_replicated + (lanes,)
    else:
        specs = (lanes,) * (n_batched + 1) + (P(),) * n_replicated
    return jax.shard_map(fn, mesh=mesh, in_specs=specs, out_specs=lanes,
                         check_vma=False)


def _chunk_fn(kernel, n_batched: int):
    """lax.map the flat kernel over the chunk axis of stacked inputs."""
    def fn(*args):
        batched, valid = args[:n_batched], args[n_batched]
        rep = args[n_batched + 1:]

        def one(xs):
            *b, v = xs
            return kernel(*b, *rep, v)
        return jax.lax.map(one, (*batched, valid))
    return fn


def dispatch_flat(entry: str, kernel, batched, replicated=(), *,
                  statics_key=(), mesh=None, element_cost: int = 1,
                  config: DispatchConfig | None = None,
                  mode: str = "auto",
                  config_label: str | None = None) -> dict:
    """Dispatch one flat-batch kernel call shape-stably.

    ``kernel(*batched, *replicated, valid)`` maps the leading (flat batch)
    axis of every array in ``batched`` elementwise; ``valid`` is a boolean
    [N_padded] lane mask the kernel threads to its reductions/outputs (dead
    lanes may hold arbitrary copies of lane 0).  ``replicated`` operands
    ride along unpadded.  Outputs must be a dict of arrays with the flat
    axis leading; they come back sliced to the true N.

    The flat axis is padded to the smallest bucket (``n_devices * 2**k``)
    so arbitrary N hit a warm executable; requests larger than the top
    bucket — or whose ``N * element_cost`` footprint exceeds
    ``config.max_elements_resident`` — run as a ``lax.map`` over fixed-size
    chunks with donated stacked inputs (peak memory O(chunk)).  With a
    multi-device ``mesh`` the resident flat axis is sharded over
    ``("batch",)`` exactly like the direct calls; bucket and chunk sizes
    are mesh-divisible by construction.

    ``mode``: "auto" (bucket, chunk on overflow), "bucketed", "chunked".
    The host stages run under :func:`span`: ``<entry>.put`` (padding and
    the copies to the device as far as the host waits for them), the
    executable's ``compile`` / ``dispatch`` (:func:`aot_call`) and
    ``<entry>.fetch`` (the copies back and the slice to N); ``put`` and
    ``fetch`` carry the bytes copied as attr ``bytes``.  ``put`` also
    carries the dispatch's shape, which the entry's :func:`stats` row
    sums: ``lanes`` (N), ``padded_lanes`` (the bucket, or ``chunks x
    chunk``, less N), ``devices`` (the mesh size) and ``chunks`` (1 for a
    resident bucket).  Float64 outputs cross as ``uint32`` words
    converted inside the executable; ``fetch`` carries their bytes as
    attr ``wire_bytes``.
    ``config_label`` is forwarded to :func:`aot_call` for stats reporting
    of the caller's resolved kernel-tuning config (see that docstring).
    """
    cfg = config or DispatchConfig()
    mesh = mesh_lib.make_batch_mesh() if mesh is None else mesh
    n_devices = int(mesh.devices.size)
    if n_devices > 1:
        # compiled executables are shard-committed: two meshes with equal
        # shapes must not share an executable
        statics_key = tuple(statics_key) + (
            "mesh", tuple(int(d.id) for d in mesh.devices.flat))
    batched = [np.asarray(a) for a in batched]
    n = batched[0].shape[0]
    ladder = bucket_ladder(n_devices, cfg.max_bucket)
    budget = max(cfg.max_elements_resident, int(element_cost) * ladder[0])
    fits = [b for b in ladder if b * element_cost <= budget]
    if mode == "bucketed":
        fits = list(ladder)
        if pick_bucket(n, fits) is None:
            raise ValueError(
                f"dispatch='bucketed' forced, but N={n} exceeds the top "
                f"bucket {fits[-1]}; use 'auto'/'chunked' or raise "
                "max_bucket")
    bucket = pick_bucket(n, fits) if mode != "chunked" else None

    kernel = _f64_as_words(kernel)
    if bucket is not None:
        with span(entry + ".put",
                  bytes=_put_bytes(batched, replicated, bucket),
                  **_count_lanes(entry, n, bucket, n_devices, 1)):
            args = tuple(jnp.asarray(pad_axis(a, bucket)) for a in batched) \
                + (jnp.asarray(_valid_mask(n, bucket)),)
            if n_devices > 1:
                args = tuple(
                    jax.device_put(a, mesh_lib.batch_sharding(mesh, a.ndim))
                    for a in args)
            rep = _replicate(replicated, mesh, n_devices)
        if n_devices > 1:
            kernel = lane_sharded(kernel, mesh, len(batched), len(rep), 0)
        out = aot_call(entry, kernel, args[:-1] + rep + args[-1:],
                       statics_key=statics_key, resident=bucket,
                       config_label=config_label)
        with _fetch_span(entry, out):
            return {k: v[:n] for k, v in _host_outputs(out).items()}

    # ---- chunked megabatch: lax.map over fixed-size chunks ---------------
    chunk = pick_bucket(n, fits) or fits[-1]
    k = -(-n // chunk)
    with span(entry + ".put",
              bytes=_put_bytes(batched, replicated, k * chunk),
              **_count_lanes(entry, n, k * chunk, n_devices, k)):
        stacked = tuple(
            jnp.asarray(pad_axis(a, k * chunk).reshape((k, chunk)
                                                       + a.shape[1:]))
            for a in batched)
        valid = jnp.asarray(_valid_mask(n, k * chunk).reshape(k, chunk))
        if n_devices > 1:
            put = lambda a: jax.device_put(
                a, mesh_lib.chunked_batch_sharding(mesh, a.ndim))
            stacked = tuple(put(a) for a in stacked)
            valid = put(valid)
        rep = _replicate(replicated, mesh, n_devices)
    with _LOCK:
        _stats_entry(entry)["chunked_calls"] += 1
    fn = _chunk_fn(kernel, len(stacked))
    if n_devices > 1:
        fn = lane_sharded(fn, mesh, len(stacked), len(rep), 1)
    out = aot_call(entry + "/chunked", fn,
                   stacked + (valid,) + rep, statics_key=statics_key,
                   donate=True, resident=chunk, config_label=config_label)
    with _fetch_span(entry, out):
        return {key: v.reshape((k * chunk,) + v.shape[2:])[:n]
                for key, v in _host_outputs(out).items()}


def _count_lanes(entry: str, n: int, lanes: int, n_devices: int,
                 chunks: int) -> dict:
    """Add a dispatch of ``n`` true lanes padded to ``lanes`` to the
    entry's counters; returns the ``put`` span's shape attrs."""
    with _LOCK:
        s = _stats_entry(entry)
        s["lanes_total"] += n
        s["padded_lanes_total"] += lanes - n
        s["devices"] = n_devices
    return {"lanes": n, "padded_lanes": lanes - n, "devices": n_devices,
            "chunks": chunks}


def _put_bytes(batched, replicated, lanes: int) -> int:
    """Bytes a dispatch copies to the device: the batched operands and
    the lane mask padded to ``lanes``, and the replicated operands."""
    per_lane = 1 + sum(a.itemsize * int(np.prod(a.shape[1:]))
                       for a in batched)
    return lanes * per_lane + sum(np.asarray(a).nbytes for a in replicated)


def _f64_as_words(kernel):
    """``kernel`` returning ``(same, rows, lanes)``: its float64 outputs as
    :func:`_f64_words`, the others untouched in ``same``.  A chip that
    emulates float64 turns it into IEEE bits inside the executable, where
    it is cheap, and not in the copy to the host, where it is not.  In
    ``rows`` an ``[..., F]`` output's words fill a last axis of ``2F``,
    which a TPU lays out row-major, so the host views the copy without
    another; ``lanes`` holds the ``[N]`` outputs as ``[N, 2]``.  Named
    after ``kernel`` and given its signature, so its module keeps its name
    and parameter names, and a kernel with no float64 output traces the
    program it traced before."""
    def fn(*args):
        same, rows, lanes = {}, {}, {}
        for k, v in kernel(*args).items():
            if v.dtype != jnp.float64:
                same[k] = v
            elif v.ndim == 1:
                lanes[k] = _f64_words(v)
            else:
                rows[k] = _f64_words(v).reshape(v.shape[:-1] + (-1,))
        return same, rows, lanes
    fn.__name__ = getattr(_named(kernel), "__name__", fn.__name__)
    fn.__wrapped__ = kernel
    return fn


_EXP_STEPS = (512, 256, 128, 64, 32, 16, 8, 4, 2, 1)


def _f64_words(x):
    """The IEEE-754 bits of float64 ``x`` as ``uint32[..., 2]``, low word
    first (a little-endian float64), so the host reads them back with
    ``.view(np.float64)``: a ``bitcast_convert_type`` where float64 is
    native, and :func:`_emulated_f64_words` on a TPU, whose XLA cannot
    bitcast the float64 it emulates."""
    return jax.lax.platform_dependent(
        x, tpu=_emulated_f64_words,
        default=lambda v: jax.lax.bitcast_convert_type(v, jnp.uint32))


def _emulated_f64_words(x):
    """:func:`_f64_words` by float64 arithmetic that IEEE float64 does
    exactly: scaling by powers of two, a subtraction of 1 from a value in
    [1, 2), ``floor`` and integer-valued converts.  A TPU's emulated
    float64 drops low bits in such arithmetic near the bottom of the
    float32 range: on a v5e the words equal the runtime's own copy for
    magnitudes above about 2**-86, and may lose low bits below.

    The exponent is found by ten halving steps; the 52-bit fraction is
    rounded to nearest even, which is a no-op for a true float64 and
    rounds an emulated value with more than 53 significant bits as the
    copy to the host would.  Infinities keep their bits; a NaN comes back as the quiet NaN of its
    sign; a zero as +0.0, which is what the copy makes of an emulated
    zero unless both of its float32 halves are -0.0 (only the high half's
    sign can be seen).  Subnormals are exact where the arithmetic keeps
    them (XLA:CPU flushes them to zero)."""
    a = jnp.abs(x)
    finite = jnp.isfinite(x)
    m = jnp.where(finite & (a > 0), a, 1.0)
    e = jnp.zeros(x.shape, jnp.int32)
    for k in _EXP_STEPS:                      # m = a * 2**-e in [1, 2)
        big = m >= 2.0 ** k
        m = jnp.where(big, m * 2.0 ** -k, m)
        e = e + jnp.where(big, k, 0)
    for k in _EXP_STEPS:
        small = m < 2.0 ** (1 - k)
        m = jnp.where(small, m * 2.0 ** k, m)
        e = e - jnp.where(small, k, 0)
    sub = e < -1022                           # a < 2**-1022: exponent 0
    frac = jnp.round(jnp.where(sub, a * 2.0 ** 1022 * 2.0 ** 52,
                               (m - 1.0) * 2.0 ** 52))
    carry = frac >= 2.0 ** 52                 # rounded up to the next power
    frac = jnp.where(carry, 0.0, frac)
    biased = jnp.where(sub, 0, e + 1023) + carry
    zero = a == 0
    biased = jnp.where(finite, jnp.where(zero, 0, biased), 2047)
    frac = jnp.where(finite & ~zero, frac,
                     jnp.where(jnp.isnan(x), 2.0 ** 51, 0.0))
    # the fraction as 20 + 16 + 16 bits: each piece converts exactly
    top20 = jnp.floor(frac * 2.0 ** -32)
    low32 = frac - top20 * 2.0 ** 32
    mid16 = jnp.floor(low32 * 2.0 ** -16)
    u = lambda v: v.astype(jnp.uint32)
    sign = jax.lax.bitcast_convert_type(x.astype(jnp.float32),
                                        jnp.uint32) & jnp.uint32(1 << 31)
    sign = jnp.where(zero, jnp.uint32(0), sign)
    hi = sign | (u(biased) << 20) | u(top20)
    lo = (u(mid16) << 16) | u(low32 - mid16 * 2.0 ** 16)
    return jnp.stack([lo, hi], axis=-1)


def _fetch_span(entry: str, out: tuple):
    """The ``<entry>.fetch`` span: attr ``bytes`` is every output byte
    copied back, ``wire_bytes`` the float64 ones that crossed as words
    (also added to the entry's ``wire_bytes_total``)."""
    same, rows, lanes = out
    wire = sum(int(v.nbytes) for v in (*rows.values(), *lanes.values()))
    with _LOCK:
        s = _stats_entry(entry)
        s["wire_bytes_total"] = s.get("wire_bytes_total", 0) + wire
    return span(entry + ".fetch", wire_bytes=wire,
                bytes=wire + sum(int(v.nbytes) for v in same.values()))


def _host_outputs(out: tuple) -> dict:
    """The executable's ``(same, rows, lanes)`` on the host, as one dict
    in key order, each run of ``uint32`` words viewed as the float64s it
    holds.  A narrow last axis arrives column-major from a TPU and is put
    in row order first (a copy of a small output)."""
    same, rows, lanes = out
    as_f64 = lambda w: np.ascontiguousarray(w).view(np.float64)
    host = {k: np.asarray(v) for k, v in same.items()}
    host.update((k, as_f64(v)) for k, v in rows.items())
    host.update((k, as_f64(v)[..., 0]) for k, v in lanes.items())
    return {k: host[k] for k in sorted(host)}


def _replicate(replicated, mesh, n_devices: int) -> tuple:
    rep = tuple(jnp.asarray(a) for a in replicated)
    if n_devices > 1:
        full = jax.sharding.NamedSharding(mesh,
                                          jax.sharding.PartitionSpec())
        rep = tuple(jax.device_put(a, full) for a in rep)
    return rep


# --------------------------------------------------------------------------
# Persistent compilation cache
# --------------------------------------------------------------------------
def enable_persistent_cache() -> str:
    """Point JAX's persistent compilation cache at
    ``$JAX_COMPILATION_CACHE_DIR`` when it is set, and otherwise at
    ``<checkout>/artifacts/jax_cache`` (:data:`DEFAULT_CACHE_DIR`, the same
    from any working directory), with the size/compile-time thresholds
    dropped to zero so every engine kernel persists.  Call it before the
    first compile.  Safe to call repeatedly; returns the directory, and
    raises when the directory cannot be made or JAX refuses the setting."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
