"""Streaming fleet service: async request coalescing over the warm engine.

The dispatch layer (:mod:`repro.engine.dispatch`) made every entry point
shape-stable — mesh-divisible buckets, warm AOT executables, lane masks —
but callers still pay one dispatch round-trip per request.  A fleet
deployment serves a continuous stream of characterization / min-latency /
controller queries from many tenants, and those queries are exactly the
kind of work the buckets were built to pack: per-lane independent, shape
compatible within an entry point, indifferent to batch composition.

:class:`EngineService` is the coalescing front-end:

- ``await service.submit(request)`` lowers the request to the per-lane
  operands of its engine kernel and parks it in a *coalescing group* keyed
  by everything that must match for lanes to share one dispatch (entry
  point, replicated-operand bytes, statics).  A group flushes when either
  trigger fires: the **batching window** (``ServiceConfig.window_s``) or
  the **size trigger** (enough pending lanes to fill the largest bucket
  that fits the resident budget, capped by ``max_batch_lanes``).
- A flush concatenates the pending per-lane arrays into one megabatch,
  runs it through :func:`repro.engine.dispatch.dispatch_flat` on a single
  worker thread (the same entry names and kernels as the batch APIs, so
  executables are shared both ways), slices the outputs back per request
  and resolves each caller's future.
- **Bit-exactness**: every lowered lane depends only on its own
  (module, voltage, temperature) / (workload, DIMM) coordinates — the
  lowering helpers (``test1.min_latency_inputs``,
  ``population.characterize_inputs``, ``controller.flat_operands``) are
  the exact code the batch APIs run, and the kernels reduce only within a
  lane — so a coalesced lane is bit-identical to the same request served
  alone, which is in turn the dispatch layer's bit-exact contract against
  ``dispatch="direct"``.  Precisely: the float64 entry points
  (min-latency, characterize) and the fleet controller's voltage
  *selections* are bit-exact regardless of batch composition; the fleet's
  float32 derived metrics agree to XLA's shape-dependent vectorization
  tolerance (~1e-6 relative across bucket rungs — the batch API exhibits
  the identical drift across compositions, coalescing adds none).
- **Admission control**: every admitted request reserves
  ``lanes x element_cost`` against ``ServiceConfig.max_queue_elements``
  (default: the dispatch layer's ``max_elements_resident`` budget).  Past
  the budget, ``admission="shed"`` fails fast with
  :class:`AdmissionError`; ``admission="queue"`` suspends the caller until
  completed work frees budget.  A single request larger than the whole
  budget is always refused — it could never be admitted.  Oversized
  *flushes* never OOM regardless: the dispatch layer streams them in
  chunks under the same ``max_elements_resident``.
- **Live tables**: fleet requests resolve their per-DIMM safe-voltage
  table rows *at flush time* from the service's registry
  (``install_tables`` / ``drop_table``).  Dropping a DIMM mid-stream —
  the :class:`repro.engine.fleet.FleetTables` failure-injection scenario —
  fails that DIMM's queued and future requests fast with
  :class:`TableUnavailableError` while every other lane in the same
  megabatch completes bit-exact; re-deriving the table via
  ``fleet.build_tables`` + ``install_tables`` restores service without a
  restart.  The registry is keyed by *policy stack*
  (``FleetTables.policy_stack`` identity, or an explicit ``stack=`` name):
  several table sets — ECC-on vs ECC-off admission, a temperature
  excursion — stay installed side by side, and each
  :class:`FleetRequest` picks one via ``policy_stack`` (None = the default
  stack).  Requests against different stacks coalesce into the same
  megabatch whenever their candidate grids agree, since table rows are
  per-lane operands, never statics.

``run_request`` serves one request synchronously through the same lowering
(one dispatch per request) — the request-at-a-time baseline the coalescing
path is benchmarked against (``benchmarks/serve_bench.py``).

Threading note: dispatches run on one worker thread, which serializes
the service's own dispatches.  The float64 entry points enter
``jax.enable_x64(True)`` on that thread; JAX's config contexts are
thread-local, so callers on other threads keep their own setting.
"""
from __future__ import annotations

import asyncio
import contextvars
import dataclasses
import functools
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np

from repro import power as power_lib
from repro.engine import controller
from repro.engine import dispatch as dispatch_lib
from repro.engine import fleet as fleet_lib
from repro.engine import population
from repro.engine import solve as engine_solve
from repro.engine import test1 as engine_test1
from repro.engine.batch import WorkloadBatch
from repro.engine.population import DimmGrid


class ServiceError(Exception):
    """Base class for typed serving failures."""


class TableUnavailableError(ServiceError):
    """A fleet request named a DIMM whose safe-voltage table is not (or no
    longer) installed — fail fast; unrelated lanes are unaffected."""

    def __init__(self, module: str, detail: str = "no table installed"):
        super().__init__(f"DIMM {module!r}: {detail}")
        self.module = module


class AdmissionError(ServiceError):
    """The request was refused by admission control (queue budget)."""


# --------------------------------------------------------------------------
# Requests
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class MinLatencyRequest:
    """Section 4.2 latency search for one DIMM over a voltage grid.
    Result: float64 [V, 2] (tRCD, tRP), NaN pairs = unrecoverable."""

    module: str
    voltages: tuple
    step: float = 2.5
    max_latency: float = 20.0
    temp_c: float = 20.0


@dataclasses.dataclass(frozen=True)
class CharacterizeRequest:
    """Secs. 4-5 characterization of one DIMM over a V x T grid.  Result:
    dict of float64 arrays keyed/shaped like the single-DIMM slice of
    :class:`repro.engine.population.CharacterizationBatch`."""

    module: str
    voltages: tuple
    temps: tuple = (20.0,)
    patterns: tuple = ("0xaa",)
    retention_ms: tuple = population.RETENTION_GRID_MS
    t_rcd: float = 10.0
    t_rp: float = 10.0


@dataclasses.dataclass(frozen=True)
class FleetRequest:
    """Voltron interval controller over a W workloads x D DIMMs slice of
    the fleet.  Result: :class:`repro.engine.fleet.FleetBatchResult`."""

    workloads: tuple
    modules: tuple
    n_intervals: int = 8
    target_loss_pct: float = 5.0
    interval_cycles: int | None = None
    phase_seed: int | None = None
    phase_amplitude: float = 0.15
    # Per-(workload, DIMM) phase decorrelation: each lane draws its own
    # schedule via voltron.fleet_phase_matrix instead of every DIMM
    # repeating the workload's shared column.
    decorrelate_phases: bool = False
    # Optional repro.power device-model override for every lane of this
    # request; None uses each DIMM's installed table model.
    device_model: str | None = None
    # Which installed table stack serves this request: a name passed to
    # (or derived by) ``install_tables``.  None = the service's default
    # stack.  Lets ECC-on / ECC-off / temperature-excursion table sets
    # coexist mid-stream — requests against different stacks still
    # coalesce into one megabatch when their candidate grids agree,
    # because table rows are per-lane operands, never statics.
    policy_stack: str | None = None


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    """Coalescing / admission knobs.

    ``window_s``: max time a request waits for lane-mates before its group
    flushes.  ``max_batch_lanes``: size trigger — a group with this many
    pending lanes flushes immediately (also the prewarm bound).
    ``max_elements_resident``: the dispatch resident budget for flushed
    megabatches (oversized flushes stream in chunks).
    ``admission``: "shed" fails over-budget submits fast, "queue" suspends
    them until budget frees.  ``max_queue_elements``: admission budget in
    element-cost units (default: ``max_elements_resident``)."""

    window_s: float = 0.002
    max_batch_lanes: int = 1024
    max_elements_resident: int = dispatch_lib.DEFAULT_MAX_ELEMENTS_RESIDENT
    admission: str = "shed"
    max_queue_elements: int | None = None

    def __post_init__(self):
        if self.admission not in ("shed", "queue"):
            raise ValueError(f"unknown admission {self.admission!r}")


# --------------------------------------------------------------------------
# Lowered form
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class _GroupSpec:
    """Everything a flush needs that is shared by the whole group.

    ``config_label`` carries the resolved kernel-tuning config label (when
    the lowering consulted ``autotune.active_config``) so the dispatch
    stats row reports it; the config itself rides ``statics_key``."""

    entry: str
    kernel: object
    replicated: tuple
    statics_key: tuple
    element_cost: int
    x64: bool
    config_label: str | None = None


@dataclasses.dataclass(frozen=True)
class _Lowered:
    key: tuple            # coalescing key (hashable)
    spec: _GroupSpec
    n_lanes: int
    resolve: object       # () -> list of per-lane arrays (flush time)
    postprocess: object   # dict of sliced [n_lanes, ...] arrays -> result


class _Group:
    __slots__ = ("spec", "pending", "lanes", "timer")

    def __init__(self, spec):
        self.spec = spec
        self.pending = []     # [(lowered, future, cost)]
        self.lanes = 0
        self.timer = None


@dataclasses.dataclass(frozen=True)
class _TableRow:
    vendor: str
    timings: np.ndarray        # [K, 3]
    valid: np.ndarray          # [K]
    lat_feat: np.ndarray       # [K-1]
    hammer_margin: np.ndarray  # [K]; NaN where min-latency excluded
    model: str = "ddr3l"       # repro.power device-model name
    # reliability-transparency rows ([K] each; None when the stack that
    # built the tables had no ECC policy)
    correctable: np.ndarray | None = None
    detectable: np.ndarray | None = None
    silent: np.ndarray | None = None


@dataclasses.dataclass
class _StackTables:
    """One installed table set: the per-module rows of a policy stack plus
    the candidate grid they were built against."""

    cand_v: np.ndarray
    rows: dict                 # module -> _TableRow
    policy_stack: tuple = ()   # FleetTables.policy_stack descriptors


# --------------------------------------------------------------------------
# The service
# --------------------------------------------------------------------------
class EngineService:
    """Async coalescing front-end over the warm engine (module docstring
    has the full contract).  ``grid`` scopes characterization / min-latency
    requests; ``workloads`` (``[(name, cores), ...]``) and ``tables``
    (:class:`repro.engine.fleet.FleetTables`) scope fleet requests."""

    def __init__(self, grid: DimmGrid, *, tables=None, workloads=(),
                 model=None, config: ServiceConfig | None = None, mesh=None):
        self.config = config or ServiceConfig()
        self._grid = grid
        self._workloads = dict(workloads)
        self._model = model
        self._mesh = mesh
        self._n_devices = 1 if mesh is None else int(mesh.devices.size)
        self._stacks: dict = {}            # stack name -> _StackTables
        self._default_stack: str | None = None
        self._feat_rows: dict = {}
        self._lane_cache: dict = {}
        if tables is not None:
            self.install_tables(tables)

        self._groups: dict = {}
        self._tasks: set = set()
        self._waiters: list = []
        self._executor = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="engine-service")
        self._queued_elements = 0
        self._depth = 0
        self._stats = {"submitted": 0, "completed": 0, "failed": 0,
                       "shed": 0, "flushes": 0, "flushed_lanes": 0,
                       "max_flush_lanes": 0, "max_queue_depth": 0,
                       "max_queued_elements": 0}

    @property
    def workload_names(self) -> tuple:
        return tuple(self._workloads)

    @property
    def table_modules(self) -> tuple:
        st = self._stacks.get(self._default_stack)
        return tuple(st.rows) if st is not None else ()

    @property
    def table_stacks(self) -> tuple:
        """Names of every installed table stack (the default stack first)."""
        names = list(self._stacks)
        if self._default_stack in names:
            names.remove(self._default_stack)
            names.insert(0, self._default_stack)
        return tuple(names)

    # -- table registry (live swap / failure injection) --------------------
    def install_tables(self, tables, stack: str | None = None, *,
                       make_default: bool = True) -> str:
        """Install/replace per-DIMM safe-voltage table rows from a
        :class:`repro.engine.fleet.FleetTables` (e.g. re-derived via
        ``fleet.build_tables`` after a mid-stream drop).

        ``stack`` names the table set; None derives the name from the
        tables' own ``policy_stack`` identity.  Installing into an existing
        stack with the same candidate grid merges the rows (per-module
        replacement — the historical single-registry behavior); a different
        ``cand_v`` replaces the stack wholesale and stales its queued fleet
        requests.  ``make_default`` (default True) points requests that
        carry no ``FleetRequest.policy_stack`` at this stack; pass False to
        install a scenario stack (ECC-on, a temperature excursion) beside
        the live default.  Returns the stack name.
        """
        name = stack if stack is not None else tables.stack_name
        cand_v = np.asarray(tables.cand_v, np.float64)
        st = self._stacks.get(name)
        if st is None or st.cand_v.tobytes() != cand_v.tobytes():
            st = _StackTables(cand_v, {}, tuple(tables.policy_stack))
            self._stacks[name] = st
        row = lambda a, i: None if a is None else a[i]
        for i, module in enumerate(tables.modules):
            st.rows[module] = _TableRow(
                tables.vendors[i], tables.timings[i], tables.valid[i],
                tables.lat_feat[i], tables.hammer_margin[i],
                tables.device_models[i],
                correctable=row(tables.correctable, i),
                detectable=row(tables.detectable, i),
                silent=row(tables.silent, i))
        if make_default or self._default_stack is None:
            self._default_stack = name
        return name

    def drop_table(self, module: str, stack: str | None = None) -> None:
        """Drop one DIMM's table mid-stream (failure injection): queued
        and future fleet requests naming it fail fast with
        :class:`TableUnavailableError`; other lanes are unaffected.
        ``stack`` limits the drop to one table stack; None (the default)
        drops the DIMM from every installed stack."""
        targets = (self._stacks.values() if stack is None
                   else filter(None, [self._stacks.get(stack)]))
        for st in targets:
            st.rows.pop(module, None)

    # -- observability -----------------------------------------------------
    def stats(self) -> dict:
        out = dict(self._stats)
        out["queue_depth"] = self._depth
        out["queued_elements"] = self._queued_elements
        return out

    def _record_gauges(self) -> None:
        self._stats["max_queue_depth"] = max(
            self._stats["max_queue_depth"], self._depth)
        self._stats["max_queued_elements"] = max(
            self._stats["max_queued_elements"], self._queued_elements)
        dispatch_lib.record_gauge("service", queue_depth=self._depth,
                                  queue_elements=self._queued_elements)

    # -- submission --------------------------------------------------------
    async def submit(self, request):
        """Serve one request through the coalescer; returns its result (or
        raises its typed error).  Concurrency is the whole point: many
        concurrent ``submit`` calls inside one batching window share one
        dispatch."""
        low = self._lower(request)
        cost = low.n_lanes * low.spec.element_cost
        budget = self.config.max_queue_elements \
            or self.config.max_elements_resident
        if cost > budget:
            raise AdmissionError(
                f"request needs {cost} resident elements; the admission "
                f"budget is {budget} — it can never be admitted")
        if self._queued_elements + cost > budget \
                and self.config.admission == "shed":
            self._stats["shed"] += 1
            raise AdmissionError(
                f"queue at {self._queued_elements}/{budget} elements; "
                f"request for {cost} more shed")
        while self._queued_elements + cost > budget:
            ev = asyncio.Event()
            self._waiters.append(ev)
            await ev.wait()
        self._queued_elements += cost
        self._depth += 1
        self._stats["submitted"] += 1

        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        g = self._groups.get(low.key)
        if g is None:
            g = self._groups[low.key] = _Group(low.spec)
        g.pending.append((low, fut, cost))
        g.lanes += low.n_lanes
        self._record_gauges()
        if g.lanes >= self._flush_target(low.spec):
            self._flush(low.key)
        elif g.timer is None:
            g.timer = loop.call_later(self.config.window_s, self._flush,
                                      low.key)
        return await fut

    def run_request(self, request, *, mode: str = "auto"):
        """Serve one request synchronously: same lowering, one dispatch —
        the request-at-a-time baseline (and the warm path tests compare
        the coalesced results against)."""
        low = self._lower(request)
        out = self._run_dispatch(low.spec, low.resolve(), mode)
        return low.postprocess(out)

    async def drain(self) -> None:
        """Flush every pending group and wait for in-flight work."""
        while self._groups or self._tasks:
            for key in list(self._groups):
                self._flush(key)
            if self._tasks:
                await asyncio.gather(*list(self._tasks),
                                     return_exceptions=True)

    async def aclose(self) -> None:
        await self.drain()
        self._executor.shutdown(wait=True)

    async def __aenter__(self) -> "EngineService":
        return self

    async def __aexit__(self, *exc) -> None:
        await self.aclose()

    def prewarm(self, requests, max_lanes: int | None = None) -> None:
        """Compile every bucket the coalescer can produce for these request
        shapes (one executable per (entry, rung) up to ``max_lanes``,
        default 2x the flush target — a flush can overshoot the size
        trigger by one request), so a serving run never pays XLA
        compilation inside a latency window."""
        seen = set()
        for request in requests:
            low = self._lower(request)
            if low.key in seen:
                continue
            seen.add(low.key)
            arrays = low.resolve()
            cap = max_lanes or 2 * self._flush_target(low.spec)
            ladder = dispatch_lib.bucket_ladder(self._n_devices)
            for rung in [b for b in ladder if b <= cap]:
                reps = -(-rung // low.n_lanes)
                big = [np.concatenate([a] * reps, axis=0)[:rung]
                       for a in arrays]
                self._run_dispatch(low.spec, big, "auto")

    # -- coalescing / flush ------------------------------------------------
    def _flush_target(self, spec: _GroupSpec) -> int:
        ladder = dispatch_lib.bucket_ladder(self._n_devices)
        fits = [b for b in ladder
                if b * spec.element_cost <= self.config.max_elements_resident]
        target = fits[-1] if fits else ladder[0]
        return max(1, min(target, self.config.max_batch_lanes))

    def _flush(self, key) -> None:
        g = self._groups.pop(key, None)
        if g is None:            # already flushed by the other trigger
            return
        if g.timer is not None:
            g.timer.cancel()
        task = asyncio.ensure_future(self._run_group(g))
        self._tasks.add(task)
        task.add_done_callback(self._tasks.discard)

    async def _run_group(self, g: _Group) -> None:
        live, arrays = [], []
        for low, fut, cost in g.pending:
            try:
                arrays.append(low.resolve())
                live.append((low, fut, cost))
            except Exception as e:      # noqa: BLE001 — typed, per-lane
                self._finish(fut, cost, error=e)
        if not live:
            return
        batched = [np.concatenate([a[i] for a in arrays], axis=0)
                   for i in range(len(arrays[0]))]
        self._stats["flushes"] += 1
        self._stats["flushed_lanes"] += batched[0].shape[0]
        self._stats["max_flush_lanes"] = max(
            self._stats["max_flush_lanes"], batched[0].shape[0])
        loop = asyncio.get_running_loop()
        try:
            # in this task's context, so the dispatch spans keep their parent
            out = await loop.run_in_executor(
                self._executor, contextvars.copy_context().run,
                self._run_dispatch, g.spec, batched, "auto")
        except Exception as e:          # noqa: BLE001 — fail every lane
            for low, fut, cost in live:
                self._finish(fut, cost, error=e)
            return
        ofs = 0
        for low, fut, cost in live:
            sl = {k: v[ofs:ofs + low.n_lanes] for k, v in out.items()}
            ofs += low.n_lanes
            try:
                self._finish(fut, cost, result=low.postprocess(sl))
            except Exception as e:      # noqa: BLE001
                self._finish(fut, cost, error=e)

    def _finish(self, fut, cost: int, *, result=None, error=None) -> None:
        self._queued_elements -= cost
        self._depth -= 1
        if error is not None:
            self._stats["failed"] += 1
            if not fut.done():
                fut.set_exception(error)
        else:
            self._stats["completed"] += 1
            if not fut.done():
                fut.set_result(result)
        for ev in self._waiters:
            ev.set()
        self._waiters.clear()
        self._record_gauges()

    def _run_dispatch(self, spec: _GroupSpec, batched, mode: str) -> dict:
        cfg = dispatch_lib.DispatchConfig(
            max_elements_resident=self.config.max_elements_resident)

        def call():
            return dispatch_lib.dispatch_flat(
                spec.entry, spec.kernel, batched, spec.replicated,
                statics_key=spec.statics_key, mesh=self._mesh,
                element_cost=spec.element_cost, config=cfg, mode=mode,
                config_label=spec.config_label)

        if spec.x64:
            with jax.enable_x64(True):
                return call()
        return call()

    # -- lowering ----------------------------------------------------------
    def _lower(self, request) -> _Lowered:
        if isinstance(request, MinLatencyRequest):
            return self._lower_min_latency(request)
        if isinstance(request, CharacterizeRequest):
            return self._lower_characterize(request)
        if isinstance(request, FleetRequest):
            return self._lower_fleet(request)
        raise TypeError(f"unknown request type {type(request).__name__}")

    def _subgrid(self, module: str) -> DimmGrid:
        if module not in self._grid.modules:
            raise ServiceError(f"DIMM {module!r} is not in the service's "
                               "characterization grid")
        return self._grid.select([module])

    def _minlat_lane(self, module: str, v: float, step: float,
                     max_latency: float, temp_c: float) -> tuple:
        """One (module, voltage) min-latency lane's operands, memoized —
        lanes are bit-independent per voltage (verified against the
        batched lowering), so steady-state serving concatenates cached
        lanes instead of re-deriving the eager float64 thresholds."""
        key = (module, float(v), float(step), float(max_latency),
               float(temp_c))
        arrs = self._lane_cache.get(key)
        if arrs is None:
            if len(self._lane_cache) > 65536:
                self._lane_cache.clear()
            inputs, _ = engine_test1.min_latency_inputs(
                self._grid.select([module]), np.array([float(v)]),
                step=step, max_latency=max_latency, temp_c=temp_c)
            arrs = tuple(np.asarray(a) for a in inputs)
            self._lane_cache[key] = arrs
        return arrs

    def _lower_min_latency(self, req: MinLatencyRequest) -> _Lowered:
        self._subgrid(req.module)            # validate the module early
        v = np.atleast_1d(np.asarray(req.voltages, np.float64))
        lat = np.arange(10.0, float(req.max_latency) + 1e-9, float(req.step))
        spec = _GroupSpec("min_latency", engine_test1._min_latency_flat_fn,
                          (lat,), (), 8 * lat.size * lat.size, True)
        key = ("min_latency", float(req.temp_c), lat.tobytes())

        def resolve():
            parts = [self._minlat_lane(req.module, vv, req.step,
                                       req.max_latency, req.temp_c)
                     for vv in v]
            return [np.concatenate([p[i] for p in parts], axis=0)
                    for i in range(len(parts[0]))]

        def post(out):
            return np.asarray(out["lat"], np.float64).reshape(v.size, 2)

        return _Lowered(key, spec, v.size, resolve, post)

    def _lower_characterize(self, req: CharacterizeRequest) -> _Lowered:
        sub = self._subgrid(req.module)
        v = np.atleast_1d(np.asarray(req.voltages, np.float64))
        t_grid = tuple(float(t) for t in req.temps)
        ret = np.asarray(req.retention_ms, np.float64)
        pattern_h = np.array([population.chips.pattern_phase(p)
                              for p in req.patterns], np.float64)
        # the programmed latencies ride the lanes (as their thresholds), so
        # requests at different latencies still share a megabatch
        spec = _GroupSpec("characterize", population._characterize_flat_fn,
                          (pattern_h, ret), (), 8 * population.FIELD_SIZE,
                          True)
        key = ("characterize", tuple(req.patterns), ret.tobytes())
        v_, t_ = v.size, len(t_grid)

        def resolve():
            inputs, _ = population.characterize_inputs(
                sub, v, t_grid, req.patterns, req.retention_ms,
                req.t_rcd, req.t_rp)
            return inputs

        def post(out):
            f64 = lambda k: np.asarray(out[k], np.float64)
            return {
                "line_error_fraction": f64("frac").reshape(v_, t_),
                "ber": f64("ber").reshape(v_, t_, len(req.patterns)),
                "t_rcd_min": f64("tmin_rcd").reshape(v_, t_),
                "t_rp_min": f64("tmin_rp").reshape(v_, t_),
                "row_error_prob": f64("row_map").reshape(
                    v_, t_, population.chips.BANKS, -1),
                "line_error_prob": f64("line_map").reshape(
                    v_, t_, population.chips.BANKS, -1),
                "expected_weak_cells": f64("weak").reshape(v_, t_, ret.size),
            }

        return _Lowered(key, spec, v_ * t_, resolve, post)

    def _workload_feats(self, name: str) -> dict:
        """Per-workload Algorithm-1 feature row, memoized by name.  Feature
        extraction is ~1 ms of eager numpy per workload — by far the
        dominant per-request lowering cost — and each row depends only on
        its own workload (verified row-for-row against the batched
        ``_wb_feats``), so steady-state serving assembles cached rows
        instead of re-deriving them per request."""
        row = self._feat_rows.get(name)
        if row is None:
            wb1 = WorkloadBatch.from_workloads(
                [(name, self._workloads[name])])
            row = {k: np.asarray(a)[0]
                   for k, a in engine_solve._wb_feats(wb1).items()}
            self._feat_rows[name] = row
        return row

    def _fleet_model(self):
        if self._model is None:
            from repro.core import perf_model
            self._model = perf_model.fit()
        return self._model

    def _lower_fleet(self, req: FleetRequest) -> _Lowered:
        from repro.core import voltron
        stack_name = (req.policy_stack if req.policy_stack is not None
                      else self._default_stack)
        stack = self._stacks.get(stack_name)
        if stack is None:
            raise TableUnavailableError(
                "*", "no FleetTables installed on this service"
                if stack_name is None else
                f"no FleetTables installed for policy stack {stack_name!r} "
                f"(installed: {list(self._stacks)})")
        for name in req.workloads:
            if name not in self._workloads:
                raise ServiceError(f"workload {name!r} is not registered "
                                   "with the service")
        if req.device_model is not None:
            power_lib.get(req.device_model)  # fail fast on unknown models
        model = self._fleet_model()
        pairs = [(name, self._workloads[name]) for name in req.workloads]
        wb = WorkloadBatch.from_workloads(pairs)
        cycles = (voltron.DEFAULT_INTERVAL_CYCLES
                  if req.interval_cycles is None else req.interval_cycles)
        # per-workload (or, decorrelated, per-lane) columns are name-seeded,
        # so the schedule is independent of which workloads share the
        # request/megabatch
        if req.decorrelate_phases:
            phases = voltron.fleet_phase_matrix(
                wb.names, req.modules, req.n_intervals, cycles,
                req.phase_seed, req.phase_amplitude)          # [T, W*D]
        else:
            phases = voltron._phase_matrix(wb.names, req.n_intervals, cycles,
                                           req.phase_seed, req.phase_amplitude)
        impl = ("pallas" if jax.default_backend() == "tpu" else "reference")
        cand_v = stack.cand_v
        cand_bytes = cand_v.tobytes()
        w, d = wb.n_workloads, len(req.modules)
        t = int(req.n_intervals)
        c = wb.mpki.shape[1]
        coef_lo32 = np.asarray(model.coef_low, np.float32)
        coef_hi32 = np.asarray(model.coef_high, np.float32)
        # the tuned solve config participates in the coalescing key: lanes
        # compiled against different configs must not share an executable
        from repro.kernels import autotune
        solve_cfg = autotune.active_config("sweep_solve", (w * d, c))
        key = ("fleet", impl, t, c, float(req.target_loss_pct),
               coef_lo32.tobytes(), coef_hi32.tobytes(), cand_bytes,
               solve_cfg.key())
        spec = _GroupSpec(
            "fleet", functools.partial(controller._controller_flat_fn,
                                       impl=impl, solve_cfg=solve_cfg),
            (coef_lo32, coef_hi32, np.float32(req.target_loss_pct),
             np.asarray(cand_v, np.float32)),
            (impl, solve_cfg.key()), controller.element_cost(t), False,
            config_label=solve_cfg.key())

        def resolve():
            st = self._stacks.get(stack_name)
            if st is None or st.cand_v.tobytes() != cand_bytes:
                raise TableUnavailableError(
                    "*", f"table stack {stack_name!r}'s candidate grid "
                    "changed while the request was queued")
            rows = []
            for m in req.modules:
                row = st.rows.get(m)
                if row is None:
                    raise TableUnavailableError(m)
                rows.append(row)
            feat_rows = [self._workload_feats(n) for n in req.workloads]
            feats = {k: np.stack([r[k] for r in feat_rows])
                     for k in feat_rows[0]}
            rep_w = lambda a: np.repeat(a, d, axis=0)
            tile_d = lambda a: np.tile(a, (w,) + (1,) * (a.ndim - 1))
            flat_feats = {k: rep_w(a) for k, a in feats.items()}
            phases_flat = (phases if phases.shape[1] == w * d
                           else np.repeat(phases, d, axis=1))   # [T, W*D]
            timings = np.stack([r.timings for r in rows])       # [D, K, 3]
            cand_t = {"t_rcd": tile_d(timings[:, :, 0]),
                      "t_rp": tile_d(timings[:, :, 1]),
                      "t_ras": tile_d(timings[:, :, 2])}
            lat_feat = tile_d(np.stack([r.lat_feat for r in rows]))
            valid = tile_d(np.stack([r.valid for r in rows]))
            # per-lane power-model coefficients: the request override, or
            # each DIMM's installed table model, tiled per workload
            models = [req.device_model or r.model for r in rows]
            coeff_lanes = tile_d(power_lib.coeff_rows(models, np.float32))
            batched, _ = controller.flat_operands(
                flat_feats, phases_flat, model.coef_low, model.coef_high,
                req.target_loss_pct, cand_v, lat_feat, cand_t, valid,
                model_coeffs=coeff_lanes)
            return batched

        def post(out):
            out = {k: (np.asarray(a) if k == "selected_idx"
                       else np.asarray(a).astype(np.float64))
                   for k, a in out.items()}
            selected = cand_v[out["selected_idx"]]
            shape2 = lambda a: a.reshape(w, d)
            st = self._stacks.get(stack_name)
            rows = st.rows if st is not None else {}
            vendors = tuple(rows[m].vendor if m in rows
                            else "?" for m in req.modules)
            device_models = tuple(
                req.device_model or (rows[m].model if m in rows else "ddr3l")
                for m in req.modules)
            k = cand_v.size
            margin = np.stack([
                np.asarray(rows[m].hammer_margin, np.float64)
                if m in rows else np.full(k, np.nan)
                for m in req.modules])                          # [D, K]
            # reliability-transparency rows: present iff every named
            # module's row carries them (a stack built with an ECC policy)
            rel = {}
            if all(m in rows and rows[m].silent is not None
                   for m in req.modules):
                for key in ("correctable", "detectable", "silent"):
                    rel[key] = np.stack([
                        np.asarray(getattr(rows[m], key), np.float64)
                        for m in req.modules])                  # [D, K]
            return fleet_lib.FleetBatchResult(
                wb.names, tuple(req.modules), vendors, cand_v,
                selected.reshape(w, d, -1),
                shape2(out["perf_loss_pct"]),
                shape2(out["dram_power_savings_pct"]),
                shape2(out["dram_energy_savings_pct"]),
                shape2(out["system_energy_savings_pct"]),
                shape2(out["perf_per_watt_gain_pct"]),
                margin,
                base_component_j=out["base_component_j"].reshape(w, d, -1),
                pt_component_j=out["pt_component_j"].reshape(w, d, -1),
                device_models=device_models,
                correctable=rel.get("correctable"),
                detectable=rel.get("detectable"),
                silent=rel.get("silent"),
                policy_stack=st.policy_stack if st is not None else ())

        return _Lowered(key, spec, w * d, resolve, post)
