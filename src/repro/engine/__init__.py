"""Batched, jit-compiled simulation engine for the paper's design-space
sweeps.

Every result in the paper is a sweep: the system half (Figs. 12-19,
Table 5) over (workload, V_array, profiling interval), the
characterization half (Figs. 4, 6, 8, 11) over (DIMM, V_supply,
temperature, data pattern).  The scalar pipeline ran each point through
Python one at a time; this package runs each grid as struct-of-arrays JAX
computation.

Batching axes
=============

- **W** — workloads (``WorkloadBatch``: stacked Table 4 benchmark features,
  C cores each).
- **P** — DRAM operating points (``PointGrid``: stacked ``OperatingPoint``
  voltages/rates with timings resolved via the vectorized circuit model).
- **T** — Voltron profiling intervals, scanned (``controller.run_batched``
  carries the selected voltage per workload through one ``lax.scan``).
- **D** — DIMMs (``DimmGrid``: stacked Table 7 identities with the derived
  per-DIMM latency scale, cell sigma and susceptibility field).
- **D x V x P x R** — the Test-1 stress sweep (``test1.run_batch``: DIMMs x
  voltages x data-pattern groups x rounds, flattened into one batch axis;
  per-element PRNG key data and word-corruption probabilities ride the flat
  axis, the [P, 2] pattern words stay replicated, and the bit injection is
  a single ``voltage_inject`` dispatch over the flattened
  [N * banks * rows, words] plane).
- **D x V x H x R** — the RowHammer stress sweep (``test1.run_hammer_batch``:
  DIMMs x wordline voltages x hammer counts x rounds on the same flat
  Test-1 axis, stats entry ``"hammer"``).  Even rows are the aggressors
  (toggled ``hammer_count`` times, flip probability exactly zero), odd
  rows the blast-radius-1 victims; the aggressor/victim structure lives
  entirely in the per-lane word-corruption table
  (``dram.errors.hammer_word_probs``, voltage-dependent threshold
  ``hammer_threshold``), so the injection reuses the Test-1 kernel and
  ``voltage_inject`` dispatch plane unchanged, and the per-element PRNG
  key data reproduces ``dram.test1.run_hammer``'s scalar split chain
  bit-exactly.
- **W x D** — the Voltron fleet (``fleet.run_fleet_batched``: workloads x
  characterized DIMMs, flattened with the DIMM axis fastest — lane
  ``n = w * D + d``).  Workload features and the [T, W] phase schedule are
  repeated per DIMM — or, under per-(workload, DIMM) phase decorrelation
  (``voltron.fleet_phase_matrix`` / ``run_fleet(decorrelate_phases=)`` /
  ``FleetRequest.decorrelate_phases``), a [T, W*D] schedule supplies one
  independently-seeded column per lane (seed
  ``voltron._lane_phase_seed(name, module, phase_seed)``, so any lane can
  be replayed solo via ``run_suite(..., tables=, phase_seed=)``).  Each
  lane carries its DIMM's [K] safe candidate timing table, latency
  features and candidate-exclusion mask (``fleet.FleetTables``, derived
  from ``test1.find_min_latency_batch`` — NaN minimum latency = candidate
  excluded; the table also carries a per-candidate [D, K]
  ``hammer_margin`` = disturbance threshold over refresh-window
  activations, and candidates with margin < 1 are excluded with the same
  NaN semantics), and the whole cross-product runs as one dispatched
  interval scan (``controller.run_flat``, stats entry ``"fleet"``).  The
  [K] candidate-voltage vector and the Eq. 1 coefficients stay
  replicated.

The flat batch-axis convention
==============================

Every engine entry point follows the same shape discipline:

1. resolve all circuit-model inputs **eagerly and vectorized** at container
   construction (``PointGrid`` resolves timings, ``characterize_batch``
   resolves required raw latencies — one call per vendor x temperature, no
   per-element Python loop);
2. **flatten the full grid into one leading batch axis** (W x P for
   ``simulate_batch``/``evaluate_batch``, D x V x T for
   ``characterize_batch``) and run it as a single jit-compiled call;
3. **shard the flat axis, never loop it**: the flat axis is padded to a
   multiple of the device count and split with a
   ``jax.sharding.NamedSharding`` over the 1-D ``("batch",)`` mesh from
   ``repro.launch.mesh.make_batch_mesh()``.  On one device the mesh has a
   single slot and sharding is skipped entirely — results are identical
   with and without it.  Per-element constants ride along on the flat
   axis; genuinely shared operands (the [P, 2] Test-1 pattern words) stay
   replicated.

The bucketing / chunking contract
=================================

Entry points reach their kernels through :mod:`repro.engine.dispatch`
(``dispatch="direct"`` bypasses it — the exact-shape jit call kept as the
parity reference).  The contract:

- **When callers get padding:** a flat batch of size N <= the largest
  bucket is padded up to the smallest bucket ``n_devices * 2**k`` and runs
  on a warm AOT-compiled executable (one compile per (entry point, bucket,
  static config) — ``dispatch.stats()`` exposes the counters).  Results
  are sliced back to N and are bit-exact per element: the padded lanes are
  finite copies of lane 0 and never mix with real lanes.
- **Mask semantics:** kernels with per-element reductions take a boolean
  ``valid`` [N] lane mask as their last argument and must zero dead lanes
  in every output (``test1._test1_flat_fn`` masks its counts/maps,
  ``population._characterize_flat_fn`` its fractions,
  ``test1._min_latency_flat_fn`` its latency pairs — NaN there is a real
  "unrecoverable" verdict, so dead lanes land on 0.0 instead).  Per-lane
  kernels (``solve._grid_sim_fn``, ``controller._controller_flat_fn``)
  reduce only over the unpadded core/interval axes, so they pad-and-slice
  without consulting the mask.
- **When callers get chunking:** a request larger than the top bucket —
  or whose ``N * element_cost`` exceeds the ``max_elements_resident``
  budget — streams through a ``lax.map`` over fixed-size chunks (donated
  stacked inputs, per-chunk in-jit randomness), keeping peak memory
  O(chunk).  Outputs are reassembled and remain bit-exact.
- **Mesh-divisibility rule:** buckets and chunks are ``n_devices * 2**k``
  by construction, so the ``("batch",)`` sharding of the resident axis
  (``launch.mesh.batch_sharding`` / ``chunked_batch_sharding``) always
  splits evenly — never re-pad a bucketed batch for the mesh.

The per-component power axis and device models
==============================================

DRAM power is computed per component, not as one scalar:
:mod:`repro.power` defines the six-component DRAMPower-style decomposition
(``background_array``, ``refresh``, ``act_pre``, ``rw_array`` in the array
domain — scaling with V_array**2 — and ``background_periph``, ``rw_periph``
in the peripheral domain — scaling with V_periph**2 and frequency), with
row-buffer locality as the coupling variable between the activity rates
(``acts_per_ns = lines_per_ns * (1 - row_hit_rate)``).  The engine
conventions:

- **Component axis:** stacked component arrays put the component last, in
  ``power.COMPONENTS`` order — ``[..., NC]`` (``BatchResult.components_w``
  / ``components_j`` unstack it to dicts; ``FleetBatchResult
  .base_component_j`` / ``pt_component_j`` are [W, D, NC] summed over
  intervals, with ``vendor_component_energy()`` as the Fig. 15-17-analogue
  report).  The legacy scalar totals are exact sums over the axis
  (``power.power_totals`` regroups the components into the pre-refactor
  (dynamic, static) split), so the axis is purely additive reporting.
- **Device models on the flat batch axis:** a ``power.DeviceModel`` names
  a part class (registered ``ddr3l`` / ``hbm2`` / ``lpddr4``) as
  coefficients of the same six components.  Homogeneous sweeps pass the
  model as a hashable static (``simulate_batch(...,
  device_model="hbm2")``); heterogeneous fleets gather one
  ``power.coeff_rows`` row per lane **eagerly at table construction**
  (``FleetTables.device_models`` — one extra [D] column, tiled per
  workload) so inside jit the model is just more per-lane operands, with
  no Python dispatch and no operand-structure change (the coefficient
  operand is always present, defaulting to ``ddr3l`` rows).
- **Selections are model-independent:** Algorithm 1 reads only the loss
  predictions, never the energy accumulators, so fleet voltage selections
  are bit-equal across device-model assignments; baseline energies use
  the *lane's own* model at nominal (the comparison is reduced-voltage vs
  nominal on the same part, never across parts).

The reliability-policy pipeline
===============================

``fleet.build_tables`` does not hard-code its admission rules: candidate
admission is an ordered pipeline of :class:`repro.engine.fleet
.ReliabilityPolicy` stages.  Each policy reads a frozen
``PolicyContext`` (grid, candidate voltages, latency search knobs, mesh /
dispatch mode) and mutates a ``PolicyState`` holding the per-(DIMM,
candidate) ``timings`` [D, K, 3], the boolean admission mask ``valid``
[D, K], named margin rows (``state.margins``), and optional reliability
rate rows.  The contract:

- **Composition is mask intersection + NaN exclusion:** a policy may only
  narrow ``valid`` (AND its own verdict in) or — for admission policies —
  widen it by filling previously-NaN timing rows it can vouch for.  After
  the pipeline runs, ``build_tables`` re-NaNs every excluded candidate's
  timings, so downstream consumers keep the single "NaN = excluded"
  convention regardless of which stack produced the table.
- **The legacy stack is built-in and bit-exact:** ``legacy_policies()``
  returns ``(MinLatencyFloor(), HammerFloor())`` — re-expressions of the
  pre-pipeline error-free-latency floor and hammer-margin floor whose
  composed output is bit-equal to the old monolithic ``build_tables``
  (property-tested in ``tests/test_reliability.py``), and is the default
  when ``policies=`` is omitted.
- **ECC-aware admission rides the same flat axis:** ``EccAdmission``
  (stack helper ``ecc_policies()``) re-admits candidates the latency
  floor rejected when an ECC profile (``dram.errors.ecc_profile`` —
  ``"secded"`` / ``"on_die_sec"``) corrects their residual beat-error
  distribution at the operating temperature and the silent/residual rates
  fit the configured budgets, with the vendor recovery/fail voltage
  floors kept binding.  The beat-error distribution is evaluated for the
  whole D x K x T grid in one dispatched call
  (``population.beat_error_batch``, stats entry ``"beat_error"``; the
  scalar reference ``dram.chips.DIMM.beat_error_distribution`` /
  ``dram.errors.secded_outcomes`` loop is kept as ``impl="scalar"``).
- **Tables carry their provenance:** ``FleetTables.policy_stack`` records
  each stage's parameterized descriptor and ``stack_name`` names the
  stack (``"min_latency+hammer"`` for the default, ``"legacy"`` on
  hand-built tables predating the pipeline); ECC-built tables additionally
  carry per-candidate ``correctable`` / ``detectable`` / ``silent`` [D, K]
  rate rows, surfaced per vendor by
  ``FleetBatchResult.vendor_reliability()``.

The serving contract
====================

:mod:`repro.engine.service` puts a streaming front-end over the warm
engine: ``EngineService.submit`` (async) accepts a continuous stream of
``MinLatencyRequest`` / ``CharacterizeRequest`` / ``FleetRequest`` and
coalesces concurrent requests into bucket-sized megabatches — groups are
keyed by everything that must match for lanes to share one dispatch
(entry point, replicated operands, statics), and a group flushes on the
batching window (``ServiceConfig.window_s``) or the size trigger
(``max_batch_lanes`` / the resident-budget bucket), whichever fires
first.  The contract:

- **Parity:** a coalesced lane is bit-identical to the same request
  served alone (``run_request``, the request-at-a-time baseline) for the
  float64 entry points and the fleet voltage selections; the fleet's
  float32 derived metrics agree to XLA's shape-dependent vectorization
  tolerance (~1e-6 relative across bucket rungs).
- **Admission:** every admitted request reserves ``lanes x
  element_cost`` against ``ServiceConfig.max_queue_elements``; past the
  budget, ``admission="shed"`` fails fast with ``AdmissionError`` and
  ``admission="queue"`` suspends the caller.  Occupancy never exceeds
  the budget.
- **Live tables:** fleet requests resolve their per-DIMM safe-voltage
  rows at flush time; ``drop_table`` mid-stream fails that DIMM's
  queued/future requests fast with ``TableUnavailableError`` while
  unrelated lanes complete, and ``fleet.build_tables`` +
  ``install_tables`` restores service without a restart.  Each installed
  row also carries its DIMM's device-model name, so heterogeneous fleet
  requests coalesce with homogeneous ones (the per-lane coefficient rows
  are batched operands, not statics); ``FleetRequest.device_model``
  overrides the model for every lane of one request.  Tables install
  into a named per-stack registry (``install_tables(tables, stack=)`` /
  ``table_stacks``), so ECC-on, ECC-off and temperature-excursion
  variants of the same DIMMs coexist mid-stream and
  ``FleetRequest.policy_stack`` routes each request to its stack;
  requests against different stacks still coalesce into one megabatch
  when their candidate grids agree, because the per-lane table rows are
  batched operands too.

``launch.fleet_serve`` drives the service under bursty open-loop load.

Kernel tiling and speed
=======================

Each kernel takes its tiling from its own shape rule:
``sweep_solve.kernel.block_rows`` (at most ``ROW_BLOCK`` rows of 128
samples a grid step, fewer for a small batch) and ``voltage_inject``'s
``ROW_BLOCK`` x ``WORD_BLOCK`` tile, with non-tile-aligned planes padded
and sliced back.  The block sizes stay plain keywords at the kernel
layer, where the tests run several grid steps at small sizes; the engine
never passes them.  Speed is judged on the chip by the benchmark in
``bench/`` (cells listed in ``BENCHMARK.json``), not on the CPU.

Scalar-wrapper compatibility
============================

The legacy entry points survive as thin wrappers: ``memsim.system.simulate``
and ``evaluate`` call the engine with W=P=1 (the original NumPy path is kept
as ``system.simulate_scalar`` and is what the parity tests compare against),
and ``core.voltron.run_controller`` is ``run_suite`` with one workload.
The characterization path keeps its reference as
``characterize_batch(..., impl="scalar")`` — the original per-DIMM
chips/errors loop — and the Test-1 path as
``test1.run_batch(..., impl="scalar")`` — a loop over ``dram.test1.run``
(the hammer sweep keeps ``dram.test1.run_hammer`` /
``test1._run_hammer_scalar`` as its reference the same way).
Results match the scalar paths to float32 tolerance (system sweep) / 1e-6
(characterization, float64 end to end) / bit-exactly (Test-1 error counts,
same PRNG keys); shapes and dataclass fields are unchanged.
"""
from repro.engine import dispatch  # noqa: F401
from repro.engine import fleet  # noqa: F401
from repro.engine import test1  # noqa: F401
from repro.engine.batch import PointGrid, WorkloadBatch  # noqa: F401
from repro.engine.controller import (ControllerBatchResult,  # noqa: F401
                                     run_batched)
from repro.engine.fleet import (EccAdmission, FleetBatchResult,  # noqa: F401
                                FleetTables, HammerFloor,
                                MinLatencyFloor, PolicyContext,
                                PolicyState, ReliabilityPolicy,
                                build_tables, ecc_policies,
                                legacy_policies, run_fleet_batched)
from repro.engine.population import (CharacterizationBatch,  # noqa: F401
                                     DimmGrid, characterize_batch)
from repro.engine.service import (AdmissionError,  # noqa: F401
                                  CharacterizeRequest, EngineService,
                                  FleetRequest, MinLatencyRequest,
                                  ServiceConfig, ServiceError,
                                  TableUnavailableError)
from repro.engine.solve import (BatchResult, ComparisonBatch,  # noqa: F401
                                evaluate_batch, simulate_batch)
from repro.engine.test1 import (HammerBatch, Test1Batch,  # noqa: F401
                                run_hammer_batch)
