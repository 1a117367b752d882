"""Batch job: Voltron over an operator's fleet of DIMMs resampled from
Table 7, sharded over the cell's chips.

Set-up draws the fleet from the run's seed
(``chips.resampled_population``), builds its ECC-aware safe-voltage
tables on the ``("batch",)`` mesh of the cell's chips
(``fleet.build_tables`` with ``ecc_policies()``) and fits the loss
predictor.  One job runs ``fleet.run_fleet_batched`` over every workload
mix x DIMM lane on that mesh, with per-lane memory-intensity phases drawn
from the seed and the job's index (``bench/jobs/fleet.py``); past the top
bucket the dispatch layer streams the lanes as chunks.

``check`` draws lanes of the window's jobs from the seed, as many from
each chip's share of a chunk as from any other, resamples the fleet with
the reference's own code (``bench/ref/opfleet.py``), builds the picked
DIMMs' ECC tables there, and runs the picked lanes through the reference
controller: every table field of a picked DIMM must be exact, the
selected voltage of every interval must match, and the five Fig. 14/17
metrics must lie within the cell's limit in percentage points.
"""
from __future__ import annotations

import numpy as np

from bench.jobs.fleet import METRICS, phases
from bench.jobs.tables import _gap

TABLE_FIELDS = ("valid", "timings", "lat_feat")
RATES = ("correctable", "detectable", "silent")


class Job:
    entries = ("fleet",)

    def __init__(self, config: dict, params: dict, seed: int):
        import jax

        from repro import engine
        from repro.core import perf_model
        from repro.dram import chips
        from repro.engine import fleet
        from repro.launch import mesh as mesh_lib
        from repro.memsim import workloads

        self.jax = jax
        self.config, self.params = config, params
        self.seed = seed
        devices = jax.devices()[:params["devices"]]
        if len(devices) < params["devices"]:
            raise RuntimeError(f"the cell shards over {params['devices']} "
                               f"devices; JAX sees {len(devices)}")
        self.mesh = mesh_lib.make_batch_mesh(devices)
        self.check_rng = np.random.default_rng(
            np.random.default_rng(seed).integers(2**63))
        grid = engine.DimmGrid.from_dimms(
            chips.resampled_population(config["n_dimms"], seed))
        self.tables = fleet.build_tables(
            grid, np.array(config["candidate_voltages"]),
            policies=fleet.ecc_policies(), mesh=self.mesh)
        self.model = perf_model.fit()
        wls = (workloads.homogeneous_workloads()
               + workloads.heterogeneous_workloads())
        self.wb = engine.WorkloadBatch.from_workloads(
            wls[:config["n_workloads"]])

    def lanes(self) -> int:
        return self.config["n_workloads"] * self.config["n_dimms"]

    def _phases(self, i: int) -> np.ndarray:
        p = self.params
        return phases(np.random.default_rng([self.seed, i + 1]),
                      p["n_intervals"], self.lanes(), p["phase_amplitude"],
                      p["phase_len"])

    def _run(self, i: int) -> dict:
        from repro.engine import fleet
        with self.jax.profiler.TraceAnnotation("bench.entry.fleet"):
            res = fleet.run_fleet_batched(
                self.wb, self.tables, self._phases(i), self.model.coef_low,
                self.model.coef_high, self.config["target_loss_pct"],
                impl=self.params["impl"], mesh=self.mesh)
        return {"job": i, "result": res}

    def warm(self) -> None:
        self._run(-1)

    def run(self, i: int) -> dict:
        return self._run(i)

    # ---- correctness ------------------------------------------------------
    def shard_of(self, lanes: np.ndarray) -> np.ndarray:
        """The chip each lane runs on: the dispatch layer lays lane ``n``
        at position ``n mod B`` of a resident bucket or chunk of ``B``
        lanes, and each chip holds ``B / devices`` consecutive positions."""
        from repro.engine import dispatch
        ladder = dispatch.bucket_ladder(len(self.mesh.devices.flat))
        b = dispatch.pick_bucket(self.lanes(), ladder) or ladder[-1]
        return (np.asarray(lanes) % b) // (b // len(self.mesh.devices.flat))

    def picks(self, n_jobs: int) -> list:
        """``check_lanes`` (job, lane) pairs, an equal number from each
        chip's share of the window's lanes."""
        n_dev = len(self.mesh.devices.flat)
        shard = self.shard_of(np.arange(self.lanes()))
        out = []
        for j in range(n_dev):
            own = np.flatnonzero(shard == j)
            k = min(self.params["check_lanes"] // n_dev, n_jobs * own.size)
            flat = self.check_rng.choice(n_jobs * own.size, k, replace=False)
            out += [(int(f) // own.size, int(own[f % own.size]))
                    for f in flat]
        return sorted(out)

    def reference(self, picks, dtype=None) -> tuple:
        """``(tables, lanes)``: the reference's ECC tables of the picked
        lanes' DIMMs and its controller results at ``picks``, on the
        host."""
        import jax
        import jax.numpy as jnp

        from bench.ref import fleet as ref
        from bench.ref import opfleet as ref_op

        dtype = jnp.float32 if dtype is None else dtype
        c = self.config
        d_ = c["n_dimms"]
        cpu = jax.local_devices(backend="cpu")[0]
        with jax.default_device(cpu):
            if not hasattr(self, "_ref_setup"):
                self._ref_setup = (ref_op.population(d_, self.seed),
                                   ref.fit(),
                                   ref.fleet_workloads()[:c["n_workloads"]],
                                   {})
            pop, (lo, hi), wls, tabs = self._ref_setup
            want = sorted({lane % d_ for _, lane in picks} - set(tabs))
            if want:
                new = ref_op.ecc_tables([pop[d] for d in want])
                for i, d in enumerate(want):
                    tabs[d] = {k: (v if k == "cand_v" else v[i])
                               for k, v in new.items()}
            ph, cols, cores = {}, [], []
            rows = {"timings": [], "valid": [], "lat_feat": []}
            for job, lane in picks:
                if job not in ph:
                    ph[job] = self._phases(job)
                cols.append(ph[job][:, lane])
                w, d = divmod(lane, d_)
                cores.append(wls[w][1])
                for k in rows:
                    rows[k].append(tabs[d][k])
            table_rows = {k: np.stack(v) for k, v in rows.items()}
            table_rows["cand_v"] = ref.candidate_voltages()
            lanes = ref.controller(cores, table_rows, np.stack(cols, axis=1),
                                   lo, hi, dtype=dtype)
        dimms = sorted({lane % d_ for _, lane in picks})
        return {d: tabs[d] for d in dimms}, lanes

    def compare(self, got: tuple, ref: tuple) -> list:
        """Table fields of the picked DIMMs (name, validity, timings and
        latency features exactly; ECC rates and hammer margins as their
        largest gap), then the picked lanes' selections and metrics."""
        (got_t, got_l), (ref_t, ref_l) = got, ref
        bad, rate_gap, margin_gap = 0, 0.0, 0.0
        for d, want in ref_t.items():
            have = got_t[d]
            bad += int(have["modules"] != want["modules"])
            bad += sum(int(not np.array_equal(have[k], want[k],
                                              equal_nan=True))
                       for k in TABLE_FIELDS)
            rate_gap = max([rate_gap] + [_gap(have[k], want[k])
                                         for k in RATES])
            margin_gap = max(margin_gap, _gap(have["hammer_margin"],
                                              want["hammer_margin"],
                                              relative=True))
        sel = (got_l["selected_idx"] != ref_l["selected_idx"]).any(axis=1)
        gap = max(float(np.max(np.abs(got_l[m] - ref_l[m])))
                  for m in METRICS)
        p = self.params
        return [("table_fields_differing", bad, p["limit_table_fields"]),
                ("ecc_rate_gap", rate_gap, p["limit_rate"]),
                ("hammer_margin_rel_gap", margin_gap, p["limit_margin"]),
                ("lanes_with_other_voltages", int(sel.sum()),
                 p["limit_lanes"]),
                ("metric_gap_pp", gap, p["limit_pp"])]

    def gathered(self, outputs: list, picks) -> tuple:
        """The program's tables and values at ``picks``, shaped as the
        reference's."""
        t = self.tables
        cand = np.asarray(t.cand_v, np.float64)
        d_ = self.config["n_dimms"]
        tabs = {}
        for d in sorted({lane % d_ for _, lane in picks}):
            tabs[d] = {"modules": t.modules[d],
                       **{k: np.asarray(getattr(t, k))[d]
                          for k in TABLE_FIELDS + RATES
                          + ("hammer_margin",)}}
        out = {m: [] for m in METRICS}
        out["selected_idx"] = []
        for job, lane in picks:
            r = outputs[job]["result"]
            w, d = divmod(lane, d_)
            for m in METRICS:
                out[m].append(getattr(r, m)[w, d])
            v = r.selected_voltages[w, d]
            out["selected_idx"].append(
                np.abs(v[:, None] - cand[None, :]).argmin(axis=1))
        return tabs, {k: np.asarray(v) for k, v in out.items()}

    def check(self, outputs: list) -> list:
        picks = self.picks(len(outputs))
        return self.compare(self.gathered(outputs, picks),
                            self.reference(picks))

    def control(self, outputs: list) -> list:
        """The reference controller in bfloat16 (float32 as stated) in the
        program's place, at the picks ``check`` would draw; the tables
        are the reference's own on both sides."""
        import jax.numpy as jnp
        picks = self.picks(len(outputs))
        return self.compare(self.reference(picks, jnp.bfloat16),
                            self.reference(picks))
