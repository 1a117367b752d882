"""Batch job: Voltron (Algorithm 1) over the paper's fleet.

Set-up builds the per-DIMM safe-voltage tables and fits the loss
predictor once, as an operator would for a population.  One job runs
``fleet.run_fleet_batched`` over every workload mix x DIMM lane for the
cell's intervals, with per-lane memory-intensity phases that the
benchmark draws from the run's seed and the job's index: every job does
the same work on different phases.

The window keeps ``KEEP_JOBS`` whole jobs' outputs, drawn from the seed.
``check`` draws lanes of the kept jobs from the seed and runs them
through the plain reference in ``bench/ref/fleet.py`` on the host, with
its own tables and its own fitted predictor: the selected voltage of
every interval must match, and the five Fig. 14/17 metrics must lie
within the cell's limit in percentage points.
"""
from __future__ import annotations

import numpy as np

METRICS = ("perf_loss_pct", "dram_power_savings_pct",
           "dram_energy_savings_pct", "system_energy_savings_pct",
           "perf_per_watt_gain_pct")
# whole outputs the window keeps for the check: few, since the jobs run
# while the keep fills are slower (on a TPU v5e host, keeping 64 slowed
# jobs 24-63 of each window by 6%)
KEEP_JOBS = 8


def job_lanes(outputs: list, picks) -> list:
    """``[(job, lane), ...]`` of ``picks`` ``[(position, lane), ...]``
    among the window's kept ``outputs``: the job index that the
    reference recomputes the lane's phases from."""
    return [(outputs[pos]["job"], lane) for pos, lane in picks]


def phases(rng: np.random.Generator, n_intervals: int, n_lanes: int,
           amplitude: float, phase_len: int) -> np.ndarray:
    """[T, N] piecewise-constant memory-intensity factors, one column per
    lane: a phase spans ``phase_len`` intervals and scales the lane's
    MPKI by a factor uniform in 1 +- ``amplitude``."""
    n_ph = -(-n_intervals // phase_len)
    f = 1.0 + amplitude * rng.uniform(-1.0, 1.0, (n_ph, n_lanes))
    return np.repeat(f, phase_len, axis=0)[:n_intervals]


class Job:
    entries = ("fleet",)
    keep_jobs = KEEP_JOBS

    def __init__(self, config: dict, params: dict, seed: int):
        import jax

        from repro import engine
        from repro.core import perf_model
        from repro.engine import fleet
        from repro.memsim import workloads

        self.jax = jax
        self.config, self.params = config, params
        self.seed = seed
        rng = np.random.default_rng(seed)
        self.check_rng = np.random.default_rng(rng.integers(2**63))
        grid = engine.DimmGrid.from_population(config["modules"])
        cand = np.array(config["candidate_voltages"])
        self.tables = fleet.build_tables(grid, cand,
                                         policies=fleet.legacy_policies())
        self.model = perf_model.fit()
        wls = (workloads.homogeneous_workloads()
               + workloads.heterogeneous_workloads())
        self.wb = engine.WorkloadBatch.from_workloads(
            wls[:config["n_workloads"]])

    def lanes(self) -> int:
        return self.config["n_workloads"] * len(self.config["modules"])

    def _phases(self, i: int) -> np.ndarray:
        p = self.params
        return phases(np.random.default_rng([self.seed, i + 1]),
                      p["n_intervals"], self.lanes(), p["phase_amplitude"],
                      p["phase_len"])

    def _run(self, i: int) -> dict:
        from repro.engine import fleet
        ph = self._phases(i)
        with self.jax.profiler.TraceAnnotation("bench.entry.fleet"):
            res = fleet.run_fleet_batched(
                self.wb, self.tables, ph, self.model.coef_low,
                self.model.coef_high, self.config["target_loss_pct"],
                impl=self.params["impl"])
        return {"job": i, "result": res}

    def warm(self) -> None:
        self._run(-1)

    def run(self, i: int) -> dict:
        return self._run(i)

    # ---- correctness ------------------------------------------------------
    def reference(self, picks, dtype=None) -> dict:
        """Reference results of ``picks`` [(job, lane), ...], on the host."""
        import jax
        import jax.numpy as jnp

        from bench.ref import fleet as ref

        dtype = jnp.float32 if dtype is None else dtype
        c = self.config
        cpu = jax.local_devices(backend="cpu")[0]
        with jax.default_device(cpu):
            if not hasattr(self, "_ref_setup"):
                self._ref_setup = (ref.tables(c["modules"]), ref.fit(),
                                   ref.fleet_workloads()[:c["n_workloads"]])
            tab, (lo, hi), wls = self._ref_setup
            d_ = len(c["modules"])
            cols, cores, rows = [], [], {"timings": [], "valid": [],
                                         "lat_feat": []}
            ph = {}
            for job, lane in picks:
                if job not in ph:
                    ph[job] = self._phases(job)
                cols.append(ph[job][:, lane])
                w, d = divmod(lane, d_)
                cores.append(wls[w][1])
                for k in rows:
                    rows[k].append(tab[k][d])
            table_rows = {k: np.stack(v) for k, v in rows.items()}
            table_rows["cand_v"] = tab["cand_v"]
            return ref.controller(cores, table_rows, np.stack(cols, axis=1),
                                  lo, hi, dtype=dtype)

    def picks(self, n_jobs: int) -> list:
        """``check_lanes`` (position, lane) pairs over ``n_jobs`` kept
        outputs, drawn from the seed."""
        n = min(self.params["check_lanes"], n_jobs * self.lanes())
        flat = self.check_rng.choice(n_jobs * self.lanes(), n, replace=False)
        return [divmod(int(f), self.lanes()) for f in sorted(flat)]

    def compare(self, got: dict, ref: dict) -> list:
        sel = (got["selected_idx"] != ref["selected_idx"]).any(axis=1)
        gap = max(float(np.max(np.abs(got[m] - ref[m]))) for m in METRICS)
        return [("lanes_with_other_voltages", int(sel.sum()),
                 self.params["limit_lanes"]),
                ("metric_gap_pp", gap, self.params["limit_pp"])]

    def gathered(self, outputs: list, picks) -> dict:
        """The program's values at ``picks`` (positions among the kept
        ``outputs``), shaped as the reference's."""
        cand = np.asarray(self.tables.cand_v, np.float64)
        d_ = len(self.config["modules"])
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
        return {k: np.asarray(v) for k, v in out.items()}

    def check(self, outputs: list) -> list:
        picks = self.picks(len(outputs))
        return self.compare(self.gathered(outputs, picks),
                            self.reference(job_lanes(outputs, picks)))

    def control(self, outputs: list) -> list:
        """The reference in bfloat16 (float32 as stated) in the program's
        place, at the picks ``check`` would draw."""
        import jax.numpy as jnp
        at = job_lanes(outputs, self.picks(len(outputs)))
        return self.compare(self.reference(at, jnp.bfloat16),
                            self.reference(at))
