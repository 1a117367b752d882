"""Batch job: re-qualifying a DIMM population.

One job characterizes every DIMM of the configuration over the cell's
voltage x temperature grid (``characterize_batch``, float64) and derives
the ECC-aware safe-voltage tables over the Algorithm-1 candidates
(``fleet.build_tables`` with ``ecc_policies()``).  Job ``i`` takes the
DIMMs in an order drawn from the run's seed: the same work, laid out
differently on the batch axis.

The window keeps one job's output, drawn from the seed (each holds a
134.7 MB characterization).  ``check`` recomputes a few of its DIMMs'
characterization, drawn from the seed, with the plain reference on the
host (largest absolute gap, float64) and every DIMM's table (validity,
timings and latency features exactly; hammer margins and ECC rates as
their largest gap).
"""
from __future__ import annotations

import numpy as np

CHAR_FIELDS = ("line_error_fraction", "ber", "t_rcd_min", "t_rp_min",
               "row_error_prob", "line_error_prob")


class Job:
    entries = ("characterize", "min_latency", "beat_error")
    metric = "requalify_s"
    # the check reads one job: the window keeps one, drawn from the seed
    keep_jobs = 1

    def __init__(self, config: dict, params: dict, seed: int):
        import jax

        from repro import engine
        self.jax = jax
        self.config, self.params = config, params
        self.seed = seed
        self.check_rng = np.random.default_rng(
            np.random.default_rng(seed).integers(2**63))
        self.grid = engine.DimmGrid.from_population(config["modules"])
        p = params
        self.voltages = np.round(np.arange(p["v_max"], p["v_min"] - 1e-9,
                                           -p["v_step"]), 4)

    def order(self, i: int) -> list:
        mods = list(self.config["modules"])
        perm = np.random.default_rng([self.seed, i + 1]).permutation(len(mods))
        return [mods[j] for j in perm]

    def _run(self, i: int) -> dict:
        from repro import engine
        from repro.engine import fleet
        p, ann = self.params, self.jax.profiler.TraceAnnotation
        grid = self.grid.select(self.order(i))
        with ann("bench.entry.characterize"):
            char = engine.characterize_batch(grid, self.voltages, p["temps"],
                                             p["patterns"])
        with ann("bench.entry.tables"):
            tables = fleet.build_tables(
                grid, np.array(self.config["candidate_voltages"]),
                policies=fleet.ecc_policies())
        return {"job": i, "char": char, "tables": tables}

    def warm(self) -> None:
        self._run(-1)

    def run(self, i: int) -> dict:
        return self._run(i)

    # ---- correctness ------------------------------------------------------
    def reference_char(self, module: str, round_to=None) -> dict:
        import jax

        from bench.ref import characterize as ref_char
        from bench.ref import chips

        dimm = {d.module: d for d in chips.population()}[module]
        with jax.default_device(jax.local_devices(backend="cpu")[0]):
            out = ref_char.characterize(dimm, self.voltages,
                                        self.params["temps"],
                                        self.params["patterns"],
                                        self.params["retention_ms"])
        if round_to is not None:
            out = {k: np.asarray(v).astype(round_to).astype(np.float64)
                   for k, v in out.items()}
        return out

    def reference_tables(self) -> dict:
        import jax

        from bench.ref import tables as ref_tables
        if not hasattr(self, "_ref_tables"):
            with jax.default_device(jax.local_devices(backend="cpu")[0]):
                self._ref_tables = ref_tables.ecc_tables(
                    self.config["modules"])
        return self._ref_tables

    def picks(self, n_jobs: int) -> list:
        """``[(position, module), ...]``: a kept output and DIMMs of it,
        drawn from the seed."""
        job = int(self.check_rng.integers(n_jobs))
        mods = self.check_rng.choice(self.config["modules"],
                                     self.params["check_dimms"],
                                     replace=False)
        return [(job, str(m)) for m in mods]

    def check(self, outputs: list, round_to=None) -> list:
        """``[(name, value, limit), ...]``.  With ``round_to`` the
        characterization reference rounded to that dtype stands in the
        program's place (the precision control)."""
        picks = self.picks(len(outputs))
        char_gap = 0.0
        for job, mod in picks:
            ref = self.reference_char(mod)
            if round_to is None:
                got = outputs[job]["char"]
                d = got.modules.index(mod)
                got = {k: getattr(got, k)[d] for k in CHAR_FIELDS} | {
                    "expected_weak_cells": got.expected_weak_cells}
            else:
                got = self.reference_char(mod, round_to)
            for k in CHAR_FIELDS + ("expected_weak_cells",):
                char_gap = max(char_gap, _gap(got[k], ref[k]))
        ref = self.reference_tables()
        got = outputs[picks[0][0]]["tables"]
        idx = [got.modules.index(m) for m in ref["modules"]]
        bad = sum(int(not np.array_equal(np.asarray(getattr(got, k))[idx],
                                         ref[k], equal_nan=True))
                  for k in ("valid", "timings", "lat_feat"))
        rate_gap = max(_gap(np.asarray(getattr(got, k))[idx], ref[k])
                       for k in ("correctable", "detectable", "silent"))
        margin_gap = _gap(np.asarray(got.hammer_margin)[idx],
                          ref["hammer_margin"], relative=True)
        p = self.params
        return [("char_gap", char_gap, p["limit_char"]),
                ("table_fields_differing", bad, 0),
                ("ecc_rate_gap", rate_gap, p["limit_rate"]),
                ("hammer_margin_rel_gap", margin_gap, p["limit_margin"])]

    def control(self, outputs: list) -> list:
        """``check`` with the float64 characterization reference rounded
        to float32 in the program's place (the precision control)."""
        return self.check(outputs, round_to=np.float32)


def _gap(got, ref, relative: bool = False) -> float:
    """Largest |got - ref| (over |ref| with ``relative``); infinite where
    the NaN patterns differ."""
    g = np.asarray(got, np.float64)
    r = np.asarray(ref, np.float64).reshape(g.shape)
    if not np.array_equal(np.isnan(g), np.isnan(r)):
        return float("inf")
    ok = ~np.isnan(r)
    if not ok.any():
        return 0.0
    d = np.abs(g[ok] - r[ok])
    return float(np.max(d / np.abs(r[ok]) if relative else d))
