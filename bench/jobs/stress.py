"""Batch job: the paper's Test-1 campaign and the RowHammer stress.

One job runs ``test1.run_batch`` over every DIMM of the configuration at
the cell's voltages and pattern groups, then ``test1.run_hammer_batch``
at the cell's voltages and hammer counts, both at the configuration's
geometry.  Job ``i`` of a run draws its Test-1 seed from the run's seed,
so every job does the same work on different random bits.

The window keeps ``check_jobs`` jobs' outputs, drawn from the seed, and
``check`` recomputes every lane of them with the plain reference in
``bench/ref/stress.py`` and counts the lanes whose bit-error count,
erroneous-line count or error-row map differ: the comparison is exact.
"""
from __future__ import annotations

import numpy as np

# Test-1 seeds enter the program's key chain as seed * 1000003 + index,
# which must stay a 32-bit signed integer.
SEED_SPAN = 2000


class Job:
    entries = ("test1", "hammer")

    def __init__(self, config: dict, params: dict, seed: int):
        import jax

        from repro import engine
        self.jax = jax
        self.config, self.params = config, params
        self.seed = seed
        self.keep_jobs = params["check_jobs"]
        self.grid = engine.DimmGrid.from_population(config["modules"])
        rng = np.random.default_rng(seed)
        self.seed0 = int(rng.integers(0, SEED_SPAN))
        self.check_rng = np.random.default_rng(rng.integers(2**63))
        self.kw = dict(banks=config["banks"], rows=config["rows_per_bank"],
                       row_bytes=config["row_bytes"],
                       nplanes=config["nplanes"],
                       inject_impl=params["inject_impl"])
        self.pattern_groups = [tuple(g) for g in params["pattern_groups"]]

    # ---- the timed job ----------------------------------------------------
    def _run(self, seed: int) -> dict:
        from repro.engine import test1
        ann = self.jax.profiler.TraceAnnotation
        p = self.params
        with ann("bench.entry.test1"):
            t1 = test1.run_batch(self.grid, p["voltages"],
                                 self.pattern_groups, rounds=p["rounds"],
                                 t_rcd=self.config["t_rcd_ns"],
                                 t_rp=self.config["t_rp_ns"],
                                 temp_c=self.config["temp_c"], seed=seed,
                                 **self.kw)
        with ann("bench.entry.hammer"):
            ham = test1.run_hammer_batch(self.grid, p["hammer_voltages"],
                                         p["hammer_counts"],
                                         rounds=p["rounds"], seed=seed,
                                         **self.kw)
        return {"seed": seed, "test1": t1, "hammer": ham}

    def warm(self) -> None:
        self._run(self.seed0)

    def run(self, i: int) -> dict:
        return self._run(self.seed0 + 1 + i)

    # ---- work counted from the cell's shapes ------------------------------
    def lanes(self) -> tuple:
        p = self.params
        d = len(self.config["modules"])
        n_t1 = d * len(p["voltages"]) * len(p["pattern_groups"]) * p["rounds"]
        n_ham = (d * len(p["hammer_voltages"]) * len(p["hammer_counts"])
                 * p["rounds"])
        return n_t1, n_ham

    def plane_bytes(self) -> int:
        """Bytes one read of every lane's data plane takes per job:
        lanes x banks x rows x row_bytes."""
        c = self.config
        return (sum(self.lanes()) * c["banks"] * c["rows_per_bank"]
                * c["row_bytes"])

    # ---- correctness ------------------------------------------------------
    def reference(self, seed: int, p_dtype=np.float32) -> dict:
        """Reference counts of job ``seed``'s lanes, shaped as the
        program's [D, V, P|H, R] outputs."""
        import jax
        from bench.ref import stress as ref

        c, p = self.config, self.params
        dimms = ref.dimms()
        cpu = jax.local_devices(backend="cpu")[0]
        words = c["row_bytes"] // 4
        out = {}
        for name in ("test1", "hammer"):
            probs, keys, pats = [], [], []
            for mod in c["modules"]:
                dimm = dimms[mod]
                with jax.default_device(cpu):
                    kd = {r: ref.bank_keys(dimm.index, seed + r, c["banks"])
                          for r in range(p["rounds"])}
                if name == "test1":
                    grid = [(v, g) for v in p["voltages"]
                            for g in self.pattern_groups]
                else:
                    grid = [(v, h) for v in p["hammer_voltages"]
                            for h in p["hammer_counts"]]
                for v, x in grid:
                    if name == "test1":
                        pw = ref.test1_word_probs(dimm, v, c["rows_per_bank"],
                                                  c["t_rcd_ns"], c["t_rp_ns"],
                                                  c["temp_c"])
                        pat = x
                    else:
                        pw = ref.hammer_word_probs(dimm, v, x,
                                                   c["rows_per_bank"])
                        pat = ref.HAMMER_GROUP
                    for r in range(p["rounds"]):
                        probs.append(pw)
                        keys.append(kd[r])
                        pats.append([ref.DATA_PATTERNS[pat[0]],
                                     ref.DATA_PATTERNS[pat[1]]])
            res = ref.run_lanes(np.stack(probs), np.stack(keys),
                                np.array(pats, np.uint32), words=words,
                                nplanes=c["nplanes"], p_dtype=p_dtype)
            out[name] = res
        return out

    def picks(self, n_jobs: int) -> list:
        """Positions among the window's kept outputs to check, drawn from
        the seed."""
        return sorted(int(i) for i in self.check_rng.choice(
            n_jobs, min(self.params["check_jobs"], n_jobs), replace=False))

    def _readings(self, outputs: list, got) -> list:
        bad = {"test1": 0, "hammer": 0}
        for i in self.picks(len(outputs)):
            seed = outputs[i]["seed"]
            ref = self.reference(seed)
            mine = got(outputs[i])
            for name in bad:
                bad[name] += lane_mismatches(mine[name], ref[name])
        return [(f"{name}_lanes_differing", bad[name], 0) for name in bad]

    def check(self, outputs: list) -> list:
        """``[(name, value, limit), ...]``: lanes of the checked jobs that
        differ from the reference, per stage; exact, so the limit is 0."""
        return self._readings(outputs, lambda o: o)

    def control(self, outputs: list) -> list:
        """``check`` with the reference in the program's place, its
        float32 probabilities rounded to bfloat16 (the precision
        control)."""
        from types import SimpleNamespace

        import ml_dtypes
        return self._readings(outputs, lambda o: {
            k: SimpleNamespace(**v) for k, v in
            self.reference(o["seed"], ml_dtypes.bfloat16).items()})


def lane_mismatches(got, ref: dict) -> int:
    """Lanes whose bit errors, erroneous lines or error-row map differ
    between a program result (flat lane order D, V, P|H, R) and the
    reference's per-lane arrays."""
    n = ref["bit_errors"].shape[0]
    bits = np.asarray(got.bit_errors).reshape(n)
    lines = np.asarray(got.erroneous_lines).reshape(n)
    rows = np.asarray(got.error_rows).reshape((n,) + ref["error_rows"].shape[1:])
    diff = ((bits != ref["bit_errors"]) | (lines != ref["erroneous_lines"])
            | (rows != ref["error_rows"]).reshape(n, -1).any(axis=1))
    return int(diff.sum())
