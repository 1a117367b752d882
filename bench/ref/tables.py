"""Plain reference of the ECC-aware safe-voltage tables.

For every DIMM and Algorithm-1 candidate voltage, in order:

1. the error-free latency floor: the smallest (tRCD, tRP) <= 20 ns with
   no failing line (``fleet.min_latency``), at the circuit model's tRAS;
2. ECC admission (SECDED): a candidate without an error-free latency is
   admitted at the reliable-minimum 10 ns if it lies above the vendor's
   recovery and signal-integrity floors and SECDED handles its residual
   beat errors (Fig. 9): it corrects at least half of the erroneous
   beats, or the silent rate is <= 1e-5 and detected + silent <= 1e-4;
   the correctable / detectable / silent rates are recorded at every
   candidate's own timings;
3. the RowHammer floor: the worst cell's threshold over the activations
   one 0.25 ms refresh window holds at the candidate's timings must be
   >= 1.
"""
from __future__ import annotations

import numpy as np

from . import chips, circuit, hw
from .fleet import (HAMMER_FIELD_SENS, HAMMER_HC0, HAMMER_V_SENS,
                    HAMMER_WINDOW_MS, candidate_voltages, min_latency)

SUFFICIENCY = 0.5
MAX_SILENT = 1e-5
MAX_RESIDUAL = 1e-4
PROBE_NS = 10.0


def ecc_tables(modules, temp_c: float = 20.0) -> dict:
    pop = {d.module: d for d in chips.population()}
    cand = candidate_voltages()
    t_ras = circuit.timings_for_voltages(cand)[:, 2]
    d_, k_ = len(modules), cand.size
    timings = np.full((d_, k_, 3), np.nan)
    rates = {k: np.zeros((d_, k_)) for k in ("correctable", "detectable",
                                              "silent")}
    margin = np.full((d_, k_), np.nan)
    for i, m in enumerate(modules):
        dimm = pop[m]
        vm = circuit.VENDORS[dimm.vendor]
        field_max = float(np.max(dimm.susceptibility))
        for k, v in enumerate(cand):
            lat = min_latency(dimm, float(v), temp_c=temp_c)
            t_rcd, t_rp = lat if lat is not None else (PROBE_NS, PROBE_NS)
            dist = dimm.beat_error_distribution(float(v), t_rcd, t_rp, temp_c)
            one, two, many = (float(np.asarray(dist[c])[0])
                              for c in ("one", "two", "many"))
            rates["correctable"][i, k] = one
            rates["detectable"][i, k] = two
            rates["silent"][i, k] = many
            if lat is None:
                bad = one + two + many
                ratio = one / max(bad, 1e-300) if bad > 0.0 else 1.0
                floors = v >= vm.recovery_floor and v >= vm.fail_floor
                handled = (bad <= 0.0 or ratio >= SUFFICIENCY
                           or (many <= MAX_SILENT
                               and two + many <= MAX_RESIDUAL))
                if not (floors and handled):
                    continue
            timings[i, k] = (t_rcd, t_rp, t_ras[k])
            threshold = HAMMER_HC0 * np.power(10.0,
                HAMMER_V_SENS * (v - hw.VDD_NOMINAL) / chips.DEFICIT_RANGE_V
                - HAMMER_FIELD_SENS * field_max)
            margin[i, k] = threshold / (HAMMER_WINDOW_MS * 1e6
                                        / (t_ras[k] + t_rp))
            if margin[i, k] < 1.0:
                timings[i, k] = np.nan
    valid = np.isfinite(timings).all(axis=-1)
    return {"modules": tuple(modules), "cand_v": cand, "timings": timings,
            "valid": valid, "hammer_margin": margin,
            "lat_feat": timings[:, :-1, 1] + timings[:, :-1, 2],
            **{k: np.where(valid, r, np.nan) for k, r in rates.items()}}
