"""Plain reference of the operator fleet: DIMMs resampled from Table 7,
their ECC-aware safe-voltage tables, and the Voltron controller over them.

1. ``population``: DIMM ``i`` of a fleet of ``n`` copies the Table 7 row
   ``np.random.default_rng(seed).integers(31, size=n)[i]`` under the name
   ``<row>.r<i>`` (four digits at least) and the index ``31 + i``, which
   seeds its own susceptibility field and latency scale.
2. ``ecc_tables``: ``tables.ecc_tables`` over DIMM objects (that one looks
   DIMMs up by Table 7 name), in the same order of steps: the error-free
   latency floor, SECDED admission, the RowHammer floor.
3. The controller is ``fleet.controller``, unchanged.
"""
from __future__ import annotations

import numpy as np

from . import chips, circuit, hw
from .fleet import (HAMMER_FIELD_SENS, HAMMER_HC0, HAMMER_V_SENS,
                    HAMMER_WINDOW_MS, candidate_voltages, min_latency)
from .tables import MAX_RESIDUAL, MAX_SILENT, PROBE_NS, SUFFICIENCY


def population(n: int, seed: int) -> list:
    rows = np.random.default_rng(seed).integers(len(chips.TABLE7), size=n)
    digits = max(4, len(str(n - 1)))
    out = []
    for i, r in enumerate(rows):
        module, vendor, date, die, vmin = chips.TABLE7[int(r)]
        out.append(chips.DIMM(f"{module}.r{i:0{digits}d}", vendor, date, die,
                              vmin, len(chips.TABLE7) + i))
    return out


def ecc_tables(dimms, temp_c: float = 20.0) -> dict:
    """Per-DIMM rows, as ``tables.ecc_tables`` gives them, of ``dimms``."""
    cand = candidate_voltages()
    t_ras = circuit.timings_for_voltages(cand)[:, 2]
    d_, k_ = len(dimms), cand.size
    timings = np.full((d_, k_, 3), np.nan)
    rates = {k: np.zeros((d_, k_)) for k in ("correctable", "detectable",
                                              "silent")}
    margin = np.full((d_, k_), np.nan)
    for i, dimm in enumerate(dimms):
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
    if not valid[:, -1].all():
        raise ValueError("the 1.35 V fallback must be safe on every DIMM")
    return {"modules": tuple(d.module for d in dimms), "cand_v": cand,
            "timings": timings, "valid": valid, "hammer_margin": margin,
            "lat_feat": timings[:, :-1, 1] + timings[:, :-1, 2],
            **{k: np.where(valid, r, np.nan) for k, r in rates.items()}}
