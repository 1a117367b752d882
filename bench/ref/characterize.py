"""Plain reference of the Sections 4-5 characterization of one DIMM.

For each voltage and temperature, from this directory's copy of the DIMM
population model, in float64 on the host: the fraction of cache lines
with errors (Fig. 4), the bit error rate per data pattern (Appendix B),
the platform-quantized minimum tRCD / tRP (Fig. 6), the per-(bank,
row-group) probability that a row, or a line, fails (Fig. 8), and the
expected weak cells per retention time (Fig. 11).
"""
from __future__ import annotations

import numpy as np

from . import chips, hw, timing


def _x_threshold(dimm, op: str, v: float, t_prog: float, temp_c: float):
    req = dimm.required_latency(op, v, temp_c)            # float32
    return (t_prog / req - 1.0) / dimm.cell_sigma


def line_probs(dimm, v: float, t_rcd: float, t_rp: float,
               temp_c: float) -> np.ndarray:
    """P(one cache line fails) per (bank, row-group), [8, 256]."""
    field = dimm.susceptibility
    p_ok = np.ones_like(field)
    for op, t_prog in (("rcd", t_rcd), ("rp", t_rp)):
        p_ok = p_ok * chips._trunc_phi(
            _x_threshold(dimm, op, v, t_prog, temp_c) - field)
    return 1.0 - p_ok


def row_probs(dimm, v: float, t_rcd: float, t_rp: float,
              temp_c: float) -> np.ndarray:
    """P(a row has >= 1 failing line) per (bank, row-group), [8, 256]."""
    field = dimm.susceptibility
    p_ok = np.ones_like(field)
    for op, t_prog in (("rcd", t_rcd), ("rp", t_rp)):
        p_line_ok = chips._trunc_phi(
            _x_threshold(dimm, op, v, t_prog, temp_c) - field)
        p_ok = p_ok * p_line_ok ** hw.LINES_PER_ROW
    return 1.0 - p_ok


def characterize(dimm, voltages, temps, patterns, retention_ms,
                 t_rcd: float = 10.0, t_rp: float = 10.0) -> dict:
    """One DIMM over a V x T grid; arrays keyed like the program's
    single-DIMM characterization results."""
    v = np.atleast_1d(np.asarray(voltages, np.float64))
    ret = np.asarray(retention_ms, np.float64)
    v_, t_ = v.size, len(temps)
    out = {"line_error_fraction": np.zeros((v_, t_)),
           "ber": np.zeros((v_, t_, len(patterns))),
           "t_rcd_min": np.zeros((v_, t_)), "t_rp_min": np.zeros((v_, t_)),
           "row_error_prob": np.zeros((v_, t_, chips.BANKS, 256)),
           "line_error_prob": np.zeros((v_, t_, chips.BANKS, 256)),
           "expected_weak_cells": np.zeros((v_, t_, ret.size))}
    for ti, temp in enumerate(temps):
        temp = float(temp)
        out["line_error_fraction"][:, ti] = dimm.line_error_fraction(
            v, t_rcd, t_rp, temp)
        for op in ("rcd", "rp"):
            out[f"t_{op}_min"][:, ti] = timing.platform_quantize(
                dimm.required_latency(op, v, temp))
        for pi, p in enumerate(patterns):
            out["ber"][:, ti, pi] = dimm.bit_error_rate(v, t_rcd, t_rp, temp,
                                                        p)
        for vi, vv in enumerate(v):
            out["row_error_prob"][vi, ti] = row_probs(dimm, float(vv), t_rcd,
                                                      t_rp, temp)
            out["line_error_prob"][vi, ti] = line_probs(dimm, float(vv),
                                                        t_rcd, t_rp, temp)
            out["expected_weak_cells"][vi, ti] = chips.expected_weak_cells(
                ret, temp, float(vv))
    return out
