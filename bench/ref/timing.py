"""DDR3L timing-parameter bookkeeping.

The memory controller programs DRAM operations in integer multiples of the
controller clock (1.25 ns at DDR3L-1600).  Manufacturers add a ~38% guardband
on top of the *inherent* (circuit) latency before quantizing — Section 6.1 of
the paper describes exactly this procedure for Table 3, and we reuse it.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from . import hw


@dataclasses.dataclass(frozen=True)
class TimingParams:
    """One set of the three retimable DRAM operation latencies, in ns."""

    t_rcd: float = hw.T_RCD_STD
    t_rp: float = hw.T_RP_STD
    t_ras: float = hw.T_RAS_STD

    @property
    def t_rc(self) -> float:
        """Row-cycle time: ACT -> ACT to the same bank."""
        return self.t_ras + self.t_rp


# The reliable minimum at nominal voltage / 20 C found experimentally in
# Section 4.1 (10 ns tRCD/tRP).  tRAS is kept at the standard value for
# Test-1-style sweeps because the paper's test overlaps tRAS with the column
# reads (footnote 8).
RELIABLE_MIN_NOMINAL = TimingParams(
    t_rcd=hw.T_RCD_RELIABLE_MIN, t_rp=hw.T_RP_RELIABLE_MIN, t_ras=hw.T_RAS_STD
)


def guardband_and_quantize(raw_ns, guard: float = hw.GUARDBAND,
                           clk_ns: float = hw.DDR3L_CLK_NS):
    """Apply the manufacturer guardband and round up to the controller clock.

    This is the exact procedure the paper uses to turn SPICE latencies into
    Table 3: ``ceil(raw * 1.38 / 1.25) * 1.25``.
    """
    raw_ns = np.asarray(raw_ns, dtype=np.float64)
    return np.ceil(raw_ns * guard / clk_ns - 1e-9) * clk_ns


def platform_quantize(raw_ns, step: float = hw.PLATFORM_LATENCY_STEP):
    """Round *up* to the SoftMC platform's 2.5 ns latency granularity.

    The FPGA platform can only program latencies on a 2.5 ns grid
    (Section 4.2), so a measured ``tRCD_min`` of 10 ns means the true value
    lies in (7.5, 10].
    """
    raw_ns = np.asarray(raw_ns, dtype=np.float64)
    return np.ceil(raw_ns / step - 1e-9) * step
