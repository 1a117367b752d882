"""DDR3L constants of the paper's device under test (a copy of the
program's ``hw`` module, DRAM part only)."""
from __future__ import annotations

# --------------------------------------------------------------------------
# DDR3L (the paper's device under test)
# --------------------------------------------------------------------------
VDD_NOMINAL = 1.35               # V  (JESD79-3-1A.01 nominal)
VDD_SPEC_MIN = 1.283             # V  (DDR3L allowed deviation, Section 2.3)
VDD_SPEC_MAX = 1.45              # V
VDD_SWEEP_FLOOR = 0.90           # V  (lowest voltage evaluated by the paper)

DDR3L_DATA_RATE = 1600           # MT/s (DIMM rating)
FPGA_DATA_RATE = 800             # MT/s (test-platform limit, Section 3)
DDR3L_CLK_NS = 1.25              # ns per controller clock at 1600 MT/s
BEAT_BITS = 64                   # data-bus width per beat (Section 4.4)
CACHE_LINE_BYTES = 64
BEATS_PER_LINE = CACHE_LINE_BYTES * 8 // BEAT_BITS   # 8 beats / line
LINES_PER_ROW = 128              # 8 KB row = 128 x 64 B lines (Section 2.1)

# One cache-line burst on the data bus: 8 beats at two beats per clock
# (DDR), in ns — and the DIMM's peak bandwidth at the rated transfer
# speed across the 2-channel system (Table 2): 2 * 1600 MT/s * 8 B/beat.
# These parameterize the benign pad rows of the sweep-solve feature
# packing and the benchmark/tuner synthetic inputs (one source of truth;
# they used to be the magic numbers 5.0 / 25.6).
LINE_TRANSFER_NS = BEATS_PER_LINE * DDR3L_CLK_NS / 2          # 5.0 ns
PEAK_BW_GBPS = 2 * DDR3L_DATA_RATE * (BEAT_BITS // 8) / 1000.0  # 25.6 GB/s

BANKS_PER_RANK = 8
ROWS_PER_BANK = 32 * 1024        # Section 4.3 (32K rows/bank)
DIMM_BYTES = 2 * 1024**3         # 2 GB DIMMs (Table 1)
CHIPS_PER_DIMM = 4               # x16 chips (Table 7)

REFRESH_INTERVAL_MS = 64.0       # DDR3 worst-case retention assumption
GUARDBAND = 1.38                 # manufacturer latency guardband (Section 6.1)

# Host CPU of the DDR3L system (Table 2): 4x ARM Cortex-A9-class @ 2 GHz.
# One source of truth — memsim.core, memsim.energy and the engine's
# vectorized energy math all derive from these (they used to hard-code
# ``2.0e9`` / ``n_cores=4`` independently).
CPU_FREQ_GHZ = 2.0
CPU_CORES = 4

# Standard DDR3L timings in ns (Table 1): tRCD / tRP / tRAS.
T_RCD_STD = 13.75
T_RP_STD = 13.75
T_RAS_STD = 35.0
T_CL_STD = 13.75                 # CAS latency (DRAM-internal, not retimable)
T_CWL_STD = 10.0

# Reliable minimum latencies found at 20 C / 1.35 V (Section 4.1).
T_RCD_RELIABLE_MIN = 10.0
T_RP_RELIABLE_MIN = 10.0

# Experimental platform latency granularity (SoftMC), ns.
PLATFORM_LATENCY_STEP = 2.5

# DRAM power model split (array vs peripheral), used by memsim.energy.
# Calibrated so the baseline system-energy breakdown reproduces Fig. 15.
ARRAY_POWER_FRACTION = 0.60      # fraction of DRAM power in the array domain
