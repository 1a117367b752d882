"""Benchmark profiles (Table 4) and multiprogrammed workload construction.

The paper's Table 4 gives each benchmark's L3 MPKI; the remaining
microarchitectural characteristics (base IPC, row-buffer hit rate, write
fraction, memory-level parallelism) are not published, so they are
synthesized deterministically per benchmark from published-plausible ranges
(seeded by the benchmark name) and then *calibrated at the population level*
against the paper's system results (Figs. 12-15, Table 5).
"""
from __future__ import annotations

import dataclasses
import hashlib

import numpy as np

# Table 4: (name, L3 MPKI)
TABLE4 = [
    ("YCSB-a", 6.66), ("YCSB-b", 5.95), ("YCSB-c", 5.74), ("YCSB-d", 5.30),
    ("YCSB-e", 6.07), ("astar", 3.43), ("bwaves", 19.97), ("bzip2", 8.23),
    ("cactusADM", 6.79), ("calculix", 0.01), ("gamess", 0.01), ("gcc", 3.20),
    ("GemsFDTD", 39.17), ("gobmk", 3.94), ("h264ref", 2.14), ("hmmer", 6.33),
    ("libquantum", 37.95), ("mcf", 123.65), ("milc", 27.91), ("namd", 2.76),
    ("omnetpp", 27.87), ("perlbench", 0.95), ("povray", 0.01),
    ("sjeng", 0.73), ("soplex", 64.98), ("sphinx3", 13.59), ("zeusmp", 4.88),
]

MEM_INTENSIVE_MPKI = 15.0      # the paper's threshold (Section 5.2)


def _unit_hash(name: str, salt: str) -> float:
    h = hashlib.sha256(f"{name}:{salt}".encode()).digest()
    return int.from_bytes(h[:8], "little") / 2.0 ** 64


@dataclasses.dataclass(frozen=True)
class Benchmark:
    name: str
    mpki: float                 # L3 misses per kilo-instruction (Table 4)
    ipc_base: float             # IPC with a perfect (zero-latency) memory
    row_hit_rate: float         # row-buffer hit fraction of misses
    write_frac: float           # fraction of memory traffic that is writes
    bank_parallelism: float     # avg banks usable concurrently (1..8)

    @property
    def memory_intensive(self) -> bool:
        return self.mpki >= MEM_INTENSIVE_MPKI


def _make(name: str, mpki: float) -> Benchmark:
    u1, u2, u3, u4 = (_unit_hash(name, s) for s in "1234")
    # compute-heavy benchmarks run close to machine width; memory-heavy
    # ones have lower inherent IPC even with perfect memory
    ipc_base = 2.4 - 1.3 * (mpki / (mpki + 20.0)) + 0.3 * (u1 - 0.5)
    # streaming benchmarks (high MPKI) tend to have high row locality
    row_hit = 0.45 + 0.35 * (mpki / (mpki + 15.0)) + 0.15 * (u2 - 0.5)
    write_frac = 0.22 + 0.16 * u3
    # memory-level parallelism grows with outstanding misses (Section 5.2:
    # "with more outstanding memory requests, the memory system is more
    # likely to service them in parallel")
    bank_par = 1.0 + 5.5 * (mpki / (mpki + 18.0)) + 0.8 * u4
    return Benchmark(name, mpki, float(np.clip(ipc_base, 0.6, 2.6)),
                     float(np.clip(row_hit, 0.3, 0.92)), write_frac,
                     float(np.clip(bank_par, 1.0, 7.5)))


def benchmarks() -> dict:
    return {name: _make(name, mpki) for name, mpki in TABLE4}


def homogeneous_workloads() -> list:
    """27 four-core workloads: one benchmark replicated on each core."""
    return [(b.name, (b,) * 4) for b in benchmarks().values()]


def heterogeneous_workloads(seed: int = 7) -> list:
    """50 four-core mixes: 10 per memory-intensive fraction in
    {0, 25, 50, 75, 100}% (Section 6.6)."""
    rng = np.random.default_rng(seed)
    bms = list(benchmarks().values())
    mem = [b for b in bms if b.memory_intensive]
    non = [b for b in bms if not b.memory_intensive]
    out = []
    for frac_idx, n_mem in enumerate([0, 1, 2, 3, 4]):
        for w in range(10):
            picks = (list(rng.choice(len(mem), n_mem, replace=True))
                     if n_mem else [])
            cores = [mem[i] for i in picks]
            picks_n = list(rng.choice(len(non), 4 - n_mem, replace=True))
            cores += [non[i] for i in picks_n]
            rng.shuffle(cores)
            name = f"hetero-{n_mem * 25}pct-{w}"
            out.append((name, tuple(cores)))
    return out
