"""Roofline-term extraction from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), in seconds:

  compute    = HLO_FLOPs   / (chips * peak_FLOP/s)
  memory     = HLO_bytes   / (chips * HBM_bw)
  collective = coll_bytes  / (chips * link_bw)

``HLO_FLOPs`` / ``bytes accessed`` come from ``compiled.cost_analysis()``
(the step functions are lowered with *unrolled* layer loops so loop bodies
are fully counted — validated by the scan-vs-unroll spike).  Collective
bytes are parsed from the optimized HLO: the summed operand sizes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute.

MODEL_FLOPS uses 6·N·D (dense) or 6·N_active·D (MoE) for training and
2·N(_active) per generated token for decode; the ratio MODEL/HLO flags
remat or redundancy waste.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

import numpy as np

from repro import hw
from repro.configs.base import ModelConfig, ShapeConfig

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "token": 0, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# matches e.g. "bf16[256,4096,2304]{2,1,0}" or "f32[8]"
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum operand bytes of every collective op in the (SPMD) HLO.

    The HLO is the per-device program; operand shapes are per-shard, so the
    sum approximates bytes each device moves.  Multiplied by chips for the
    global number, then divided back per the roofline denominator."""
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"^(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|\S+)\s+"
                     r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
                     r"collective-permute)", s)
        if not m:
            continue
        result_type, op = m.group(1), m.group(2)
        if s.startswith("//"):
            continue
        out[op] += _shape_bytes(result_type)
        counts[op] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    out["counts"] = counts
    return out


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """6·N·D (train) / 2·N·tokens (decode/prefill fwd-only), N = active."""
    n_active = active_params(cfg)
    tokens = shape.global_batch * shape.seq_len
    if shape.kind == "train":
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.global_batch      # one token per request


def total_params(cfg: ModelConfig) -> float:
    return _params(cfg, active_only=False)


def active_params(cfg: ModelConfig) -> float:
    return _params(cfg, active_only=True)


def _params(cfg: ModelConfig, active_only: bool) -> float:
    d, hd = cfg.d_model, cfg.head_dim
    n = 0.0
    for i in range(cfg.n_layers):
        kind = cfg.layer_kind(i)
        if kind == "M":
            din, ns, h = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
            n += d * (2 * din + 2 * ns + h) + din * d
            n += din * cfg.conv_width + 2 * ns * cfg.conv_width
        elif kind == "S":
            pass                                   # shared weights (below)
        else:
            n += d * hd * (cfg.n_heads + 2 * cfg.n_kv_heads) \
                + cfg.n_heads * hd * d
            if cfg.n_experts:
                e = cfg.top_k if active_only else cfg.n_experts
                n += e * 3 * d * cfg.d_ff + d * cfg.n_experts
            else:
                n += 3 * d * cfg.d_ff
    if "S" in cfg.layer_pattern:
        n_shared_apps = sum(1 for i in range(cfg.n_layers)
                            if cfg.layer_kind(i) == "S")
        shared = d * hd * (cfg.n_heads + 2 * cfg.n_kv_heads) \
            + cfg.n_heads * hd * d + 3 * d * cfg.shared_d_ff
        n += shared * (n_shared_apps if active_only else 1)
    if cfg.family == "encdec":
        n += cfg.n_enc_layers * (d * hd * (cfg.n_heads + 2 * cfg.n_kv_heads)
                                 + cfg.n_heads * hd * d + 3 * d * cfg.d_ff)
        n += cfg.n_layers * (d * hd * (cfg.n_heads + 2 * cfg.n_kv_heads)
                             + cfg.n_heads * hd * d) * 0  # cross counted below
        n += cfg.n_layers * (d * hd * (cfg.n_heads + 2 * cfg.n_kv_heads)
                             + cfg.n_heads * hd * d)       # cross-attn
    n += cfg.vocab * d * (1 if cfg.tie_embeddings else 2)
    return n


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    coll_bytes_per_chip: float
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops: float
    per_device_bytes: int

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Achievable fraction of the compute roofline, assuming perfect
        overlap: compute / max(all three)."""
        return self.compute_s / self.bound_s if self.bound_s else 0.0

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d.update(dominant=self.dominant,
                 useful_flops_ratio=self.useful_flops_ratio,
                 roofline_fraction=self.roofline_fraction)
        return d


def _spec_denom(spec, mesh) -> int:
    denom = 1
    for part in spec:
        if part is None:
            continue
        for ax in (part if isinstance(part, tuple) else (part,)):
            denom *= mesh.shape[ax]
    return denom


def _sharded_bytes(abstract_tree, spec_tree, mesh) -> int:
    import jax
    from jax.sharding import PartitionSpec as P
    total = 0
    leaves = jax.tree.leaves(abstract_tree)
    specs = jax.tree.leaves(spec_tree, is_leaf=lambda x: isinstance(x, P))
    for leaf, spec in zip(leaves, specs):
        total += leaf.size * leaf.dtype.itemsize // _spec_denom(spec, mesh)
    return int(total)


def analytic_memory(cfg: ModelConfig, shape: ShapeConfig, mesh,
                    policy=None) -> dict:
    """Exact per-device steady-state bytes (params/opt/caches from the real
    sharding specs) + a coarse activation estimate.  This is the number to
    judge HBM fit by — the XLA CPU backend's ``temp_size_in_bytes`` uses the
    CPU scheduler's buffer assignment, which does not model HBM reuse (it
    wildly over-reports; see EXPERIMENTS.md §Dry-run note)."""
    from repro.models import lm as lm_mod
    from repro.optim import adamw as adamw_mod
    from repro.parallel.sharding import Sharder, default_policy as dp_fn
    policy = policy or dp_fn(cfg, mesh.shape["model"])
    sh = Sharder(mesh, cfg, policy)
    params_abs = lm_mod.abstract_params(cfg)
    p_specs = sh.param_specs(params_abs)
    out = {"params": _sharded_bytes(params_abs, p_specs, mesh)}
    dp = sh.dp
    b_loc = max(shape.global_batch // dp, 1)
    d = cfg.d_model
    if shape.kind == "train":
        opt_specs = sh.opt_specs(params_abs)
        # m, v, master are f32: each is 2x the bf16 param bytes, ZeRO-sharded
        out["optimizer"] = 3 * 2 * _sharded_bytes(params_abs, opt_specs, mesh)
        out["grads"] = out["params"]
        # remat: layer-boundary residuals + logits (f32) + one layer live;
        # gradient accumulation divides live activations by the microbatch
        # count
        mb = max(getattr(policy, "microbatches", 1), 1)
        acts = cfg.n_layers * b_loc * shape.seq_len * d * 2 / mb
        logits = b_loc * shape.seq_len * cfg.vocab // max(sh.tp, 1) * 4 * 2 / mb
        out["activations"] = int(acts + logits)
    else:
        enc_len = shape.seq_len if cfg.family == "encdec" else 0
        caches_abs = lm_mod.abstract_caches(shape.global_batch, shape.seq_len,
                                            cfg, enc_len=enc_len)
        c_specs = sh.cache_specs(caches_abs, shape.global_batch)
        out["kv_cache"] = _sharded_bytes(caches_abs, c_specs, mesh)
        if shape.kind == "prefill":
            out["activations"] = int(4 * b_loc * shape.seq_len * d * 2)
        else:
            out["activations"] = int(8 * b_loc * d * 2)
    out["total"] = int(sum(v for k, v in out.items()))
    return out


import jax          # noqa: E402  (used by _sharded_bytes/analytic_memory)
import jax.numpy as jnp  # noqa: E402

jnp_f32 = jnp.float32

# HBM-visible boundary tensors per layer per token, assuming the TPU target
# fuses elementwise chains and attention runs as a flash kernel (scores
# never round-trip HBM).  fwd ~6 tensors of size D (x, q/k/v block in, attn
# out, mlp hidden in/out, residual), bwd ~2x fwd including remat recompute.
FWD_TENSORS = 6
BWD_TENSORS = 12


def analytic_hbm_bytes(cfg: ModelConfig, shape: ShapeConfig, mesh,
                       policy=None) -> dict:
    """Modeled per-device HBM traffic per step (the roofline memory term).

    The XLA CPU backend's ``bytes accessed`` counts every unfused HLO op's
    operands — an upper bound ~100x above real TPU HBM traffic, so the
    memory term is modeled instead: weight/optimizer/gradient streams are
    exact (from the sharding specs); activation traffic uses the boundary-
    tensor counts above; decode adds one full KV-cache read per step.
    """
    from repro.models import lm as lm_mod
    from repro.parallel.sharding import Sharder, default_policy as dp_fn
    policy = policy or dp_fn(cfg, mesh.shape["model"])
    sh = Sharder(mesh, cfg, policy)
    params_abs = lm_mod.abstract_params(cfg)
    if getattr(policy, "weight_dtype", "bfloat16") == "int8" \
            and shape.kind == "decode":
        params_abs = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape, jnp.int8)
            if l.ndim >= 2 and jnp.issubdtype(l.dtype, jnp.floating) else l,
            params_abs)
    p_bytes = _sharded_bytes(params_abs, sh.param_specs(params_abs), mesh)
    dp = sh.dp
    b_loc = max(shape.global_batch / dp, shape.global_batch / dp)
    tokens_loc = b_loc * shape.seq_len
    d = cfg.d_model
    out = {}
    if shape.kind == "train":
        opt_bytes = 6 * _sharded_bytes(params_abs,
                                       sh.opt_specs(params_abs), mesh)
        out["weights"] = 3 * p_bytes             # fwd read, bwd read, write
        out["optimizer"] = 2 * opt_bytes         # read + write m/v/master
        out["grads"] = 2 * p_bytes
        out["activations"] = int((FWD_TENSORS + BWD_TENSORS) * cfg.n_layers
                                 * tokens_loc * d * 2)
        v_shard = cfg.vocab // max(sh.tp, 1)
        out["logits"] = int(3 * tokens_loc * v_shard * 4)
    elif shape.kind == "prefill":
        out["weights"] = p_bytes
        out["activations"] = int(FWD_TENSORS * cfg.n_layers * tokens_loc * d * 2)
        enc_len = shape.seq_len if cfg.family == "encdec" else 0
        caches_abs = lm_mod.abstract_caches(shape.global_batch, shape.seq_len,
                                            cfg, enc_len=enc_len)
        out["cache_write"] = _sharded_bytes(
            caches_abs, sh.cache_specs(caches_abs, shape.global_batch), mesh)
    else:                                        # decode: one token
        out["weights"] = p_bytes                 # every weight read per step
        enc_len = shape.seq_len if cfg.family == "encdec" else 0
        caches_abs = lm_mod.abstract_caches(shape.global_batch, shape.seq_len,
                                            cfg, enc_len=enc_len)
        if getattr(policy, "kv_cache_dtype", "bfloat16") == "int8":
            import jax.tree_util as jtu
            def _kv8(path, leaf):
                name = getattr(path[-1], "key", "")
                if name in ("k", "v", "cross_k", "cross_v"):
                    return jax.ShapeDtypeStruct(leaf.shape, jnp.int8)
                return leaf
            caches_abs = jtu.tree_map_with_path(_kv8, caches_abs)
        out["cache_read"] = _sharded_bytes(
            caches_abs, sh.cache_specs(caches_abs, shape.global_batch), mesh)
        out["activations"] = int(FWD_TENSORS * cfg.n_layers * b_loc * d * 2)
    out["total"] = int(sum(out.values()))
    return out


def analyze(cfg: ModelConfig, shape: ShapeConfig, mesh_name: str, chips: int,
            cost: dict, hlo_text: str, memstats=None,
            spec: hw.TpuSpec = hw.TPU_V5E) -> Roofline:
    # cost_analysis on the SPMD module reports per-device numbers on CPU
    flops_per_dev = float(cost.get("flops", 0.0))
    bytes_per_dev = float(cost.get("bytes accessed", 0.0))
    coll = collective_bytes(hlo_text)
    per_dev_bytes = int(getattr(memstats, "temp_size_in_bytes", 0) or 0) + \
        int(getattr(memstats, "argument_size_in_bytes", 0) or 0)
    return Roofline(
        arch=cfg.name, shape=shape.name, mesh=mesh_name, chips=chips,
        hlo_flops=flops_per_dev * chips,
        hlo_bytes=bytes_per_dev * chips,
        coll_bytes_per_chip=float(coll["total"]),
        compute_s=flops_per_dev / spec.peak_flops,
        memory_s=bytes_per_dev / spec.hbm_bw,
        collective_s=float(coll["total"]) / spec.ici_bw,
        model_flops=model_flops(cfg, shape),
        per_device_bytes=per_dev_bytes)
