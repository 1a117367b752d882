"""Sharding rules + a real multi-device integration test (subprocess with
forced host devices) + one real dry-run cell."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import base
from repro.models import lm
from repro.parallel.sharding import Sharder, ShardingPolicy, default_policy


class FakeMesh:
    """Shape-only stand-in so spec rules are testable without 256 devices."""
    def __init__(self, shape_map):
        self.shape = shape_map
        self.axis_names = tuple(shape_map)


def _sharder(arch, policy=None):
    cfg = base.get_config(arch)
    mesh = FakeMesh({"data": 16, "model": 16})
    policy = policy or default_policy(cfg, 16)
    return cfg, Sharder(mesh, cfg, policy)


@pytest.mark.parametrize("arch", base.ARCH_IDS)
def test_param_specs_divisible(arch):
    """Every sharded dim divides by its mesh axes (no silent padding)."""
    cfg, sh = _sharder(arch)
    params = lm.abstract_params(cfg)
    specs = sh.param_specs(params)
    leaves = jax.tree.leaves(params)
    spec_leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(leaves) == len(spec_leaves)
    for leaf, spec in zip(leaves, spec_leaves):
        for dim, part in zip(leaf.shape, spec):
            if part is None:
                continue
            size = 1
            for ax in (part if isinstance(part, tuple) else (part,)):
                size *= 16
            assert dim % size == 0, (arch, leaf.shape, spec)


def test_policy_selection():
    assert default_policy(base.get_config("olmoe_1b_7b"), 16).attn_mode == "heads"
    assert default_policy(base.get_config("qwen3_4b"), 16).attn_mode == "seq"
    assert default_policy(base.get_config("gemma3_1b"), 16).attn_mode == "seq"
    assert default_policy(base.get_config("dbrx_132b"), 16).fsdp


def test_zero1_adds_data_axis():
    cfg, sh = _sharder("qwen3_4b")
    params = lm.abstract_params(cfg)
    pspecs = jax.tree.leaves(sh.param_specs(params),
                             is_leaf=lambda x: isinstance(x, P))
    ospecs = jax.tree.leaves(sh.opt_specs(params),
                             is_leaf=lambda x: isinstance(x, P))
    def uses_data(spec):
        return any("data" in ((s,) if not isinstance(s, tuple) else s)
                   for s in spec if s is not None)
    gained = sum(uses_data(o) and not uses_data(p)
                 for p, o in zip(pspecs, ospecs))
    assert gained > 0


MULTIDEV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"   # skip the libtpu probe/timeout
    import jax, jax.numpy as jnp, numpy as np, sys
    sys.path.insert(0, "src")
    from repro.configs import base
    from repro.parallel import steps as steps_lib
    from repro.models import lm
    from repro.optim import adamw
    from repro.configs.base import ShapeConfig
    import dataclasses

    from repro.launch import mesh as mesh_lib
    mesh = mesh_lib.make_mesh((4, 2), ("data", "model"))
    cfg = base.get_config("smollm_135m", "smoke")
    cfg = dataclasses.replace(cfg, n_heads=4, n_kv_heads=2, remat=True)
    shape = ShapeConfig("tiny_train", 64, 8, "train")
    bundle = steps_lib.build_step(cfg, shape, mesh)
    compiled = bundle.lower(mesh).compile()
    # run for real with concrete sharded values
    params = lm.init_params(jax.random.key(0), cfg)
    opt = adamw.init_state(params)
    tok = jax.random.randint(jax.random.key(1), (8, 64), 0, cfg.vocab)
    params = jax.device_put(params, bundle.in_shardings[0])
    opt = jax.device_put(opt, bundle.in_shardings[1])
    batch = jax.device_put({"tokens": tok, "labels": tok},
                           bundle.in_shardings[2])
    p2, o2, m = compiled(params, opt, batch)
    loss = float(m["loss"])
    assert np.isfinite(loss), loss
    # decode cell on the same mesh
    dshape = ShapeConfig("tiny_decode", 128, 8, "decode")
    db = steps_lib.build_step(cfg, dshape, mesh)
    dc = db.lower(mesh).compile()
    print("MULTIDEV_OK", loss)
""")


@pytest.mark.slow
def test_multidevice_train_and_decode_run():
    """8 host devices, (4 data x 2 model) mesh: compile AND execute a real
    sharded train step + compile a decode step."""
    env = dict(os.environ)
    # host-device tests run on the forced-CPU backend; probing a (absent)
    # TPU through libtpu first wastes minutes per subprocess
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", MULTIDEV_SCRIPT],
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(__file__)),
                         env=env)
    assert "MULTIDEV_OK" in out.stdout, out.stderr[-3000:]


@pytest.mark.slow
def test_dryrun_production_cell(tmp_path):
    """One real production-mesh (16x16=256 devices) dry-run cell end-to-end
    via the launcher (compile + roofline extraction).  The artifact goes to
    a fresh temporary directory, so the run never passes on a cached one
    and leaves the checkout as it was."""
    env = dict(os.environ)
    # host-device tests run on the forced-CPU backend; probing a (absent)
    # TPU through libtpu first wastes minutes per subprocess
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(__file__))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "gemma3-1b",
         "--shape", "decode_32k", "--out", str(tmp_path)],
        capture_output=True, text=True, timeout=600, cwd=repo,
        env={**env, "PYTHONPATH": "src"})
    assert "OK" in out.stdout, out.stderr[-3000:]
    art = os.path.join(tmp_path, "gemma3-1b_decode_32k_256.json")
    with open(art) as f:
        d = json.load(f)
    assert d["status"] == "ok"
    assert d["roofline"]["hlo_flops"] > 0
