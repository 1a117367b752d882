"""Roofline analyzer: HLO collective parsing + FLOP accounting."""
import numpy as np
import pytest

from repro import hw
from repro.configs import base
from repro.roofline import analyze

SYNTH_HLO = """
HloModule jit_step

fused_computation {
  p0 = bf16[8,4096,2304]{2,1,0} parameter(0)
  ROOT t = bf16[8,4096,2304]{2,1,0} tanh(p0)
}

ENTRY main {
  x = bf16[8,4096,2304]{2,1,0} parameter(0)
  ar = bf16[8,4096,2304]{2,1,0} all-reduce(x), replica_groups={}, to_apply=add
  ag = f32[16,128]{1,0} all-gather(y), dimensions={0}
  cp = u32[64]{0} collective-permute(z), source_target_pairs={{0,1}}
  ROOT out = bf16[8,4096,2304]{2,1,0} tanh(ar)
}
"""


def test_collective_parser_counts_and_bytes():
    c = analyze.collective_bytes(SYNTH_HLO)
    assert c["counts"]["all-reduce"] == 1
    assert c["counts"]["all-gather"] == 1
    assert c["counts"]["collective-permute"] == 1
    assert c["all-reduce"] == 8 * 4096 * 2304 * 2
    assert c["all-gather"] == 16 * 128 * 4
    assert c["collective-permute"] == 64 * 4
    assert c["total"] == sum(c[k] for k in
                             ("all-reduce", "all-gather", "reduce-scatter",
                              "all-to-all", "collective-permute"))


def test_collective_parser_ignores_non_collectives():
    assert analyze.collective_bytes("ROOT t = bf16[8]{0} tanh(x)")["total"] == 0


@pytest.mark.parametrize("arch,lo,hi", [
    ("smollm_135m", 0.12e9, 0.16e9),      # ~135M params
    ("gemma2_2b", 2.0e9, 3.5e9),
    ("mamba2_2p7b", 2.2e9, 3.2e9),
    ("dbrx_132b", 110e9, 150e9),
])
def test_total_params_match_model_names(arch, lo, hi):
    cfg = base.get_config(arch)
    n = analyze.total_params(cfg)
    assert lo <= n <= hi, (arch, n / 1e9)


def test_moe_active_params_smaller():
    cfg = base.get_config("dbrx_132b")
    assert analyze.active_params(cfg) < 0.5 * analyze.total_params(cfg)


def test_model_flops_train_is_6nd():
    cfg = base.get_config("smollm_135m")
    shape = base.SHAPES_BY_NAME["train_4k"]
    f = analyze.model_flops(cfg, shape)
    n = analyze.active_params(cfg)
    assert f == pytest.approx(6 * n * shape.global_batch * shape.seq_len)


def test_roofline_terms_and_dominance():
    rf = analyze.Roofline(
        arch="x", shape="y", mesh="16x16", chips=256,
        hlo_flops=256 * 197e12, hlo_bytes=256 * 819e9 * 0.5,
        coll_bytes_per_chip=50e9 * 2.0,
        compute_s=1.0, memory_s=0.5, collective_s=2.0,
        model_flops=256 * 197e12 * 0.8, per_device_bytes=0)
    assert rf.dominant == "collective"
    assert rf.bound_s == 2.0
    assert rf.roofline_fraction == pytest.approx(0.5)
    assert rf.useful_flops_ratio == pytest.approx(0.8)


def test_device_peaks_are_keyed_by_device_kind():
    """The v5e entry is the published chip, and a device kind without an
    entry raises instead of borrowing another chip's peaks."""
    assert hw.device_spec("TPU v5 lite") is hw.TPU_V5E
    assert hw.TPU_V5E.peak_flops == 197e12 and hw.TPU_V5E.hbm_bw == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        hw.device_spec("TPU v4")
