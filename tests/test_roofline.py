"""Roofline extraction machinery: HLO collective parsing, replica-group
decoding (explicit + iota forms), cross-boundary classification, and term
arithmetic — the §Roofline numbers are only as good as this parser."""
import numpy as np
import pytest

from repro.launch import roofline as R

HLO = """
HloModule test
ENTRY %main {
  %p0 = bf16[128,512]{1,0} parameter(0)
  %ar = bf16[128,512]{1,0} all-reduce(%p0), replica_groups=[4,2]<=[8], to_apply=%add
  %ag = f32[64,32]{1,0} all-gather(%ar), replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}
  %a2a = f32[16,16]{1,0} all-to-all(%ag), replica_groups=[2,4]<=[4,2]T(1,0)
  %rs-start = bf16[8,8]{1,0} reduce-scatter(%a2a), replica_groups={}
  %done = bf16[8,8]{1,0} all-reduce-done(%rs-start)
}
"""


def test_collective_bytes_sums_result_shapes():
    out = R.collective_bytes(HLO)
    assert out["all-reduce"] == 128 * 512 * 2
    assert out["all-gather"] == 64 * 32 * 4
    assert out["all-to-all"] == 16 * 16 * 4
    assert out["reduce-scatter"] == 8 * 8 * 2
    # -done halves of async pairs are not double counted
    assert out["total"] == sum(v for k, v in out.items() if k != "total")


def test_parse_replica_groups_explicit():
    g = R.parse_replica_groups("{{0,1,2,3},{4,5,6,7}}")
    assert g == [[0, 1, 2, 3], [4, 5, 6, 7]]


def test_parse_replica_groups_iota():
    g = R.parse_replica_groups("[4,2]<=[8]")
    assert g == [[0, 1], [2, 3], [4, 5], [6, 7]]


def test_parse_replica_groups_iota_transposed():
    g = R.parse_replica_groups("[2,4]<=[4,2]T(1,0)")
    # arange(8).reshape(4,2).T = [[0,2,4,6],[1,3,5,7]] -> reshape (2,4)
    assert g == [[0, 2, 4, 6], [1, 3, 5, 7]]


def test_parse_replica_groups_empty_means_all():
    assert R.parse_replica_groups("{}", num_devices=4) == [[0, 1, 2, 3]]


def test_cross_block_bytes_classification():
    # block=2: the [4,2] iota groups {0,1},{2,3}.. stay inside blocks;
    # the explicit {0,1,2,3} group crosses them.
    xb = R.cross_block_bytes(HLO, block=2, num_devices=8)
    assert xb >= 64 * 32 * 4                      # the all-gather crosses
    within = R.cross_block_bytes(HLO, block=8, num_devices=8)
    assert within == 0                            # nothing crosses one big block


def test_model_flops_kinds():
    from repro.configs import ARCHS, INPUT_SHAPES
    cfg = ARCHS["llama3.2-1b"]
    tr = R.model_flops(cfg, INPUT_SHAPES["train_4k"], "train")
    de = R.model_flops(cfg, INPUT_SHAPES["decode_32k"], "decode")
    pf = R.model_flops(cfg, INPUT_SHAPES["prefill_32k"], "prefill")
    assert tr > pf > de > 0
    # train = 6·N·D, prefill = 2·N·D at the same token count would be 3×;
    # the shapes differ in tokens so just check the 6/2 structure per token
    tok_tr = 256 * 4096
    tok_pf = 32 * 32768
    assert abs((tr / tok_tr) / (pf / tok_pf) - 3.0) < 1e-6


def test_hw_constants_prescribed():
    assert R.peaks_for("TPU v5 lite") == {
        "peak_flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9}


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="TPU v9"):
        R.peaks_for("TPU v9")
