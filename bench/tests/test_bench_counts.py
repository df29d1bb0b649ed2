"""Operation and byte counts against hand counts at llava-ov-0.5b's
widths (d 896, 14/2 heads of 64, d_ff 4864, 24 layers, vocab 151,936)."""
import json
import os

from bench import counts

CONFIG = os.path.join(os.path.dirname(__file__), "..", "configs",
                      "llava-ov-0.5b.json")
with open(CONFIG) as f:
    SIZES = json.load(f)["config"]


def test_fused_qkv_by_hand():
    cols = (14 + 2 * 2) * 64                     # 1152 q, k, v columns
    assert counts.fused_qkv(64, SIZES) == (
        2 * 64 * 896 * cols + 64 * cols,
        2 * (896 * cols + cols + 64 * 896 + 64 * cols))
    assert counts.fused_qkv(64, SIZES) == (132_194_304, 2_328_832)


def test_fused_mlp_by_hand():
    flops, nbytes = counts.fused_mlp(64, SIZES)
    assert flops == 2 * 64 * 896 * 4864 * 3 + 4 * 64 * 4864
    assert nbytes == 2 * (3 * 896 * 4864 + 2 * 64 * 896)


def test_model_flops_by_hand():
    per_layer = 896 * 1152 + 14 * 64 * 896 + 3 * 896 * 4864
    assert per_layer == 14_909_440
    assert counts.linear_flops_per_token(SIZES) == 2 * 24 * per_layer
    assert counts.head_flops(SIZES) == 272_269_312
    # about 1 GFLOP a decoded token, plus 86,016 per context position
    assert counts.decode_flops(SIZES, 0) == 987_922_432
    assert counts.decode_flops(SIZES, 10) - counts.decode_flops(SIZES, 0) \
        == 10 * 4 * 24 * 14 * 64
    # prefill: projector on its patches, causal attention, head once
    S, n = 300, 196
    assert counts.prefill_flops(SIZES, S, n) == (
        2 * n * (1152 * 896 + 896 * 896) + S * 2 * 24 * per_layer
        + 4 * 24 * 14 * 64 * S * (S + 1) // 2 + 272_269_312)
