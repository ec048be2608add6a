"""The benchmark's operation and byte counts against hand counts."""
import json

from bench import flops
from bench.tests.util import BENCH

QWEN = flops.DenseDecoder.from_config(
    json.loads((BENCH / "configs" / "qwen2-0.5b.json").read_text()))


def test_qwen2_sizes():
    assert (QWEN.layers, QWEN.d_model, QWEN.heads, QWEN.kv_heads,
            QWEN.head_dim, QWEN.d_ff, QWEN.vocab) == \
        (24, 896, 14, 2, 64, 4864, 151936)
    # per layer: q 896x896, k and v 896x128, o 896x896, gate/up 896x4864,
    # down 4864x896; plus the tied unembedding 896x151936
    per_layer = 2 * 896 * 896 + 2 * 896 * 128 + 3 * 896 * 4864
    assert per_layer == 14_909_440
    assert QWEN.matmul_params() == 24 * 14_909_440 + 896 * 151936


def test_one_qwen2_projection():
    # the q projection of a 2048-token prefill: [2048, 896] @ [896, 896]
    assert flops.gemm_flops(2048, 896, 896) == 2 * 2048 * 896 * 896 \
        == 3_288_334_336
    # bf16 operands, fp32 result
    assert flops.gemm_bytes(2048, 896, 896, 2, 4) == \
        (2048 * 896 + 896 * 896) * 2 + 2048 * 896 * 4 == 12_615_680


def test_attention_and_forward_counts():
    # one query at position 9 attends to 10 keys: QK^T and PV, each
    # 2 * 10 * 64 FLOPs per head, 14 heads, 24 layers
    assert QWEN.attention_flops(9) == 2 * 2 * 10 * 64 * 14 * 24
    two = QWEN.forward_flops(0, 2)
    assert two == 2 * 2 * QWEN.matmul_params() + \
        QWEN.attention_flops(0) + QWEN.attention_flops(1)
    # a continuation is the rest of the whole sequence
    assert QWEN.forward_flops(0, 100) == \
        QWEN.forward_flops(0, 60) + QWEN.forward_flops(60, 100)
    assert QWEN.train_flops(4, 2048) == 3 * 4 * QWEN.forward_flops(0, 2048)
    # about 3.2 GFLOP per trained token at 2048 positions
    per_tok = QWEN.train_flops(1, 2048) / 2048
    assert 3.1e9 < per_tok < 3.3e9


def test_one_summa_step():
    mb = 16384
    # one local update C += A_panel @ B_panel on 16384^2 float32 blocks
    assert flops.gemm_flops(mb, mb, mb) == 2 * mb ** 3 == 8_796_093_022_208
    assert flops.gemm_bytes(mb, mb, mb, 4, 4, accumulate=True) == \
        4 * 4 * mb * mb


def test_roofline_bound():
    t, bound = flops.roofline_seconds(197e12, 1e9, 197e12, 819e9)
    assert (t, bound) == (1.0, "compute")
    t, bound = flops.roofline_seconds(1e9, 819e9, 197e12, 819e9)
    assert (t, bound) == (1.0, "memory")
