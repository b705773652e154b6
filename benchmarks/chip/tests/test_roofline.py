"""Roofline operations and bytes against a hand count at Qwen3-4B shapes
(36 layers, d_model 2560, 32 query / 8 KV heads of 128, d_ff 9728,
vocab 151936)."""
import common
import roofline

Q = common.load_config("qwen3-4b")
PEAK = common.peaks("TPU v5 lite")


def test_layer_weights():
    # q 2560x4096, k and v 2560x1024 each, o 4096x2560, MLP 3 x 2560x9728
    hand = 2560 * 4096 + 2 * 2560 * 1024 + 4096 * 2560 + 3 * 2560 * 9728
    assert common.family(Q).matmul_params_per_token(Q) == hand \
        == 100_925_440


def test_decode_attention():
    f, b = roofline.decode_attn(Q, [1000, 3000])
    # 4 FLOPs (q.k and p.v, multiply-add) per query head, head dim and
    # context position, in 36 layers
    assert f == 4 * 32 * 128 * 4000 * 36
    # K and V of 8 heads x 128 in bf16 per position, plus q and out rows
    assert b == (2 * 8 * 128 * 4000 * 2 + 2 * 32 * 128 * 2 * 2) * 36


def test_prefill_attention_is_causal():
    f, b = roofline.prefill_attn(Q, 3000, 256)
    pairs = 256 * 3000 + 256 * 257 / 2
    assert f == 4 * 32 * 128 * pairs * 36
    assert b == (2 * 8 * 128 * 3256 * 2 + 2 * 32 * 128 * 256 * 2) * 36
    # one token at position p is the decode row of context p + 1
    assert roofline.prefill_attn(Q, 99, 1)[0] == \
        roofline.decode_attn(Q, [100])[0]


def test_step_flops():
    f = roofline.step_flops(Q, [(0, 256), (511, 1)], logit_rows=2)
    per_tok = 2 * 100_925_440 * 36
    attn = roofline.prefill_attn(Q, 0, 256)[0] + roofline.decode_attn(
        Q, [512])[0]
    assert f == per_tok * 257 + attn + 2 * 2560 * 151936 * 2


def test_least_time_takes_the_binding_bound():
    # decode of 32 rows at 1k context is bound by bytes, a long prefill
    # chunk by FLOPs
    f, b = roofline.decode_attn(Q, [1024] * 32)
    assert roofline.least_time(f, b, PEAK) == b / 819e9
    f, b = roofline.prefill_attn(Q, 3000, 256)
    assert roofline.least_time(f, b, PEAK) == f / 197e12
