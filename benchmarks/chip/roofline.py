"""Operations and bytes that the served model's work needs, from shapes.

Counts are of the live work only: the context a row attends to, the
tokens a chunk holds, the weights a step must read. Padding, the pool
copies of undonated steps and recomputation are not counted, so a share
of a peak computed from these is a lower bound on the chip's use and can
only reach 100% when the program wastes nothing.

``dims`` is a configuration file's dict (Hugging Face keys). bf16: two
bytes an element.
"""
from __future__ import annotations

from typing import Iterable, Sequence, Tuple

import common

BYTES = 2


def _d(dims: dict):
    return (dims["num_hidden_layers"], dims["hidden_size"],
            dims["num_attention_heads"], dims["num_key_value_heads"],
            dims["head_dim"], dims["vocab_size"])


def decode_attn(dims: dict, ctx_lens: Sequence[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode call's attention, all layers: each row
    reads its ``ctx`` cached keys and values once (its query and output
    are counted too) and does q.k and p.v over them."""
    n_l, _, hq, hkv, hd, _ = _d(dims)
    ctx = sum(ctx_lens)
    flops = 4.0 * hq * hd * ctx * n_l
    kv = 2.0 * hkv * hd * ctx * BYTES
    qo = 2.0 * hq * hd * len(ctx_lens) * BYTES
    return flops, (kv + qo) * n_l


def prefill_attn(dims: dict, start: int, n: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one prefill chunk's attention, all layers: ``n``
    new tokens at positions ``start..start+n-1``, each attending causally
    to the positions up to its own; the keys and values of the whole
    context are read once, the chunk's queries read and outputs written."""
    n_l, _, hq, hkv, hd, _ = _d(dims)
    pairs = n * start + n * (n + 1) / 2
    flops = 4.0 * hq * hd * pairs * n_l
    kv = 2.0 * hkv * hd * (start + n) * BYTES
    qo = 2.0 * hq * hd * n * BYTES
    return flops, (kv + qo) * n_l


def step_flops(dims: dict, tokens: Iterable[Tuple[int, int]],
               logit_rows: int) -> float:
    """Model FLOPs of computing ``tokens`` ((start, n) spans of positions;
    a decode row is a span of 1) and ``logit_rows`` rows of the output
    head: 2 x matmul weights per token, plus causal attention at each
    token's context, plus 2 x d_model x vocab per logit row. The matmul
    weights a token multiplies by in a layer are its family's
    (``matmul_params_per_token``)."""
    n_l, d, _, _, _, v = _d(dims)
    per_tok = 2.0 * common.family(dims).matmul_params_per_token(dims) * n_l
    total = 0.0
    for start, n in tokens:
        total += per_tok * n + prefill_attn(dims, start, n)[0]
    return total + 2.0 * d * v * logit_rows


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """Roofline bound: the larger of compute time and memory time."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
