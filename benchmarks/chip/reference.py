"""Plain reference of the served models, and the comparison that decides
``correct``.

The reference is the published decoder in straightforward ``jax.numpy``
and float32 at ``highest`` matmul precision: token embedding, the layers,
a final RMSNorm and the (tied or untied) output head. The layers are the
configuration's family's (``families/<family>.py``: its ``layer`` on the
residual stream, from the rotary tables, the norm's epsilon and the
family's static configuration keys in ``Env``); they call the helpers
here: ``_mm`` (a matmul, fp8-rounded in the control), ``_rms``, ``_rope``
(rotate-half, HF convention) and ``_attention`` (causal grouped-query
softmax). It imports nothing of the program: it reads the benchmark's own
weights (``weights.py``) through the family's layout adapter ``view``.
Layers run in a scan, each upcast to float32 as it is used, so the
full-width model fits beside its bfloat16 weights.

The comparison is teacher-forced over a request's prompt and the tokens
the program served: at each position where a token was served, the gap by
which the served token's reference logit lies below the reference's best
logit. Greedy decoding serves the argmax of the program's own logits, so
a sound program reads a gap of rounding size, and the widest gap over a
sample of finished requests is the number compared.

The control is the same reference computed one precision step below the
configuration's bfloat16: every matmul operand fake-quantized to fp8
(e4m3, per-row or per-output-channel scale). Its token at each position
is its own argmax, read against the float32 reference the same way.
"""
from __future__ import annotations

import math
from functools import partial
from typing import List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import common

HIGHEST = jax.lax.Precision.HIGHEST
T_BLOCK = 1024           # sequence lengths are padded to a multiple of this
ROWS = 1024              # logit rows read per request (longest output)
Q_BLOCK = 512            # attention is computed in blocks of query rows
E4M3_MAX = 448.0


class Env(NamedTuple):
    """What a family's ``layer`` reads beside its weights."""
    cos: object          # rotary tables, (T, head_dim / 2)
    sin: object
    eps: float           # RMSNorm epsilon
    fp8: bool            # the control: matmul operands rounded to fp8
    cfg: dict            # the static configuration keys (``_cfg_items``)


def view(weights, cfg: dict) -> dict:
    """The benchmark's weights through the family's layout adapter."""
    return common.family(cfg).view(weights, cfg)


def fake_e4m3(x, axis):
    """Round ``x`` to fp8 e4m3 with one scale per slice along ``axis``
    (the scale maps the slice's largest magnitude to 448)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    y = x / s
    e = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(y), 1e-30)))
    step = jnp.exp2(jnp.maximum(e, -6.0) - 3.0)      # 3 mantissa bits
    y = jnp.clip(jnp.round(y / step) * step, -E4M3_MAX, E4M3_MAX)
    return y * s


def _mm(spec, a, b, a_axis, b_axis, fp8):
    if fp8:
        a = fake_e4m3(a, a_axis)
        b = fake_e4m3(b, b_axis)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attention(q, k, v, fp8):
    """Causal GQA. q (T,Hq,hd), k/v (T,Hkv,hd) -> (T,Hq,hd)."""
    t, hq, hd = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    qg = q.reshape(t, hkv, g, hd) / math.sqrt(hd)
    outs = []
    for i0 in range(0, t, Q_BLOCK):
        qb = qg[i0:i0 + Q_BLOCK]
        sc = _mm("tkgd,skd->kgts", qb, k, -1, -1, fp8)
        qi = i0 + jnp.arange(qb.shape[0])[:, None]
        sc = jnp.where(jnp.arange(t)[None, :] <= qi, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(_mm("kgts,skd->tkgd", p, v, -1, 0, fp8))
    return jnp.concatenate(outs, 0).reshape(t, hq, hd)


@partial(jax.jit, static_argnames=("layer", "cfg_items", "fp8"))
def _logits(view, tokens, rows, layer, cfg_items, fp8):
    cfg = dict(cfg_items)
    eps, hd = cfg["rms_norm_eps"], cfg["head_dim"]
    t = tokens.shape[0]
    x = view["embed"][tokens].astype(jnp.float32)
    inv = 1.0 / (cfg["rope_theta"] ** (jnp.arange(0, hd, 2, dtype=jnp.float32)
                                       / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    env = Env(jnp.cos(ang), jnp.sin(ang), eps, fp8, cfg)

    def step(x, w):
        w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
        return layer(x, w, env), None

    x, _ = jax.lax.scan(step, x, view["layers"])
    xr = _rms(x[rows], view["final_ln"].astype(jnp.float32), eps)
    return _mm("td,dv->tv", xr, view["head"].astype(jnp.float32), -1, 0, fp8)


@jax.jit
def _gaps(ref, tokens):
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, tokens[:, None], axis=-1)[:, 0]
    return best - got


def _cfg_items(cfg: dict) -> Tuple:
    keys = ("rms_norm_eps", "head_dim", "rope_theta"
            ) + tuple(common.family(cfg).CONFIG_KEYS)
    return tuple((k, cfg[k]) for k in keys)


def _pad(seq: Sequence[int], n: int) -> np.ndarray:
    out = np.zeros(n, np.int32)
    out[:len(seq)] = seq
    return out


def request_gaps(view, cfg: dict, prompt: Sequence[int],
                 served: Sequence[int], control: bool = False
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Gaps below the reference's best logit at each served position: of
    the served tokens, and (with ``control``) of the fp8 control's argmax
    (empty otherwise)."""
    n = len(served)
    if not 0 < n <= ROWS:
        raise ValueError(f"{n} served tokens; the comparison reads 1..{ROWS}")
    seq = list(prompt) + list(served[:-1])
    t = -(-len(seq) // T_BLOCK) * T_BLOCK
    tokens = jnp.asarray(_pad(seq, t))
    rows = jnp.asarray(_pad(range(len(prompt) - 1, len(seq)), ROWS))
    layer, items = common.family(cfg).layer, _cfg_items(cfg)
    ctrl_gap = np.zeros(0)
    with jax.default_matmul_precision("highest"):
        ref = _logits(view, tokens, rows, layer, items, False)
        gap = np.asarray(_gaps(ref, jnp.asarray(_pad(served, ROWS))))[:n]
        if control:
            ctrl = jnp.argmax(_logits(view, tokens, rows, layer, items, True),
                              -1)
            ctrl_gap = np.asarray(_gaps(ref, ctrl.astype(jnp.int32)))[:n]
    return gap, ctrl_gap


def sample(finished: List[Tuple[int, List[int], List[int]]], seed: int, *,
           min_tokens: int, max_requests: int) -> List[int]:
    """Indices into ``finished`` ((key, prompt, served) of requests that
    finished): the longest (prompt + served), then others in an order
    drawn from the seed until ``min_tokens`` served tokens are in the
    sample, at most ``max_requests``."""
    if not finished:
        return []
    longest = max(range(len(finished)),
                  key=lambda i: (len(finished[i][1]) + len(finished[i][2]),
                                 finished[i][0]))
    rest = [i for i in range(len(finished)) if i != longest]
    order = np.random.default_rng([int(seed) & (2**63 - 1), 7]).permutation(
        len(rest))
    pick, n_tok = [longest], len(finished[longest][2])
    for j in order:
        if n_tok >= min_tokens or len(pick) >= max_requests:
            break
        pick.append(rest[j])
        n_tok += len(finished[rest[j]][2])
    return pick
