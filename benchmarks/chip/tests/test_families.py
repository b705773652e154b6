"""Families are found by name: ``families/dense.py`` gives the program
config, the weights and the reference logits that the harness gave before
it had families, bit for bit; a family that is new files only (a toy
written into a temporary directory) runs a whole run; a leaf without a
rule and an unknown family are errors that name what is missing."""
import json
import math
import textwrap
import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cell
import common
import reference
import roofline
import weights as weight_gen
from repro.configs.base import ModelConfig
from repro.models import Model
from reference import _attention, _mm, _rms, _rope

TINY = common.load_json(common.HERE / "tests" / "tiny.json")
MIX = common.load_json(common.HERE / "tests" / "tiny-mix.json")
CONFIGS = [TINY, common.load_config("qwen3-4b"),
           common.load_config("yi-9b-half")]
SEED = 3_000_000_029


# ---- the harness as it was before families, frozen here
def _model_config_before(cfg):
    return ModelConfig(
        name=cfg["name"], family="dense", source=cfg["source"],
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        vocab_size=cfg["vocab_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], qk_norm=cfg["qk_norm"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], dtype=cfg["torch_dtype"])


FAN_IN_BEFORE = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
                 "w1": (0,), "w3": (0,), "w2": (0,)}
NORMS_BEFORE = {"ln1", "ln2", "final_ln", "q_norm", "k_norm"}
EMBEDS_BEFORE = {"embed", "unembed"}


def _make_weights_before(specs, seed):
    flat, treedef = jax.tree_util.tree_flatten_with_path(specs)

    def draw(key, name, shape, dtype, stacked):
        if name in NORMS_BEFORE:
            return jax.random.uniform(key, shape, jnp.float32, 0.8, 1.2
                                      ).astype(dtype)
        if name in EMBEDS_BEFORE:
            return (jax.random.normal(key, shape, jnp.float32) * 0.02
                    ).astype(dtype)
        dims = shape[1:] if stacked else shape
        fan_in = int(np.prod([dims[a] for a in FAN_IN_BEFORE[name]]))
        return (jax.random.normal(key, shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dtype)

    def gen(key):
        out = []
        for path, s in flat:
            where = jax.tree_util.keystr(path)
            k = jax.random.fold_in(key, zlib.crc32(where.encode()) & 0x7FFFFFFF)
            out.append(draw(k, str(path[-1].key), s.shape, s.dtype,
                            "layers" in where))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(gen)(weight_gen.seed_key(seed))


def _dense_view_before(weights, cfg):
    (seg,) = weights["layers"]
    (blk,) = seg
    head = (weights["embed"].T if cfg["tie_word_embeddings"]
            else weights["unembed"])
    return {"embed": weights["embed"], "final_ln": weights["final_ln"],
            "head": head, "layers": blk}


@partial(jax.jit, static_argnames=("cfg_items", "fp8"))
def _logits_before(view, tokens, rows, cfg_items, fp8):
    cfg = dict(cfg_items)
    eps, hd = cfg["rms_norm_eps"], cfg["head_dim"]
    t = tokens.shape[0]
    x = view["embed"][tokens].astype(jnp.float32)
    inv = 1.0 / (cfg["rope_theta"] ** (jnp.arange(0, hd, 2, dtype=jnp.float32)
                                       / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)

    def layer(x, w):
        w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
        a = w["attn"]
        h = _rms(x, w["ln1"], eps)
        q = _mm("td,dhk->thk", h, a["wq"], -1, 0, fp8)
        k = _mm("td,dhk->thk", h, a["wk"], -1, 0, fp8)
        v = _mm("td,dhk->thk", h, a["wv"], -1, 0, fp8)
        if cfg["qk_norm"]:
            q = _rms(q, a["q_norm"], eps)
            k = _rms(k, a["k_norm"], eps)
        o = _attention(_rope(q, cos, sin), _rope(k, cos, sin), v, fp8)
        x = x + _mm("thk,hkd->td", o, a["wo"], (-2, -1), (0, 1), fp8)
        h = _rms(x, w["ln2"], eps)
        m = w["mlp"]
        u = (jax.nn.silu(_mm("td,df->tf", h, m["w1"], -1, 0, fp8))
             * _mm("td,df->tf", h, m["w3"], -1, 0, fp8))
        return x + _mm("tf,fd->td", u, m["w2"], -1, 0, fp8), None

    x, _ = jax.lax.scan(layer, x, view["layers"])
    xr = _rms(x[rows], view["final_ln"].astype(jnp.float32), eps)
    return _mm("td,dv->tv", xr, view["head"].astype(jnp.float32), -1, 0, fp8)


def _specs(cfg):
    return jax.eval_shape(Model(cell.model_config(cfg)).init,
                          jax.random.PRNGKey(0))


# ---- the dense family is the code it replaced
@pytest.mark.parametrize("cfg", CONFIGS, ids=lambda c: c["name"])
def test_dense_program_config_is_as_before(cfg):
    assert common.family(cfg) is common.family({"family": "dense"})
    assert cell.model_config(cfg) == _model_config_before(cfg)


def test_dense_weights_are_as_before():
    specs = _specs(TINY)
    got = weight_gen.make_weights(specs, SEED, common.family(TINY))
    want = _make_weights_before(specs, SEED)
    leaves = jax.tree_util.tree_leaves_with_path(got)
    assert {jax.tree_util.keystr(p) for p, _ in leaves} == {
        jax.tree_util.keystr(p)
        for p, _ in jax.tree_util.tree_leaves_with_path(want)}
    for (path, a), b in zip(leaves, jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype, jax.tree_util.keystr(path)
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


@pytest.mark.parametrize("fp8", [False, True], ids=["reference", "control"])
def test_dense_reference_logits_are_bit_identical(fp8):
    w = weight_gen.make_weights(_specs(TINY), SEED, common.family(TINY))
    rng = np.random.default_rng(5)
    tokens = jnp.asarray(rng.integers(0, TINY["vocab_size"], 96), jnp.int32)
    rows = jnp.asarray(np.arange(40, 96), jnp.int32)
    items_before = tuple((k, TINY[k]) for k in (
        "rms_norm_eps", "head_dim", "rope_theta", "qk_norm"))
    with jax.default_matmul_precision("highest"):
        got = reference._logits(reference.view(w, TINY), tokens, rows,
                                common.family(TINY).layer,
                                reference._cfg_items(TINY), fp8)
        want = _logits_before(_dense_view_before(w, TINY), tokens, rows,
                              items_before, fp8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---- errors name what is missing
def test_a_leaf_without_a_rule_raises_naming_it():
    specs = dict(_specs(TINY))
    specs["router"] = jax.ShapeDtypeStruct((64, 8), jnp.float32)
    with pytest.raises(KeyError, match="'router'.*dense.py"):
        weight_gen.make_weights(specs, SEED, common.family(TINY))


def test_an_unknown_family_names_the_missing_file():
    with pytest.raises(KeyError, match="no-such-family.py"):
        common.family({"name": "x", "family": "no-such-family"})


# ---- a family that is new files only
TOY_FAMILY = '''
"""A toy family: Qwen3-style attention blocks whose MLP is a routed
mixture of SwiGLU experts, top-k normalized."""
import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from reference import _attention, _mm, _rms, _rope

FAN_IN_AXES = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
               "router": (0,), "we1": (1,), "we3": (1,), "we2": (1,)}
NORMS = {"ln1", "ln2", "final_ln", "q_norm", "k_norm"}
EMBEDS = {"embed", "unembed"}
CONFIG_KEYS = ("num_experts_per_tok",)


def program_config(cfg):
    e = cfg["num_experts"]
    return ModelConfig(
        name=cfg["name"], family="moe", source=cfg["source"],
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        vocab_size=cfg["vocab_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["moe_intermediate_size"], num_experts=e,
        top_k=cfg["num_experts_per_tok"],
        capacity_factor=e / cfg["num_experts_per_tok"], qk_norm=True,
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=False, dtype=cfg["torch_dtype"])


def view(weights, cfg):
    (seg,) = weights["layers"]
    (blk,) = seg
    return {"embed": weights["embed"], "final_ln": weights["final_ln"],
            "head": weights["unembed"], "layers": blk}


def layer(x, w, env):
    a, f8 = w["attn"], env.fp8
    h = _rms(x, w["ln1"], env.eps)
    q = _rms(_mm("td,dhk->thk", h, a["wq"], -1, 0, f8), a["q_norm"], env.eps)
    k = _rms(_mm("td,dhk->thk", h, a["wk"], -1, 0, f8), a["k_norm"], env.eps)
    v = _mm("td,dhk->thk", h, a["wv"], -1, 0, f8)
    o = _attention(_rope(q, env.cos, env.sin), _rope(k, env.cos, env.sin),
                   v, f8)
    x = x + _mm("thk,hkd->td", o, a["wo"], (-2, -1), (0, 1), f8)
    h = _rms(x, w["ln2"], env.eps)
    m = w["moe"]
    gates = jax.nn.softmax(_mm("td,de->te", h, m["router"], -1, 0, f8), -1)
    kth = jax.lax.top_k(gates, env.cfg["num_experts_per_tok"])[0][:, -1:]
    gates = jnp.where(gates >= kth, gates, 0.0)
    gates = gates / jnp.sum(gates, -1, keepdims=True)
    u = (jax.nn.silu(_mm("td,edf->tef", h, m["we1"], -1, 1, f8))
         * _mm("td,edf->tef", h, m["we3"], -1, 1, f8))
    y = _mm("tef,efd->ted", u, m["we2"], -1, 1, f8)
    return x + jnp.einsum("te,ted->td", gates, y)


def matmul_params_per_token(dims):
    d, hq, hkv, hd = (dims["hidden_size"], dims["num_attention_heads"],
                      dims["num_key_value_heads"], dims["head_dim"])
    return (d * hd * (hq + 2 * hkv) + hq * hd * d + d * dims["num_experts"]
            + 3 * d * dims["moe_intermediate_size"]
            * dims["num_experts_per_tok"])
'''

TOY_CONFIG = {
    "name": "toy-moe", "family": "toy-moe",
    "source": "a two-layer routed-expert model, for tests on the CPU",
    "hidden_size": 64, "moe_intermediate_size": 32, "num_experts": 4,
    "num_experts_per_tok": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "vocab_size": 512,
    "engine": TINY["engine"], "slo": TINY["slo"], "knee_rps": 8.0,
    "correct": {"logit_gap_limit": 0.015}}


def test_a_new_family_is_new_files_only(tmp_path, monkeypatch):
    """A family written, with its configuration, into a directory that
    discovery is pointed at serves a whole run through ``cell.run`` and
    compares correct against its own reference layer."""
    (tmp_path / "toy-moe.py").write_text(textwrap.dedent(TOY_FAMILY))
    (tmp_path / "toy-moe.json").write_text(json.dumps(TOY_CONFIG))
    monkeypatch.setattr(common, "FAMILIES", tmp_path)
    cfg = common.load_json(tmp_path / "toy-moe.json")
    assert cell.model_config(cfg).num_experts == 4
    # a backlog that the warm-up and the window do not run dry
    mix = dict(MIX, offline=dict(MIX["offline"], docs=120))
    out = cell.run("toy-moe.test", SEED, 2.0, False, cfg=cfg, mix=mix,
                   require_tpu=False)
    checks = out["checks"]
    assert checks["compared_tokens"]["value"] >= 40
    assert checks["logit_gap"]["value"] <= checks["logit_gap"]["limit"]
    assert checks["backlog_left"]["value"] >= 1
    assert out["correct"]
    per_tok = 2.0 * (16 * 64 * 8 + 64 * 64 + 64 * 4 + 3 * 64 * 32 * 4) * 2
    assert roofline.step_flops(cfg, [(0, 1)], 0) == pytest.approx(
        per_tok + roofline.prefill_attn(cfg, 0, 1)[0])
    assert math.isfinite(out["metrics"]["setup_s"]["value"])
