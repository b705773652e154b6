"""The dense decoder family: Llama / Qwen3 blocks, every layer alike, of
grouped-query attention (optional per-head RMSNorm of q and k) and a
SwiGLU MLP (``qwen3-4b``, ``yi-9b-half``).

A family module gives the harness, by these names:

- ``program_config(cfg)``: the program's ``ModelConfig`` for a
  configuration file (``cell.model_config``);
- ``FAN_IN_AXES``, ``NORMS``, ``EMBEDS``: the weight rules by leaf name of
  the program's parameters (``weights.py``): the axes a matrix contracts
  over, after the stacked layer axis; the norm scales; the embedding and
  output head;
- ``view(weights, cfg)``: the weights named by what they are, with
  ``embed``, ``final_ln``, ``head`` and ``layers``, whose leaves are
  stacked along a leading axis that the reference scans;
- ``layer(x, w, env)`` and ``CONFIG_KEYS``: one element of ``layers`` on
  the residual stream ``x`` (float32, (T, d)), and the configuration keys
  it reads from ``env.cfg`` beside the reference's own (``reference.Env``);
- ``matmul_params_per_token(dims)``: the weights one token multiplies by
  in one layer, on average (``roofline.step_flops``).
"""
import jax

from repro.configs.base import ModelConfig
from reference import _attention, _mm, _rms, _rope

FAN_IN_AXES = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
               "w1": (0,), "w3": (0,), "w2": (0,)}
NORMS = {"ln1", "ln2", "final_ln", "q_norm", "k_norm"}
EMBEDS = {"embed", "unembed"}
CONFIG_KEYS = ("qk_norm",)


def program_config(cfg: dict) -> ModelConfig:
    """The program's ``ModelConfig`` for a configuration file."""
    return ModelConfig(
        name=cfg["name"], family="dense", source=cfg["source"],
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        vocab_size=cfg["vocab_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], qk_norm=cfg["qk_norm"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], dtype=cfg["torch_dtype"])


def view(weights, cfg: dict) -> dict:
    """The benchmark's weights, named by what they are: one scanned
    segment of identical attention+MLP blocks with stacked parameters."""
    (seg,) = weights["layers"]
    (blk,) = seg
    head = (weights["embed"].T if cfg["tie_word_embeddings"]
            else weights["unembed"])
    return {"embed": weights["embed"], "final_ln": weights["final_ln"],
            "head": head, "layers": blk}


def layer(x, w, env):
    """One block: RMSNorm, q/k/v, optional per-head q/k RMSNorm, rotary
    embedding, causal GQA, output projection; RMSNorm and the SwiGLU MLP;
    residuals."""
    a = w["attn"]
    h = _rms(x, w["ln1"], env.eps)
    q = _mm("td,dhk->thk", h, a["wq"], -1, 0, env.fp8)
    k = _mm("td,dhk->thk", h, a["wk"], -1, 0, env.fp8)
    v = _mm("td,dhk->thk", h, a["wv"], -1, 0, env.fp8)
    if env.cfg["qk_norm"]:
        q = _rms(q, a["q_norm"], env.eps)
        k = _rms(k, a["k_norm"], env.eps)
    o = _attention(_rope(q, env.cos, env.sin), _rope(k, env.cos, env.sin), v,
                   env.fp8)
    x = x + _mm("thk,hkd->td", o, a["wo"], (-2, -1), (0, 1), env.fp8)
    h = _rms(x, w["ln2"], env.eps)
    m = w["mlp"]
    u = (jax.nn.silu(_mm("td,df->tf", h, m["w1"], -1, 0, env.fp8))
         * _mm("td,df->tf", h, m["w3"], -1, 0, env.fp8))
    return x + _mm("tf,fd->td", u, m["w2"], -1, 0, env.fp8)


def matmul_params_per_token(dims: dict) -> int:
    """Weights every token multiplies by in one layer (q, k, v, o, MLP)."""
    d, hq, hkv, hd = (dims["hidden_size"], dims["num_attention_heads"],
                      dims["num_key_value_heads"], dims["head_dim"])
    return (d * hd * (hq + 2 * hkv) + hq * hd * d
            + 3 * d * dims["intermediate_size"])
