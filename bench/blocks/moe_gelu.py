"""The repo's MoE decoder block with tanh-GELU experts, as ``gpt2-moe`` and
``transformer-xl-moe`` run it.

Every layer is MoE: RMSNorm (eps 1e-5) before attention and before the MoE
layer, RoPE (theta 1e4) on queries and keys, no biases, causal MHA/GQA
attention; a softmax router, top-k experts with the top-k weights
renormalised, tanh-GELU expert FFNs of two matrices, the output summed into
the residual.  The configuration files say where this departs from the
published GPT-2 and Transformer-XL blocks.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference import F32, mm, rms_norm, rope

MODEL_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff",
              "vocab_size", "ffn_type", "dtype", "param_dtype")
MOE_KEYS = ("n_experts", "top_k", "capacity_factor", "aux_loss_weight")

# --- weight layout -----------------------------------------------------------


def shapes(cfg: dict) -> dict:
    if cfg["ffn_type"] != "gelu":
        raise ValueError(f"block moe_gelu has gelu experts; the "
                         f"configuration states {cfg['ffn_type']!r}")
    L, d, f, e = (cfg["n_layers"], cfg["d_model"], cfg["d_ff"],
                  cfg["n_experts"])
    hq = cfg["n_heads"] * (d // cfg["n_heads"])
    hkv = cfg["n_kv_heads"] * (d // cfg["n_heads"])
    return {
        "ln1": ((L, d), None),
        "ln2": ((L, d), None),
        "wq": ((L, d, hq), d ** -0.5),
        "wk": ((L, d, hkv), d ** -0.5),
        "wv": ((L, d, hkv), d ** -0.5),
        "wo": ((L, hq, d), hq ** -0.5),
        "router": ((L, d, e), d ** -0.5),
        "wi": ((L, e, d, f), d ** -0.5),
        "wo_e": ((L, e, f, d), f ** -0.5),
    }


PROGRAM_NAMES = {
    ("ln1",): "ln1", ("ln2",): "ln2",
    ("attn", "wq"): "wq", ("attn", "wk"): "wk", ("attn", "wv"): "wv",
    ("attn", "wo"): "wo", ("moe", "router"): "router",
    ("moe", "wi"): "wi", ("moe", "wo"): "wo_e",
}

EXPERT_SHARDED = ("wi", "wo_e")

# --- the plain float32 forward -----------------------------------------------


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def attention(x, lw, cfg, precision):
    """Causal self-attention sub-block with its residual; x [B, S, d]."""
    b, s, d = x.shape
    hn, kvn = cfg["n_heads"], cfg["n_kv_heads"]
    hd = d // hn
    h = rms_norm(x, lw["ln1"])
    q = mm("bsd,de->bse", h, lw["wq"], precision).reshape(b, s, hn, hd)
    k = mm("bsd,de->bse", h, lw["wk"], precision).reshape(b, s, kvn, hd)
    v = mm("bsd,de->bse", h, lw["wv"], precision).reshape(b, s, kvn, hd)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    q, k = rope(q, pos), rope(k, pos)
    if kvn != hn:
        k = jnp.repeat(k, hn // kvn, axis=2)
        v = jnp.repeat(v, hn // kvn, axis=2)
    logits = mm("bqhd,bkhd->bhqk", q, k, precision) / math.sqrt(hd)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    p = jax.nn.softmax(jnp.where(causal, logits, -1e30), axis=-1)
    o = mm("bhqk,bkhd->bqhd", p, v, precision).reshape(b, s, hn * hd)
    return x + mm("bse,ed->bsd", o, lw["wo"], precision)


def route(h, router, k, precision):
    """h [..., T, d] -> (probs [..., T, E], idx [..., T, k], w [..., T, k])."""
    probs = jax.nn.softmax(mm("...td,de->...te", h, router, precision), -1)
    w, idx = jax.lax.top_k(probs, k)
    return probs, idx, w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)


def load_balance_loss(probs, idx, cfg):
    """The routers' auxiliary loss of one layer, over its capacity groups:
    weight x E x the mean over groups of sum_e (share of first choices on
    e) x (mean router probability of e)."""
    e = cfg["n_experts"]
    f = jnp.mean(jax.nn.one_hot(idx[..., 0], e, dtype=F32), axis=1)
    return cfg["aux_loss_weight"] * e * jnp.mean(
        jnp.sum(f * jnp.mean(probs, axis=1), -1))


def experts(h, wdense, lw, precision):
    """sum_e wdense[:, e] * FFN_e(h) over every expert; h [T, d]."""
    def one(y, xs):
        wi, wo, we = xs
        a = gelu(mm("td,df->tf", h, wi, precision))
        return y + we[:, None] * mm("tf,fd->td", a, wo, precision), None

    y0 = jnp.zeros(h.shape, F32)
    y, _ = jax.lax.scan(jax.checkpoint(one), y0,
                        (lw["wi"], lw["wo_e"], wdense.T))
    return y


def layer(x, lw, cfg, moe, precision):
    """One layer: (x, the router's probs and choices, kept choices)."""
    x = attention(x, lw, cfg, precision)
    h = rms_norm(x, lw["ln2"])
    y, probs, idx, keep = moe(
        h, lambda hs: route(hs, lw["router"], moe.k, precision),
        lambda rows, wd: experts(rows, wd, lw, precision))
    return x + y, probs, idx, keep


def _layer_weights(w):
    return {n: w[n] for n in PROGRAM_NAMES.values()}


def train_layers(x, w, cfg, moe, precision):
    def one(x, lw):
        x, probs, idx, keep = layer(x, lw, cfg, moe, precision)
        return x, (load_balance_loss(probs, idx, cfg),
                   jnp.mean(keep.astype(F32)))

    x, (auxs, kept) = jax.lax.scan(jax.checkpoint(one), x, _layer_weights(w))
    return x, auxs, kept


def serve_layers(x, w, cfg, moe, precision):
    def one(x, lw):
        return layer(x, lw, cfg, moe, precision)[0], None

    x, _ = jax.lax.scan(one, x, _layer_weights(w))
    return x


# --- counts ------------------------------------------------------------------


def expert_params(cfg: dict) -> int:
    return 2 * cfg["d_model"] * cfg["d_ff"]


def layer_params(cfg: dict, k: int) -> list:
    """Attention projections, router and k experts, in every layer."""
    d, e = cfg["d_model"], cfg["n_experts"]
    hd = d // cfg["n_heads"]
    attn = d * cfg["n_heads"] * hd * 2 + d * cfg["n_kv_heads"] * hd * 2
    return [attn + d * e + k * expert_params(cfg)] * cfg["n_layers"]


def moe_layers(cfg: dict) -> int:
    return cfg["n_layers"]


def attention_flops_train(cfg: dict, seq: int) -> float:
    """Causal attention's score and value products, forward and backward:
    6 x layers x seq x d (half of the full square)."""
    return 6.0 * cfg["n_layers"] * seq * cfg["d_model"]


def attention_flops_prefill(cfg: dict, s: int) -> float:
    return 2.0 * cfg["n_layers"] * s * s * cfg["d_model"]


def attention_flops_decode(cfg: dict, pos: int) -> float:
    return 4.0 * cfg["n_layers"] * (pos + 1) * cfg["d_model"]
