"""A second block, for the tests: the repo's MoE decoder block with SwiGLU
experts and one always-on shared SwiGLU expert of the experts' width, as
the program runs ``ffn_type="swiglu"`` with ``moe.shared_expert``.

It is added the way a new architecture is, by a file alone: it takes the
attention and the router of ``bench/blocks/moe_gelu.py`` and the shared
pieces of ``bench/reference.py``, and brings its own experts, shared
expert, weight layout and counts.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.blocks import moe_gelu
from bench.reference import F32, mm, rms_norm

MODEL_KEYS = moe_gelu.MODEL_KEYS
MOE_KEYS = moe_gelu.MOE_KEYS + ("shared_expert",)


def shapes(cfg: dict) -> dict:
    if cfg["ffn_type"] != "swiglu" or not cfg["shared_expert"]:
        raise ValueError("this block has SwiGLU experts and a shared expert")
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
        "wu_e": ((L, e, d, f), d ** -0.5),
        "wo_e": ((L, e, f, d), f ** -0.5),
        "ws_in": ((L, d, f), d ** -0.5),
        "ws_up": ((L, d, f), d ** -0.5),
        "ws_out": ((L, f, d), f ** -0.5),
    }


PROGRAM_NAMES = {
    **moe_gelu.PROGRAM_NAMES,
    ("moe", "wu"): "wu_e",
    ("shared", "w_in"): "ws_in", ("shared", "w_up"): "ws_up",
    ("shared", "w_out"): "ws_out",
}

EXPERT_SHARDED = ("wi", "wu_e", "wo_e")


def experts(h, wdense, lw, precision):
    """sum_e wdense[:, e] * SwiGLU_e(h) over every expert; h [T, d]."""
    def one(y, xs):
        wi, wu, wo, we = xs
        a = jax.nn.silu(mm("td,df->tf", h, wi, precision)) \
            * mm("td,df->tf", h, wu, precision)
        return y + we[:, None] * mm("tf,fd->td", a, wo, precision), None

    y0 = jnp.zeros(h.shape, F32)
    y, _ = jax.lax.scan(jax.checkpoint(one), y0,
                        (lw["wi"], lw["wu_e"], lw["wo_e"], wdense.T))
    return y


def shared_expert(h, lw, precision):
    a = jax.nn.silu(mm("...d,df->...f", h, lw["ws_in"], precision)) \
        * mm("...d,df->...f", h, lw["ws_up"], precision)
    return mm("...f,fd->...d", a, lw["ws_out"], precision)


def layer(x, lw, cfg, moe, precision):
    x = moe_gelu.attention(x, lw, cfg, precision)
    h = rms_norm(x, lw["ln2"])
    y, probs, idx, keep = moe(
        h, lambda hs: moe_gelu.route(hs, lw["router"], moe.k, precision),
        lambda rows, wd: experts(rows, wd, lw, precision))
    return x + (y + shared_expert(h, lw, precision)), probs, idx, keep


def _layer_weights(w):
    return {n: w[n] for n in PROGRAM_NAMES.values()}


def train_layers(x, w, cfg, moe, precision):
    def one(x, lw):
        x, probs, idx, keep = layer(x, lw, cfg, moe, precision)
        return x, (moe_gelu.load_balance_loss(probs, idx, cfg),
                   jnp.mean(keep.astype(F32)))

    x, (auxs, kept) = jax.lax.scan(jax.checkpoint(one), x, _layer_weights(w))
    return x, auxs, kept


def serve_layers(x, w, cfg, moe, precision):
    def one(x, lw):
        return layer(x, lw, cfg, moe, precision)[0], None

    x, _ = jax.lax.scan(one, x, _layer_weights(w))
    return x


def expert_params(cfg: dict) -> int:
    return 3 * cfg["d_model"] * cfg["d_ff"]


def layer_params(cfg: dict, k: int) -> list:
    """Attention projections, router, k routed experts and the shared one."""
    d, e = cfg["d_model"], cfg["n_experts"]
    hd = d // cfg["n_heads"]
    attn = d * cfg["n_heads"] * hd * 2 + d * cfg["n_kv_heads"] * hd * 2
    return [attn + d * e + (k + 1) * expert_params(cfg)] * cfg["n_layers"]


def moe_layers(cfg: dict) -> int:
    return cfg["n_layers"]


attention_flops_train = moe_gelu.attention_flops_train
attention_flops_prefill = moe_gelu.attention_flops_prefill
attention_flops_decode = moe_gelu.attention_flops_decode
