"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

Each function is the semantic ground truth: simple, obviously-correct
implementations with no tiling/fusion — tests sweep shapes/dtypes and assert
the kernels match these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ref_grouped_ffn(x, wi, wu, wo, ffn_type: str = "swiglu"):
    """Grouped expert FFN.  x: [E, T, D]; wi/wu: [E, D, F]; wo: [E, F, D]."""
    h = jnp.einsum("etd,edf->etf", x, wi)
    if ffn_type == "swiglu":
        h = jax.nn.silu(h) * jnp.einsum("etd,edf->etf", x, wu)
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("etf,efd->etd", h, wo).astype(x.dtype)


def router_logits(x, router):
    """``x @ router`` accumulated and returned in f32.  Every gating path
    (XLA, the fused kernel and its oracle, the server's popularity gate)
    ranks experts on these unrounded logits: rounded to bf16, two experts
    can tie, and the backends break such a tie differently."""
    return jnp.dot(x, router, preferred_element_type=jnp.float32)


def ref_topk_gating(logits, k: int):
    """Fused router softmax + top-k.  logits: [T, E].
    Returns (expert_idx [T,k] i32, gate_w [T,k] f32 renormalized, probs)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w, idx = jax.lax.top_k(probs, k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    return idx.astype(jnp.int32), w, probs


def ref_dispatch_rows(x, src_tok, scale=None):
    """Slot-buffer dispatch.  x: [T, d]; src_tok: [R] source token per slot
    row (-1 empty); scale: optional [R] f32.  -> [R, d] in x.dtype."""
    rows = x[jnp.maximum(src_tok, 0)]
    s = jnp.where(src_tok >= 0,
                  1.0 if scale is None else scale.astype(jnp.float32), 0.0)
    return (rows.astype(jnp.float32) * s[:, None]).astype(x.dtype)


def ref_combine_rows(buf, rows, weights):
    """Gate-weighted combine.  buf: [R, d]; rows: [T, k] flat slot per
    (token, choice), -1 dropped; weights: [T, k].  -> [T, d] in buf.dtype."""
    vals = buf[jnp.maximum(rows, 0)]                    # [T, k, d]
    w = jnp.where(rows >= 0, weights.astype(jnp.float32), 0.0)
    return jnp.sum(vals.astype(jnp.float32) * w[..., None],
                   axis=1).astype(buf.dtype)


def ref_topk_positions(expert_idx, n_experts: int):
    """GShard priority positions.  expert_idx: [T, k] int32 (-1 = masked)
    -> [T, k] int32 choice-major rank of each (token, choice) within its
    expert: all first choices outrank any second choice.  Masked rows get
    rank 0 and do not advance any counter."""
    t, k = expert_idx.shape
    onehot = (expert_idx[..., None]
              == jnp.arange(n_experts, dtype=jnp.int32)).astype(jnp.int32)
    flat = onehot.transpose(1, 0, 2).reshape(k * t, n_experts)
    pos = jnp.cumsum(flat, axis=0) - flat
    pos = pos.reshape(k, t, n_experts).transpose(1, 0, 2)
    return jnp.sum(pos * onehot, axis=-1).astype(jnp.int32)


def ref_weighted_route(expert_idx, position, cum_weights, slot_of,
                       slot_cap: int, xp=jnp):
    """Weighted replica-bin routing (the ``weighted_route`` kernel oracle).

    expert_idx/position: [T, k] int32; cum_weights/slot_of: [E, R] int32
    (inclusive weight cumsum / global slot per replica, -1 pads);
    -> [T, k] int32 flat row (slot * slot_cap + offset), -1 dropped.

    Pure integer arithmetic, exactly the kernel's bin partition; pass
    ``xp=numpy`` for the host-side telemetry mirror.
    """
    idx = xp.maximum(expert_idx, 0)
    cum = xp.take(cum_weights, idx, axis=0)             # [T, k, R]
    rw = cum.shape[-1]
    total = cum[..., -1]
    ge = position[..., None] >= cum
    which = xp.minimum(xp.sum(ge.astype(xp.int32), axis=-1), rw - 1)
    prev = xp.max(xp.where(ge, cum, 0), axis=-1)
    slotvals = xp.take(slot_of, idx, axis=0)            # [T, k, R]
    r_iota = xp.arange(rw, dtype=xp.int32)
    slot = xp.sum(xp.where(r_iota[None, None, :] == which[..., None],
                           slotvals, 0), axis=-1)
    rows = slot * slot_cap + (position - prev)
    keep = (expert_idx >= 0) & (position < total) & (slot >= 0)
    return xp.where(keep, rows, -1).astype(xp.int32)


def ref_attention(q, k, v, *, causal: bool = True, window: int = 0):
    """q: [B, Sq, H, hd]; k/v: [B, Skv, KV, hd] -> [B, Sq, H, hd]."""
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    rep = h // kv
    kk = jnp.repeat(k, rep, axis=2) if rep > 1 else k
    vv = jnp.repeat(v, rep, axis=2) if rep > 1 else v
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        kk.astype(jnp.float32)) / (hd ** 0.5)
    skv = k.shape[1]
    qpos, kpos = jnp.arange(sq), jnp.arange(skv)
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= kpos[None] <= qpos[:, None]
    if window:
        mask &= kpos[None] > qpos[:, None] - window
    logits = jnp.where(mask[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      vv.astype(jnp.float32)).astype(q.dtype)


def ref_rwkv6(r, k, v, w, u):
    """Naive RWKV6 recurrence.  r/k/v/w: [B, T, H, hd] (w = log decay < 0);
    u: [H, hd].  Returns y [B, T, H, hd] (f32)."""
    b, t, h, hd = r.shape

    def step(s, inp):
        r_t, k_t, v_t, w_t = inp                     # [B,H,hd]
        kv = k_t[..., :, None] * v_t[..., None, :]   # [B,H,hd,hd]
        y = jnp.einsum("bhk,bhkv->bhv", r_t, s + u[None, :, :, None] * kv)
        s = jnp.exp(w_t)[..., None] * s + kv
        return s, y

    s0 = jnp.zeros((b, h, hd, hd), jnp.float32)
    seq = tuple(a.astype(jnp.float32).transpose(1, 0, 2, 3)
                for a in (r, k, v, w))
    _, ys = jax.lax.scan(step, s0, seq)
    return ys.transpose(1, 0, 2, 3)


def ref_ssd(x, dt, a_log, b, c, d_skip):
    """Naive Mamba2/SSD recurrence.  x: [B,T,H,P]; dt: [B,T,H] (pre-softplus);
    a_log: [H]; b,c: [B,T,N]; d_skip: [H].  Returns y [B,T,H,P] (f32)."""
    bsz, t, h, p = x.shape
    a = -jnp.exp(a_log.astype(jnp.float32))
    dtp = jax.nn.softplus(dt.astype(jnp.float32))

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp                   # [B,H,P],[B,H],[B,N],[B,N]
        dec = jnp.exp(dt_t * a[None])               # [B,H]
        upd = jnp.einsum("bhp,bn->bhpn", x_t * dt_t[..., None], b_t)
        s = s * dec[..., None, None] + upd
        y = jnp.einsum("bhpn,bn->bhp", s, c_t)
        return s, y

    s0 = jnp.zeros((bsz, h, p, b.shape[-1]), jnp.float32)
    seq = (x.astype(jnp.float32).transpose(1, 0, 2, 3),
           dtp.transpose(1, 0, 2),
           b.astype(jnp.float32).transpose(1, 0, 2),
           c.astype(jnp.float32).transpose(1, 0, 2))
    _, ys = jax.lax.scan(step, s0, seq)
    y = ys.transpose(1, 0, 2, 3)
    return y + x.astype(jnp.float32) * d_skip[None, None, :, None]
