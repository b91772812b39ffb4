"""Plain float32 reference of the MoE decoder the program runs.

Straightforward ``jax.numpy`` at ``Precision.HIGHEST``, no kernels, no
cache, no batching across requests.  It imports nothing of the program and
takes nothing the program made: the weights come from ``bench.weights``
(regenerated from the seed), the tokens from the traffic generator or from
the program's served output.

The block is the repo's transformer block, which departs from the
published GPT-2 / Transformer-XL blocks (the configuration files say so):
RMSNorm (eps 1e-5) before attention and before the MoE layer, RoPE
(theta 1e4) on queries and keys, no biases, causal attention; the MoE
layer is a softmax router, top-k experts with the top-k weights
renormalised, tanh-GELU expert FFNs, and the output summed into the
residual.

Capacity rule (shared with the program; the reference must drop what the
program drops): per expert, ``capacity(n)`` = max(8, 8 * ceil((floor(n * k
* cf / E) + 1) / 8)) rows for a group of n tokens.  Tokens claim rows in
priority order, all first choices in token order before any second choice
(GShard); a choice whose rank among the choices of its expert in the group
is >= capacity is dropped: its weight becomes 0, the other choice keeps its
renormalised weight.  The group is one device's token shard in training
(batch-major, the sequence split over the expert-parallel axis), and one
dispatch in serving: a prefill of one request, or one decode step.

``precision="fp8"`` is the control: every matrix product takes operands
rounded to float8 e4m3 with one scale per tensor (absolute maximum to 448)
and accumulates in float32; in training the backward pass takes the same
rounded operands, and the cotangents stay in float32.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
NORM_EPS = 1e-5
ROPE_THETA = 1e4


def capacity(n: int, n_experts: int, k: int, cf: float) -> int:
    c = int(n * k * cf / n_experts) + 1
    return max(8, -(-c // 8) * 8)


def _q(x, precision: str):
    x = x.astype(F32)
    if precision == "f32":
        return x
    if precision == "fp8":
        # rounded forward values; the gradient passes straight through in
        # float32 (rounding cotangents to fp8 would flush them to zero)
        s = jax.lax.stop_gradient(448.0 / jnp.maximum(jnp.max(jnp.abs(x)),
                                                      1e-30))
        q = (x * s).astype(jnp.float8_e4m3fn).astype(F32) / s
        return x + jax.lax.stop_gradient(q - x)
    raise ValueError(f"unknown precision {precision!r}")


def mm(eq: str, a, b, precision: str):
    return jnp.einsum(eq, _q(a, precision), _q(b, precision),
                      precision=HIGHEST, preferred_element_type=F32)


def rms_norm(x, w):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + NORM_EPS) \
        * w.astype(F32)


def rope(x, pos):
    """x [B, S, H, hd]; pos [B, S]."""
    hd = x.shape[-1]
    freqs = ROPE_THETA ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos[..., None].astype(F32) * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


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


def ranks(idx, n_experts, member=None):
    """Priority rank of each choice among its expert's choices in the group
    (all first choices in token order, then second choices).  idx [G, T, k]
    over G groups; ``member`` [G, T] bool leaves tokens out of the group."""
    g, t, k = idx.shape
    oh = jax.nn.one_hot(idx, n_experts, dtype=jnp.int32)       # [G,T,k,E]
    if member is not None:
        oh = oh * member[:, :, None, None].astype(jnp.int32)
    flat = oh.transpose(0, 2, 1, 3).reshape(g, k * t, n_experts)
    pos = jnp.cumsum(flat, axis=1) - flat
    pos = pos.reshape(g, k, t, n_experts).transpose(0, 2, 1, 3)
    return jnp.sum(pos * oh, -1)                               # [G,T,k]


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


def _layers(w):
    names = ("ln1", "ln2", "wq", "wk", "wv", "wo", "router", "wi", "wo_e")
    return {n: w[n] for n in names}


# --- training ----------------------------------------------------------------

def _no_exchange(wd, ep: int):
    """The fault of an expert layer whose all-to-all is left out: each
    chip's tokens bound for expert j * E/ep + i are computed by its own
    local expert i instead.  wd [ep, T, E] dense combine weights."""
    g, t, e = wd.shape
    el = e // ep
    local = wd.reshape(g, t, ep, el).sum(axis=2)                # [ep,T,el]
    own = jax.nn.one_hot(jnp.arange(ep), ep, dtype=F32)         # [ep,ep]
    return (own[:, None, :, None] * local[:, :, None, :]).reshape(g, t, e)


def train_loss(w, batch, cfg, ep: int, precision: str = "f32",
               fault: str | None = None):
    """(Mean next-token cross entropy plus the routers' auxiliary loss,
    share of expert choices kept), with the expert layer's token groups
    laid out as ``ep`` sequence shards (the program's expert-parallel
    layout).

    ``fault`` plants a fault in the reference put in the program's place:
    ``"half_batch"`` leaves out half of the batch and takes the mean over
    the rest; ``"no_exchange"`` leaves out the exchange between chips."""
    tokens, labels = batch["tokens"], batch["labels"]
    if fault == "half_batch":
        tokens, labels = tokens[:tokens.shape[0] // 2], \
            labels[:labels.shape[0] // 2]
    b, s = tokens.shape
    e, k, cf = cfg["n_experts"], cfg["top_k"], cfg["capacity_factor"]
    t_loc = b * s // ep
    cap = capacity(t_loc, e, k, cf)
    x = w["embed"].astype(F32)[tokens]

    def layer(x, lw):
        x = attention(x, lw, cfg, precision)
        h = rms_norm(x, lw["ln2"])
        hs = h.reshape(b, ep, s // ep, -1).transpose(1, 0, 2, 3) \
            .reshape(ep, t_loc, -1)
        probs, idx, gw = route(hs, lw["router"], k, precision)
        keep = ranks(idx, e) < cap
        wd = jnp.sum(jax.nn.one_hot(idx, e, dtype=F32)
                     * (gw * keep)[..., None], axis=2)          # [ep,T,E]
        if fault == "no_exchange":
            wd = _no_exchange(wd, ep)
        f = jnp.mean(jax.nn.one_hot(idx[..., 0], e, dtype=F32), axis=1)
        aux = cfg["aux_loss_weight"] * e * jnp.mean(
            jnp.sum(f * jnp.mean(probs, axis=1), -1))
        y = experts(hs.reshape(ep * t_loc, -1), wd.reshape(ep * t_loc, e),
                    lw, precision)
        y = y.reshape(ep, b, s // ep, -1).transpose(1, 0, 2, 3) \
            .reshape(b, s, -1)
        return x + y, (aux, jnp.mean(keep.astype(F32)))

    x, (auxs, kept) = jax.lax.scan(jax.checkpoint(layer), x, _layers(w))
    x = rms_norm(x, w["final_norm"])

    def ce(tot, xs):
        xc, lc = xs
        logits = mm("bd,dv->bv", xc, w["lm_head"], precision)
        lse = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, lc[:, None], -1)[:, 0]
        return tot + jnp.sum(lse - gold), None

    c = math.gcd(s, 64)
    xc = x.reshape(b, s // c, c, -1).transpose(1, 0, 2, 3) \
        .reshape(s // c, b * c, -1)
    lc = labels.reshape(b, s // c, c).transpose(1, 0, 2).reshape(s // c, -1)
    tot, _ = jax.lax.scan(jax.checkpoint(ce), jnp.zeros((), F32), (xc, lc))
    return tot / (b * s) + jnp.sum(auxs), jnp.mean(kept)


def lr_at(step, ocfg):
    warm = jnp.minimum(step / max(ocfg["warmup_steps"], 1), 1.0)
    t = jnp.clip((step - ocfg["warmup_steps"])
                 / max(ocfg["total_steps"] - ocfg["warmup_steps"], 1), 0.0, 1.0)
    return ocfg["lr"] * warm * 0.5 * (1.0 + jnp.cos(jnp.pi * t))


def adamw_step(w, m, v, step, grads, ocfg):
    """AdamW after clipping the gradient to a global norm; ``step`` is the
    number of updates made before this one.  Returns (w, m, v, clipped
    gradients)."""
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, ocfg["grad_clip"] / jnp.maximum(gn, 1e-9))
    g = jax.tree.map(lambda x: x * scale, grads)
    t = step + 1
    lr = lr_at(t, ocfg)
    b1, b2 = ocfg["betas"]
    m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
    v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
    w = jax.tree.map(lambda p, m_, v_: p - lr * (
        (m_ / bc1) / (jnp.sqrt(v_ / bc2) + ocfg["eps"])
        + ocfg["weight_decay"] * p), w, m, v)
    return w, m, v, g


def train_readings(w0, batches, cfg, ocfg, ep: int, precision: str = "f32",
                   shardings=None, fault: str | None = None):
    """Three training steps from ``w0`` on ``batches``: the loss of each,
    the first (clipped) gradient as float32 host arrays, and the per-leaf
    norm of the weights' change after the three."""
    loss_grad = jax.value_and_grad(partial(train_loss, cfg=cfg, ep=ep,
                                           precision=precision, fault=fault),
                                   has_aux=True)

    def step(w, m, v, t, batch):
        (loss, kept), g = loss_grad(w, batch)
        w, m, v, gc = adamw_step(w, m, v, t, g, ocfg)
        return w, m, v, loss, kept, gc

    step = jax.jit(step, donate_argnums=(0, 1, 2),
                   out_shardings=None if shardings is None else
                   (shardings, shardings, shardings, None, None, shardings))
    w = jax.tree.map(lambda x: jnp.copy(x.astype(F32)), w0)
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    losses, kept, grad0 = [], [], None
    for t, batch in enumerate(batches):
        w, m, v, loss, kp, gc = step(w, m, v, t, batch)
        losses.append(float(loss))
        kept.append(float(kp))
        if grad0 is None:
            grad0 = {n: np.asarray(x, np.float32) for n, x in gc.items()}
        del gc
    change = jax.jit(lambda a, b: {n: jnp.sqrt(jnp.sum(
        (a[n] - b[n].astype(F32)) ** 2)) for n in a})(w, w0)
    return {"loss": losses, "grad0": grad0, "kept_share": float(np.mean(kept)),
            "change": {n: float(x) for n, x in change.items()}}


# --- serving -----------------------------------------------------------------

def serve_logits(w, tokens, prompt_len, caps, query, cfg, precision="f32"):
    """Next-token logits of a block of requests.

    tokens [R, N]: each request's prompt followed by its served tokens
    (right-padded); prompt_len [R]; caps [R]: the expert capacity of the
    request's prefill; query [R, Q]: positions whose logits are wanted.
    Prompt positions form one capacity group per request (the prefill
    dispatch); served-token positions are never dropped (each decode
    step's group holds at most as many tokens as its capacity)."""
    r, n = tokens.shape
    e, k = cfg["n_experts"], cfg["serve_top_k"]
    x = w["embed"].astype(F32)[tokens]
    in_prompt = jnp.arange(n)[None, :] < prompt_len[:, None]

    def layer(x, lw):
        x = attention(x, lw, cfg, precision)
        h = rms_norm(x, lw["ln2"])
        _, idx, gw = route(h, lw["router"], k, precision)
        rk = ranks(idx, e, member=in_prompt)
        keep = ~(in_prompt[..., None] & (rk >= caps[:, None, None]))
        wd = jnp.sum(jax.nn.one_hot(idx, e, dtype=F32)
                     * (gw * keep)[..., None], axis=2)
        y = experts(h.reshape(r * n, -1), wd.reshape(r * n, e), lw,
                    precision)
        return x + y.reshape(r, n, -1), None

    x, _ = jax.lax.scan(layer, x, _layers(w))
    xq = jnp.take_along_axis(x, query[..., None], axis=1)
    xq = rms_norm(xq, w["final_norm"])
    return mm("rqd,dv->rqv", xq, w["lm_head"], precision)


@partial(jax.jit, static_argnames=("cfg_items", "control"))
def _serve_block(w, tokens, prompt_len, caps, query, served, *, cfg_items,
                 control):
    cfg = dict(cfg_items)
    ref = serve_logits(w, tokens, prompt_len, caps, query, cfg)
    best = jnp.max(ref, -1)
    pick = served
    if control:
        low = serve_logits(w, tokens, prompt_len, caps, query, cfg, "fp8")
        pick = jnp.argmax(low, -1)
    return best - jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]


def serve_gaps(w, requests, cfg, *, control: bool = False, block: int = 8):
    """For each request (prompt int array, served int array), the gap by
    which each served token's reference logit lies below the reference's
    best at that position.  ``control=True`` replaces the served tokens by
    the first choices of the fp8 reference."""
    keys = ("n_layers", "d_model", "n_heads", "n_kv_heads", "n_experts",
            "serve_top_k", "capacity_factor")
    cfg_items = tuple((k_, cfg[k_]) for k_ in keys)
    n = max(len(p) + len(s) - 1 for p, s in requests)
    n = -(-n // 8) * 8
    q = max(len(s) for _, s in requests)
    out = []
    for i in range(0, len(requests), block):
        part = requests[i:i + block]
        tok = np.zeros((block, n), np.int32)
        plen = np.ones((block,), np.int32)
        caps = np.full((block,), 8, np.int32)
        query = np.zeros((block, q), np.int32)
        served = np.zeros((block, q), np.int32)
        for j, (p, s) in enumerate(part):
            seq = np.concatenate([p, s[:-1]])
            tok[j, :len(seq)] = seq
            plen[j] = len(p)
            caps[j] = capacity(len(p), cfg["n_experts"], cfg["serve_top_k"],
                               cfg["capacity_factor"])
            query[j, :len(s)] = len(p) - 1 + np.arange(len(s))
            served[j, :len(s)] = s
        gaps = np.asarray(_serve_block(w, tok, plen, caps, query, served,
                                       cfg_items=cfg_items, control=control))
        for j, (_, s) in enumerate(part):
            out.append(gaps[j, :len(s)])
    return out
