"""Plain float32 reference of the MoE decoders the program runs: what every
block shares.

Straightforward ``jax.numpy`` at ``Precision.HIGHEST``, no kernels, no
cache, no batching across requests.  It imports nothing of the program and
takes nothing the program made: the weights come from ``bench.weights``
(regenerated from the seed), the tokens from the traffic generator or from
the program's served output.

A block module (``bench/blocks/<block>.py``, named by the configuration)
holds the layer equations: attention, router, experts and how the layers
are walked.  Here is the frame they sit in (token embedding, final RMSNorm,
untied LM head, the loss and the logits), the MoE dispatch every block's
router hands its choices to, the fp8 control, AdamW and the readings.

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

import json
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


def rope(x, pos, theta: float = ROPE_THETA):
    """x [B, S, H, hd]; pos [B, S]."""
    hd = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos[..., None].astype(F32) * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


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


def combine_weights(idx, w, keep, n_experts: int):
    """Dense combine weights [..., T, E] of the kept choices."""
    return jnp.sum(jax.nn.one_hot(idx, n_experts, dtype=F32)
                   * (w * keep)[..., None], axis=-2)


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


class TrainDispatch:
    """A training MoE layer's dispatch, the same for every block: the
    token groups are ``ep`` sequence shards (batch-major, the program's
    expert-parallel layout) of ``capacity`` rows per expert; the fault
    ``"no_exchange"`` leaves out the exchange between chips.

    ``moe(h, route, experts)``: ``route(groups [G, T, d])`` is the block's
    router, giving (probs, choices [G, T, k], their weights);
    ``experts(rows [G*T, d], combine [G*T, E])`` the block's weighted sum
    over its experts.  Returns the output in h's layout [B, S, d] and the
    probs, choices and kept choices per group."""

    def __init__(self, b: int, s: int, ep: int, cfg: dict,
                 fault: str | None = None):
        self.b, self.s, self.ep, self.fault = b, s, ep, fault
        self.n_experts, self.k = cfg["n_experts"], cfg["top_k"]
        self.cap = capacity(b * s // ep, self.n_experts, self.k,
                            cfg["capacity_factor"])

    def __call__(self, h, route, experts):
        b, s, ep, e = self.b, self.s, self.ep, self.n_experts
        t_loc = b * s // ep
        hs = h.reshape(b, ep, s // ep, -1).transpose(1, 0, 2, 3) \
            .reshape(ep, t_loc, -1)
        probs, idx, gw = route(hs)
        keep = ranks(idx, e) < self.cap
        wd = combine_weights(idx, gw, keep, e)                 # [ep,T,E]
        if self.fault == "no_exchange":
            wd = _no_exchange(wd, ep)
        y = experts(hs.reshape(ep * t_loc, -1), wd.reshape(ep * t_loc, e))
        y = y.reshape(ep, b, s // ep, -1).transpose(1, 0, 2, 3) \
            .reshape(b, s, -1)
        return y, probs, idx, keep


def train_loss(block, w, batch, cfg, ep: int, precision: str = "f32",
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
    moe = TrainDispatch(b, s, ep, cfg, fault)
    x = w["embed"].astype(F32)[tokens]
    x, auxs, kept = block.train_layers(x, w, cfg, moe, precision)
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


def train_readings(block, w0, batches, cfg, ocfg, ep: int,
                   precision: str = "f32", shardings=None,
                   fault: str | None = None):
    """Three training steps from ``w0`` on ``batches``: the loss of each,
    the first (clipped) gradient as float32 host arrays, and the per-leaf
    norm of the weights' change after the three."""
    loss_grad = jax.value_and_grad(partial(train_loss, block, cfg=cfg, ep=ep,
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

class ServeDispatch:
    """A serving MoE layer's dispatch, the same for every block: each
    request's prompt positions form one capacity group (its prefill
    dispatch) of ``caps`` [R] rows per expert; served-token positions are
    never dropped (each decode step's group holds at most as many tokens as
    its capacity).  Called as ``TrainDispatch`` is, on h [R, N, d]."""

    def __init__(self, in_prompt, caps, cfg: dict):
        self.in_prompt, self.caps = in_prompt, caps
        self.n_experts, self.k = cfg["n_experts"], cfg["serve_top_k"]

    def __call__(self, h, route, experts):
        r, n = self.in_prompt.shape
        e = self.n_experts
        probs, idx, gw = route(h)
        rk = ranks(idx, e, member=self.in_prompt)
        keep = ~(self.in_prompt[..., None]
                 & (rk >= self.caps[:, None, None]))
        wd = combine_weights(idx, gw, keep, e)
        y = experts(h.reshape(r * n, -1), wd.reshape(r * n, e))
        return y.reshape(r, n, -1), probs, idx, keep


def serve_logits(block, w, tokens, prompt_len, caps, query, cfg,
                 precision="f32"):
    """Next-token logits of a batch of requests.

    tokens [R, N]: each request's prompt followed by its served tokens
    (right-padded); prompt_len [R]; caps [R]: the expert capacity of the
    request's prefill; query [R, Q]: positions whose logits are wanted."""
    r, n = tokens.shape
    x = w["embed"].astype(F32)[tokens]
    in_prompt = jnp.arange(n)[None, :] < prompt_len[:, None]
    x = block.serve_layers(x, w, cfg, ServeDispatch(in_prompt, caps, cfg),
                           precision)
    xq = jnp.take_along_axis(x, query[..., None], axis=1)
    xq = rms_norm(xq, w["final_norm"])
    return mm("rqd,dv->rqv", xq, w["lm_head"], precision)


@partial(jax.jit, static_argnames=("block", "cfg_json", "control"))
def _batch_gaps(w, tokens, prompt_len, caps, query, served, *, block,
                cfg_json, control):
    cfg = json.loads(cfg_json)
    ref = serve_logits(block, w, tokens, prompt_len, caps, query, cfg)
    best = jnp.max(ref, -1)
    pick = served
    if control:
        low = serve_logits(block, w, tokens, prompt_len, caps, query, cfg,
                           "fp8")
        pick = jnp.argmax(low, -1)
    return best - jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]


def serve_gaps(block, w, requests, cfg, *, control: bool = False,
               batch: int = 8):
    """For each request (prompt int array, served int array), the gap by
    which each served token's reference logit lies below the reference's
    best at that position, ``batch`` requests a call.  ``control=True``
    replaces the served tokens by the first choices of the fp8
    reference."""
    cfg_json = json.dumps(cfg, sort_keys=True)
    n = max(len(p) + len(s) - 1 for p, s in requests)
    n = -(-n // 8) * 8
    q = max(len(s) for _, s in requests)
    out = []
    for i in range(0, len(requests), batch):
        part = requests[i:i + batch]
        tok = np.zeros((batch, n), np.int32)
        plen = np.ones((batch,), np.int32)
        caps = np.full((batch,), 8, np.int32)
        query = np.zeros((batch, q), np.int32)
        served = np.zeros((batch, q), np.int32)
        for j, (p, s) in enumerate(part):
            seq = np.concatenate([p, s[:-1]])
            tok[j, :len(seq)] = seq
            plen[j] = len(p)
            caps[j] = capacity(len(p), cfg["n_experts"], cfg["serve_top_k"],
                               cfg["capacity_factor"])
            query[j, :len(s)] = len(p) - 1 + np.arange(len(s))
            served[j, :len(s)] = s
        gaps = np.asarray(_batch_gaps(w, tok, plen, caps, query, served,
                                      block=block, cfg_json=cfg_json,
                                      control=control))
        for j, (_, s) in enumerate(part):
            out.append(gaps[j, :len(s)])
    return out
