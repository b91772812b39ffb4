"""Per-kernel allclose sweeps against the pure-jnp oracles (ref.py).

Every Pallas kernel runs in interpret mode (kernel body executed on CPU)
across a shape/dtype sweep and must match its oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_ffn import grouped_ffn
from repro.kernels.rwkv6 import rwkv6_wkv
from repro.kernels.ssd import ssd_scan
from repro.kernels.topk_gating import topk_gating_fused

KEY = jax.random.PRNGKey(42)


def keys(n):
    return jax.random.split(KEY, n)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("e,t,d,f", [(2, 32, 64, 128), (4, 64, 128, 256),
                                     (1, 16, 256, 128), (8, 128, 64, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("ffn_type", ["swiglu", "gelu"])
def test_grouped_ffn(e, t, d, f, dtype, ffn_type):
    k = keys(4)
    x = (jax.random.normal(k[0], (e, t, d)) * 0.3).astype(dtype)
    wi = (jax.random.normal(k[1], (e, d, f)) * 0.05).astype(dtype)
    wu = (jax.random.normal(k[2], (e, d, f)) * 0.05).astype(dtype)
    wo = (jax.random.normal(k[3], (e, f, d)) * 0.05).astype(dtype)
    got = grouped_ffn(x, wi, wu, wo, ffn_type=ffn_type, block_t=16,
                      block_f=32)
    want = ref.ref_grouped_ffn(x, wi, wu, wo, ffn_type)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("e,t,d,f", [(2, 300, 64, 96), (3, 17, 32, 40),
                                     (1, 130, 64, 200)])
def test_grouped_ffn_ragged_shapes_pad(e, t, d, f):
    """T/F that do not tile the requested blocks pad up instead of
    shrinking the tile (the old path halved bt/bf down to scalar tiles)."""
    k = keys(4)
    x = jax.random.normal(k[0], (e, t, d)) * 0.3
    wi = jax.random.normal(k[1], (e, d, f)) * 0.05
    wu = jax.random.normal(k[2], (e, d, f)) * 0.05
    wo = jax.random.normal(k[3], (e, f, d)) * 0.05
    got = grouped_ffn(x, wi, wu, wo, block_t=128, block_f=128)
    want = ref.ref_grouped_ffn(x, wi, wu, wo, "swiglu")
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_grouped_ffn_gelu_without_up_projection():
    """gelu FFNs pass wu=None; no zeros tensor is built for it."""
    k = keys(3)
    e, t, d, f = 2, 32, 16, 48
    x = jax.random.normal(k[0], (e, t, d)) * 0.3
    wi = jax.random.normal(k[1], (e, d, f)) * 0.05
    wo = jax.random.normal(k[2], (e, f, d)) * 0.05
    got = grouped_ffn(x, wi, None, wo, ffn_type="gelu", block_t=16)
    want = ref.ref_grouped_ffn(x, wi, None, wo, "gelu")
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    with pytest.raises(ValueError):
        grouped_ffn(x, wi, None, wo, ffn_type="swiglu")


@pytest.mark.parametrize("e,m,k_,n", [(2, 37, 24, 41), (4, 64, 16, 64),
                                      (1, 256, 32, 100)])
def test_grouped_matmul(e, m, k_, n):
    from repro.kernels.moe_ffn import grouped_matmul
    kk = keys(2)
    a = jax.random.normal(kk[0], (e, m, k_))
    b = jax.random.normal(kk[1], (e, k_, n))
    got = grouped_matmul(a, b)
    want = jnp.einsum("emk,ekn->emn", a, b)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_grouped_ffn_op_custom_vjp_matches_oracle_grads():
    """The kernel-path backward (dgrad/wgrad as grouped GEMMs) must match
    autodiff through the einsum oracle, for both FFN types."""
    from repro.kernels.ops import grouped_ffn_op
    for ffn_type in ("swiglu", "gelu"):
        k = keys(4)
        e, t, d, f = 2, 24, 16, 32
        x = jax.random.normal(k[0], (e, t, d)) * 0.3
        wi = jax.random.normal(k[1], (e, d, f)) * 0.05
        wu = jax.random.normal(k[2], (e, d, f)) * 0.05 \
            if ffn_type == "swiglu" else None
        wo = jax.random.normal(k[3], (e, f, d)) * 0.05

        gp = jax.grad(lambda a: (grouped_ffn_op(*a, ffn_type,
                                                use_pallas=True) ** 2).sum())(
            (x, wi, wu, wo))
        gr = jax.grad(lambda a: (ref.ref_grouped_ffn(*a, ffn_type)
                                 ** 2).sum())((x, wi, wu, wo))
        for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_block_and_pad_alignment_invariants():
    """Chosen tiles are always hardware-aligned and tile the padded extent;
    ragged extents pad up instead of shrinking the tile (incl. the T=17
    full-extent case, which must not yield an unaligned 17-row tile)."""
    from repro.kernels.tiling import LANE, SUBLANE, block_and_pad
    for n in (5, 16, 17, 50, 100, 128, 130, 256, 300, 1000, 4096):
        for block in (16, 128, 256, 1024):
            for sub in (SUBLANE, LANE):
                b, n_pad = block_and_pad(n, block, sub=sub)
                assert b % sub == 0, (n, block, sub, b)
                assert n_pad % b == 0 and n_pad >= n, (n, block, sub, b, n_pad)
                # padding never exceeds one tile's worth
                assert n_pad - n < b, (n, block, sub, b, n_pad)


@pytest.mark.parametrize("t,e,k", [(64, 8, 1), (128, 16, 2), (32, 4, 2),
                                   (50, 8, 2)])
def test_topk_gating(t, e, k):
    logits = jax.random.normal(keys(1)[0], (t, e))
    idx, w, probs = topk_gating_fused(logits, k, block_t=16)
    ridx, rw, rprobs = ref.ref_topk_gating(logits, k)
    assert (np.asarray(idx) == np.asarray(ridx)).all()
    np.testing.assert_allclose(w, rw, atol=1e-6)
    np.testing.assert_allclose(probs, rprobs, atol=1e-6)


@pytest.mark.parametrize("t,d,e,k", [(64, 16, 8, 2), (50, 32, 4, 1),
                                     (128, 8, 16, 2)])
def test_topk_gating_fused_router(t, d, e, k):
    """Router matmul folded into the kernel == matmul-then-gate oracle."""
    kk = keys(2)
    x = jax.random.normal(kk[0], (t, d))
    router = jax.random.normal(kk[1], (d, e)) * 0.3
    idx, w, probs = topk_gating_fused(x, k, router=router, block_t=16)
    ridx, rw, rprobs = ref.ref_topk_gating(x @ router, k)
    assert (np.asarray(idx) == np.asarray(ridx)).all()
    np.testing.assert_allclose(w, rw, atol=1e-6)
    np.testing.assert_allclose(probs, rprobs, atol=1e-6)


@pytest.mark.parametrize("t,n_rows,d,k", [(32, 40, 16, 2), (64, 72, 8, 1),
                                          (100, 60, 32, 2)])
def test_dispatch_combine_rows(t, n_rows, d, k):
    """The fused scatter/gather kernels vs their jnp oracles, including
    empty rows (-1) and dropped choices."""
    from repro.kernels.dispatch import combine_rows, dispatch_rows
    kk = keys(4)
    x = jax.random.normal(kk[0], (t, d))
    rows = jax.random.randint(kk[1], (t, k), -1, n_rows)
    # de-duplicate destination rows (gating guarantees uniqueness)
    flat = np.full((t * k,), -1, np.int64)
    seen = set()
    for i, r in enumerate(np.asarray(rows).reshape(-1)):
        if r >= 0 and r not in seen:
            flat[i] = r
            seen.add(r)
    rows = jnp.asarray(flat.reshape(t, k), jnp.int32)

    src = np.full((n_rows,), -1, np.int64)
    for i, r in enumerate(flat):
        if r >= 0:
            src[r] = i // k
    src = jnp.asarray(src, jnp.int32)

    buf = dispatch_rows(x, src, block_rows=16)
    np.testing.assert_allclose(buf, ref.ref_dispatch_rows(x, src), atol=1e-6)

    w = jnp.abs(jax.random.normal(kk[2], (t, k)))
    big = jax.random.normal(kk[3], (n_rows, d))
    y = combine_rows(big, rows, w, block_t=16)
    np.testing.assert_allclose(y, ref.ref_combine_rows(big, rows, w),
                               atol=1e-5, rtol=1e-5)


def test_dispatch_combine_rows_nonfinite_reach():
    """The one-hot gathers match the oracles only on finite inputs.  A NaN
    at (row, feature c) of the source reaches feature c of EVERY output row,
    empty rows included: each output tile streams every source tile, and
    0 * NaN is NaN.  The other features stay exact.  (The oracles' gather
    keeps the NaN in the one row that selects it.)"""
    from repro.kernels.dispatch import combine_rows, dispatch_rows
    t, n_rows, d, c = 32, 24, 8, 3
    x = jax.random.normal(keys(1)[0], (t, d)).at[5, c].set(jnp.nan)
    src = jnp.asarray([i + 6 if i % 4 else -1 for i in range(n_rows)],
                      jnp.int32)                    # token 5 unselected
    out = np.asarray(dispatch_rows(x, src, block_rows=8, block_src=16))
    want = np.asarray(ref.ref_dispatch_rows(x, src))
    assert np.isfinite(want).all()
    assert np.isnan(out[:, c]).all()
    others = np.arange(d) != c
    np.testing.assert_array_equal(out[:, others], want[:, others])

    rows = jnp.asarray([[i, -1] if i < n_rows else [-1, -1]
                        for i in range(t)], jnp.int32)
    buf = x[:n_rows]                                 # NaN in slot row 5
    w = jnp.ones((t, 2), jnp.float32)
    y = np.asarray(combine_rows(buf, rows, w, block_t=8, block_rows=8))
    want = np.asarray(ref.ref_combine_rows(buf, rows, w))
    assert np.isnan(want[5, c]) and np.isfinite(np.delete(want, 5, 0)).all()
    assert np.isnan(y[:, c]).all()
    np.testing.assert_array_equal(y[:, others], want[:, others])


@pytest.mark.parametrize("b,s,h,kv,hd", [(1, 64, 2, 2, 32), (2, 128, 4, 2, 32),
                                         (2, 64, 8, 1, 64), (1, 256, 4, 4, 16)])
@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 40)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(b, s, h, kv, hd, causal, window, dtype):
    k = keys(3)
    q = (jax.random.normal(k[0], (b, s, h, hd)) * 0.3).astype(dtype)
    kk = (jax.random.normal(k[1], (b, s, kv, hd)) * 0.3).astype(dtype)
    v = (jax.random.normal(k[2], (b, s, kv, hd)) * 0.3).astype(dtype)
    got = flash_attention(q, kk, v, causal=causal, window=window,
                          block_q=32, block_k=32)
    want = ref.ref_attention(q, kk, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype] * 2, rtol=TOL[dtype])


@pytest.mark.parametrize("b,t,h,hd,chunk", [(1, 32, 2, 16, 8),
                                            (2, 64, 2, 32, 16),
                                            (2, 48, 4, 16, 16)])
def test_rwkv6(b, t, h, hd, chunk):
    k = keys(5)
    r = jax.random.normal(k[0], (b, t, h, hd)) * 0.3
    kk = jax.random.normal(k[1], (b, t, h, hd)) * 0.3
    v = jax.random.normal(k[2], (b, t, h, hd)) * 0.3
    w = -jnp.exp(jax.random.normal(k[3], (b, t, h, hd)) * 0.5)
    u = jax.random.normal(k[4], (h, hd)) * 0.3
    got = rwkv6_wkv(r, kk, v, w, u, chunk=chunk)
    want = ref.ref_rwkv6(r, kk, v, w, u)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("b,t,h,p,n,chunk", [(1, 32, 2, 16, 8, 8),
                                             (2, 64, 2, 32, 16, 16),
                                             (2, 48, 4, 16, 8, 16)])
def test_ssd(b, t, h, p, n, chunk):
    k = keys(4)
    x = jax.random.normal(k[0], (b, t, h, p)) * 0.3
    dt = jax.random.normal(k[1], (b, t, h)) * 0.5
    a_log = jnp.log(jnp.linspace(1.0, 4.0, h))
    bb = jax.random.normal(k[2], (b, t, n)) * 0.3
    cc = jax.random.normal(k[3], (b, t, n)) * 0.3
    d = jnp.ones((h,))
    got = ssd_scan(x, dt, a_log, bb, cc, d, chunk=chunk)
    want = ref.ref_ssd(x, dt, a_log, bb, cc, d)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_model_ssd_chunked_matches_naive():
    """The model's chunked SSD (models/ssm.py) is itself oracle-checked."""
    from repro.models.ssm import ssd_chunked
    k = keys(4)
    b, t, h, p, n = 2, 64, 2, 16, 8
    x = jax.random.normal(k[0], (b, t, h, p)) * 0.3
    dt = jax.random.normal(k[1], (b, t, h)) * 0.5
    a_log = jnp.log(jnp.linspace(1.0, 4.0, h))
    bb = jax.random.normal(k[2], (b, t, n)) * 0.3
    cc = jax.random.normal(k[3], (b, t, n)) * 0.3
    d = jnp.ones((h,))
    got, _ = ssd_chunked(x, dt, a_log, bb, cc, d, chunk=16)
    want = ref.ref_ssd(x, dt, a_log, bb, cc, d)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_model_wkv_chunked_matches_naive():
    from repro.models.rwkv import wkv_chunked
    k = keys(5)
    b, t, h, hd = 2, 64, 2, 16
    r = jax.random.normal(k[0], (b, t, h * hd)) * 0.3
    kk = jax.random.normal(k[1], (b, t, h * hd)) * 0.3
    v = jax.random.normal(k[2], (b, t, h * hd)) * 0.3
    w = -jnp.exp(jax.random.normal(k[3], (b, t, h * hd)) * 0.5)
    u = jax.random.normal(k[4], (h * hd,)) * 0.3
    got, _ = wkv_chunked(r, kk, v, w, u, h, hd, chunk=16)
    want = ref.ref_rwkv6(*(a.reshape(b, t, h, hd) for a in (r, kk, v, w)),
                         u.reshape(h, hd)).reshape(b, t, h * hd)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
