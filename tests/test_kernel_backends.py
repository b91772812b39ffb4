"""Parity suites for the Pallas MoE hot-path backends.

``compute_backend="pallas"`` (fused gating + grouped FFN + fused
dispatch/combine, all in interpret mode on CPU) must be indistinguishable —
gating metadata exactly, numerics within dtype tolerance — from the XLA
einsum path, through the raw ops, the MoE layer, the jitted train step on a
multi-device mesh, and ``serve_moe_layer``.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
from _hyp_compat import given, settings, st

from repro.configs.base import MoEConfig
from repro.core import dispatch as D
from repro.core import init_moe_params, moe_layer
from repro.core.gating import capacity, router_top_k_gating, top_k_gating
from repro.core.placement import plan_placement
from repro.core.serving import PlanArrays, serve_moe_layer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _assert_gating_equal(a, b):
    assert (np.asarray(a.expert_idx) == np.asarray(b.expert_idx)).all()
    assert (np.asarray(a.position) == np.asarray(b.position)).all()
    assert (np.asarray(a.dropped) == np.asarray(b.dropped)).all()
    np.testing.assert_allclose(a.gate_weights, b.gate_weights, atol=1e-6)
    np.testing.assert_allclose(a.router_probs, b.router_probs, atol=1e-6)
    np.testing.assert_allclose(float(a.aux_loss), float(b.aux_loss),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# fused gating vs core.gating.top_k_gating
# ---------------------------------------------------------------------------

@given(t=st.sampled_from([16, 50, 128]), e=st.sampled_from([4, 8, 16]),
       k=st.sampled_from([1, 2]), seed=st.integers(0, 50))
@settings(max_examples=20, deadline=None)
def test_fused_gating_matches_topk_gating(t, e, k, seed):
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    x = jax.random.normal(keys[0], (t, 16))
    router = jax.random.normal(keys[1], (16, e)) * 0.3
    cap = capacity(t, e, k, 1.25)
    ref = top_k_gating(x @ router, k, cap)
    got = router_top_k_gating(x, router, k, cap, compute_backend="pallas")
    _assert_gating_equal(got, ref)


def test_fused_gating_tie_breaking():
    """Duplicated router columns produce exactly tied logits for every
    token; both backends must break the tie the same way (lowest index)."""
    t, d, e = 32, 8, 6
    x = jax.random.normal(jax.random.PRNGKey(0), (t, d))
    router = jax.random.normal(jax.random.PRNGKey(1), (d, e))
    router = router.at[:, 3].set(router[:, 1])      # cols 1 and 3 tie
    router = router.at[:, 5].set(router[:, 1])      # three-way tie
    cap = capacity(t, e, 2, 2.0)
    ref = top_k_gating(x @ router, 2, cap)
    got = router_top_k_gating(x, router, 2, cap, compute_backend="pallas")
    _assert_gating_equal(got, ref)
    # ties actually occur and resolve to the lowest expert index
    probs = np.asarray(ref.router_probs)
    assert (probs[:, 1] == probs[:, 3]).all()
    idx = np.asarray(ref.expert_idx)
    assert (idx != 5).all()                  # 3rd tie member never in top-2
    assert ((idx[:, 1] != 3) | (idx[:, 0] == 1)).all()  # 3 only after 1


def test_fused_gating_all_dropped():
    """Everyone wants expert 0 at tiny capacity: most tokens drop all their
    choices; drops/positions/zeroed weights must match exactly."""
    t, d, e = 256, 8, 4
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(0), (t, d))) + 0.1
    router = jnp.zeros((d, e)).at[:, 0].set(10.0)
    cap = 8
    ref = top_k_gating(x @ router, 1, cap)
    got = router_top_k_gating(x, router, 1, cap, compute_backend="pallas")
    _assert_gating_equal(got, ref)
    dropped = np.asarray(ref.dropped)
    assert dropped.sum() == t - cap                 # all-but-cap dropped
    assert (np.asarray(got.gate_weights)[dropped] == 0).all()


def test_fused_gating_gradients_match():
    t, d, e = 48, 16, 8
    x = jax.random.normal(jax.random.PRNGKey(0), (t, d))
    router = jax.random.normal(jax.random.PRNGKey(1), (d, e)) * 0.3
    cap = capacity(t, e, 2, 1.25)

    def loss(backend):
        def f(x, r):
            g = router_top_k_gating(x, r, 2, cap, compute_backend=backend)
            return (g.gate_weights ** 2).sum() + g.aux_loss
        return f

    gx = jax.jit(jax.grad(loss("xla"), argnums=(0, 1)))(x, router)
    gp = jax.jit(jax.grad(loss("pallas"), argnums=(0, 1)))(x, router)
    for a, b in zip(gx, gp):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# pallas dispatch backend vs einsum oracle
# ---------------------------------------------------------------------------

@given(t=st.sampled_from([16, 64]), e=st.sampled_from([4, 8]),
       k=st.sampled_from([1, 2]), seed=st.integers(0, 50))
@settings(max_examples=20, deadline=None)
def test_pallas_dispatch_matches_einsum_oracle(t, e, k, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (t, 16))
    logits = jax.random.normal(jax.random.PRNGKey(seed + 1), (t, e))
    cap = capacity(t, e, k, 2.0)
    g = top_k_gating(logits, k, cap)
    b1 = D.dispatch_einsum(x, g, e, cap)
    b2 = D.dispatch_pallas(x, g, e, cap)
    np.testing.assert_allclose(b1, b2, atol=1e-5)
    buf = jax.random.normal(jax.random.PRNGKey(seed + 2), (e, cap, 16))
    y1 = D.combine_einsum(buf, g, e, cap)
    y2 = D.combine_pallas(buf, g, e, cap)
    np.testing.assert_allclose(y1, y2, atol=1e-4, rtol=1e-3)


def test_pallas_dispatch_gradients_match_oracle():
    t, e, k, d = 32, 4, 2, 16
    x = jax.random.normal(jax.random.PRNGKey(0), (t, d))
    logits = jax.random.normal(jax.random.PRNGKey(1), (t, e))
    cap = capacity(t, e, k, 2.0)
    g = top_k_gating(logits, k, cap)

    def roundtrip(backend):
        disp, comb = D.get_backend(backend)

        def f(x, w):
            gg = g._replace(gate_weights=w)
            buf = disp(x, gg, e, cap)
            return (comb(buf, gg, e, cap) ** 2).sum()
        return f

    gx = jax.jit(jax.grad(roundtrip("einsum"), argnums=(0, 1)))(
        x, g.gate_weights)
    gp = jax.jit(jax.grad(roundtrip("pallas"), argnums=(0, 1)))(
        x, g.gate_weights)
    np.testing.assert_allclose(gx[0], gp[0], atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(gx[1], gp[1], atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# full layer / train step / serving
# ---------------------------------------------------------------------------

def _cfgs():
    cfg_x = MoEConfig(n_experts=4, top_k=2, d_ff=32, n_microops=2,
                      compute_backend="xla")
    return cfg_x, dataclasses.replace(cfg_x, compute_backend="pallas")


def test_moe_layer_pallas_backend_fwd_bwd():
    cfg_x, cfg_p = _cfgs()
    params = init_moe_params(jax.random.PRNGKey(0), 16, 32, 4)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 16))

    a = jax.jit(lambda x, p: moe_layer(None, x, p, cfg_x))(x, params)
    b = jax.jit(lambda x, p: moe_layer(None, x, p, cfg_p,
                                       dispatch_backend="pallas"))(x, params)
    np.testing.assert_allclose(a.y, b.y, atol=1e-5)
    assert (np.asarray(a.expert_idx) == np.asarray(b.expert_idx)).all()
    np.testing.assert_allclose(float(a.aux_loss), float(b.aux_loss),
                               atol=1e-6)

    def loss(cfg, db):
        def f(x, p):
            out = moe_layer(None, x, p, cfg, dispatch_backend=db)
            return (out.y ** 2).sum() + out.aux_loss
        return f

    ga = jax.jit(jax.grad(loss(cfg_x, "scatter"), argnums=(0, 1)))(x, params)
    gb = jax.jit(jax.grad(loss(cfg_p, "pallas"), argnums=(0, 1)))(x, params)
    for u, v in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_allclose(u, v, atol=2e-4, rtol=1e-3)


def test_serve_moe_layer_pallas_backend_matches_xla():
    cfg_x, cfg_p = _cfgs()
    params = init_moe_params(jax.random.PRNGKey(0), 16, 32, 4)
    x = jax.random.normal(jax.random.PRNGKey(2), (64, 16))
    for seed in range(3):
        pop = np.random.RandomState(seed).dirichlet(np.ones(4) * 0.3)
        plan = PlanArrays.from_plan(plan_placement(pop, 1, max_pack=4))
        y1, e1, p1 = jax.jit(lambda x, p, pl: serve_moe_layer(
            None, x, p, cfg_x, pl, top_k=1))(x, params, plan)
        y2, e2, p2 = jax.jit(lambda x, p, pl: serve_moe_layer(
            None, x, p, cfg_p, pl, top_k=1))(x, params, plan)
        np.testing.assert_allclose(y1, y2, atol=1e-5)
        assert (np.asarray(e1) == np.asarray(e2)).all()
        np.testing.assert_allclose(p1, p2, atol=1e-6)


def test_train_step_pallas_backend_matches_xla_on_mesh():
    """The jitted train step (fwd+bwd) with compute_backend="pallas" and the
    pallas dispatch backend produces the same loss and gradients as the xla
    backend on a real multi-device CPU mesh."""
    code = textwrap.dedent("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.data import DataConfig, SyntheticLM
        from repro.launch.mesh import make_mesh, mesh_context
        from repro.models import lm as lm_mod

        mesh = make_mesh((2, 4), ("data", "model"))
        cfg_x = get_config("gpt2-moe").smoke()
        cfg_p = dataclasses.replace(
            cfg_x, moe=dataclasses.replace(cfg_x.moe,
                                           compute_backend="pallas"))
        dc = DataConfig(vocab_size=cfg_x.vocab_size, seq_len=32,
                        global_batch=8)
        batch = {k: jnp.asarray(v)
                 for k, v in SyntheticLM(dc).batch(0).items()}
        params = lm_mod.init_params(cfg_x, jax.random.PRNGKey(0))

        def loss_fn(cfg, db):
            def f(p, b):
                return lm_mod.forward_train(mesh, cfg, p, b, fsdp=False,
                                            dispatch_backend=db).loss
            return f

        with mesh_context(mesh):
            lx, gx = jax.jit(jax.value_and_grad(
                loss_fn(cfg_x, "scatter")))(params, batch)
            lp, gp = jax.jit(jax.value_and_grad(
                loss_fn(cfg_p, "pallas")))(params, batch)
        assert abs(float(lx) - float(lp)) < 1e-5, (float(lx), float(lp))
        for a, b in zip(jax.tree.leaves(gx), jax.tree.leaves(gp)):
            d = float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
            assert d < 2e-4, d

        # one full optimizer step on each backend stays in tolerance too
        from repro.launch.steps import make_train_step
        from repro.optim.adamw import AdamWConfig, init_opt_state
        ocfg = AdamWConfig()
        opt = init_opt_state(params, ocfg)
        with mesh_context(mesh):
            px, _, mx = jax.jit(make_train_step(
                cfg_x, mesh, ocfg, fsdp=False))(params, opt, batch)
            pp, _, mp = jax.jit(make_train_step(
                cfg_p, mesh, ocfg, fsdp=False,
                dispatch_backend="pallas"))(params, opt, batch)
        assert abs(mx["loss"] - mp["loss"]) < 1e-5
        for a, b in zip(jax.tree.leaves(px), jax.tree.leaves(pp)):
            d = float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
            assert d < 1e-4, d
        print("OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, f"stderr:\n{p.stderr[-3000:]}"
    assert "OK" in p.stdout
