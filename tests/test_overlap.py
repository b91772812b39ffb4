"""Overlap-pipeline tests: chunk-count resolution edge cases, the
double-buffered ``pipelined_expert_ffn`` vs the serial baseline across
chunk counts (n > C, non-divisors, single-chunk fallback), end-to-end
numerical equivalence of the pipelined+grouped and shortcut variants
against the baseline model (loss / grads / params, both compute
backends) on a forced 8-device mesh, and the pass-2 static check that
the (value, token) pair survives a chunked caller loop.
"""
import textwrap

import pytest

from tests.test_distributed import run_snippet


# --------------------------------------------------- chunk resolution --

def test_resolve_chunk_count():
    from repro.core.microop import resolve_chunk_count
    assert resolve_chunk_count(12, 4) == 4       # exact divisor
    assert resolve_chunk_count(12, 5) == 4       # non-divisor -> largest ≤
    assert resolve_chunk_count(12, 100) == 12    # n > C caps at C
    assert resolve_chunk_count(7, 3) == 1        # prime C: only 1 divides
    assert resolve_chunk_count(8, 8) == 8
    assert resolve_chunk_count(1, 4) == 1
    assert resolve_chunk_count(20, 0) == 1       # degenerate request


def test_chunked_a2a_surfaces_chosen_count():
    """len() of the returned micro-op list IS the chosen chunk count —
    callers can always report requested vs chosen (no silent caps)."""
    out = run_snippet("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_mesh, mesh_context
        from repro.core.microop import chunked_all_to_all, resolve_chunk_count
        mesh = make_mesh((8,), ("model",))
        buf = jax.random.normal(jax.random.PRNGKey(0), (8, 12, 4))

        for req in (1, 4, 5, 100):
            def body(b):
                outs = chunked_all_to_all(b, "model", req)
                assert len(outs) == resolve_chunk_count(12, req), (req,
                                                                   len(outs))
                return jnp.concatenate(outs, axis=1)
            with mesh_context(mesh):
                jax.jit(shard_map(body, mesh=mesh,
                                  in_specs=(P(None, None, None),),
                                  out_specs=P(None, None, None),
                                  check_vma=False))(buf)
        print("OK")
    """)
    assert "OK" in out


# ------------------------------------------- pipeline vs serial baseline --

def test_pipelined_ffn_equals_serial_across_chunk_counts():
    """The double-buffered pipeline is numerically exact vs the serial
    (pipeline=False) path for dividing, non-dividing, oversized (n > C)
    and single-chunk counts; pipeline=False matches n_chunks=1."""
    out = run_snippet("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_mesh, mesh_context
        from repro.core.microop import pipelined_expert_ffn
        mesh = make_mesh((8,), ("model",))
        E, C, D = 8, 12, 4
        buf = jax.random.normal(jax.random.PRNGKey(0), (E, C, D))
        w = jax.random.normal(jax.random.PRNGKey(1), (D, D)) * 0.3

        def run(n_chunks, pipeline=True):
            def body(b):
                y, tok = pipelined_expert_ffn(
                    b, lambda r: jnp.tanh(r @ w), "model", n_chunks, E,
                    pipeline=pipeline)
                return y + tok   # token is a zero scalar; keeps it live
            with mesh_context(mesh):
                return np.asarray(jax.jit(shard_map(
                    body, mesh=mesh, in_specs=(P(None, None, None),),
                    out_specs=P(None, None, None), check_vma=False))(buf))

        ref = run(4, pipeline=False)            # serial baseline
        assert np.array_equal(run(1), ref)      # single-chunk fallback
        for n in (2, 4, 5, 12, 100):            # incl. non-divisor, n > C
            got = run(n)
            assert np.allclose(got, ref, atol=1e-6), (n,
                np.abs(got - ref).max())
        print("OK")
    """)
    assert "OK" in out


# --------------------------------------- end-to-end variant equivalence --

_VARIANT_EQUIV = """
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.data import DataConfig, SyntheticLM
    from repro.launch.mesh import make_mesh, mesh_context
    from repro.models import lm as lm_mod

    mesh = make_mesh((2, 4), ("data", "model"))
    base = get_config("gpt2-moe").smoke()
    base = dataclasses.replace(
        base, moe=dataclasses.replace(base.moe,
                                      compute_backend="%(backend)s"))
    dc = DataConfig(vocab_size=base.vocab_size, seq_len=32, global_batch=8)
    batch = {k: jnp.asarray(v)
             for k, v in SyntheticLM(dc).batch(0).items()}

    def loss_and_grads(cfg, params):
        def f(p):
            return lm_mod.forward_train(mesh, cfg, p, batch, lina=True).loss
        with mesh_context(mesh):
            loss, grads = jax.jit(jax.value_and_grad(f))(params)
        return float(loss), grads

    def maxdiff(a, b):
        return max(float(np.max(np.abs(np.asarray(x, np.float32)
                                       - np.asarray(y, np.float32))))
                   for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))

    # --- pipelined (+grouped under pallas) vs the serial baseline:
    # identical params, chunk pipeline on/off must not change the math.
    params = lm_mod.init_params(base, jax.random.PRNGKey(0))
    serial = dataclasses.replace(
        base, moe=dataclasses.replace(base.moe, pipeline_ffn=False))
    piped = dataclasses.replace(
        base, moe=dataclasses.replace(base.moe, pipeline_ffn=True,
                                      n_microops=4))
    l0, g0 = loss_and_grads(serial, params)
    l1, g1 = loss_and_grads(piped, params)
    assert abs(l0 - l1) < 1e-5, (l0, l1)
    d = maxdiff(g0, g1)
    assert d < 1e-5, d

    # --- shortcut vs shared_expert: same dense branch, fused under the
    # a2a shadow vs added outside — identical params, loss, and grads.
    sh = dataclasses.replace(
        base, moe=dataclasses.replace(base.moe, shared_expert=True))
    sc = dataclasses.replace(
        base, moe=dataclasses.replace(base.moe, shortcut=True,
                                      pipeline_ffn=True, n_microops=4))
    p_sh = lm_mod.init_params(sh, jax.random.PRNGKey(0))
    p_sc = lm_mod.init_params(sc, jax.random.PRNGKey(0))
    assert maxdiff(p_sh, p_sc) == 0.0           # same init incl. shortcut
    l2, g2 = loss_and_grads(sh, p_sh)
    l3, g3 = loss_and_grads(sc, p_sc)
    assert abs(l2 - l3) < 1e-5, (l2, l3)
    d = maxdiff(g2, g3)
    assert d < 1e-5, d
    print("OK")
"""


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_variants_match_baseline_on_mesh(backend):
    out = run_snippet(_VARIANT_EQUIV % {"backend": backend}, timeout=900)
    assert "OK" in out


# ---------------------------------------------------- pass-2 chunk loop --

_SYN_CHUNK_LOOP = textwrap.dedent('''
    """Synthetic chunked callers for the pass-2 ordering-token check."""

    def pipelined_expert_ffn(x):
        return x, object()

    def loop_keeps_token(xs):
        outs, tok = [], None
        for x in xs:
            y, tok = pipelined_expert_ffn(x)
            outs.append(y)
        return outs, tok

    def loop_drops_token(xs):
        outs = []
        for x in xs:
            y, _ = pipelined_expert_ffn(x)
            outs.append(y)
        return outs
''')


def test_chunk_loop_keeps_ordering_token_pass2(tmp_path):
    """The (value, token) contract survives a chunked caller loop: a loop
    body that discards the a2a completion token is flagged, one that
    threads it through is clean — and the real tree stays clean."""
    from repro.analysis.collectives import analyze_collectives
    (tmp_path / "mod.py").write_text(_SYN_CHUNK_LOOP)
    fs = analyze_collectives(str(tmp_path), rel_prefix="syn",
                             producers={"pipelined_expert_ffn": 1})
    drops = [f.qualname for f in fs
             if f.category == "dropped-ordering-token"]
    assert drops == ["loop_drops_token"]

    import os
    from tests.test_distributed import REPO
    root = os.path.join(REPO, "src", "repro")
    real = [f for f in analyze_collectives(root)
            if f.category == "dropped-ordering-token"]
    assert real == [], [f.key for f in real]
