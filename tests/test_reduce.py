"""Unit tests for the gradient-reduction subsystem (optim/reduce.py):
config validation, micro-op sizing, single-device schedule identity, the
backward-a2a ordering token, and int8 error-feedback behavior.

Multi-device schedule-vs-baseline equivalence lives in
tests/test_distributed.py (subprocess with forced host devices)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.core.moe import MoEParams, default_mesh
from repro.optim import reduce as R
from repro.optim.compression import compress_int8_ef, init_int8_state


def tiny_tree():
    return {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4) / 7.0,
            "b": jnp.ones((5,), jnp.float32) * 0.3}


def replicated(tree):
    return jax.tree.map(lambda _: P(), tree)


# ---------------------------------------------------------------------------
# config / sizing
# ---------------------------------------------------------------------------

def test_reduce_config_validates():
    with pytest.raises(ValueError, match="unknown schedule"):
        R.ReduceConfig(schedule="fastest")
    with pytest.raises(ValueError, match="unknown compression"):
        R.ReduceConfig(compression="fp4")
    c = R.ReduceConfig("priority+partition+pipeline")
    assert c.ordered and c.partitioned
    assert not R.ReduceConfig("baseline").ordered
    assert not R.ReduceConfig("priority").partitioned


def test_n_chunks_for_bytes():
    g = {"a": jnp.zeros((1000,), jnp.float32)}       # 4000 bytes
    assert R.n_chunks_for_bytes(g, 1000) == 4
    assert R.n_chunks_for_bytes(g, 4000) == 1
    assert R.n_chunks_for_bytes(g, 1e12) == 1        # never zero chunks
    assert R.n_chunks_for_bytes(g, 999) == 5         # ceil


@pytest.mark.parametrize("ep", [1, 2, 4])
def test_shard_n_chunks_is_per_device(ep):
    """``partition_bytes`` sizes the micro-ops of each device's own shard:
    an expert gradient split over ``ep`` devices gives the same micro-op
    count as the replicated layout of one device's share of it."""
    mesh = AbstractMesh((2, ep), ("data", "model"))
    per_dev = {"experts": jax.ShapeDtypeStruct((4, 250), jnp.float32),
               "dense": jax.ShapeDtypeStruct((500,), jnp.float32)}
    sharded = {"experts": jax.ShapeDtypeStruct((4 * ep, 250), jnp.float32),
               "dense": per_dev["dense"]}
    specs = {"experts": P("model", None), "dense": P()}
    assert R.shard_n_chunks(mesh, sharded, specs, 1000) == \
        R.shard_n_chunks(mesh, per_dev, replicated(per_dev), 1000) == 6
    # the whole global tree would have been cut into more micro-ops
    assert R.n_chunks_for_bytes(sharded, 1000) == 4 * ep + 2


# ---------------------------------------------------------------------------
# single-device identity (collectives over a size-1 dp axis)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("schedule", R.SCHEDULES)
def test_schedules_identity_on_default_mesh(schedule):
    g = tiny_tree()
    cfg = R.ReduceConfig(schedule, partition_bytes=16)
    red, state = R.reduce_gradients(default_mesh(), g, cfg, replicated(g),
                                    after=jnp.zeros((), jnp.float32))
    assert state is None
    for k in g:
        np.testing.assert_allclose(np.asarray(red[k]), np.asarray(g[k]),
                                   atol=1e-6)


def test_bf16_compression_roundtrip_close():
    g = tiny_tree()
    cfg = R.ReduceConfig("priority+partition", partition_bytes=16,
                         compression="bf16")
    red, _ = R.reduce_gradients(default_mesh(), g, cfg, replicated(g))
    for k in g:
        np.testing.assert_allclose(np.asarray(red[k]), np.asarray(g[k]),
                                   rtol=1e-2, atol=1e-2)
        assert red[k].dtype == g[k].dtype          # decompressed back


def test_int8_ef_requires_state():
    cfg = R.ReduceConfig("priority", compression="int8_ef")
    with pytest.raises(ValueError, match="ReduceState"):
        R.reduce_gradients(default_mesh(), tiny_tree(), cfg,
                           replicated(tiny_tree()))


def test_int8_ef_state_threads_through_reduce():
    g = tiny_tree()
    cfg = R.ReduceConfig("priority+partition", partition_bytes=16,
                         compression="int8_ef")
    state = R.init_reduce_state(g, cfg)
    red, state2 = R.reduce_gradients(default_mesh(), g, cfg, replicated(g),
                                     state=state)
    assert jax.tree_util.tree_structure(state) == \
        jax.tree_util.tree_structure(state2)
    # residual became nonzero (quantization error was captured, not lost)
    res_norm = sum(float(jnp.abs(r).sum())
                   for r in jax.tree.leaves(state2.int8.residual))
    assert res_norm > 0
    for k in g:
        np.testing.assert_allclose(np.asarray(red[k]), np.asarray(g[k]),
                                   rtol=0.02, atol=0.02)


# ---------------------------------------------------------------------------
# error feedback: quantization error must not accumulate across steps
# ---------------------------------------------------------------------------

def test_int8_error_feedback_shrinks_error_across_steps():
    """With EF the *cumulative* applied gradient tracks the true cumulative
    gradient to within one quantization step (the residual), so the time-
    averaged error shrinks ~1/t; without EF the per-step bias adds up."""
    g = {"w": jnp.linspace(0.011, 0.989, 64).reshape(8, 8)}
    steps = 12

    ef_state = init_int8_state(g)
    cum_ef = jnp.zeros_like(g["w"])
    cum_raw = jnp.zeros_like(g["w"])
    avg_err_ef = []
    for t in range(1, steps + 1):
        (q, s), ef_state = compress_int8_ef(g, ef_state)
        cum_ef = cum_ef + q["w"].astype(jnp.float32) * s["w"]
        avg_err_ef.append(float(jnp.abs(cum_ef / t - g["w"]).max()))
        # no-EF reference: quantize fresh every step
        (q0, s0), _ = compress_int8_ef(g, init_int8_state(g))
        cum_raw = cum_raw + q0["w"].astype(jnp.float32) * s0["w"]

    err_ef = float(jnp.abs(cum_ef - steps * g["w"]).max())
    err_raw = float(jnp.abs(cum_raw - steps * g["w"]).max())
    # EF cumulative error is bounded by one step's residual; without EF the
    # constant bias grows linearly in t
    assert err_ef < err_raw
    # and the time-averaged EF error shrinks as steps accumulate
    assert avg_err_ef[-1] < avg_err_ef[0]


# ---------------------------------------------------------------------------
# the ordering token
# ---------------------------------------------------------------------------

def test_backward_a2a_token_none_for_dense_tree():
    assert R.backward_a2a_token(tiny_tree()) is None


def test_backward_a2a_token_from_moe_leaves_and_marker():
    moe = MoEParams(router=jnp.ones((4, 2)), wi=jnp.ones((2, 4, 8)),
                    wu=None, wo=jnp.ones((2, 8, 4)))
    tree = {"dense": jnp.ones((3,)), "moe": moe}
    tok = R.backward_a2a_token(tree)
    assert tok is not None and float(tok) == 0.0
    tok2 = R.backward_a2a_token(tiny_tree(),
                                fwd_marker=jnp.zeros((), jnp.float32))
    assert tok2 is not None and float(tok2) == 0.0
