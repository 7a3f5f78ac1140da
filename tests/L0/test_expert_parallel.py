"""Expert-parallel MoE tests on the 8-device CPU mesh: the sharded
all-to-all routing must match the single-device MoE exactly (oracle
pattern), forward AND backward, and tokens must actually reach the right
experts."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.parallel.expert import MoELayer, moe_ffn

N_DEV = 8
T, D, F, E = 64, 16, 32, 8          # tokens, d_model, d_ff, experts


def _mesh():
    return Mesh(np.array(jax.devices()[:N_DEV]), ("expert",))


def _layer(n_shards):
    return MoELayer(d_model=D, d_ff=F, num_experts=E, n_shards=n_shards,
                    capacity_factor=8.0)   # big capacity: no drops -> exact


SHARD_SPEC = {"router": P(), "w_in": P("expert"), "w_out": P("expert")}


def _oracle_per_shard(params, x):
    """Single-device MoE applied per token-shard (each device routes its
    OWN tokens with per-shard capacity — the semantics of the distributed
    run with tokens sharded over the same devices)."""
    single = _layer(1)
    outs = [single.apply(params, xs)[0]
            for xs in x.reshape(N_DEV, T // N_DEV, D)]
    return jnp.concatenate(outs, axis=0)


def test_sharded_matches_single_device():
    key = jax.random.PRNGKey(0)
    params = _layer(1).init(key)
    x = jax.random.normal(jax.random.PRNGKey(1), (T, D))

    mesh = _mesh()

    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh, in_specs=(SHARD_SPEC, P("expert")),
        out_specs=(P("expert"), P()))
    def sharded(params, x):
        out, aux = moe_ffn(x, params["router"], params["w_in"],
                           params["w_out"], axis_name="expert",
                           capacity_factor=8.0)
        return out, jax.lax.pmean(aux, "expert")

    out, aux = sharded(params, x)
    ref = _oracle_per_shard(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    assert np.isfinite(float(aux)) and float(aux) > 0


def test_sharded_gradients_match():
    params = _layer(1).init(jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (T, D))
    g = jax.random.normal(jax.random.PRNGKey(4), (T, D))
    mesh = _mesh()

    @jax.jit
    def dist_grads(params, x, g):
        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(SHARD_SPEC, P("expert"), P("expert")),
                           out_specs=P())
        def f(params, x, g):
            out, _ = moe_ffn(x, params["router"], params["w_in"],
                             params["w_out"], axis_name="expert",
                             capacity_factor=8.0)
            return jax.lax.psum(jnp.sum(out * g), "expert")
        return jax.grad(lambda p: f(p, x, g))(params)

    @jax.jit
    def ref_grads(params, x, g):
        return jax.grad(lambda p: jnp.sum(_oracle_per_shard(p, x) * g))(
            params)

    gd, gr = dist_grads(params, x, g), ref_grads(params, x, g)
    for k in ("router", "w_in", "w_out"):
        np.testing.assert_allclose(np.asarray(gd[k]), np.asarray(gr[k]),
                                   atol=5e-5, err_msg=k)


def test_routing_reaches_argmax_expert():
    """With an identity-ish router, each token's output must come from the
    expert its argmax selects (routing correctness, not just numerics)."""
    # expert e scales tokens by (e+1) via identity w_in/w_out
    w_in = jnp.stack([jnp.eye(D, F) for _ in range(E)])
    w_out = jnp.stack([(e + 1.0) * jnp.eye(F, D) for e in range(E)])
    # positive tokens + a strong router column send every token to expert 3
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (T, D))) + 0.1
    router = jnp.zeros((D, E)).at[:, 3].set(1.0)
    out, _ = moe_ffn(x, router, w_in, w_out, axis_name=None,
                     capacity_factor=float(E))
    gate = jax.nn.softmax(x.astype(jnp.float32) @ router, -1)[:, 3]
    expect = 4.0 * x * gate[:, None]    # expert 3 scales by 4, times prob
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-4)


def test_capacity_overflow_drops_tokens():
    """Tokens beyond an expert's capacity pass through with ZERO expert
    output (switch semantics)."""
    w_in = jnp.stack([jnp.eye(D, F) for _ in range(E)])
    w_out = jnp.stack([jnp.eye(F, D) for _ in range(E)])
    router = jnp.zeros((D, E)).at[:, 0].set(5.0)   # everyone -> expert 0
    x = jnp.ones((T, D))
    out, _ = moe_ffn(x, router, w_in, w_out, axis_name=None,
                     capacity_factor=0.25)         # capacity = 2 tokens
    capacity = max(int(0.25 * T / E), 1)
    nonzero_rows = int((np.abs(np.asarray(out)).sum(axis=1) > 1e-6).sum())
    assert nonzero_rows == capacity


def test_layer_init_shapes_and_shard_validation():
    layer = MoELayer(d_model=D, d_ff=F, num_experts=E, n_shards=4)
    params = layer.init(jax.random.PRNGKey(7))
    assert params["w_in"].shape == (2, D, F)       # 8/4 local experts
    with pytest.raises(ValueError):
        MoELayer(d_model=D, d_ff=F, num_experts=6, n_shards=4).init(
            jax.random.PRNGKey(8))
