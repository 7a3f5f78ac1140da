"""Sequence/context parallelism tests on the 8-device CPU mesh: ring
attention and Ulysses must match single-device full attention exactly
(oracle pattern, SURVEY §4), forward AND backward."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.parallel.sequence import ring_attention, ulysses_attention

B, H, S, D = 2, 8, 64, 16     # S sharded 8-ways -> 8 per device


def _mesh(n=8):
    return Mesh(np.array(jax.devices()[:n]), ("seq",))


def _qkv(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    mk = lambda k: jax.random.normal(k, (B, H, S, D), jnp.float32)
    return mk(ks[0]), mk(ks[1]), mk(ks[2])


def reference_attention(q, k, v, causal):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / (D ** 0.5)
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, (S, S), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (S, S), 1)
        s = jnp.where((cols <= rows)[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def run_sharded(fn, q, k, v, causal, n=8):
    mesh = _mesh(n)
    spec = P(None, None, "seq", None)

    @jax.jit
    @functools.partial(shard_map, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec)
    def sharded(q, k, v):
        return fn(q, k, v, axis_name="seq", causal=causal)

    return sharded(q, k, v)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("fn", [ring_attention, ulysses_attention],
                         ids=["ring", "ulysses"])
def test_matches_single_device(fn, causal):
    q, k, v = _qkv()
    out = run_sharded(fn, q, k, v, causal)
    ref = reference_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("fn", [ring_attention, ulysses_attention],
                         ids=["ring", "ulysses"])
def test_gradients_match_single_device(fn):
    q, k, v = _qkv(1)
    g = jax.random.normal(jax.random.PRNGKey(9), (B, H, S, D))
    mesh = _mesh()
    spec = P(None, None, "seq", None)

    @jax.jit
    def dist_grads(q, k, v):
        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(spec, spec, spec), out_specs=spec)
        def apply(q, k, v):
            return fn(q, k, v, axis_name="seq", causal=True)
        return jax.grad(lambda q_, k_, v_: jnp.sum(apply(q_, k_, v_) * g),
                        argnums=(0, 1, 2))(q, k, v)

    @jax.jit
    def ref_grads(q, k, v):
        return jax.grad(lambda q_, k_, v_: jnp.sum(
            reference_attention(q_, k_, v_, True) * g),
            argnums=(0, 1, 2))(q, k, v)

    for a, b in zip(dist_grads(q, k, v), ref_grads(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_flash_matches_single_device(causal):
    """ulysses_flash_attention (all_to_all re-shard + Pallas flash core)
    == full single-device attention, fwd and bwd.  check_vma=False: the
    pallas interpreter's grid-loop carry is untyped (the documented jax
    limitation); compiled TPU pallas is unaffected."""
    from apex_tpu.parallel.sequence import ulysses_flash_attention
    q, k, v = _qkv(3)
    g = jax.random.normal(jax.random.PRNGKey(7), (B, H, S, D))
    mesh = _mesh()
    spec = P(None, None, "seq", None)

    @jax.jit
    def dist(q, k, v):
        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(spec, spec, spec), out_specs=spec,
                           check_vma=False)
        def apply(q, k, v):
            return ulysses_flash_attention(q, k, v, axis_name="seq",
                                           causal=causal)
        out = apply(q, k, v)
        grads = jax.grad(lambda q_, k_, v_: jnp.sum(apply(q_, k_, v_) * g),
                         argnums=(0, 1, 2))(q, k, v)
        return out, grads

    out, grads = dist(q, k, v)
    ref = reference_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)
    ref_grads = jax.jit(jax.grad(
        lambda q_, k_, v_: jnp.sum(reference_attention(q_, k_, v_, causal)
                                   * g), argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(grads, ref_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_self_mha_ulysses_fast_inner_matches_default():
    """SelfMultiheadAttn(impl='ulysses', seq_inner_impl='fast') == the
    jnp inner core, through the module path."""
    from apex_tpu.contrib.multihead_attn import SelfMultiheadAttn
    E, HEADS = 32, 8
    T, BB = 64, 2
    outs = {}
    for inner in ("default", "fast"):
        mha = SelfMultiheadAttn(E, HEADS, impl="ulysses", causal=True,
                                seq_inner_impl=inner)
        params = mha.init_params(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (T, BB, E))
        mesh = _mesh()
        spec = P("seq", None, None)
        rep = jax.tree_util.tree_map(lambda _: P(), params)

        @jax.jit
        @functools.partial(shard_map, mesh=mesh, in_specs=(rep, spec),
                           out_specs=spec, check_vma=False)
        def apply(p, x):
            return mha(p, x)[0]

        outs[inner] = apply(params, x)
    np.testing.assert_allclose(np.asarray(outs["fast"]),
                               np.asarray(outs["default"]), atol=2e-4)

    for other in ("ring", "default", "fast"):
        with pytest.raises(AssertionError, match="ulysses"):
            SelfMultiheadAttn(E, HEADS, impl=other, seq_inner_impl="fast")


def test_ring_cross_attention_different_kv_len():
    """k/v sequence length may differ from q's (cross attention)."""
    q, _, _ = _qkv(2)
    k = jax.random.normal(jax.random.PRNGKey(3), (B, H, 2 * S, D))
    v = jax.random.normal(jax.random.PRNGKey(4), (B, H, 2 * S, D))
    mesh = _mesh()
    spec = P(None, None, "seq", None)

    @jax.jit
    @functools.partial(shard_map, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec)
    def sharded(q, k, v):
        return ring_attention(q, k, v, axis_name="seq", causal=False)

    out = sharded(q, k, v)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / (D ** 0.5)
    ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ulysses_rejects_ragged_heads():
    q = jnp.ones((B, 6, S, D))   # 6 heads over 8 devices
    mesh = _mesh()
    spec = P(None, None, "seq", None)
    with pytest.raises(ValueError):
        @jax.jit
        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(spec, spec, spec), out_specs=spec)
        def sharded(q, k, v):
            return ulysses_attention(q, k, v, axis_name="seq")
        sharded(q, q, q)


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
@pytest.mark.parametrize("causal", [False, True])
def test_self_mha_ring_impl_matches_default(causal, impl):
    """SelfMultiheadAttn(impl='ring'|'ulysses') inside shard_map ==
    impl='default' unsharded (module-level sequence parallelism)."""
    from apex_tpu.contrib.multihead_attn import SelfMultiheadAttn

    E, HEADS = 32, 8       # 8 heads divide the 8-device axis (ulysses)
    mha_ring = SelfMultiheadAttn(E, HEADS, impl=impl, causal=causal)
    mha_ref = SelfMultiheadAttn(E, HEADS, impl="default")
    params = mha_ring.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (S, B, E))  # (T, B, C)
    tmask = (jnp.triu(jnp.ones((S, S)), 1) > 0) if causal else None

    ref, _ = mha_ref(params, x, attn_mask=tmask, is_training=False)

    mesh = _mesh()
    xspec = P("seq", None, None)

    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: P(), params), xspec),
        out_specs=xspec)
    def sharded(params, x):
        out, _ = mha_ring(params, x, is_training=False)
        return out

    out = sharded(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)
