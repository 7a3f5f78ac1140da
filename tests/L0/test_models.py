"""Model zoo smoke + semantics tests (shapes, train/eval BN behavior, grads,
SyncBN-on-mesh parity for the RN50 workload)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import (ResNetConfig, resnet18_config, resnet_init,
                             resnet_apply, DCGANConfig, dcgan_init,
                             generator_apply, discriminator_apply,
                             TransformerConfig, transformer_init,
                             transformer_apply, transformer_loss)


@pytest.fixture(scope="module")
def tiny_rn():
    cfg = resnet18_config(num_classes=10, width=16)
    params, state = resnet_init(jax.random.PRNGKey(0), cfg)
    return cfg, params, state


def test_resnet_shapes_and_state(tiny_rn):
    cfg, params, state = tiny_rn
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
    logits, new_state = resnet_apply(params, state, x, cfg, train=True)
    assert logits.shape == (2, 10)
    # training updates running stats
    a = state["bn_init"]["mean"]
    b = new_state["bn_init"]["mean"]
    assert not np.allclose(np.asarray(a), np.asarray(b))
    # eval keeps them and is deterministic
    l1, s1 = resnet_apply(params, new_state, x, cfg, train=False)
    l2, s2 = resnet_apply(params, new_state, x, cfg, train=False)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    assert s1 is new_state or np.allclose(
        np.asarray(s1["bn_init"]["mean"]),
        np.asarray(new_state["bn_init"]["mean"]))


def test_resnet_grads_finite(tiny_rn):
    cfg, params, state = tiny_rn
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 32, 3))
    y = jnp.array([1, 3])

    def loss(p):
        logits, _ = resnet_apply(p, state, x, cfg, train=True)
        return -jnp.mean(jax.nn.log_softmax(logits)[jnp.arange(2), y])

    g = jax.grad(loss)(params)
    for leaf in jax.tree_util.tree_leaves(g):
        assert np.all(np.isfinite(np.asarray(leaf)))


def test_resnet_syncbn_matches_large_batch(tiny_rn):
    """SyncBN over a shard_map'd batch == plain BN on the full batch — the
    two_gpu_unit_test.py oracle, on a CPU device mesh."""
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    cfg, params, state = tiny_rn
    n_dev = min(4, len(jax.devices()))
    x = jax.random.normal(jax.random.PRNGKey(3), (2 * n_dev, 32, 32, 3))
    full_logits, full_state = resnet_apply(params, state, x, cfg, train=True)

    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("data",))

    @jax.jit
    def sharded(params, state, x):
        def f(x):
            return resnet_apply(params, state, x, cfg, train=True,
                                axis_name="data")
        return shard_map(f, mesh=mesh, in_specs=P("data"),
                         out_specs=(P("data"), P()))(x)

    logits, sh_state = sharded(params, state, x)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full_logits),
                               atol=2e-2, rtol=1e-2)
    np.testing.assert_allclose(
        np.asarray(sh_state["bn_init"]["mean"]),
        np.asarray(full_state["bn_init"]["mean"]), atol=1e-5, rtol=1e-5)


def test_resnet50_param_count():
    cfg = ResNetConfig(num_classes=1000)
    params, _ = resnet_init(jax.random.PRNGKey(0), cfg)
    n = sum(p.size for p in jax.tree_util.tree_leaves(params))
    assert 25_000_000 < n < 26_000_000, n  # torchvision RN50: 25.56M


def test_dcgan_shapes_and_training_signal():
    cfg = DCGANConfig(feat_g=8, feat_d=8)
    params, bstate = dcgan_init(jax.random.PRNGKey(0), cfg)
    z = jax.random.normal(jax.random.PRNGKey(1), (2, cfg.latent_dim))
    img, bstate2 = generator_apply(params, bstate, z, cfg, train=True)
    assert img.shape == (2, 64, 64, 3)
    assert float(jnp.max(jnp.abs(img))) <= 1.0
    logits, _ = discriminator_apply(params, bstate2, img, cfg, train=True)
    assert logits.shape == (2,)

    def d_loss(p):
        out, _ = discriminator_apply(p, bstate2, img, cfg, train=True)
        return jnp.mean(jax.nn.softplus(-out))  # BCE-with-logits, real label

    g = jax.grad(d_loss)(params)
    disc_norm = sum(float(jnp.sum(l ** 2)) for l in
                    jax.tree_util.tree_leaves(g["disc"]))
    assert disc_norm > 0


def test_dcgan_eval_is_batch_composition_independent():
    """Eval-mode BN uses running stats: a fixed z yields the same image
    regardless of batch companions (review finding)."""
    cfg = DCGANConfig(feat_g=8, feat_d=8)
    params, bstate = dcgan_init(jax.random.PRNGKey(0), cfg)
    z0 = jax.random.normal(jax.random.PRNGKey(1), (1, cfg.latent_dim))
    other = jax.random.normal(jax.random.PRNGKey(2), (3, cfg.latent_dim))
    a, _ = generator_apply(params, bstate, z0, cfg, train=False)
    b, _ = generator_apply(params, bstate,
                           jnp.concatenate([z0, other]), cfg, train=False)
    np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b[0]), atol=1e-5)


def test_norm_path_regex_matches_model_bn_names():
    """keep_batchnorm_fp32 must recognize bn1/bn_init/bn_bias paths
    (review finding: \bbn\b fails on them)."""
    from apex_tpu.utils.pytree import convert_network
    cfg = resnet18_config(num_classes=10, width=16)
    params, _ = resnet_init(jax.random.PRNGKey(0), cfg)
    cast = convert_network(params, jnp.bfloat16, keep_batchnorm_fp32=True)
    assert cast["bn_init"]["scale"].dtype == jnp.float32
    assert cast["stage0_block0"]["bn1"]["bn_bias"].dtype == jnp.float32
    assert cast["conv_init"].dtype == jnp.bfloat16


def test_transformer_mask_polarity_nonzero_is_pad():
    """Regression for the round-1 inversion: the key-padding mask uses the
    repo-wide nonzero=PAD polarity (contrib.multihead_attn convention).
    An all-zeros mask must be a no-op; marking positions as pad must (a)
    change other positions' outputs and (b) starve the padded queries'
    attention of real keys only when the REAL keys are marked."""
    cfg = TransformerConfig(vocab_size=64, max_len=32, num_layers=1,
                            d_model=32, num_heads=2, d_ff=64)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    toks = (jnp.arange(16)[None] % 64).astype(jnp.int32)

    o_none = transformer_apply(params, toks, cfg)
    o_zeros = transformer_apply(params, toks, cfg,
                                mask=jnp.zeros((1, 16), jnp.int32))
    np.testing.assert_allclose(np.asarray(o_none), np.asarray(o_zeros),
                               atol=1e-5)

    mask_tail = jnp.zeros((1, 16), jnp.int32).at[0, 8:].set(1)
    o_tail = transformer_apply(params, toks, cfg, mask=mask_tail)
    # masking the tail must change the head's outputs (tail keys dropped)
    assert not np.allclose(np.asarray(o_none[0, :8]),
                           np.asarray(o_tail[0, :8]), atol=1e-5)
    # and the head positions must see ONLY head keys: masking the head
    # instead yields a different result than masking the tail
    mask_head = jnp.zeros((1, 16), jnp.int32).at[0, :8].set(1)
    o_head = transformer_apply(params, toks, cfg, mask=mask_head)
    assert not np.allclose(np.asarray(o_tail), np.asarray(o_head), atol=1e-5)


@pytest.mark.slow   # ~15s: the flash-vs-default numerics oracle at
# model scale; the kernel-level oracles (test_multihead_attn, chip_smoke
# --rehearse) keep the surface in tier-1 (ISSUE 12 budget reclaim)
def test_transformer_fast_attention_matches_default():
    """attn_impl='fast' (contrib flash kernel) must match the jnp oracle
    path in forward AND gradients — the analog of the reference examples
    swapping in fast_self_multihead_attn (self_multihead_attn.py:29).
    Covered: no mask, key-padding mask, causal."""
    import dataclasses as dc
    from apex_tpu.models import transformer_loss
    cfg = TransformerConfig(vocab_size=64, max_len=32, num_layers=2,
                            d_model=64, num_heads=2, d_ff=128)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    toks = (jnp.arange(32)[None] % 64).astype(jnp.int32)
    mask_tail = jnp.zeros((1, 32), jnp.int32).at[0, 24:].set(1)

    for causal, mask in ((False, None), (False, mask_tail), (True, None)):
        c_def = dc.replace(cfg, causal=causal)
        c_fast = dc.replace(cfg, causal=causal, attn_impl="fast")
        o_def = transformer_apply(params, toks, c_def, mask=mask)
        o_fast = transformer_apply(params, toks, c_fast, mask=mask)
        np.testing.assert_allclose(np.asarray(o_fast), np.asarray(o_def),
                                   atol=2e-4, rtol=2e-4)

        batch = {"tokens": toks, "targets": toks, "mask": mask}
        g_def = jax.grad(lambda p: transformer_loss(p, batch, c_def))(params)
        g_fast = jax.grad(lambda p: transformer_loss(p, batch, c_fast))(params)
        for a, b in zip(jax.tree_util.tree_leaves(g_def),
                        jax.tree_util.tree_leaves(g_fast)):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       atol=5e-4, rtol=5e-3)

    import pytest
    with pytest.raises(ValueError, match="attn_impl"):
        transformer_apply(params, toks, dc.replace(cfg, attn_impl="nope"))


def test_transformer_remat_same_numerics_less_memory():
    """cfg.remat=True recomputes layer activations in backward: gradients
    identical (same math), backward temp memory strictly smaller for a
    deep model (the jax.checkpoint design goal: trade FLOPs for memory)."""
    from apex_tpu.models import (TransformerConfig, transformer_init,
                                 transformer_loss)

    def make(remat):
        return TransformerConfig(vocab_size=128, max_len=128, num_layers=6,
                                 d_model=64, num_heads=2, d_ff=256,
                                 remat=remat)

    params = transformer_init(jax.random.PRNGKey(0), make(False))
    batch = {"tokens": jnp.ones((2, 128), jnp.int32),
             "targets": jnp.ones((2, 128), jnp.int32)}

    grads = {}
    temp = {}
    for remat in (False, True):
        cfg = make(remat)
        g_fn = jax.grad(lambda p: transformer_loss(p, batch, cfg))
        grads[remat] = g_fn(params)
        compiled = jax.jit(g_fn).lower(params).compile()
        mem = compiled.memory_analysis()
        temp[remat] = int(getattr(mem, "temp_size_in_bytes", 0) or 0)

    for a, b in zip(jax.tree_util.tree_leaves(grads[False]),
                    jax.tree_util.tree_leaves(grads[True])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    assert 0 < temp[True] < temp[False], temp
