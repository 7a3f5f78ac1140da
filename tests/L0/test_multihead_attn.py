"""Fast-vs-default parity tests for contrib.multihead_attn — mirrors
``apex/contrib/test/multihead_attn`` (fwd + bwd parity across mask variants,
norm-add, encdec)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.contrib.multihead_attn import (SelfMultiheadAttn,
                                             EncdecMultiheadAttn,
                                             flash_attention,
                                             self_attn_func)
from apex_tpu.contrib.multihead_attn.functional import (attention_core,
                                                        build_bias)

E, H = 64, 4
ATOL = 2e-3  # fp32 flash vs direct softmax


def _inputs(sq=32, b=3, sk=None, seed=0):
    sk = sk or sq
    kq, kk = jax.random.split(jax.random.PRNGKey(seed))
    q = jax.random.normal(kq, (sq, b, E), jnp.float32)
    kv = jax.random.normal(kk, (sk, b, E), jnp.float32)
    return q, kv


@pytest.mark.parametrize("sq", [32, 100, 128])
def test_flash_matches_reference_core(sq):
    b, d = 2, 16
    h = 4
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(k1, (b, h, sq, d))
    k = jax.random.normal(k2, (b, h, sq, d))
    v = jax.random.normal(k3, (b, h, sq, d))
    bias = jnp.zeros((1, 1, sq), jnp.float32)
    ref = attention_core(q, k, v, bias)
    got = flash_attention(q.reshape(b * h, sq, d), k.reshape(b * h, sq, d),
                          v.reshape(b * h, sq, d), bias, 0, False, 0.0, h)
    np.testing.assert_allclose(np.asarray(got).reshape(b, h, sq, d),
                               np.asarray(ref), atol=ATOL, rtol=1e-3)


def test_flash_causal_matches_reference():
    b, h, s, d = 2, 2, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = (jax.random.normal(kk, (b, h, s, d)) for kk in ks)
    bias = jnp.zeros((1, 1, s), jnp.float32)
    ref = attention_core(q, k, v, bias, causal=True)
    got = flash_attention(q.reshape(b * h, s, d), k.reshape(b * h, s, d),
                          v.reshape(b * h, s, d), bias, 0, True, 0.0, h)
    np.testing.assert_allclose(np.asarray(got).reshape(b, h, s, d),
                               np.asarray(ref), atol=ATOL, rtol=1e-3)


def test_flash_grads_match_reference():
    b, h, s, d = 2, 2, 32, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (jax.random.normal(kk, (b, h, s, d)) for kk in ks)
    bias = jnp.zeros((1, 1, s), jnp.float32)

    def loss_ref(q, k, v):
        return attention_core(q, k, v, bias).sum()

    def loss_flash(q, k, v):
        return flash_attention(q.reshape(b * h, s, d),
                               k.reshape(b * h, s, d),
                               v.reshape(b * h, s, d), bias, 0, False, 0.0,
                               h).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, bb in zip(g_ref, g_fl):
        np.testing.assert_allclose(np.asarray(bb).reshape(a.shape),
                                   np.asarray(a), atol=5e-3, rtol=1e-3)


@pytest.mark.parametrize("impl", ["default", "fast"])
def test_self_attn_module_fwd_bwd(impl):
    attn = SelfMultiheadAttn(E, H, dropout=0.0, bias=True, impl=impl)
    params = attn.init_params(jax.random.PRNGKey(0))
    q, _ = _inputs()

    def f(params):
        out, _ = attn(params, q, q, q, is_training=False)
        return (out ** 2).mean()

    val, grads = jax.value_and_grad(f)(params)
    assert np.isfinite(float(val))
    for leaf in jax.tree_util.tree_leaves(grads):
        assert np.all(np.isfinite(np.asarray(leaf)))


def test_self_attn_fast_matches_default():
    q, _ = _inputs(sq=48, b=2)
    fast = SelfMultiheadAttn(E, H, dropout=0.0, bias=True, impl="fast")
    dflt = SelfMultiheadAttn(E, H, dropout=0.0, bias=True, impl="default")
    params = fast.init_params(jax.random.PRNGKey(0))
    out_f, _ = fast(params, q, is_training=False)
    out_d, _ = dflt(params, q, is_training=False)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_d),
                               atol=ATOL, rtol=1e-3)

    gf = jax.grad(lambda p: (fast(p, q, is_training=False)[0] ** 2).sum())(params)
    gd = jax.grad(lambda p: (dflt(p, q, is_training=False)[0] ** 2).sum())(params)
    for a, b in zip(jax.tree_util.tree_leaves(gf),
                    jax.tree_util.tree_leaves(gd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-2,
                                   rtol=2e-3)


def test_self_attn_key_padding_mask_parity():
    q, _ = _inputs(sq=32, b=2, seed=5)
    pad = jnp.zeros((2, 32), jnp.int32).at[:, 24:].set(1)  # 1 = pad
    fast = SelfMultiheadAttn(E, H, impl="fast")
    dflt = SelfMultiheadAttn(E, H, impl="default")
    params = fast.init_params(jax.random.PRNGKey(0))
    out_f, _ = fast(params, q, key_padding_mask=pad, is_training=False)
    out_d, _ = dflt(params, q, key_padding_mask=pad, is_training=False)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_d),
                               atol=ATOL, rtol=1e-3)


def test_self_attn_additive_mask_parity():
    q, _ = _inputs(sq=32, b=2, seed=6)
    add = jnp.zeros((2, 32), jnp.float32).at[:, 20:].set(-1e9)
    fast = SelfMultiheadAttn(E, H, impl="fast", mask_additive=True, bias=True)
    dflt = SelfMultiheadAttn(E, H, impl="default", mask_additive=True,
                             bias=True)
    params = fast.init_params(jax.random.PRNGKey(0))
    out_f, _ = fast(params, q, key_padding_mask=add, is_training=False)
    out_d, _ = dflt(params, q, key_padding_mask=add, is_training=False)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_d),
                               atol=ATOL, rtol=1e-3)


def test_self_attn_time_mask_parity():
    s = 32
    q, _ = _inputs(sq=s, b=2, seed=7)
    tm = ~jnp.tril(jnp.ones((s, s), bool))  # True above diagonal = masked
    fast = SelfMultiheadAttn(E, H, impl="fast")
    dflt = SelfMultiheadAttn(E, H, impl="default")
    params = fast.init_params(jax.random.PRNGKey(0))
    out_f, _ = fast(params, q, attn_mask=tm, is_training=False)
    out_d, _ = dflt(params, q, attn_mask=tm, is_training=False)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_d),
                               atol=ATOL, rtol=1e-3)


def test_norm_add_residual():
    q, _ = _inputs(sq=16, b=2, seed=8)
    for impl in ("fast", "default"):
        attn = SelfMultiheadAttn(E, H, include_norm_add=True, impl=impl)
        params = attn.init_params(jax.random.PRNGKey(0))
        out, _ = attn(params, q, is_training=False)
        assert out.shape == q.shape
    # zero weights => attention contributes ~0; residual must pass through
    attn = SelfMultiheadAttn(E, H, include_norm_add=True, impl="default")
    params = attn.init_params(jax.random.PRNGKey(0))
    params["out_proj_weight"] = jnp.zeros_like(params["out_proj_weight"])
    out, _ = attn(params, q, is_training=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(q), atol=1e-6)


def test_encdec_fast_matches_default():
    q, kv = _inputs(sq=24, b=2, sk=40, seed=9)
    fast = EncdecMultiheadAttn(E, H, impl="fast")
    dflt = EncdecMultiheadAttn(E, H, impl="default")
    params = fast.init_params(jax.random.PRNGKey(0))
    out_f, _ = fast(params, q, kv, is_training=False)
    out_d, _ = dflt(params, q, kv, is_training=False)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_d),
                               atol=ATOL, rtol=1e-3)


def test_separate_qkv_params_match_fused():
    """separate q/k/v params interleave into the same (3E, E) layout
    (self_multihead_attn.py:133-141)."""
    q, _ = _inputs(sq=16, b=2, seed=10)
    sep = SelfMultiheadAttn(E, H, impl="default", separate_qkv_params=True,
                            bias=True)
    fused = SelfMultiheadAttn(E, H, impl="default", bias=True)
    sp = sep.init_params(jax.random.PRNGKey(3))
    w, b = sep._input_weights(sp)
    fp = {"in_proj_weight": w, "in_proj_bias": b,
          "out_proj_weight": sp["out_proj_weight"],
          "out_proj_bias": sp["out_proj_bias"]}
    out_s, _ = sep(sp, q, is_training=False)
    out_fu, _ = fused(fp, q, is_training=False)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_fu),
                               atol=1e-6)


def test_self_attn_func_signature():
    """Functional mirror of SelfAttnFunc.forward runs and differentiates."""
    q, _ = _inputs(sq=16, b=2, seed=11)
    w_in = jax.random.normal(jax.random.PRNGKey(1), (3 * E, E)) * 0.05
    w_out = jax.random.normal(jax.random.PRNGKey(2), (E, E)) * 0.05
    out = self_attn_func(False, False, H, (E // H) ** -0.5, q, w_in, w_out,
                         None, None, None, False, 0.0)
    assert out.shape == q.shape


def test_flash_dropout_grads_match_finite_differences():
    """Dropout masks must regenerate identically in fwd and both bwd kernels
    (counter-based hash on global coords); FD ratio ~1 proves it."""
    h, s, d = 2, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (h, s, d), jnp.float32) for kk in ks)
    bias = jnp.zeros((1, 1, s), jnp.float32)

    def f(q):
        return flash_attention(q, k, v, bias, 7, False, 0.3, h).sum()

    g = jax.grad(f)(q)
    t = jax.random.normal(jax.random.PRNGKey(9), q.shape)
    eps = 1e-3
    fd = (f(q + eps * t) - f(q - eps * t)) / (2 * eps)
    ratio = float(jnp.sum(g * t) / fd)
    assert abs(ratio - 1.0) < 0.02, ratio


def test_flash_dropout_traced_seed_under_jit():
    """Seed is a traced argument (review finding: nondiff_argnums seed made
    any jitted dropout call crash)."""
    h, s, d = 2, 32, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (h, s, d))
    bias = jnp.zeros((1, 1, s), jnp.float32)

    @jax.jit
    def step(q, seed):
        return flash_attention(q, q, q, bias, seed, False, 0.2, h).sum()

    a = step(q, jnp.int32(3))
    b = step(q, jnp.int32(4))
    assert np.isfinite(float(a)) and float(a) != float(b)


def test_flash_fully_masked_rows_emit_zeros():
    """A row whose keys are ALL masked outputs zeros (no pad leakage) and
    zero grads, instead of attending uniformly to pad content."""
    h, s, d = 1, 16, 8
    q = jax.random.normal(jax.random.PRNGKey(0), (h, s, d))
    bias = jnp.full((1, 1, s), -1e30, jnp.float32)  # everything masked

    out = flash_attention(q, q, q, bias, 0, False, 0.0, h)
    np.testing.assert_array_equal(np.asarray(out), 0.0)
    g = jax.grad(lambda q: flash_attention(q, q, q, bias, 0, False, 0.0,
                                           h).sum())(q)
    assert np.all(np.isfinite(np.asarray(g)))
    np.testing.assert_array_equal(np.asarray(g), 0.0)


# ---------------------------------------------------------------------------
# selectable backward backend (backward="pallas"|"xla"|"auto")
# ---------------------------------------------------------------------------

def _ref_attention(q, k, v, bias, causal, heads):
    """jax.nn reference on (BH, S, D) layouts — the parity oracle for the
    backward-backend tests (no dropout; dead rows not exercised here)."""
    Sq, Sk = q.shape[1], k.shape[1]
    s = jnp.einsum("bqd,bkd->bqk", q, k)
    b = bias
    if b.shape[0] != 1:
        b = jnp.repeat(b, heads, axis=0)
    s = s + b
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((Sq, Sk), bool))[None], s, -1e30)
    return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, axis=-1), v)


def _bias_layouts(b, sq, sk):
    """The three supported additive-bias layouts: none, per-batch
    key-padding (B, 1, Sk), full per-query score mask (B, Sq, Sk)."""
    pad = jnp.zeros((b, 1, sk), jnp.float32).at[:, :, sk - 8:].set(-1e30)
    full = jnp.zeros((b, sq, sk), jnp.float32).at[:, sq // 2:, :4].set(-1e9)
    return {"none": jnp.zeros((1, 1, sk), jnp.float32),
            "padding": pad, "full": full}


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("layout", ["none", "padding", "full"])
def test_flash_backward_xla_matches_pallas_and_reference(causal, layout):
    """backward="xla" and backward="pallas" produce matching (q, k, v)
    gradients, and both match autodiff of the jax.nn reference — across
    causal x bias layouts (the acceptance parity matrix)."""
    b, h, s, d = 2, 2, 48, 16
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q, k, v = (jax.random.normal(kk, (b * h, s, d), jnp.float32) * 0.5
               for kk in ks)
    bias = _bias_layouts(b, s, s)[layout]

    def loss(backend):
        return lambda q, k, v: flash_attention(
            q, k, v, bias, 0, causal, 0.0, h, backend).sum()

    g_pl = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, k, v)
    g_xla = jax.grad(loss("xla"), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda q, k, v: _ref_attention(
        q, k, v, bias, causal, h).sum(), argnums=(0, 1, 2))(q, k, v)
    for name, a, bb in zip("qkv", g_pl, g_xla):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   atol=5e-3, rtol=1e-3, err_msg=name)
    for name, a, r in zip("qkv", g_pl, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   atol=5e-3, rtol=1e-3, err_msg=name)


def test_flash_backward_xla_matches_pallas_with_dropout():
    """With dropout the two routes share the counter-based keep mask
    bit-for-bit, so their gradients must agree exactly as closely as the
    no-dropout pair (the jax.nn oracle can't see the mask, so the A/B is
    pallas-vs-xla only here)."""
    h, s, d = 2, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(12), 3)
    q, k, v = (jax.random.normal(kk, (h, s, d), jnp.float32) for kk in ks)
    bias = jnp.zeros((1, 1, s), jnp.float32)

    def loss(backend):
        return lambda q, k, v: flash_attention(
            q, k, v, bias, 7, True, 0.3, h, backend).sum()

    g_pl = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, k, v)
    g_xla = jax.grad(loss("xla"), argnums=(0, 1, 2))(q, k, v)
    for name, a, bb in zip("qkv", g_pl, g_xla):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   atol=5e-3, rtol=1e-3, err_msg=name)


@pytest.mark.parametrize("causal,rate", [(False, 0.0), (True, 0.0),
                                         (True, 0.3)])
def test_flash_bwd_fused_matches_split(monkeypatch, causal, rate):
    """The fused one-recompute kernel and the split dq/dkv kernels are the
    same math: forcing each strategy via APEX_TPU_FLASH_BWD_FUSE must give
    matching gradients (incl. the causal dq-partial zero-fill path and the
    shared dropout-mask regeneration)."""
    h, s, d = 2, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    q, k, v = (jax.random.normal(kk, (h, s, d), jnp.float32) for kk in ks)
    bias = jnp.zeros((1, 1, s), jnp.float32)

    def grads():
        return jax.grad(lambda q, k, v: flash_attention(
            q, k, v, bias, 5, causal, rate, h, "pallas").sum(),
            argnums=(0, 1, 2))(q, k, v)

    monkeypatch.setenv("APEX_TPU_FLASH_BWD_FUSE", "1")
    g_fused = grads()
    monkeypatch.setenv("APEX_TPU_FLASH_BWD_FUSE", "0")
    g_split = grads()
    for name, a, bb in zip("qkv", g_fused, g_split):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   atol=1e-5, rtol=1e-5, err_msg=name)


def test_flash_backward_auto_resolution_chain(monkeypatch):
    """backward="auto" resolves env > amp-config default > pallas
    built-in; explicit arguments bypass the chain entirely."""
    from apex_tpu.contrib.multihead_attn import flash as F
    monkeypatch.delenv("APEX_TPU_FLASH_BWD_IMPL", raising=False)
    assert F._resolve_backward("auto") == "pallas"      # built-in
    # the amp-config default beats the built-in
    F.set_default_backward("xla")
    try:
        assert F._resolve_backward("auto") == "xla"
        # env beats the amp-config default
        monkeypatch.setenv("APEX_TPU_FLASH_BWD_IMPL", "pallas")
        assert F._resolve_backward("auto") == "pallas"
    finally:
        F.set_default_backward("auto")
    # explicit argument beats everything
    assert F._resolve_backward("xla") == "xla"
    with pytest.raises(ValueError):
        F._resolve_backward("cuda")
    with pytest.raises(ValueError):
        F.set_default_backward("cuda")


def test_flash_backward_auto_routes_to_xla_under_the_env_pin(monkeypatch):
    """Functional proof of the route: with ``APEX_TPU_FLASH_BWD_IMPL=xla``
    a grad through backward="auto" runs the XLA backward (and matches the
    Pallas kernels numerically)."""
    from apex_tpu.contrib.multihead_attn import flash as F
    monkeypatch.setenv("APEX_TPU_FLASH_BWD_IMPL", "xla")
    h, s, d = 2, 32, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (h, s, d))
    bias = jnp.zeros((1, 1, s), jnp.float32)
    routed = {}
    real_xla_bwd = F._xla_bwd

    def spy(*args, **kw):
        routed["xla"] = True
        return real_xla_bwd(*args, **kw)

    monkeypatch.setattr(F, "_xla_bwd", spy)
    g_auto = jax.grad(lambda q: flash_attention(
        q, q, q, bias, 0, True, 0.0, h, "auto").sum())(q)
    assert routed.get("xla"), "auto did not route the backward to XLA"
    g_pl = jax.grad(lambda q: flash_attention(
        q, q, q, bias, 0, True, 0.0, h, "pallas").sum())(q)
    np.testing.assert_allclose(np.asarray(g_auto), np.asarray(g_pl),
                               atol=5e-3, rtol=1e-3)


def test_flash_backward_arg_validated_at_call_site():
    """A bogus backward= raises at the flash_attention call on BOTH the
    inference and the training path — not at the first backward trace."""
    h, s, d = 1, 16, 8
    q = jax.random.normal(jax.random.PRNGKey(0), (h, s, d))
    bias = jnp.zeros((1, 1, s), jnp.float32)
    with pytest.raises(ValueError):
        flash_attention(q, q, q, bias, 0, False, 0.0, h, "cuda")
    with pytest.raises(ValueError):
        jax.grad(lambda q: flash_attention(q, q, q, bias, 0, False, 0.0,
                                           h, "cuda").sum())(q)


def test_module_backward_knob_validated():
    with pytest.raises(AssertionError):
        SelfMultiheadAttn(E, H, backward="cuda")
    with pytest.raises(AssertionError):
        EncdecMultiheadAttn(E, H, backward="cuda")
    # the knob threads through the module fwd+bwd without disturbing parity
    q, _ = _inputs(sq=32, b=2, seed=4)
    m_x = SelfMultiheadAttn(E, H, impl="fast", backward="xla")
    m_p = SelfMultiheadAttn(E, H, impl="fast", backward="pallas")
    params = m_x.init_params(jax.random.PRNGKey(0))
    gx = jax.grad(lambda p: (m_x(p, q, is_training=False)[0] ** 2).sum())(
        params)
    gp = jax.grad(lambda p: (m_p(p, q, is_training=False)[0] ** 2).sum())(
        params)
    for a, b in zip(jax.tree_util.tree_leaves(gx),
                    jax.tree_util.tree_leaves(gp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-2, rtol=2e-3)


# ---------------------------------------------------------------------------
# dropout mask statistics (the counter-based keep hash)
# ---------------------------------------------------------------------------

def _keep_mask(seed, bh, row0=0, col0=0, shape=(512, 512), rate=0.5):
    from apex_tpu.contrib.multihead_attn.flash import _dropout_keep
    return np.asarray(_dropout_keep(jnp.int32(seed), jnp.int32(bh),
                                    row0, col0, shape, rate))


def test_dropout_keep_rate_uniform():
    """Keep-rate within binomial tolerance of 1-rate at scale (n=2^18 per
    mask; 0.01 is ~10 sigma at rate 0.5 — a biased hash fails, noise
    doesn't)."""
    for rate in (0.1, 0.3, 0.5, 0.7, 0.9):
        frac = _keep_mask(123, 5, rate=rate).mean()
        assert abs(frac - (1.0 - rate)) < 0.01, (rate, frac)
    # and per-row / per-column: no stripes (the hash mixes rows and cols
    # with different odd constants; a weak mix shows up as row bias)
    m = _keep_mask(7, 3, rate=0.5)
    assert np.abs(m.mean(axis=0) - 0.5).max() < 0.12     # cols, n=512 each
    assert np.abs(m.mean(axis=1) - 0.5).max() < 0.12     # rows


def test_dropout_mask_independence_at_scale():
    """Masks across different (seed, batch-head, block-offset) coordinates
    are pairwise ~independent: agreement with the base mask stays near the
    0.5 expected of independent fair coins (n=2^18, so 0.52 is ~20 sigma),
    and no variant reproduces the base mask exactly."""
    base = _keep_mask(1, 0)
    variants = {
        "seed+1": _keep_mask(2, 0),
        "seed+7919": _keep_mask(1 + 7919, 0),   # the round-1 collision pair
        "head+1": _keep_mask(1, 1),
        "head+7919": _keep_mask(1, 7919),
        "row-offset": _keep_mask(1, 0, row0=512),
        "col-offset": _keep_mask(1, 0, col0=512),
        "row+col-offset": _keep_mask(1, 0, row0=512, col0=512),
    }
    for name, m in variants.items():
        agree = (base == m).mean()
        assert 0.48 < agree < 0.52, (name, agree)
    # the historical regression: (seed, head) pairs colliding — seed s
    # with head b must not reuse the mask of seed s+7919 with head b'
    cross = _keep_mask(1 + 7919, 1)
    assert 0.48 < (base == cross).mean() < 0.52
    assert not np.array_equal(base, cross)


def test_dropout_mask_block_offset_consistency():
    """A mask generated at a block offset equals the corresponding slice of
    the full mask — the property that makes masks identical across the
    fwd/dq/dkv/fused kernels' different grid shapes."""
    full = _keep_mask(42, 2, shape=(256, 256), rate=0.3)
    sub = _keep_mask(42, 2, row0=128, col0=64, shape=(128, 192), rate=0.3)
    np.testing.assert_array_equal(sub, full[128:, 64:256])


def test_flash_block_clamp():
    """VMEM-budget clamp: defaults fit an 8 MiB budget at common head dims;
    a tiny budget forces aligned shrink on env-defaulted blocks; explicit
    block sizes are never rewritten; the bwd footprint model is genuinely
    stricter; and the kernel stays correct at clamped sizes."""
    import os
    from apex_tpu.contrib.multihead_attn import flash as F

    # sanitize the WHOLE test against ambient tuning env (the knobs this
    # feature documents would otherwise skew the assertions below)
    old = dict(os.environ)
    for k in ("APEX_TPU_FLASH_BLOCK_Q", "APEX_TPU_FLASH_BLOCK_K",
              "APEX_TPU_FLASH_VMEM_MB"):
        os.environ.pop(k, None)
    try:
        bq, bk = F._clamp_blocks(None, None, 64, 4, bias_per_q=False)
        assert (bq, bk) == (512, 1024)      # default shapes fit the budget
        bq, bk = F._clamp_blocks(None, None, 256, 4, bias_per_q=True)
        assert bq % 8 == 0 and bk % 128 == 0 and (bq, bk) != (512, 1024)

        # short sequences cap the blocks BEFORE the budget shrink: at
        # D=512 f32 per-q bias the unconstrained clamp would go below 256,
        # but (256, 256) already fits
        assert F._clamp_blocks(None, None, 512, 4, True,
                               sq=256, sk=256) == (256, 256)

        # bwd model is strictly stricter: at bf16 D=64 under a 1.5 MiB
        # budget the fwd estimate (~0.89 MiB) keeps (512, 1024) while the
        # bwd estimate (~2.0 MiB) must shrink bk
        os.environ["APEX_TPU_FLASH_VMEM_MB"] = "1.5"
        fwd = F._clamp_blocks(None, None, 64, 2, bias_per_q=False)
        bwd = F._clamp_blocks(None, None, 64, 2, bias_per_q=False, bwd=True)
        assert fwd == (512, 1024), fwd
        assert bwd[1] < 1024, bwd

        os.environ["APEX_TPU_FLASH_VMEM_MB"] = "0.9"
        bq, bk = F._clamp_blocks(None, None, 64, 4, bias_per_q=True)
        assert bk == 128 and bq < 512 and bq % 8 == 0
        # env pins fill the None defaults ...
        os.environ["APEX_TPU_FLASH_BLOCK_Q"] = "64"
        os.environ["APEX_TPU_FLASH_BLOCK_K"] = "256"
        del os.environ["APEX_TPU_FLASH_VMEM_MB"]
        assert F._clamp_blocks(None, None, 64, 4, False) == (64, 256)
        # ... but never rewrite PINNED block sizes — explicit arguments
        # or env pins — even under a budget that would
        # otherwise shrink them
        os.environ["APEX_TPU_FLASH_VMEM_MB"] = "0.25"
        assert F._clamp_blocks(512, 512, 64, 4, False) == (512, 512)
        assert F._clamp_blocks(None, None, 64, 4, False) == (64, 256)

        # correctness under a forced tiny budget: blocks must come out
        # strictly smaller than S so the clamped run is genuinely
        # multi-block while the default run is single-block
        os.environ.pop("APEX_TPU_FLASH_BLOCK_Q")
        os.environ.pop("APEX_TPU_FLASH_BLOCK_K")
        B, H, S, D = 1, 2, 512, 32
        bq, bk = F._clamp_blocks(None, None, D, 4, bias_per_q=False)
        assert bq < S and bk < S, (bq, bk)
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(k1, (B * H, S, D)) * 0.3
        k = jax.random.normal(k2, (B * H, S, D)) * 0.3
        v = jax.random.normal(k3, (B * H, S, D)) * 0.3
        bias = jnp.zeros((1, 1, S), jnp.float32)
        small = F.flash_attention(q, k, v, bias, causal=True, heads=H)
        del os.environ["APEX_TPU_FLASH_VMEM_MB"]
        big = F.flash_attention(q, k, v, bias, causal=True, heads=H)
        np.testing.assert_allclose(np.asarray(small), np.asarray(big),
                                   atol=2e-5)
    finally:
        os.environ.clear()
        os.environ.update(old)


def _worst_vmem_ratio(budget_bytes):
    """Worst used/budget ratio of the per-grid-step VMEM estimate over
    the block sizes every flash kernel variant resolves to at the
    production regime (D=64, seq 512): every kernel variant x stream
    dtype x bias layout the ``_clamp_blocks`` chain serves."""
    from apex_tpu.contrib.multihead_attn import flash as F
    worst = 0.0
    for bwd in (False, "dq", "dkv", "fused", True):
        for esz in (2, 4):                    # bf16 / f32 streams
            for bias_per_q in (False, True):
                bq, bk = F._clamp_blocks(None, None, 64, esz, bias_per_q,
                                         bwd=bwd, sq=512, sk=512)
                est = F.vmem_estimate(bq, bk, 64, esz, bias_per_q, bwd)
                worst = max(worst, est / budget_bytes)
    return worst


def test_clamped_blocks_model_under_the_vmem_budget(monkeypatch):
    """The block sizes ``_clamp_blocks`` resolves must model under the
    budget it enforces, and the estimator must still point the right way
    (a config the clamp would never emit models OVER budget — the check
    is not a tautology).  Pure estimator arithmetic: what Mosaic really
    accepts is chip_smoke.py's kernels phase."""
    from apex_tpu.contrib.multihead_attn import flash as F
    budget = F._VMEM_BUDGET_MB * 2 ** 20
    assert 0.0 < _worst_vmem_ratio(budget) <= 1.0
    assert F.vmem_estimate(4096, 8192, 64, 4, True, "fused") > budget
    # a shrunk budget the floors cannot meet is breached: the model and
    # the clamp read the same budget
    monkeypatch.setenv("APEX_TPU_FLASH_VMEM_MB", "0.05")
    assert _worst_vmem_ratio(0.05 * 2 ** 20) > 1.0


# ---------------------------------------------------------------------------
# whole-key backward: one tile holds a head's keys, dq finished in the kernel
# ---------------------------------------------------------------------------

_FLASH_BWD_ENV = ("APEX_TPU_FLASH_BWD_BLOCK_Q", "APEX_TPU_FLASH_BWD_BLOCK_K",
                  "APEX_TPU_FLASH_BWD_DQ_BLOCK_Q",
                  "APEX_TPU_FLASH_BWD_DQ_BLOCK_K",
                  "APEX_TPU_FLASH_BWD_DKV_BLOCK_Q",
                  "APEX_TPU_FLASH_BWD_DKV_BLOCK_K",
                  "APEX_TPU_FLASH_BWD_FUSE", "APEX_TPU_FLASH_BWD_FUSE_MB",
                  "APEX_TPU_FLASH_BWD_IMPL", "APEX_TPU_FLASH_VMEM_MB")


@pytest.fixture
def flash_env(monkeypatch):
    """The whole-key tests read the BUILT-IN end of the chain: no ambient
    pin may stand in front of it."""
    for var in _FLASH_BWD_ENV:
        monkeypatch.delenv(var, raising=False)
    return monkeypatch


@pytest.fixture
def flash_counts():
    """A default registry for the ``flash.bwd_calls.*`` counters; the
    previous default comes back afterwards."""
    from apex_tpu.telemetry import MemorySink, Registry, events
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    prev = events.set_default(reg)
    yield reg
    events.set_default(prev)


def _whole_key_case(case, S):
    """(sq, sk, causal, rate, bias layout, dtype) of one parity case."""
    sq, sk, causal, rate, layout, dtype = S, S, False, 0.0, "none", jnp.float32
    if case == "causal":
        causal = True
    elif case == "dropout":
        rate = 0.1
    elif case == "padding":
        layout = "padding"
    elif case == "per_query":
        layout = "full"
    elif case == "ragged":              # multiples of neither 8 nor 128
        sq, sk = S - 12, S - 28
    elif case == "sq_ne_sk":
        sq = S // 2
    elif case == "bf16":
        dtype = jnp.bfloat16
    return sq, sk, causal, rate, layout, dtype


def _check_bwd_against_split_and_xla(flash_counts, path, b, h, sq, sk,
                                     causal, rate, layout, dtype):
    """The unforced backward at the shape takes ``path``, and its dq / dk /
    dv are those of the split dq / dkv kernels and of the XLA twin.
    Returns the path's ``flash.bwd`` event."""
    from apex_tpu.contrib.multihead_attn import flash as F
    d = 64
    ks = jax.random.split(jax.random.PRNGKey(26), 4)
    q, do = (0.5 * jax.random.normal(kk, (b * h, sq, d), jnp.float32)
             for kk in ks[:2])
    k, v = (0.5 * jax.random.normal(kk, (b * h, sk, d), jnp.float32)
            for kk in ks[2:])
    q, k, v, do = (x.astype(dtype) for x in (q, k, v, do))
    bias = _bias_layouts(b, sq, sk)[layout]

    @jax.jit
    def grads(seed):                    # the seed is traced, as in training
        out, lse = F._flash_fwd(q, k, v, bias, causal, rate, seed, h)
        args = (q, k, v, bias, causal, rate, seed, h, out, lse, do)
        return (F._flash_bwd(*args), F._flash_bwd(*args, fuse=False),
                F._xla_bwd(*args))

    got, split, xla = grads(jnp.int32(7))
    counts = flash_counts.read()
    assert counts[f"flash.bwd_calls.{path}"] == 1
    assert counts["flash.bwd_calls.split"] == 1
    for g, x in zip(got, (q, k, v)):
        assert g.shape == x.shape and g.dtype == x.dtype
    bf16 = dtype == jnp.bfloat16
    for name, w, s_, x in zip(("dq", "dk", "dv"), got, split, xla):
        w, s_, x = (np.asarray(t, np.float32) for t in (w, s_, x))
        np.testing.assert_allclose(w, s_, atol=3e-2 if bf16 else 2e-5,
                                   rtol=2e-2 if bf16 else 1e-5, err_msg=name)
        np.testing.assert_allclose(w, x, atol=5e-2 if bf16 else 5e-3,
                                   rtol=2e-2 if bf16 else 1e-3, err_msg=name)
    return next(r["fields"] for r in flash_counts.flush()
                if r.get("name") == "flash.bwd"
                and r["fields"]["path"] == path)


@pytest.mark.parametrize("case", ["plain", "causal", "dropout", "padding",
                                  "per_query", "ragged", "sq_ne_sk", "bf16"])
@pytest.mark.parametrize("S", [128, 512])
def test_flash_bwd_whole_key_matches_split_and_xla(flash_env, flash_counts,
                                                   S, case):
    """Where one tile holds a head's keys the fused kernel writes dq itself
    (path ``whole_key``); its gradients are those of the split dq / dkv
    kernels and of the XLA twin, for every input the rule sees."""
    b, h = 2, (2 if S == 128 else 1)    # interpret mode: keep BH small
    ev = _check_bwd_against_split_and_xla(flash_counts, "whole_key", b, h,
                                          *_whole_key_case(case, S))
    assert ev["nk"] == 1


@pytest.mark.parametrize("sq,sk,D,esz,per_q,causal,want", [
    (128, 128, 64, 2, False, False, (128, 128)),      # bert_large.s128
    (512, 512, 64, 2, False, False, (512, 512)),      # bert_large.s512
    (512, 512, 64, 2, False, True, (512, 512)),
    (512, 512, 64, 4, False, False, (256, 512)),      # f32 streams: nq = 2
    (512, 512, 64, 2, True, False, (256, 512)),       # per-query bias block
    (256, 512, 64, 2, False, False, (256, 512)),      # encdec: Sq != Sk
    (100, 84, 64, 2, False, False, (104, 128)),       # ragged: rounded up
    (1024, 1024, 64, 2, False, False, (256, 1024)),
    (2048, 2048, 64, 2, False, False, (128, 2048)),
    (4096, 4096, 64, 2, False, False, None),          # keys do not fit
    (512, 512, 256, 2, False, False, (256, 512)),
    (4096, 8192, 128, 4, True, True, None),
])
def test_flash_bwd_tile_rule(flash_env, sq, sk, D, esz, per_q, causal, want):
    """The built-in end of the fused chain, as a table: one k block a head
    wherever the keys fit the VMEM budget, today's 128 x 128 where they do
    not; every tile the rule gives models under the budget."""
    from apex_tpu.contrib.multihead_attn import flash as F
    got = F._whole_key_blocks(sq, sk, D, esz, per_q, causal)
    assert got == want
    bq, bk = F._clamp_blocks(None, None, D, esz, per_q, bwd="fused",
                             sq=sq, sk=sk, causal=causal)
    budget = F._VMEM_BUDGET_MB * 2 ** 20
    assert F.vmem_estimate(bq, bk, D, esz, per_q, "fused") <= budget
    if want is None:
        assert (bq, bk) == (F.DEFAULT_BWD_BLOCK_Q, F.DEFAULT_BWD_BLOCK_K)
        assert -(-sk // bk) > 1
    else:
        assert (bq, bk) == want and -(-sk // bk) == 1
    # the split kernels, and a caller that gives no shape, keep the constants
    for bwd in ("dq", "dkv", True):
        assert F._clamp_blocks(None, None, D, esz, per_q, bwd=bwd,
                               sq=4096, sk=4096) == (128, 128)
    assert F._clamp_blocks(None, None, D, esz, per_q,
                           bwd="fused") == (128, 128)


def test_flash_bwd_tile_rule_vmem_estimate_counts_the_tile():
    """What dominates a large tile is in the model: the (bq, bk) f32
    intermediates and their MXU copies (4 MiB of 512 x 512 in bf16)."""
    from apex_tpu.contrib.multihead_attn import flash as F
    big = F.vmem_estimate(512, 512, 64, 2, False, "fused")
    assert 5 * 2 ** 20 < big < 8 * 2 ** 20
    tile = 512 * 512 * (3 * 4 + 2 * 2)
    for bwd in ("dq", "dkv", "fused", True):
        grown = (F.vmem_estimate(512, 512, 64, 2, False, bwd)
                 - F.vmem_estimate(512, 256, 64, 2, False, bwd))
        assert grown > tile // 2, bwd


@pytest.mark.parametrize("source", ["argument", "env", "env_dkv",
                                    "env_one_side", "env_dq", "budget"])
def test_flash_bwd_tile_rule_is_the_last_link(flash_env, source):
    """Explicit blocks and env pins still win over the rule, in today's
    order; a moved VMEM budget moves the rule's answer."""
    from apex_tpu.contrib.multihead_attn import flash as F
    shape = dict(D=64, esz=2, bias_per_q=False, bwd="fused", sq=512, sk=512)
    assert F._clamp_blocks(None, None, **shape) == (512, 512)
    if source == "argument":
        assert F._clamp_blocks(128, 256, **shape) == (128, 256)
        # one pinned side: the other falls to the constant, not to the rule
        assert F._clamp_blocks(256, None, **shape) == (256, 128)
    elif source == "env":
        flash_env.setenv("APEX_TPU_FLASH_BWD_BLOCK_Q", "128")
        flash_env.setenv("APEX_TPU_FLASH_BWD_BLOCK_K", "128")
        assert F._clamp_blocks(None, None, **shape) == (128, 128)
    elif source == "env_dkv":
        flash_env.setenv("APEX_TPU_FLASH_BWD_BLOCK_Q", "128")
        flash_env.setenv("APEX_TPU_FLASH_BWD_BLOCK_K", "128")
        flash_env.setenv("APEX_TPU_FLASH_BWD_DKV_BLOCK_Q", "256")
        flash_env.setenv("APEX_TPU_FLASH_BWD_DKV_BLOCK_K", "256")
        assert F._clamp_blocks(None, None, **shape) == (256, 256)
    elif source == "env_one_side":
        # one pinned side: the other falls to the constant, as with an
        # argument, and the argument beats the pin
        flash_env.setenv("APEX_TPU_FLASH_BWD_BLOCK_K", "256")
        assert F._clamp_blocks(None, None, **shape) == (128, 256)
        assert F._clamp_blocks(64, 512, **shape) == (64, 512)
    elif source == "env_dq":
        # the dq kernel's own pins name the dq kernel only: the fused
        # kernel rides the dkv names, and the forward reads neither
        flash_env.setenv("APEX_TPU_FLASH_BWD_DQ_BLOCK_Q", "256")
        flash_env.setenv("APEX_TPU_FLASH_BWD_DQ_BLOCK_K", "256")
        assert F._clamp_blocks(None, None, **shape) == (512, 512)
        assert F._clamp_blocks(None, None, **{**shape, "bwd": "dq"}) \
            == (256, 256)
        assert F._clamp_blocks(None, None, 64, 2, False, sq=512,
                               sk=512) == (512, 512)
    else:
        flash_env.setenv("APEX_TPU_FLASH_VMEM_MB", "4")
        assert F._clamp_blocks(None, None, **shape) == (256, 512)
        flash_env.setenv("APEX_TPU_FLASH_VMEM_MB", "1")
        assert F._whole_key_blocks(512, 512, 64, 2, False, False) is None


def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk_eqns(sub)


def test_flash_grad_jaxpr_has_no_dq_partials(flash_env):
    """grad(flash_attention) at S 512: the fused backward's dq output is
    (BH, Sq, D) in q.dtype and no f32 (BH, nk, Sq, D) array exists for XLA
    to sum — where the parent's 128 x 128 grid made one of nk = 4."""
    BH, S, D = 4, 512, 64
    q = jnp.zeros((BH, S, D), jnp.bfloat16)
    bias = jnp.zeros((1, 1, S), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash_attention(q, k, v, bias, 0, False, 0.0, 2,
                                        "pallas").astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))(q, q, q)
    eqns = list(_walk_eqns(jaxpr.jaxpr))
    fused = [e for e in eqns if e.primitive.name == "pallas_call"
             and e.params["name"] == "apex_flash_bwd_fused"]
    assert len(fused) == 1
    dq = fused[0].outvars[0].aval
    assert dq.shape == (BH, S, D) and dq.dtype == jnp.bfloat16
    for e in eqns:
        for var in e.outvars:
            aval = var.aval
            partial = (getattr(aval, "ndim", 0) == 4
                       and aval.dtype == jnp.float32
                       and aval.shape[0] == BH and aval.shape[2:] == (S, D))
            assert not partial, (e.primitive.name, aval)
    # pinned back to 128 x 128 the partials are there: the check can see
    flash_env.setenv("APEX_TPU_FLASH_BWD_BLOCK_Q", "128")
    flash_env.setenv("APEX_TPU_FLASH_BWD_BLOCK_K", "128")
    pinned = jax.make_jaxpr(jax.grad(
        lambda q: flash_attention(q, q, q, bias, 0, False, 0.0, 2,
                                  "pallas").astype(jnp.float32).sum()))(q)
    shapes = [v.aval.shape for e in _walk_eqns(pinned.jaxpr)
              for v in e.outvars if hasattr(v.aval, "shape")]
    assert (BH, 4, S, D) in shapes


@pytest.mark.parametrize("path", ["whole_key", "resident", "partials",
                                  "split", "xla"])
def test_flash_bwd_calls_counter_names_the_path(flash_env, flash_counts,
                                                path):
    """``flash.bwd_calls.<path>`` counts one per traced backward under a
    default registry; the event carries the tile the choice was made from."""
    from apex_tpu.contrib.multihead_attn import flash as F
    from apex_tpu.telemetry import events
    h, s, d = 2, 256, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (h, s, d))
    bias = jnp.zeros((1, 1, s), jnp.float32)
    if path == "partials":              # nk = 2: dq leaves as partials
        flash_env.setenv("APEX_TPU_FLASH_BWD_BLOCK_Q", "128")
        flash_env.setenv("APEX_TPU_FLASH_BWD_BLOCK_K", "128")
    elif path == "split":
        flash_env.setenv("APEX_TPU_FLASH_BWD_FUSE", "0")
    elif path == "resident":            # no tile holds the keys; VMEM does
        flash_env.setenv("APEX_TPU_FLASH_VMEM_MB", "0.75")
        flash_env.setattr(F, "_RESIDENT_MAX_BK", 128)
    backward = "xla" if path == "xla" else "pallas"
    jax.grad(lambda q: flash_attention(q, q, q, bias, 0, False, 0.0, h,
                                       backward).sum())(q)
    with pytest.raises(ValueError):
        events.record_flash_bwd("cuda")
    counts = {k: v for k, v in flash_counts.read().items()
              if k.startswith("flash.bwd_calls.")}
    assert counts == {f"flash.bwd_calls.{path}": 1}
    ev = [r for r in flash_counts.flush() if r.get("name") == "flash.bwd"]
    assert len(ev) == 1 and ev[0]["fields"]["path"] == path
    if path == "xla":
        assert "nk" not in ev[0]["fields"]
    else:
        pieces = {"partials": 2, "resident": 2}.get(path, 1)
        assert ev[0]["fields"]["nk"] == pieces
        if path == "resident":          # the tile the walk runs, not 128x128's
            assert (ev[0]["fields"]["bq"], ev[0]["fields"]["bk"]) == (128, 128)


def test_flash_bwd_calls_counter_is_a_noop_without_a_registry(flash_env):
    from apex_tpu.telemetry import events
    prev = events.set_default(None)
    try:
        assert not events.active()
        events.record_flash_bwd("whole_key", 8, 128, 1)
        events.record_flash_bwd("cuda")         # not even validated: free
        q = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 16))
        bias = jnp.zeros((1, 1, 64), jnp.float32)
        g = jax.grad(lambda q: flash_attention(q, q, q, bias, 0, False, 0.0,
                                               2, "pallas").sum())(q)
        assert np.all(np.isfinite(np.asarray(g)))
    finally:
        events.set_default(prev)


# ---------------------------------------------------------------------------
# resident backward: no tile holds a head's keys, VMEM does — one kernel walks
# them in pieces and finishes dq, dk and dv itself
# ---------------------------------------------------------------------------

@pytest.fixture
def small_vmem(flash_env):
    """A budget under which S 256-512 at D 64 answers the dispatcher's
    questions as S 4096 does under the real one: no whole-key tile, the
    head resident, its keys walked in several pieces of 128."""
    from apex_tpu.contrib.multihead_attn import flash as F
    flash_env.setenv("APEX_TPU_FLASH_VMEM_MB", "1.5")
    flash_env.setattr(F, "_RESIDENT_MAX_BK", 128)
    return flash_env


# case -> what differs from (sq 512, sk 512, not causal, no dropout, no
# bias, float32)
_RESIDENT_CASES = {
    "plain": {},
    "causal": dict(causal=True),
    "dropout": dict(rate=0.1),
    "causal_dropout": dict(causal=True, rate=0.1),
    "padding": dict(layout="padding"),
    "causal_padding": dict(causal=True, layout="padding"),
    "per_query": dict(layout="full"),
    "causal_per_query": dict(causal=True, layout="full"),
    "ragged": dict(sq=500, sk=484),     # multiples of neither 8 nor 128
    "causal_ragged": dict(sq=484, sk=500, causal=True),
    "sq_lt_sk": dict(sq=256, causal=True),   # the last pieces never run
    "sq_gt_sk": dict(sk=256, causal=True),   # the walk stops at the keys
    "s256": dict(sq=256, sk=256),
    "bf16": dict(dtype=jnp.bfloat16),
    "causal_bf16": dict(causal=True, dtype=jnp.bfloat16),
}


@pytest.mark.parametrize("case", sorted(_RESIDENT_CASES))
def test_flash_bwd_resident_matches_split_and_xla(small_vmem, flash_counts,
                                                  case):
    """Where no tile holds a head's keys but VMEM does, ONE kernel walks
    them in pieces (path ``resident``); its gradients are those of the
    split dq / dkv kernels and of the XLA twin, for every input the
    dispatcher sends it."""
    c = {**dict(sq=512, sk=512, causal=False, rate=0.0, layout="none",
                dtype=jnp.float32), **_RESIDENT_CASES[case]}
    ev = _check_bwd_against_split_and_xla(
        flash_counts, "resident", 2, 1, c["sq"], c["sk"], c["causal"],
        c["rate"], c["layout"], c["dtype"])
    assert ev["bk"] == 128 and ev["nk"] == -(-c["sk"] // 128) >= 2


def _traced_bwd_path(F, reg, BH, sq, sk, D, dtype, per_q=False, causal=True,
                     **pins):
    """The ``flash.bwd`` event of ONE traced (never run) ``_flash_bwd`` at
    the shape: what the dispatcher answers, whatever the size."""
    sds = jax.ShapeDtypeStruct
    q, do, out = (sds((BH, sq, D), dtype),) * 3
    k = v = sds((BH, sk, D), dtype)
    bias = sds((1, sq if per_q else 1, sk), jnp.float32)
    lse = sds((BH, sq, 1), jnp.float32)
    jax.eval_shape(
        lambda q, k, v, bias, out, lse, do: F._flash_bwd(
            q, k, v, bias, causal, 0.0, 0, 1, out, lse, do, **pins),
        q, k, v, bias, out, lse, do)
    ev = [r["fields"] for r in reg.flush() if r.get("name") == "flash.bwd"]
    assert len(ev) == 1
    return ev[0]


@pytest.mark.parametrize("BH,sq,sk,D,dtype,per_q,want", [
    # the keys fit one tile: whole_key, tile and all, as before this path
    (4, 128, 128, 64, "bfloat16", False, ("whole_key", 128, 128, 1)),
    (4, 512, 512, 64, "bfloat16", False, ("whole_key", 512, 512, 1)),
    (4, 512, 512, 64, "float32", False, ("whole_key", 256, 512, 1)),
    (4, 2048, 2048, 64, "bfloat16", False, ("whole_key", 128, 2048, 1)),
    # they do not, the head fits VMEM: lfm2_24b_a2b.ep8_s4096 is the first
    (256, 4096, 4096, 64, "bfloat16", False, ("resident", 512, 512, 8)),
    (4, 2048, 4096, 64, "bfloat16", False, ("resident", 512, 512, 8)),
    (4, 4000, 4000, 64, "bfloat16", False, ("resident", 512, 512, 8)),
    (4, 4096, 4096, 64, "float32", False, ("resident", 512, 512, 8)),
    (4, 4096, 4096, 128, "bfloat16", False, ("resident", 512, 512, 8)),
    (4, 8192, 8192, 64, "bfloat16", False, ("resident", 512, 512, 16)),
    (4, 8192, 8192, 128, "bfloat16", False, ("resident", 512, 512, 16)),
    (4, 4096, 4096, 64, "bfloat16", True, ("resident", 256, 512, 8)),
    # too long for residency (or a per-query bias block too wide): the
    # 128 x 128 grid, partials under the cap and the split pair above it
    (1, 16384, 16384, 64, "bfloat16", False, ("partials", 128, 128, 128)),
    (4, 16384, 16384, 64, "bfloat16", False, ("split", 128, 128, 128)),
    (32, 8192, 8192, 64, "float32", False, ("split", 128, 128, 64)),
    (32, 8192, 8192, 64, "bfloat16", True, ("split", 128, 128, 64)),
])
def test_flash_bwd_dispatch_rule(flash_env, flash_counts, BH, sq, sk, D,
                                 dtype, per_q, want):
    """The dispatcher's three questions as a table over shapes, under the
    real budget: one tile a head where the keys fit it, the resident walk
    where the head fits VMEM, the 128 x 128 grid's two paths beyond."""
    from apex_tpu.contrib.multihead_attn import flash as F
    dtype = jnp.dtype(dtype)
    ev = _traced_bwd_path(F, flash_counts, BH, sq, sk, D, dtype, per_q)
    assert (ev["path"], ev["bq"], ev["bk"], ev["nk"]) == want
    path, bq, bk, _ = want
    whole = F._whole_key_blocks(sq, sk, D, dtype.itemsize, per_q, True)
    resident = F._resident_blocks(sq, sk, D, dtype.itemsize, per_q)
    assert (whole is not None) == (path == "whole_key")
    if path == "resident":
        assert resident == (bq, bk)
        assert F.vmem_estimate(bq, bk, D, dtype.itemsize, per_q, "resident",
                               sk=sk) <= F._resident_budget()
    elif path != "whole_key":
        assert resident is None


@pytest.mark.parametrize("pin", ["fuse_arg", "split_arg", "fuse_env",
                                 "split_env", "blocks_arg", "blocks_env",
                                 "dkv_blocks_arg", "dkv_blocks_env"])
def test_flash_bwd_pins_keep_their_meaning_at_s4096(flash_env, flash_counts,
                                                    pin):
    """A strategy or a tile somebody chose names the 128 x 128 grid's
    kernels, as before: ``resident`` is asked only where nobody chose."""
    from apex_tpu.contrib.multihead_attn import flash as F
    shape = (2, 4096, 4096, 64, jnp.bfloat16)
    assert _traced_bwd_path(F, flash_counts, *shape)["path"] == "resident"
    pins, want = {}, "partials"
    if pin == "fuse_arg":
        pins = dict(fuse=True)
    elif pin == "split_arg":
        pins, want = dict(fuse=False), "split"
    elif pin == "fuse_env":
        flash_env.setenv("APEX_TPU_FLASH_BWD_FUSE", "1")
    elif pin == "split_env":
        flash_env.setenv("APEX_TPU_FLASH_BWD_FUSE", "0")
        want = "split"
    elif pin == "blocks_arg":
        pins = dict(bq=256, bk=256)
    elif pin == "blocks_env":
        flash_env.setenv("APEX_TPU_FLASH_BWD_BLOCK_K", "256")
    elif pin == "dkv_blocks_arg":
        pins = dict(dkv_blocks=(128, 512))
    else:
        flash_env.setenv("APEX_TPU_FLASH_BWD_DKV_BLOCK_Q", "256")
    ev = _traced_bwd_path(F, flash_counts, *shape, **pins)
    assert ev["path"] == want and ev["nk"] > 1


def test_flash_bwd_resident_vmem_estimate_counts_the_head():
    """The resident model holds what the kernel holds for a head: K, V and
    the dk / dv blocks twice (double-buffered), the f32 accumulators once,
    all at the padded key length and at whole 128-lane rows (what Mosaic
    allocates for D 64); the tile as the fused model counts it."""
    from apex_tpu.contrib.multihead_attn import flash as F
    est = functools.partial(F.vmem_estimate, D=64, esz=2, bias_per_q=False,
                            bwd="resident")
    MiB = 2 ** 20
    s4096 = est(512, 512, sk=4096)
    assert s4096 == 18.25 * MiB and est(256, 512, sk=8192) == 27.5 * MiB
    # D 64 costs what D 128 costs: half of every 128-lane row is padding
    assert s4096 == F.vmem_estimate(512, 512, 128, 2, False, "resident",
                                    sk=4096)
    assert s4096 <= F._resident_budget() < est(128, 128, sk=16384)
    # a key more is a piece more: the head is held in whole pieces
    assert est(512, 512, sk=4097) == est(512, 512, sk=4608)
    # per key: 4 streams x 2 buffers x 2 B + 2 accumulators x 4 B, x 128
    # lanes, plus the bias row (8 sublanes x 4 B x 2 buffers)
    per_key = (4 * 2 * 2 + 2 * 4) * 128 + 8 * 4 * 2
    assert est(512, 512, sk=8192) - s4096 == 4096 * per_key
    # the tile is the fused model's tile
    tile = 512 * 512 * (3 * 4 + 2 * 2)
    assert est(512, 512, sk=4096) - est(512, 256, sk=4096) == tile // 2
    # a per-query bias holds (bq, Sk) f32 twice: 512 rows do not fit
    assert F.vmem_estimate(512, 512, 64, 2, True, "resident",
                           sk=4096) > F._resident_budget()


def test_flash_grad_jaxpr_at_s4096_is_one_fused_kernel(flash_env):
    """grad(flash_attention) at the LFM2 cell's shape (b8 x 32 heads cut to
    BH 8; S 4096, D 64, bf16, causal): ONE ``apex_flash_bwd_fused`` call
    writes dq, dk and dv in their own dtypes; no dq / dkv pair, no f32
    (BH, nk, Sq, D) partials, no XLA sum over them."""
    BH, S, D = 8, 4096, 64
    q = jax.ShapeDtypeStruct((BH, S, D), jnp.bfloat16)
    bias = jnp.zeros((1, 1, S), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, bias, 0, True, 0.0, 4,
                               "pallas").astype(jnp.float32).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)
    eqns = list(_walk_eqns(jaxpr.jaxpr))
    kernels = [e.params["name"] for e in eqns
               if e.primitive.name == "pallas_call"]
    assert sorted(kernels) == ["apex_flash_bwd_fused", "apex_flash_fwd"]
    fused = next(e for e in eqns if e.primitive.name == "pallas_call"
                 and e.params["name"] == "apex_flash_bwd_fused")
    assert [(v.aval.shape, v.aval.dtype) for v in fused.outvars] == [
        ((BH, S, D), jnp.bfloat16)] * 3
    assert fused.params["grid_mapping"].grid == (BH, S // 512)
    for e in eqns:
        for var in e.outvars:
            aval = var.aval
            assert not (getattr(aval, "ndim", 0) == 4
                        and aval.dtype == jnp.float32), (e.primitive.name,
                                                         aval)
    # forced back to the split pair, the two kernels are there: the check
    # can see
    flash_env.setenv("APEX_TPU_FLASH_BWD_FUSE", "0")
    split = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)
    names = sorted(e.params["name"] for e in _walk_eqns(split.jaxpr)
                   if e.primitive.name == "pallas_call")
    assert names == ["apex_flash_bwd_dkv", "apex_flash_bwd_dq",
                     "apex_flash_fwd"]


def test_flash_bwd_resident_skips_what_lies_above_the_diagonal(small_vmem):
    """Causal: the pieces wholly above a q tile's diagonal are neither
    computed nor read — poisoned keys there change no gradient of the
    rows that cannot see them (Sq < Sk: the last 256 keys are above every
    row), and their own dk / dv come out zero."""
    from apex_tpu.contrib.multihead_attn import flash as F
    sq, sk, d = 256, 512, 64
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q, do = (0.5 * jax.random.normal(kk, (1, sq, d)) for kk in ks[:2])
    k, v = (0.5 * jax.random.normal(kk, (1, sk, d)) for kk in ks[2:])
    bias = jnp.zeros((1, 1, sk), jnp.float32)

    out, lse = F._flash_fwd(q, k, v, bias, True, 0.0, 0, 1)

    def grads(k, v):
        return F._flash_bwd(q, k, v, bias, True, 0.0, 0, 1, out, lse, do)

    clean = grads(k, v)
    poisoned = grads(k.at[:, sq:].set(jnp.nan), v.at[:, sq:].set(jnp.nan))
    for name, a, b in zip(("dq", "dk", "dv"), clean, poisoned):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
    assert not np.any(np.asarray(clean[1])[:, sq:])
    assert not np.any(np.asarray(clean[2])[:, sq:])


# ---------------------------------------------------------------------------
# the projection's layout: q, k, v read from (B, S, 3·H·hd), the context
# written as (B, S, H·hd), two heads of 64 a 128-lane block
# ---------------------------------------------------------------------------

def _transposed_entry(qkv, bias, seed, causal, rate, heads):
    """What the model did before the projection layout: the heads
    transposed to (B·H, S, hd) around ``flash_attention``, q pre-scaled."""
    B, S, width = qkv.shape
    hd = width // 3 // heads
    q, k, v = (t.reshape(B, S, heads, hd).transpose(0, 2, 1, 3)
               .reshape(B * heads, S, hd) for t in jnp.split(qkv, 3, -1))
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    q = (q.astype(jnp.float32) * scale).astype(qkv.dtype)
    ctx = flash_attention(q, k, v, bias, seed, causal, rate, heads)
    return ctx.reshape(B, heads, S, hd).transpose(0, 2, 1, 3) \
        .reshape(B, S, heads * hd)


def _qkv_inputs(B, S, heads, hd, dtype, seed=40):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    qkv = 0.5 * jax.random.normal(ks[0], (B, S, 3 * heads * hd), jnp.float32)
    do = jax.random.normal(ks[1], (B, S, heads * hd), jnp.float32)
    return qkv.astype(dtype), do.astype(dtype)


def _both_entries(qkv, do, bias, causal, rate, heads):
    """(context, d(qkv)) of ``flash_attention_qkv`` and of the transposed
    entry, under one jit with a traced seed, as in training."""
    from apex_tpu.contrib.multihead_attn import flash as F

    @jax.jit
    def run(seed):
        got = []
        for fn in (F.flash_attention_qkv, _transposed_entry):
            o, vjp = jax.vjp(lambda x: fn(x, bias, seed, causal, rate, heads),
                             qkv)
            got.append((o, vjp(do)[0]))
        return got

    return run(jnp.int32(7))


#: case -> (B, S, heads, causal, dropout rate, bias, dtype)
_QKV_CASES = {
    "plain": (2, 128, 2, False, 0.0, "none", jnp.float32),
    "causal": (2, 128, 4, True, 0.0, "none", jnp.float32),
    "padding": (2, 128, 2, False, 0.0, "padding", jnp.float32),
    "dead_row": (2, 128, 2, False, 0.0, "dead", jnp.float32),
    "dropout": (2, 128, 2, True, 0.1, "none", jnp.float32),
    "bf16_s256": (1, 256, 2, False, 0.0, "padding", jnp.bfloat16),
}


def _qkv_bias(kind, B, S):
    if kind == "none":
        return jnp.zeros((1, 1, S), jnp.float32)
    if kind == "padding":               # the model's key-padding bias
        pad = jnp.arange(S)[None, :] >= S - 16 * (1 + jnp.arange(B))[:, None]
        return jnp.where(pad[:, None, :], -1e9, 0.0).astype(jnp.float32)
    # every key of the second sequence masked out: all its rows are dead
    return jnp.zeros((B, 1, S), jnp.float32).at[1].set(-1e30)


@pytest.mark.parametrize("case", sorted(_QKV_CASES))
def test_flash_qkv_matches_the_transposed_entry(flash_env, flash_counts,
                                                case):
    """Heads of 64 in pairs at a length whose whole-key tile holds a head:
    ``flash_attention_qkv`` reads the projection in place (the backward
    records path ``projection``), and its context, ``lse`` and d(qkv) are
    those of ``flash_attention`` on the transposed heads — the same dropout
    mask, dead rows zero, within the whole-key tolerances."""
    from apex_tpu.contrib.multihead_attn import flash as F
    B, S, heads, causal, rate, kind, dtype = _QKV_CASES[case]
    qkv, do = _qkv_inputs(B, S, heads, 64, dtype)
    bias = _qkv_bias(kind, B, S)
    assert F._packed_tile(S, heads, 64, qkv.dtype.itemsize,
                          False) == (S, S)
    (o, g), (o_t, g_t) = _both_entries(qkv, do, bias, causal, rate, heads)
    counts = flash_counts.read()
    assert counts["flash.bwd_calls.projection"] == 1
    assert counts["flash.bwd_calls.whole_key"] == 1      # the transposed one
    bf16 = dtype == jnp.bfloat16
    for name, a, b in (("out", o, o_t), ("dqkv", g, g_t)):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=3e-2 if bf16 else 2e-5, rtol=2e-2 if bf16 else 1e-5,
            err_msg=name)
    # lse: (B, H/2, 2, S) rows against the (B·H, S, 1) columns
    q_t = (qkv[..., :heads * 64].reshape(B, S, heads, 64).transpose(0, 2, 1, 3)
           .reshape(B * heads, S, 64).astype(jnp.float32) / 8.0).astype(dtype)
    kv_t = [t.reshape(B, S, heads, 64).transpose(0, 2, 1, 3)
            .reshape(B * heads, S, 64)
            for t in jnp.split(qkv[..., heads * 64:], 2, -1)]
    _, lse_t = F._flash_fwd(q_t, *kv_t, bias, causal, rate, 7, heads)
    _, lse = F._flash_fwd_packed(qkv, bias, causal, rate, 7, heads)
    assert lse.shape == (B, heads // 2, 2, S) and lse.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(lse).reshape(B * heads, S),
                               np.asarray(lse_t)[..., 0], rtol=1e-5,
                               atol=1e-4)
    if kind == "dead":
        assert not np.any(np.asarray(o)[1]) and not np.any(np.asarray(g)[1])


@pytest.mark.parametrize("case", ["odd_heads", "hd128", "no_whole_key"])
def test_flash_qkv_falls_back_to_the_transposed_entry(flash_env, flash_counts,
                                                      case):
    """Every other shape takes the transposing path, with its numbers:
    three heads, a head of 128, and a length whose pair of heads' whole
    tile no longer fits the VMEM budget (here 2 MiB at S 256) go through the (B·H, S, hd) entry — bit for bit — and the
    backward records ``whole_key``, never ``projection``."""
    from apex_tpu.contrib.multihead_attn import flash as F
    heads, hd, S = {"odd_heads": (3, 64, 128), "hd128": (2, 128, 128),
                    "no_whole_key": (2, 64, 256)}[case]
    if case == "no_whole_key":
        flash_env.setenv("APEX_TPU_FLASH_VMEM_MB", "2")
    qkv, do = _qkv_inputs(2, S, heads, hd, jnp.float32)
    bias = _qkv_bias("padding", 2, S)
    assert F._packed_tile(S, heads, hd, 4, False) is None
    (o, g), (o_t, g_t) = _both_entries(qkv, do, bias, False, 0.0, heads)
    counts = flash_counts.read()
    assert counts["flash.bwd_calls.whole_key"] == 2
    assert "flash.bwd_calls.projection" not in counts
    np.testing.assert_array_equal(np.asarray(o), np.asarray(o_t))
    np.testing.assert_array_equal(np.asarray(g), np.asarray(g_t))


@pytest.mark.parametrize("pin", ["backward_xla", "bwd_block", "fwd_block",
                                 "split"])
def test_flash_qkv_pins_keep_the_transposed_entry(flash_env, pin):
    """A choice somebody made names the (B·H, S, hd) kernels — the XLA
    backward, a backward or forward block pin, the split strategy — so the
    shape that would read the projection transposes instead."""
    from apex_tpu.contrib.multihead_attn import flash as F
    shape = (128, 2, 64, 2, False)
    assert F._packed_tile(*shape) == (128, 128)
    flash_env.setenv(*{"backward_xla": ("APEX_TPU_FLASH_BWD_IMPL", "xla"),
                       "bwd_block": ("APEX_TPU_FLASH_BWD_BLOCK_Q", "128"),
                       "fwd_block": ("APEX_TPU_FLASH_BLOCK_K", "128"),
                       "split": ("APEX_TPU_FLASH_BWD_FUSE", "0")}[pin])
    assert F._packed_tile(*shape) is None


@pytest.mark.parametrize("masked", [False, True])
def test_transformer_fast_reads_the_projection_and_matches_default(masked):
    """``attn_impl="fast"`` at heads of 64 (d 128, two heads, S 128): the
    layer's attention takes the projection layout — no transpose of an
    activation in its traced gradient, one packed kernel each way — and
    its logits and gradients are the jnp oracle's."""
    import dataclasses as dc
    from apex_tpu.models import (TransformerConfig, transformer_apply,
                                 transformer_init, transformer_loss)
    cfg = TransformerConfig(vocab_size=64, max_len=128, num_layers=1,
                            d_model=128, num_heads=2, d_ff=128)
    fast = dc.replace(cfg, attn_impl="fast")
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    toks = (jnp.arange(256).reshape(2, 128) * 7 % 64).astype(jnp.int32)
    mask = (jnp.zeros((2, 128), jnp.int32).at[1, 100:].set(1)
            if masked else None)
    batch = {"tokens": toks, "targets": toks, "mask": mask}
    np.testing.assert_allclose(
        np.asarray(transformer_apply(params, toks, fast, mask=mask)),
        np.asarray(transformer_apply(params, toks, cfg, mask=mask)),
        atol=2e-4, rtol=2e-4)
    g_def = jax.grad(lambda p: transformer_loss(p, batch, cfg))(params)
    g_fast = jax.grad(lambda p: transformer_loss(p, batch, fast))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g_def),
                    jax.tree_util.tree_leaves(g_fast)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=5e-4, rtol=5e-3)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: transformer_loss(p, batch, fast)))(params)
    kernels = {e.params["name"]: tuple(e.params["grid_mapping"].grid)
               for e in _walk_eqns(jaxpr.jaxpr)
               if e.primitive.name == "pallas_call"
               and e.params["name"].startswith("apex_flash")}
    assert kernels == {"apex_flash_fwd": (2, 1, 1),
                       "apex_flash_bwd_fused": (2,)}
    moved = [e.outvars[0].aval.shape for e in _walk_eqns(jaxpr.jaxpr)
             if e.primitive.name == "transpose"
             and len(e.outvars[0].aval.shape) >= 3]
    assert moved == [], moved
