"""amp tests: casting semantics (analog of tests/L0/run_amp/test_basic_casts.py
driven by ALWAYS_HALF/ALWAYS_BFLOAT16/ALWAYS_FLOAT expectation tables),
promotion (test_promotion.py), opt-level properties, end-to-end toy training
with dynamic scaling and overflow skip (test_fused_sgd/test_checkpointing
spirit)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import apex_tpu
from apex_tpu import amp
from apex_tpu.amp import amp as amp_mod
from apex_tpu.amp import scaler as sc
from apex_tpu.optimizers import FusedAdam, FusedSGD


# --- casting semantics (expectation-table style) ---------------------------

@pytest.mark.parametrize("ptype", [jnp.float16, jnp.bfloat16])
def test_autocast_matmul_low_precision(ptype):
    with amp_mod.autocast(ptype):
        x = jnp.ones((8, 8), jnp.float32)
        y = jnp.ones((8, 8), jnp.float32)
        out = jnp.matmul(x, y)
    assert out.dtype == ptype   # ALWAYS_HALF / ALWAYS_BFLOAT16


@pytest.mark.parametrize("ptype", [jnp.float16, jnp.bfloat16])
def test_autocast_fp32_funcs(ptype):
    with amp_mod.autocast(ptype):
        x = jnp.ones((8, 8), ptype)
        out = jnp.exp(x)
        s = jnp.sum(x)
    assert out.dtype == jnp.float32   # ALWAYS_FLOAT
    assert s.dtype == jnp.float32


def test_autocast_under_jit():
    """Casts must be baked into traced graphs."""
    with amp_mod.autocast(jnp.bfloat16):
        f = jax.jit(lambda a, b: jnp.matmul(a, b))
        out = f(jnp.ones((4, 4)), jnp.ones((4, 4)))
    assert out.dtype == jnp.bfloat16
    # patches removed, but the traced fn keeps its casts
    out2 = f(jnp.ones((4, 4)), jnp.ones((4, 4)))
    assert out2.dtype == jnp.bfloat16


def test_promotion_widest_type():
    with amp_mod.autocast(jnp.bfloat16):
        a = jnp.ones((4,), jnp.bfloat16)
        b = jnp.ones((4,), jnp.float32)
        out = jnp.add(a, b)
        cat = jnp.concatenate([a, b])
    assert out.dtype == jnp.float32     # widest wins (test_promotion.py:60)
    assert cat.dtype == jnp.float32     # SEQUENCE_CASTS


def test_autocast_restores_cleanly():
    orig = jnp.matmul
    with amp_mod.autocast(jnp.bfloat16):
        assert jnp.matmul is not orig
    assert jnp.matmul is orig
    out = jnp.matmul(jnp.ones((2, 2)), jnp.ones((2, 2)))
    assert out.dtype == jnp.float32


def test_decorators():
    @amp.half_function
    def f(x):
        return x * 2

    @amp.float_function
    def g(x):
        return x * 3

    with amp_mod.autocast(jnp.bfloat16):
        assert f(jnp.ones((4,), jnp.float32)).dtype == jnp.bfloat16
        assert g(jnp.ones((4,), jnp.bfloat16)).dtype == jnp.float32
    # no-ops when amp is off
    assert f(jnp.ones((4,), jnp.float32)).dtype == jnp.float32


# --- opt-level properties ----------------------------------------------------

def test_opt_level_table():
    from apex_tpu.amp.properties import opt_levels, Properties
    p = opt_levels["O2"](Properties())
    assert p.cast_model_type == jnp.float16
    assert p.master_weights and p.keep_batchnorm_fp32
    assert p.loss_scale == "dynamic"
    p = opt_levels["O4"](Properties())
    assert p.patch_functions_type == jnp.bfloat16
    assert p.loss_scale == 1.0       # bf16 needs no scaling
    p = opt_levels["O5"](Properties())
    assert p.cast_model_type == jnp.bfloat16
    assert p.master_weights
    assert p.loss_scale == 1.0


def test_initialize_o5_casts_and_masters():
    params = {"dense": {"kernel": jnp.ones((8, 8)), "bias": jnp.zeros((8,))},
              "batch_norm": {"scale": jnp.ones((8,)), "bias": jnp.zeros((8,))}}
    st = amp.initialize(params, opt_level="O5", verbosity=0)
    assert st.model_params["dense"]["kernel"].dtype == jnp.bfloat16
    # keep_batchnorm_fp32 honored via path predicate
    assert st.model_params["batch_norm"]["scale"].dtype == jnp.float32
    assert st.master_params["dense"]["kernel"].dtype == jnp.float32
    amp_mod.uninit()


def test_initialize_bad_opt_level():
    with pytest.raises(RuntimeError):
        amp.initialize({}, opt_level="O9")


def test_initialize_flash_attn_backward_knob():
    """The amp-level flash_attn_backward option validates and lands in the
    flash module's process default, where backward="auto" resolution picks
    it up (between the env override and the built-in)."""
    from apex_tpu.contrib.multihead_attn import flash as F
    params = {"w": jnp.ones((4, 4))}
    try:
        st = amp.initialize(params, opt_level="O0", verbosity=0,
                            flash_attn_backward="xla")
        assert st.properties.flash_attn_backward == "xla"
        assert F._resolve_backward("auto") == "xla"
        # default initialize resets the process default to auto
        st = amp.initialize(params, opt_level="O0", verbosity=0)
        assert st.properties.flash_attn_backward == "auto"
        assert F._DEFAULT_BACKWARD == "auto"
    finally:
        F.set_default_backward("auto")
    with pytest.raises(ValueError):
        amp.initialize(params, opt_level="O0", verbosity=0,
                       flash_attn_backward="cuda")


# --- end-to-end toy training -------------------------------------------------

def _toy_loss(params, x, y):
    h = jnp.maximum(jnp.dot(x, params["w1"]) + params["b1"], 0.0)
    pred = jnp.dot(h, params["w2"]) + params["b2"]
    return jnp.mean((pred.astype(jnp.float32) - y) ** 2)


def _toy_params(key):
    k1, k2 = jax.random.split(key)
    return {"w1": jax.random.normal(k1, (16, 32)) * 0.1,
            "b1": jnp.zeros((32,)),
            "w2": jax.random.normal(k2, (32, 4)) * 0.1,
            "b2": jnp.zeros((4,))}


@pytest.mark.parametrize("opt_level", ["O0", "O2", "O3", "O5"])
def test_end_to_end_training(opt_level):
    params = _toy_params(jax.random.PRNGKey(0))
    opt = FusedAdam(lr=1e-2)
    st = amp.initialize(params, opt, opt_level=opt_level, verbosity=0)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
    y = jax.random.normal(jax.random.PRNGKey(2), (64, 4))

    @jax.jit
    def train_step(st, x, y):
        def scaled_loss_fn(mp):
            loss = _toy_loss(mp, st.cast_input(x), y)
            return amp.scale_loss(loss, st)
        grads = jax.grad(scaled_loss_fn)(st.model_params)
        return amp.frontend.amp_step(st, grads)

    loss0 = _toy_loss(st.params_for_eval(), x, y)
    for _ in range(20):
        st = train_step(st, x, y)
    loss1 = _toy_loss(st.params_for_eval(), x, y)
    assert float(loss1) < float(loss0), (loss0, loss1)
    amp_mod.uninit()


def test_overflow_skips_step_and_halves_scale():
    params = _toy_params(jax.random.PRNGKey(0))
    opt = FusedSGD(lr=0.1, momentum=0.9)
    st = amp.initialize(params, opt, opt_level="O2", verbosity=0)
    bad_grads = jax.tree_util.tree_map(
        lambda p: jnp.full(p.shape, jnp.inf, p.dtype), st.model_params)
    scale_before = float(st.loss_scale)
    st2 = amp.frontend.amp_step(st, bad_grads)
    # params unchanged, scale halved
    for k in st.master_params:
        np.testing.assert_array_equal(np.asarray(st2.master_params[k]),
                                      np.asarray(st.master_params[k]))
    assert float(st2.loss_scale) == scale_before / 2


def test_amp_state_dict_roundtrip():
    params = _toy_params(jax.random.PRNGKey(0))
    st = amp.initialize(params, opt_level="O2", num_losses=3, verbosity=0)
    st = st._replace(scalers=tuple(
        sc.update(s, jnp.asarray(False)) for s in st.scalers))
    d = amp.state_dict(st)
    assert len(d) == 3
    st2 = amp.initialize(params, opt_level="O2", num_losses=3, verbosity=0)
    st2 = amp.load_state_dict(st2, d)
    for a, b in zip(st.scalers, st2.scalers):
        assert float(a.loss_scale) == float(b.loss_scale)


def test_multiple_losses_independent_scalers():
    """test_multiple_models_optimizers_losses.py analog: per-loss_id scalers
    evolve independently."""
    params = _toy_params(jax.random.PRNGKey(0))
    opt = FusedAdam(lr=1e-3)
    st = amp.initialize(params, opt, opt_level="O2", num_losses=2, verbosity=0)
    good = jax.tree_util.tree_map(jnp.ones_like, st.model_params)
    bad = jax.tree_util.tree_map(
        lambda p: jnp.full(p.shape, jnp.nan, p.dtype), st.model_params)
    st = amp.frontend.amp_step(st, good, loss_id=0)
    st = amp.frontend.amp_step(st, bad, loss_id=1)
    assert float(st.scalers[0].loss_scale) == 2.0 ** 16
    assert float(st.scalers[1].loss_scale) == 2.0 ** 15


# -- legacy handle API (handle.py:170-252, opt.py:9-103) ---------------------

def test_legacy_amp_handle_flow():
    from apex_tpu.amp import init_handle, NoOpHandle
    import numpy as np

    h = init_handle(loss_scale="dynamic")
    s0 = h.loss_scale
    loss = jnp.float32(2.0)
    assert float(h.scale_loss(loss)) == 2.0 * s0
    g = {"w": jnp.ones((4,)) * s0}
    g32, skip = h.unscale_and_update(g)
    assert not skip
    np.testing.assert_allclose(np.asarray(g32["w"]), 1.0)
    # overflow path: halve + skip
    bad = {"w": jnp.full((4,), jnp.inf)}
    _, skip = h.unscale_and_update(bad)
    assert skip and h.loss_scale == s0 / 2
    # state dict round trip
    h2 = init_handle()
    h2.load_state_dict(h.state_dict())
    assert h2.loss_scale == h.loss_scale

    # disabled -> NoOpHandle passthrough
    nh = init_handle(enabled=False)
    assert isinstance(nh, NoOpHandle)
    assert float(nh.scale_loss(loss)) == 2.0
    _, skip = nh.unscale_and_update(bad)
    assert not skip


def test_legacy_optim_wrapper_multi_loss():
    from apex_tpu.amp import init_handle
    from apex_tpu.optimizers import FusedSGD
    import numpy as np

    h = init_handle()
    opt = h.wrap_optimizer(FusedSGD(lr=0.1), num_loss=2)
    with pytest.raises(RuntimeError):
        h.scale_loss(jnp.float32(1.0))   # must go through the wrapper now
    s0, s1 = opt.loss_scale(0), opt.loss_scale(1)
    g0, skip0 = opt.unscale_and_update({"w": jnp.ones((4,)) * s0}, 0)
    g1, skip1 = opt.unscale_and_update(
        {"w": jnp.full((4,), jnp.inf)}, 1)
    assert not skip0 and skip1
    assert opt.loss_scale(1) == s1 / 2 and opt.loss_scale(0) >= s0
    # attribute passthrough to the wrapped optimizer
    assert opt.lr == 0.1


def test_incoming_params_must_be_fp32():
    """check_params_fp32 analog (_initialize.py:79-116): non-fp32 incoming
    params are rejected unless allow_incoming_model_not_fp32=True."""
    import pytest
    from apex_tpu.optimizers import FusedSGD
    half = {"w": jnp.ones((4, 4), jnp.bfloat16)}
    with pytest.raises(RuntimeError, match="not fp32"):
        amp.initialize(half, FusedSGD(lr=0.1), opt_level="O0", verbosity=0)
    st = amp.initialize(half, FusedSGD(lr=0.1), opt_level="O0", verbosity=0,
                        allow_incoming_model_not_fp32=True)
    # O0's preset then applies its own cast_model_type=fp32, as in the
    # reference (frontend.py O0 preset) — the hatch only skips the check
    assert st.model_params["w"].dtype == jnp.float32
    # integer leaves (e.g. step counters riding the tree) never trigger it
    mixed = {"w": jnp.ones((4, 4), jnp.float32), "steps": jnp.zeros((), jnp.int32)}
    amp.initialize(mixed, FusedSGD(lr=0.1), opt_level="O0", verbosity=0)


def test_cast_model_outputs():
    """cast_model_outputs kwarg (reference frontend.py:269, the forward
    patch's output_caster _initialize.py:185-190): floating outputs cast,
    non-floating untouched, default is a no-op; survives add_param_group."""
    p = {"w": jnp.ones((4, 4))}
    st = amp.initialize(p, FusedSGD(lr=0.1), opt_level="O5", verbosity=0,
                        cast_model_outputs=jnp.float32)
    out = {"logits": jnp.ones((2,), jnp.bfloat16),
           "ids": jnp.zeros((2,), jnp.int32), "aux_loss": 0.5}
    cast = st.cast_output(out)
    assert cast["logits"].dtype == jnp.float32
    assert cast["ids"].dtype == jnp.int32
    assert cast["aux_loss"] == 0.5          # python scalars pass through
    st2 = amp.add_param_group(st, {"w2": jnp.ones((2, 2))})
    assert st2.cast_model_outputs == jnp.float32
    # default: no-op
    st3 = amp.initialize(p, FusedSGD(lr=0.1), opt_level="O5", verbosity=0)
    assert st3.cast_output(out)["logits"].dtype == jnp.bfloat16


def test_initialize_list_of_models():
    """Reference list API (frontend.py:296-331 +
    test_multiple_models_optimizers_losses.py): lists of models AND
    optimizers return a list of independent AmpStates; list params with a
    single optimizer stay a single-model pytree."""
    mA = {"w": jnp.ones((4, 4))}
    mB = {"w": jnp.ones((2, 2)), "b": jnp.zeros((2,))}
    states = amp.initialize([mA, mB], [FusedAdam(lr=1e-3), FusedSGD(lr=0.1)],
                            opt_level="O2", verbosity=0)
    assert isinstance(states, list) and len(states) == 2
    assert states[0].model_params["w"].dtype == jnp.float16
    assert states[1].master_params["b"].dtype == jnp.float32
    # independent scalers
    bad = jax.tree_util.tree_map(
        lambda p: jnp.full_like(p, jnp.inf), states[0].master_params)
    s0 = amp.amp_step(states[0], bad)
    assert float(s0.scalers[0].loss_scale) == 2.0 ** 15
    assert float(states[1].scalers[0].loss_scale) == 2.0 ** 16

    with pytest.raises(ValueError, match="models but"):
        amp.initialize([mA, mB], [FusedAdam(lr=1e-3)], opt_level="O2",
                       verbosity=0)

    # a list pytree with ONE optimizer is a single model
    st = amp.initialize([{"w": jnp.ones((2, 2))}], FusedAdam(lr=1e-3),
                        opt_level="O0", verbosity=0)
    assert not isinstance(st, list)
