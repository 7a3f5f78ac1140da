"""amp + fused flat engine integration: with a fused-impl optimizer the
masters live flat inside the optimizer state (no duplicate tree), and the
whole amp pipeline must match the per-leaf xla-impl trajectory exactly."""
import functools
import importlib.util
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu import amp, checkpoint
from apex_tpu.models import TransformerConfig
from apex_tpu.multi_tensor_apply import TreeFlattener
from apex_tpu.parallel import create_mesh, use_mesh
from apex_tpu.optimizers import (FusedAdam, FusedLAMB, FusedSGD,
                                 FusedNovoGrad, FusedAdagrad)


def _params():
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    return {"w": 0.3 * jax.random.normal(k1, (16, 8)),
            "bn_scale": jnp.ones((8,)),
            "b": jnp.zeros((8,))}


def _grads(i, scale):
    k = jax.random.PRNGKey(100 + i)
    return {"w": scale * jax.random.normal(k, (16, 8)),
            "bn_scale": scale * 0.01 * jnp.ones((8,)),
            "b": scale * 0.1 * jnp.ones((8,))}


@pytest.mark.parametrize("opt_level", ["O2", "O5"])
@pytest.mark.parametrize("opt_cls", [
    FusedAdam, FusedLAMB,
    functools.partial(FusedSGD, momentum=0.9),
    FusedNovoGrad, FusedAdagrad,
], ids=["adam", "lamb", "sgd", "novograd", "adagrad"])
def test_fused_flat_amp_matches_xla_amp(opt_level, opt_cls):
    params = _params()
    st_x = amp.initialize(params, opt_cls(lr=1e-2, weight_decay=0.01),
                          opt_level=opt_level, verbosity=0)
    st_f = amp.initialize(params, opt_cls(lr=1e-2, weight_decay=0.01,
                                          impl="fused"),
                          opt_level=opt_level, verbosity=0)
    # the flat path must NOT keep a master tree copy
    assert st_x.master_params is not None
    assert st_f.master_params is None
    assert st_f.opt_state.master is not None

    for i in range(4):
        s = float(st_x.loss_scale)
        st_x = amp.amp_step(st_x, _grads(i, s))
        st_f = amp.amp_step(st_f, _grads(i, float(st_f.loss_scale)))

    for k in params:
        np.testing.assert_allclose(
            np.asarray(st_x.model_params[k], np.float32),
            np.asarray(st_f.model_params[k], np.float32), atol=1e-6,
            err_msg=k)
        # model dtype policy identical on both paths
        assert st_x.model_params[k].dtype == st_f.model_params[k].dtype
    # master access helpers agree
    mx = amp.master_params(st_x)
    mf = amp.master_params(st_f)
    for a, b in zip(mx, mf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    # fp32 eval view
    ev = st_f.params_for_eval()
    assert all(l.dtype == jnp.float32
               for l in jax.tree_util.tree_leaves(ev))


def test_fused_flat_overflow_skips_and_halves():
    params = _params()
    st = amp.initialize(params, FusedAdam(lr=1e-2, impl="fused"),
                        opt_level="O2", verbosity=0)
    scale0 = float(st.loss_scale)
    master0 = np.asarray(st.opt_state.master)
    bad = jax.tree_util.tree_map(lambda x: jnp.full_like(x, jnp.inf),
                                 st.model_params)
    st2 = amp.amp_step(st, bad)
    np.testing.assert_array_equal(np.asarray(st2.opt_state.master), master0)
    assert float(st2.loss_scale) == scale0 / 2
    assert int(st2.opt_state.count) == 0      # skipped step not counted


def test_fused_flat_jits_whole_step():
    params = _params()
    st = amp.initialize(params, FusedLAMB(lr=1e-2, impl="fused"),
                        opt_level="O5", verbosity=0)
    X = jax.random.normal(jax.random.PRNGKey(1), (8, 16))

    @jax.jit
    def step(st):
        def loss_fn(p):
            h = (st.cast_input(X) @ p["w"]).astype(jnp.float32)
            return amp.scale_loss(jnp.mean(h ** 2), st), None
        g, _ = jax.grad(loss_fn, has_aux=True)(st.model_params)
        return amp.amp_step(st, g)

    l0 = None
    for _ in range(5):
        st = step(st)
    assert np.isfinite(np.asarray(st.opt_state.master)).all()
    assert int(st.opt_state.count) == 5


def test_o3_fused_no_flat_masters_and_fp32_eval():
    """master_weights=False levels (O3) with a fused optimizer must NOT
    activate the flat-master path, and params_for_eval stays fp32."""
    params = _params()
    st = amp.initialize(params, FusedAdam(lr=1e-2, impl="fused"),
                        opt_level="O3", verbosity=0)
    from apex_tpu.amp.frontend import _flat_masters_active
    assert not _flat_masters_active(st)
    ev = st.params_for_eval()
    assert all(l.dtype == jnp.float32
               for l in jax.tree_util.tree_leaves(ev))
    # and stepping still works through the generic path
    st2 = amp.amp_step(st, _grads(0, float(st.loss_scale)))
    assert int(st2.opt_state.count) == 1


def test_shared_optimizer_across_two_amp_states():
    """One fused optimizer object reused for two differently-shaped models:
    each state's step must use ITS OWN packing plan (regression for the
    stale cached-flattener hazard)."""
    opt = FusedAdam(lr=1e-2, impl="fused")
    pA = {"w": jnp.ones((16, 8)) * 0.2}
    pB = {"w": jnp.ones((4, 4)) * 0.1, "b": jnp.zeros((4,))}
    stA = amp.initialize(pA, opt, opt_level="O2", verbosity=0)
    stB = amp.initialize(pB, opt, opt_level="O2", verbosity=0)  # re-keys

    gA = {"w": jnp.full((16, 8), 0.5) * stA.loss_scale}
    stA2 = amp.amp_step(stA, gA)           # must re-key back to A's plan
    assert stA2.model_params["w"].shape == (16, 8)
    gB = {"w": jnp.full((4, 4), 0.5) * stB.loss_scale,
          "b": jnp.ones((4,)) * stB.loss_scale}
    stB2 = amp.amp_step(stB, gB)
    assert stB2.model_params["b"].shape == (4,)
    # numerics match dedicated optimizers
    ded = amp.initialize(pA, FusedAdam(lr=1e-2, impl="fused"),
                         opt_level="O2", verbosity=0)
    ded2 = amp.amp_step(ded, gA)
    np.testing.assert_allclose(
        np.asarray(stA2.model_params["w"], np.float32),
        np.asarray(ded2.model_params["w"], np.float32), atol=1e-6)


# ---------------------------------------------------------------------------
# LAMB leaf by leaf (what a replicated update runs) against the flat engine
# (what a sharded update slices): ONE mathematics, two layouts
# ---------------------------------------------------------------------------

def _lamb_tree():
    """Stacked, 2-D, vector and scalar leaves: a tensor is a LEAF on both
    paths — the stacked (3, 16, 24) leaf has one trust ratio, not three."""
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    return {"stacked": 0.2 * jax.random.normal(ks[0], (3, 16, 24)),
            "matrix": 0.3 * jax.random.normal(ks[1], (16, 8)),
            "vector": 0.5 + 0.1 * jax.random.normal(ks[2], (24,)),
            "scalar": jnp.asarray(0.7, jnp.float32)}


def _lamb_grads(params, step, size):
    ks = jax.random.split(jax.random.PRNGKey(50 + step), len(params))
    return {name: (size * jax.random.normal(k, p.shape)).astype(p.dtype)
            for k, (name, p) in zip(ks, sorted(params.items()))}


def _ref_trust_ratios(masters, grads, opt):
    """``‖p‖ / ‖u‖`` a leaf of the FIRST step (m = v = 0), in float64."""
    g = {k: np.asarray(v, np.float64) for k, v in grads.items()}
    gnorm = np.sqrt(sum((x * x).sum() for x in g.values()))
    clip = 1.0 / max(1.0, gnorm / opt.max_grad_norm)
    ratios, directions = {}, {}
    for name, p in masters.items():
        p = np.asarray(p, np.float64)
        x = g[name] * clip
        m, v = (1 - opt.beta1) * x, (1 - opt.beta2) * x * x
        u = (m / (1 - opt.beta1)) / (np.sqrt(v / (1 - opt.beta2)) + opt.eps) \
            + opt.weight_decay * p
        ratios[name] = np.sqrt((p * p).sum()) / np.sqrt((u * u).sum())
        directions[name] = u
    return ratios, directions


@pytest.mark.parametrize("case", ["clip_engaged", "clip_idle",
                                  "state_dtype_bf16", "use_nvlamb",
                                  "skipped_step"])
def test_lamb_leafwise_matches_flat(case):
    """Three steps through ``amp.amp_step`` under O5 (bfloat16 gradients,
    float32 masters): the per-leaf path and the flat one agree on the
    masters to 1e-6 of a leaf's size, take one trust ratio a LEAF, clip by
    the global norm or leave the gradients be, store narrow moments alike,
    and a step skipped under a dynamic scale leaves either state bit for
    bit."""
    kw = dict(lr=1e-2, weight_decay=0.01, max_grad_norm=1.0)
    size = 1e-3 if case == "clip_idle" else 0.5     # ‖g‖ ≈ 0.04 or 19
    if case == "state_dtype_bf16":
        kw["state_dtype"] = jnp.bfloat16
    if case == "use_nvlamb":
        kw.update(weight_decay=0.0, use_nvlamb=True)
    amp_kw = dict(opt_level="O5", verbosity=0)
    if case == "skipped_step":
        amp_kw["loss_scale"] = "dynamic"
    params = _lamb_tree()
    leaf = amp.initialize(params, FusedLAMB(impl="xla", **kw), **amp_kw)
    flat = amp.initialize(params, FusedLAMB(impl="fused", **kw), **amp_kw)
    assert leaf.master_params is not None and leaf.opt_state.master is None
    assert flat.master_params is None and flat.opt_state.master.ndim == 1
    if case == "state_dtype_bf16":
        for st in (leaf, flat):
            assert {x.dtype for x in jax.tree_util.tree_leaves(
                (st.opt_state.m, st.opt_state.v))} == {jnp.dtype(jnp.bfloat16)}
    step = jax.jit(amp.amp_step)

    masters = lambda st: dict(zip(sorted(params), amp.master_params(st)))
    for i in range(3):
        scale = float(leaf.loss_scale)
        g = _lamb_grads(leaf.model_params, i, size * scale)
        if case == "skipped_step" and i == 1:
            g["matrix"] = g["matrix"].at[3, 2].set(jnp.inf)
            before = [jax.tree_util.tree_map(np.asarray, (
                st.opt_state, st.master_params, st.model_params))
                for st in (leaf, flat)]
        if i == 0:
            unscaled = {k: np.asarray(v, np.float64) / scale
                        for k, v in g.items()}
            want, u = _ref_trust_ratios(masters(leaf), unscaled,
                                        leaf.optimizer)
            clip_on = np.sqrt(sum((x * x).sum()
                                  for x in unscaled.values())) > 1.0
            assert clip_on == (case != "clip_idle")
            old = masters(leaf)
        leaf, flat = step(leaf, g), step(flat, g)
        if i == 0 and case not in ("state_dtype_bf16",):
            # Δp = lr · ratio · u: the ratio each path took, a leaf
            for st in (leaf, flat):
                for name, new in masters(st).items():
                    dp = np.asarray(old[name], np.float64) - np.asarray(new)
                    took = np.sqrt((dp * dp).sum()) / (
                        kw["lr"] * np.sqrt((u[name] ** 2).sum()))
                    assert took == pytest.approx(want[name], rel=2e-3), name
        if case == "skipped_step" and i == 1:
            for st, was in zip((leaf, flat), before):
                now = jax.tree_util.tree_map(np.asarray, (
                    st.opt_state, st.master_params, st.model_params))
                for a, b in zip(jax.tree_util.tree_leaves(now),
                                jax.tree_util.tree_leaves(was)):
                    np.testing.assert_array_equal(a, b)
                assert float(st.loss_scale) == scale / 2
    steps = 2 if case == "skipped_step" else 3
    assert int(leaf.opt_state.count) == int(flat.opt_state.count) == steps
    for (name, a), b in zip(masters(leaf).items(), masters(flat).values()):
        a, b = np.asarray(a), np.asarray(b)
        assert np.abs(a - b).max() <= 1e-6 * np.abs(a).max(), name
    for a, b in zip(jax.tree_util.tree_leaves(leaf.model_params),
                    jax.tree_util.tree_leaves(flat.model_params)):
        assert a.dtype == b.dtype == jnp.bfloat16
        # a master 1e-6 apart may round to the next bfloat16
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), rtol=2 ** -7)


def test_run_standard_packs_no_flat_gradient():
    """The example's step as the benchmark's cells run it: the state holds
    trees shaped like the parameters and no flat buffer, and the lowered
    step packs nothing under ``apex.opt_update`` — the flat engine's
    ``concatenate`` of the ravelled leaves is what cost a relayout a leaf
    on the TPU (PERF.md section 6, PR 37)."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    spec = importlib.util.spec_from_file_location(
        "pretrain_for_update_path",
        os.path.join(root, "examples", "bert", "pretrain.py"))
    pretrain = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pretrain)
    args = pretrain.parse_args(["--batch-size", "2", "--seq-len", "16"])
    cfg = TransformerConfig(vocab_size=128, max_len=16, num_layers=2,
                            d_model=32, num_heads=2, d_ff=64,
                            dtype=jnp.bfloat16)
    mesh = create_mesh({"data": 1}, devices=jax.devices()[:1])
    tokens, targets, weights = pretrain.synthetic_mlm(
        np.random.RandomState(0), 2, 16, cfg.vocab_size)
    batch = {"tokens": tokens, "targets": targets, "weights": weights}
    with use_mesh(mesh):
        state, step = pretrain.run_standard(args, cfg, mesh)
        text = step.trace(state, batch).lower().as_text(debug_info=True)
        state, loss = step(state, batch)
    assert state.opt_state.master is None and state.optimizer.impl == "xla"
    assert jax.tree_util.tree_structure(state.master_params) \
        == jax.tree_util.tree_structure(state.opt_state.m) \
        == jax.tree_util.tree_structure(state.model_params)
    assert {x.dtype for x in jax.tree_util.tree_leaves(
        (state.master_params, state.opt_state.m, state.opt_state.v))} \
        == {jnp.dtype(jnp.float32)}
    under_update = re.findall(r'loc\("([^"]*apex\.opt_update[^"]*)"', text)
    assert under_update and np.isfinite(float(loss))
    packing = [p for p in under_update
               if p.rsplit("/", 1)[-1] in ("concatenate",
                                           "dynamic_update_slice")]
    assert not packing, packing[:3]


def test_a_flat_checkpoint_is_refused_by_name_and_converts():
    """A checkpoint of a flat state does not restore into a per-leaf one:
    the refusal names the conversion, and the conversion it names gives the
    trees the per-leaf state holds."""
    params = _lamb_tree()
    kw = dict(opt_level="O5", verbosity=0)
    flat = amp.initialize(params, FusedLAMB(impl="fused"), **kw)
    leaf = amp.initialize(params, FusedLAMB(impl="xla"), **kw)
    g = _lamb_grads(leaf.model_params, 0, 0.5)
    flat, leaf = amp.amp_step(flat, g), amp.amp_step(leaf, g)
    saved = jax.tree_util.tree_map(np.asarray, flat.opt_state)
    with pytest.raises(ValueError, match=r"TreeFlattener\(params\).unflatten"):
        checkpoint.restore_like(leaf.opt_state, saved)
    fl = TreeFlattener(params)
    for field, want in (("master", leaf.master_params),
                        ("m", leaf.opt_state.m), ("v", leaf.opt_state.v)):
        got = fl.unflatten(jnp.asarray(getattr(saved, field)),
                           dtype=jnp.float32)
        for name in params:
            np.testing.assert_allclose(got[name], want[name], rtol=2e-6,
                                       atol=1e-9, err_msg=f"{field} {name}")


@pytest.mark.parametrize("case", ["guard_flat_into_leafwise",
                                  "guard_leafwise_into_flat",
                                  "guard_changed_model",
                                  "restore_like_changed_model"])
def test_the_conversion_is_named_only_between_flat_and_per_leaf(case):
    """Both restore paths name the flat / per-leaf conversion where exactly
    one side holds a flat state; a mismatch of another kind — a model that
    gained a leaf — is refused without being sent towards it."""
    from apex_tpu.resilience.guard import TrainGuard
    params = _lamb_tree()
    kw = dict(opt_level="O5", verbosity=0)
    flat = amp.initialize(params, FusedLAMB(impl="fused"), **kw)
    leaf = amp.initialize(params, FusedLAMB(impl="xla"), **kw)
    grown = amp.initialize(dict(params, extra=jnp.ones((4, 4))),
                           FusedLAMB(impl="xla"), **kw)

    def payload(state):
        return {"step": 0, "leaves": [np.asarray(x) for x in
                                      jax.tree_util.tree_leaves(state)]}

    named = r"TreeFlattener\(params\).unflatten"
    if case == "restore_like_changed_model":
        saved = jax.tree_util.tree_map(np.asarray, grown.opt_state)
        with pytest.raises(ValueError) as err:
            checkpoint.restore_like(leaf.opt_state, saved)
    else:
        live, saved = {"guard_flat_into_leafwise": (leaf, flat),
                       "guard_leafwise_into_flat": (flat, leaf),
                       "guard_changed_model": (leaf, grown)}[case]
        with pytest.raises(checkpoint.CheckpointError) as err:
            TrainGuard._restore(None, live, payload(saved))
    assert bool(re.search(named, str(err.value))) == ("changed" not in case)


def test_amp_larc_o2_keeps_gradients_below_float16():
    """amp O2 + LARC: the wrapper sees UNSCALED float32 gradients, as the
    reference's does — a gradient of 1e-8 (below float16's smallest
    subnormal, kept alive by the 2**16 loss scale) moves the masters exactly
    as the float32 step does, and is not flushed on the way to the inner
    optimizer."""
    from apex_tpu.parallel.LARC import LARC
    params = {"w": jnp.full((8, 16), 0.5, jnp.float32),
              "b": jnp.full((16,), 0.25, jnp.float32)}
    opt = LARC(FusedSGD(lr=0.1, momentum=0.0), trust_coefficient=0.02,
               clip=False)
    state = amp.initialize(params, opt, opt_level="O2", loss_scale=2.0 ** 16,
                           verbosity=0)
    true = jax.tree_util.tree_map(
        lambda p: jnp.full(p.shape, 1e-8, jnp.float32), params)
    scaled = jax.tree_util.tree_map(
        lambda g: (g * 2.0 ** 16).astype(jnp.float16), true)
    assert float(jnp.asarray(1e-8, jnp.float16)) == 0.0
    new = amp.amp_step(state, scaled)
    unscaled = jax.tree_util.tree_map(
        lambda g: g.astype(jnp.float32) / 2.0 ** 16, scaled)
    want, _ = opt.step(opt.init(params), unscaled, params)
    for name in params:
        moved = np.abs(np.asarray(new.master_params[name] - params[name]))
        assert moved.min() > 0, name
        np.testing.assert_allclose(new.master_params[name], want[name],
                                   rtol=1e-7, atol=0, err_msg=name)
