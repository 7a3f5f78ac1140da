"""Weight-update sharding for plain DDP (ISSUE 8) on the 8-device CPU
mesh.

Covers the tentpole and its acceptance gates:

  * knob resolution (``update_sharding`` arg > ``APEX_TPU_UPDATE_SHARDING``
    env > off) and the ``DistributedDataParallel.weight_update``
    factory returning None when off;
  * THE A/B: the flagship transformer trained N steps with
    ``update_sharding="zero1"`` is BITWISE-identical to the unsharded
    fp32 DDP run (allreduce + replicated fused step + amp-style
    overflow select) when the allgather is fp32, while the NEW
    ``ddp.reduce_scatter``/``ddp.param_allgather`` meters carry the
    expected logical/wire bytes and the
    ``ddp.opt_state_bytes_per_replica`` gauge proves the ~1/N
    optimizer-state shrink;
  * int8_blockscale param allgather: >=3.5x wire compression from the
    counters at tolerance-level loss;
  * amp overflow-skip semantics: a non-finite grad on ONE replica skips
    the step on ALL replicas (the flag is computed pre-scatter), even
    under a quantized reduce-scatter;
  * the sharded per-optimizer paths: elementwise (Adam/SGD/Adagrad via
    the default ``step_flat_shard``) and cross-shard (LAMB/NovoGrad
    overrides) match their unsharded flat trajectories;
  * resilience: ``collective_fail`` chaos fires through the new
    ``ddp.reduce_scatter``/``ddp.param_allgather`` entry points, and a
    TrainGuard preempt/resume mid-run with the SHARDED optimizer state
    (+ error-feedback residual) in the step carry is bitwise-identical
    to an uninterrupted run;
  * the disabled path (``update_sharding="off"``) is bitwise-identical
    to a knob-less DDP;
  * telemetry.memory: sharded ``.m``/``.v`` state slices classify as
    optimizer and ``memory_model`` reports per-replica optimizer bytes.
"""
import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex_tpu.optimizers import (FusedAdam, FusedAdagrad, FusedLAMB,
                                 FusedNovoGrad, FusedSGD)
from apex_tpu.parallel import (DistributedDataParallel, Reducer,
                               collectives, create_mesh)
from apex_tpu.parallel import weight_update as wu
from jax import shard_map
from apex_tpu.resilience import faults
from apex_tpu.telemetry import MemorySink, Registry, events
from apex_tpu.telemetry import records_violations
from apex_tpu.utils.pallas import to_varying

N_DEV = 8


@pytest.fixture(scope="module")
def mesh():
    return create_mesh({"data": N_DEV})


@pytest.fixture(autouse=True)
def _clean_hooks():
    """No leaked default registry, fault plan, or env knobs between
    tests."""
    prev_reg = events.set_default(None)
    prev_plan = faults.install(None)
    saved = {k: os.environ.pop(k, None)
             for k in (collectives.ENV_KNOB, wu.ENV_KNOB)}
    yield
    events.set_default(prev_reg)
    faults.install(prev_plan)
    for k, v in saved.items():
        os.environ.pop(k, None)
        if v is not None:
            os.environ[k] = v


# ---------------------------------------------------------------------------
# knob resolution / construction guards
# ---------------------------------------------------------------------------

def test_resolve_mode_precedence():
    assert wu.resolve_mode() == "off"            # no env: the built-in
    os.environ[wu.ENV_KNOB] = "zero1"
    assert wu.resolve_mode() == "zero1"
    assert wu.resolve_mode("off") == "off"       # explicit beats env
    os.environ[wu.ENV_KNOB] = "bogus"
    with pytest.raises(ValueError, match="update_sharding"):
        wu.resolve_mode()
    with pytest.raises(ValueError, match="update_sharding"):
        wu.resolve_mode("zero2")


def test_construction_guards():
    with pytest.raises(ValueError, match="impl='fused'"):
        wu.ShardedUpdate(FusedAdam(lr=1e-3, impl="xla"))
    with pytest.raises(ValueError, match="update_sharding"):
        DistributedDataParallel(update_sharding="zero3")
    with pytest.raises(ValueError, match="update_sharding"):
        Reducer(update_sharding="zero3")


def test_ddp_factory_off_returns_none_and_allreduce_unchanged(mesh):
    """The disabled path: weight_update() is None and the allreduce
    route is BITWISE what a knob-less DDP produces (the knob being off
    must be indistinguishable from the knob not existing)."""
    ddp_off = DistributedDataParallel(axis_name="data",
                                      update_sharding="off")
    ddp_legacy = DistributedDataParallel(axis_name="data")
    assert ddp_off.weight_update(FusedAdam(impl="fused")) is None
    assert ddp_legacy.weight_update(FusedAdam(impl="fused")) is None
    assert Reducer(axis_name="data").weight_update(
        FusedAdam(impl="fused")) is None

    def run(ddp):
        @functools.partial(shard_map, mesh=mesh, in_specs=P("data"),
                           out_specs=P("data"))
        def red(x):
            return ddp.allreduce_grads({"w": x})["w"]
        rng = np.random.RandomState(0)
        return np.asarray(red(jnp.asarray(
            rng.randn(N_DEV, 256).astype(np.float32))))

    np.testing.assert_array_equal(run(ddp_off), run(ddp_legacy))

    # env opt-in flips the factory on
    os.environ[wu.ENV_KNOB] = "zero1"
    eng = ddp_legacy.weight_update(FusedAdam(impl="fused"))
    assert isinstance(eng, wu.ShardedUpdate)
    assert Reducer(axis_name="data").weight_update(
        FusedAdam(impl="fused")) is not None


# ---------------------------------------------------------------------------
# synthetic flat-buffer fixtures
# ---------------------------------------------------------------------------

def _flat_params():
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    return {"w": 0.3 * jax.random.normal(k1, (33, 7)),
            "b": 0.1 * jax.random.normal(k2, (130,))}


def _flat_grads(i, poison=False):
    ks = jax.random.split(jax.random.PRNGKey(100 + i), 2)
    g = {"w": jax.random.normal(ks[0], (N_DEV, 33, 7)),
         "b": jax.random.normal(ks[1], (N_DEV, 130))}
    if poison:
        g = jax.tree_util.tree_map(lambda x: x.at[0].set(jnp.inf), g)
    return g


def _make_steps(mesh, opt_unsharded, sharded_update, params):
    """(jitted unsharded amp-style step, jitted sharded step, jitted
    sharded init).  The unsharded baseline is today's DDP contract:
    per-leaf allreduce, full replicated ``step_flat``, amp's
    skip-on-overflow select."""
    ddp = DistributedDataParallel(axis_name="data")
    pspec = jax.tree_util.tree_map(lambda _: P(), params)
    gspec = jax.tree_util.tree_map(lambda _: P("data"), params)
    state_u = opt_unsharded.init(params)
    uspec = jax.tree_util.tree_map(lambda _: P(), state_u)

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(uspec, gspec, pspec),
                       out_specs=(pspec, uspec))
    def step_u(state, g, p):
        g = jax.tree_util.tree_map(lambda x: x[0], g)
        g = ddp.allreduce_grads(g)
        fl = opt_unsharded.flattener_for(p)
        flat = fl.flatten(g)
        ok = jnp.all(jnp.isfinite(flat)).astype(jnp.float32)
        new_state = opt_unsharded.step_flat(state, flat)
        new_state = jax.tree_util.tree_map(
            lambda nw, old: jnp.where(ok > 0, nw, old), new_state, state)
        return fl.unflatten(new_state.master, like=p), new_state

    sspec = sharded_update.state_pspecs(params, N_DEV)

    @functools.partial(shard_map, mesh=mesh, in_specs=(pspec,),
                       out_specs=sspec)
    def init_s(p):
        return sharded_update.init(p)

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(sspec, gspec, pspec),
                       out_specs=(pspec, sspec))
    def step_s(state, g, p):
        g = jax.tree_util.tree_map(lambda x: x[0], g)
        return sharded_update.step(state, g, p)

    return jax.jit(step_u), jax.jit(step_s), jax.jit(init_s), state_u


@pytest.mark.parametrize("opt_cls", [
    FusedAdam, functools.partial(FusedSGD, momentum=0.9), FusedAdagrad,
    FusedLAMB, FusedNovoGrad,
], ids=["adam", "sgd", "adagrad", "lamb", "novograd"])
def test_sharded_matches_unsharded_flat(mesh, opt_cls):
    """Every fused optimizer's sharded path (default elementwise or the
    LAMB/NovoGrad cross-shard overrides) tracks its unsharded flat
    trajectory.  Elementwise optimizers are exact 1/N decompositions;
    LAMB/NovoGrad re-derive their cross-tensor norms via psum'd partials
    (different reduction order than the static row-range/Pallas kernels
    — tolerance-level, not bitwise)."""
    params = _flat_params()
    opt_u = opt_cls(lr=1e-2, weight_decay=0.01, impl="fused")
    su = wu.ShardedUpdate(opt_cls(lr=1e-2, weight_decay=0.01,
                                  impl="fused"), axis_name="data")
    step_u, step_s, init_s, state_u = _make_steps(mesh, opt_u, su, params)
    state_s = init_s(params)
    pu = ps = params
    for i in range(4):
        g = _flat_grads(i)
        pu, state_u = step_u(state_u, g, pu)
        ps, state_s = step_s(state_s, g, ps)
    for k in params:
        np.testing.assert_allclose(np.asarray(pu[k]), np.asarray(ps[k]),
                                   rtol=1e-5, atol=1e-6, err_msg=k)
    assert int(state_s.count) == 4


def test_sharded_adam_bitwise_and_state_shrink(mesh):
    """Elementwise sharding is an EXACT decomposition: fp32 allgather
    Adam is bitwise the unsharded run, and the per-replica sharded state
    holds ~1/N of the unsharded optimizer-state bytes (asserted from
    live shard shapes AND the new gauge)."""
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    events.set_default(reg)
    params = _flat_params()
    opt_u = FusedAdam(lr=1e-2, weight_decay=0.01, impl="fused")
    su = wu.ShardedUpdate(FusedAdam(lr=1e-2, weight_decay=0.01,
                                    impl="fused"), axis_name="data")
    step_u, step_s, init_s, state_u = _make_steps(mesh, opt_u, su, params)
    state_s = init_s(params)
    pu = ps = params
    for i in range(6):
        g = _flat_grads(i)
        pu, state_u = step_u(state_u, g, pu)
        ps, state_s = step_s(state_s, g, ps)
    for k in params:
        np.testing.assert_array_equal(np.asarray(pu[k]),
                                      np.asarray(ps[k]), err_msg=k)

    # per-replica state: each flat field holds total/N elements
    fl = su._fl(params, N_DEV)
    assert state_s.master.addressable_shards[0].data.shape == \
        (fl.total // N_DEV,)
    unsharded_bytes = sum(
        l.size * jnp.dtype(l.dtype).itemsize
        for l in jax.tree_util.tree_leaves(state_u))
    vals = reg.read()
    per_replica = vals["ddp.opt_state_bytes_per_replica"]
    assert vals["ddp.update_shard_world"] == N_DEV
    # note the unsharded baseline pads to DEFAULT_CHUNK; compare against
    # the same layout's bytes: 3 flat fields of fl.total on 1 replica
    full_flat_bytes = 3 * fl.total * 4 + 4
    assert per_replica == pytest.approx(full_flat_bytes / N_DEV, rel=0.05)
    assert unsharded_bytes >= full_flat_bytes  # default chunk pads larger


def test_gradient_predivide_factor_matches_unsharded(mesh):
    """The reference predivide semantics (divide by f before the
    reduce, multiply back f/world after) thread through the sharded
    path — DDP's knob must not go inert under update_sharding."""
    params = _flat_params()
    ddp = DistributedDataParallel(axis_name="data",
                                  gradient_predivide_factor=4.0,
                                  update_sharding="zero1")
    opt_u = FusedAdam(lr=1e-2, impl="fused")
    su = ddp.weight_update(FusedAdam(lr=1e-2, impl="fused"))
    assert su.gradient_predivide_factor == 4.0
    pspec = jax.tree_util.tree_map(lambda _: P(), params)
    gspec = jax.tree_util.tree_map(lambda _: P("data"), params)
    state_u = opt_u.init(params)
    uspec = jax.tree_util.tree_map(lambda _: P(), state_u)

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(uspec, gspec, pspec),
                       out_specs=(pspec, uspec))
    def step_u(state, g, p):
        g = jax.tree_util.tree_map(lambda x: x[0], g)
        g = ddp.allreduce_grads(g)        # carries the predivide knob
        fl = opt_u.flattener_for(p)
        flat = fl.flatten(g)
        ok = jnp.all(jnp.isfinite(flat)).astype(jnp.float32)
        new_state = opt_u.step_flat(state, flat)
        new_state = jax.tree_util.tree_map(
            lambda nw, old: jnp.where(ok > 0, nw, old), new_state, state)
        return fl.unflatten(new_state.master, like=p), new_state

    sspec = su.state_pspecs(params, N_DEV)

    @functools.partial(shard_map, mesh=mesh, in_specs=(pspec,),
                       out_specs=sspec)
    def init_s(p):
        return su.init(p)

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(sspec, gspec, pspec),
                       out_specs=(pspec, sspec))
    def step_s(state, g, p):
        g = jax.tree_util.tree_map(lambda x: x[0], g)
        return su.step(state, g, p)

    step_u = jax.jit(step_u)
    step_s = jax.jit(step_s)
    state_s = jax.jit(init_s)(params)
    pu = ps = params
    for i in range(3):
        g = _flat_grads(i)
        pu, state_u = step_u(state_u, g, pu)
        ps, state_s = step_s(state_s, g, ps)
    for k in params:
        np.testing.assert_array_equal(np.asarray(pu[k]),
                                      np.asarray(ps[k]), err_msg=k)


# ---------------------------------------------------------------------------
# amp overflow-skip: pre-scatter flag, all replicas skip identically
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scheme", [None, "int8_blockscale"])
def test_overflow_skips_all_replicas(mesh, scheme):
    """An inf in ONE replica's local grads skips the update on ALL
    replicas — bitwise no-op state and params.  With the int8 scatter
    the flag MUST come pre-scatter (quantizing an inf block destroys
    the evidence), which is exactly what the implementation does."""
    params = _flat_params()
    su = wu.ShardedUpdate(FusedAdam(lr=1e-2, impl="fused"),
                          axis_name="data", collective_scheme=scheme)
    _, step_s, init_s, _ = _make_steps(
        mesh, FusedAdam(lr=1e-2, impl="fused"), su, params)
    state0 = init_s(params)
    m0 = np.asarray(state0.master)
    p1, state1 = step_s(state0, _flat_grads(0, poison=True), params)
    assert int(state1.count) == 0              # skipped step not counted
    np.testing.assert_array_equal(np.asarray(state1.master), m0)
    for k in params:
        np.testing.assert_array_equal(
            np.asarray(p1[k], np.float32),
            np.asarray(params[k], np.float32), err_msg=k)
    # and a clean step afterwards applies
    p2, state2 = step_s(state1, _flat_grads(1), params)
    assert int(state2.count) == 1
    assert np.abs(np.asarray(state2.master) - m0).max() > 0


def test_overflow_reverts_residual(mesh):
    """A skipped step must also revert the error-feedback residual (its
    quantization error was never applied) — the ZeRO/PR-7 contract."""
    params = _flat_params()
    su = wu.ShardedUpdate(FusedAdam(lr=1e-2, impl="fused"),
                          axis_name="data",
                          collective_scheme="int8_blockscale:min_bytes=0")
    pspec = jax.tree_util.tree_map(lambda _: P(), params)
    gspec = jax.tree_util.tree_map(lambda _: P("data"), params)
    sspec = su.state_pspecs(params, N_DEV)

    @functools.partial(shard_map, mesh=mesh, in_specs=(pspec,),
                       out_specs=(sspec, P("data")))
    def init_s(p):
        return su.init(p), su.init_residual(p)[None]

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(sspec, gspec, pspec, P("data")),
                       out_specs=(pspec, sspec, P("data")))
    def step_s(state, g, p, res):
        g = jax.tree_util.tree_map(lambda x: x[0], g)
        p2, s2, r2 = su.step(state, g, p, residual=res[0])
        return p2, s2, r2[None]

    state, res = jax.jit(init_s)(params)
    step = jax.jit(step_s)
    _, state1, res1 = step(state, _flat_grads(0), params, res)
    assert float(jnp.abs(res1).max()) > 0          # EF residual is live
    _, state2, res2 = step(state1, _flat_grads(1, poison=True), params,
                           res1)
    assert int(state2.count) == 1
    np.testing.assert_array_equal(np.asarray(res2), np.asarray(res1))
    np.testing.assert_array_equal(np.asarray(state2.master),
                                  np.asarray(state1.master))


# ---------------------------------------------------------------------------
# chaos: collective_fail through the new entry points
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw", [
    {"collective_scheme": "int8_blockscale:min_bytes=0"},
    {"allgather_scheme": "int8_blockscale"},
], ids=["reduce_scatter", "param_allgather"])
def test_collective_fail_fires_through_sharded_paths(mesh, kw):
    faults.install(faults.parse("collective_fail@0"))
    params = _flat_params()
    su = wu.ShardedUpdate(FusedAdam(lr=1e-2, impl="fused"),
                          axis_name="data", **kw)
    _, step_s, init_s, _ = _make_steps(
        mesh, FusedAdam(lr=1e-2, impl="fused"), su, params)
    state = init_s(params)
    with pytest.raises(faults.CollectiveFault):
        step_s(state, _flat_grads(0), params)
    # the fault is consumed: the replay traces clean
    faults.install(None)
    p1, _ = step_s(state, _flat_grads(0), params)
    assert all(np.isfinite(np.asarray(l)).all()
               for l in jax.tree_util.tree_leaves(p1))


# ---------------------------------------------------------------------------
# THE A/B: flagship transformer, off vs zero1 (+ quantized allgather)
# ---------------------------------------------------------------------------

def _tiny_cfg():
    from apex_tpu.models import TransformerConfig
    return TransformerConfig(vocab_size=64, max_len=16, num_layers=1,
                             d_model=32, num_heads=2, d_ff=64,
                             dtype=jnp.float32)


def _make_batch(step):
    rng = np.random.RandomState(1000 + step)
    return jnp.asarray(rng.randint(0, 64, (N_DEV, 16)).astype("int32"))


def _transformer_fns(mesh, *, sharded, rs_scheme=None, ag_scheme=None,
                     residual=False):
    """(init_state, jitted step) for the flagship transformer under DDP
    + FusedAdam(impl='fused').  ``sharded=False`` is today's path:
    per-leaf allreduce + replicated ``step_flat`` + amp's overflow
    select.  ``sharded=True`` routes through ``ShardedUpdate``.  Params
    stay replicated; grads are taken wrt a pcast-varying copy so the
    collectives actually run (wrt replicated params the cotangent rule
    pre-sums them)."""
    from apex_tpu.models import transformer_init, transformer_loss
    cfg = _tiny_cfg()
    # placed on the mesh up front: jit traces again when an argument's
    # sharding changes, and a first step fed default-device arrays
    # would be traced (and its trace-time meters counted) twice
    params0 = jax.device_put(
        transformer_init(jax.random.PRNGKey(0), cfg),
        jax.sharding.NamedSharding(mesh, P()))
    opt = FusedAdam(lr=1e-2, impl="fused")
    pspec = jax.tree_util.tree_map(lambda _: P(), params0)

    def grads_of(params, tokens):
        pv = jax.tree_util.tree_map(
            lambda p: to_varying(p, ("data",)), params)
        return jax.value_and_grad(lambda p: transformer_loss(
            p, {"tokens": tokens, "targets": tokens}, cfg))(pv)

    if not sharded:
        ddp = DistributedDataParallel(axis_name="data")
        state0 = opt.init(params0)
        uspec = jax.tree_util.tree_map(lambda _: P(), state0)

        def body(params, state, tokens):
            loss, grads = grads_of(params, tokens)
            grads = ddp.allreduce_grads(grads)
            fl = opt.flattener_for(params)
            flat = fl.flatten(grads)
            ok = jnp.all(jnp.isfinite(flat)).astype(jnp.float32)
            new_state = opt.step_flat(state, flat)
            new_state = jax.tree_util.tree_map(
                lambda nw, old: jnp.where(ok > 0, nw, old),
                new_state, state)
            return (fl.unflatten(new_state.master, like=params),
                    new_state, jax.lax.pmean(loss, "data"))

        step = jax.jit(shard_map(
            body, mesh=mesh, in_specs=(pspec, uspec, P("data")),
            out_specs=(pspec, uspec, P())))
        return (params0, state0), step

    su = wu.ShardedUpdate(opt, axis_name="data",
                          collective_scheme=rs_scheme,
                          allgather_scheme=ag_scheme)
    sspec = su.state_pspecs(params0, N_DEV)
    if residual:
        @functools.partial(shard_map, mesh=mesh, in_specs=(pspec,),
                           out_specs=(sspec, P("data")))
        def init_s(p):
            return su.init(p), su.init_residual(p)[None]

        def body(params, state, res, tokens):
            loss, grads = grads_of(params, tokens)
            params, state, r2 = su.step(state, grads, params,
                                        residual=res[0])
            return params, state, r2[None], jax.lax.pmean(loss, "data")

        step = jax.jit(shard_map(
            body, mesh=mesh,
            in_specs=(pspec, sspec, P("data"), P("data")),
            out_specs=(pspec, sspec, P("data"), P())))
        state0, res0 = jax.jit(init_s)(params0)
        return (params0, state0, res0), step

    @functools.partial(shard_map, mesh=mesh, in_specs=(pspec,),
                       out_specs=sspec)
    def init_s(p):
        return su.init(p)

    def body(params, state, tokens):
        loss, grads = grads_of(params, tokens)
        params, state = su.step(state, grads, params)
        return params, state, jax.lax.pmean(loss, "data")

    step = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(pspec, sspec, P("data")),
        out_specs=(pspec, sspec, P())))
    return (params0, jax.jit(init_s)(params0)), step


def test_ab_flagship_transformer_zero1_bitwise_and_metered(mesh):
    """ACCEPTANCE: 6-step CPU-mesh training of the flagship transformer
    with ``update_sharding="zero1"`` (fp32 allgather) is BITWISE the
    unsharded fp32 run — params and losses — while the new meters carry
    the expected bytes and the optimizer-state gauge shrinks ~1/N."""
    (pu, su_state), step_u = _transformer_fns(mesh, sharded=False)

    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    events.set_default(reg)
    (ps, ss_state), step_s = _transformer_fns(mesh, sharded=True)

    losses_u, losses_s = [], []
    for i in range(6):
        pu, su_state, lu = step_u(pu, su_state, _make_batch(i))
        ps, ss_state, ls = step_s(ps, ss_state, _make_batch(i))
        losses_u.append(float(lu))
        losses_s.append(float(ls))

    # training happened, and zero1 is bitwise the unsharded run
    assert losses_u[-1] < losses_u[0]
    assert losses_s == losses_u
    for (kp_a, a), (kp_b, b) in zip(
            jax.tree_util.tree_leaves_with_path(pu),
            jax.tree_util.tree_leaves_with_path(ps)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(kp_a))

    # the meters: one traced program moved flat-total fp32 bytes through
    # the reduce-scatter and shard-sized fp32 bytes through the gather
    eng = wu.ShardedUpdate(FusedAdam(lr=1e-2, impl="fused"),
                           axis_name="data")
    from apex_tpu.models import transformer_init
    fl = eng._fl(transformer_init(jax.random.PRNGKey(0), _tiny_cfg()),
                 N_DEV)
    vals = reg.read()
    assert vals["ddp.reduce_scatter_bytes"] == fl.total * 4
    assert vals["ddp.reduce_scatter_compressed_bytes"] == fl.total * 4
    assert vals["ddp.param_allgather_bytes"] == fl.total // N_DEV * 4
    assert vals["ddp.param_allgather_compressed_bytes"] == \
        fl.total // N_DEV * 4
    # optimizer-state bytes per replica: ~1/N of the replicated layout
    assert vals["ddp.opt_state_bytes_per_replica"] == pytest.approx(
        (3 * fl.total * 4 + 4) / N_DEV, rel=0.05)
    recs = reg.flush()
    assert records_violations(recs) == []
    names = {r.get("name") for r in recs if r.get("kind") == "event"}
    assert {"ddp.reduce_scatter", "ddp.param_allgather"} <= names


def test_ab_int8_allgather_compresses_within_tolerance(mesh):
    """int8_blockscale param allgather: >=3.5x fewer wire bytes (from
    the ddp.param_allgather counters) at tolerance-level loss vs the
    fp32 sharded run."""
    # the fp32 comparator runs (and traces) BEFORE the registry is
    # installed, so the counters below carry ONLY the int8 run's meters
    (p32, s32), step32 = _transformer_fns(mesh, sharded=True)
    l32 = l8 = None
    for i in range(6):
        p32, s32, l32 = step32(p32, s32, _make_batch(i))
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    events.set_default(reg)
    (p8, s8), step8 = _transformer_fns(mesh, sharded=True,
                                       ag_scheme="int8_blockscale")
    for i in range(6):
        p8, s8, l8 = step8(p8, s8, _make_batch(i))
    assert abs(float(l8) - float(l32)) < 0.05 * abs(float(l32))
    vals = reg.read()
    logical = vals["ddp.param_allgather_bytes"]
    wire = vals["ddp.param_allgather_compressed_bytes"]
    assert logical / wire >= 3.5, (logical, wire)
    assert vals["ddp.param_allgather_compression_ratio"] >= 3.5


def test_env_collectives_knob_reaches_reduce_scatter_not_allgather(mesh):
    """APEX_TPU_COLLECTIVES A/Bs the gradient reduce-scatter (it IS the
    DDP gradient wire) but never implicitly quantizes the param
    allgather — the ZeRO posture."""
    os.environ[collectives.ENV_KNOB] = "int8_blockscale:min_bytes=0"
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    events.set_default(reg)
    (ps, ss), step_s = _transformer_fns(mesh, sharded=True)
    ps, ss, loss = step_s(ps, ss, _make_batch(0))
    assert np.isfinite(float(loss))
    vals = reg.read()
    assert vals["ddp.reduce_scatter_compressed_bytes"] \
        < vals["ddp.reduce_scatter_bytes"]
    assert vals["ddp.param_allgather_compressed_bytes"] \
        == vals["ddp.param_allgather_bytes"]
    recs = reg.flush()
    evs = {r["name"]: r for r in recs if r.get("kind") == "event"}
    assert evs["ddp.reduce_scatter"]["fields"]["scheme"] \
        == "int8_blockscale"
    assert evs["ddp.param_allgather"]["fields"].get("scheme") \
        != "int8_blockscale"


# ---------------------------------------------------------------------------
# resilience: guard preempt/resume with sharded state in the carry
# ---------------------------------------------------------------------------

def test_guard_preempt_resume_with_sharded_state_bitwise(mesh, tmp_path):
    """Chaos acceptance (mirror of PR 7's residual test): preempt@N +
    resume with the SHARDED optimizer state (and int8 error-feedback
    residual) in the step carry is bitwise-identical to an
    uninterrupted run — the sharded state snapshots/restores cleanly
    through TrainGuard."""
    from apex_tpu.resilience import GuardConfig, TrainGuard

    (params0, state0, res0), jstep = _transformer_fns(
        mesh, sharded=True,
        rs_scheme="int8_blockscale:min_bytes=0", residual=True)

    def step_fn(state, batch):
        params, opt_state, res = state
        params, opt_state, res, loss = jstep(params, opt_state, res,
                                             batch)
        return (params, opt_state, res), loss

    def cfg(d):
        return GuardConfig(ckpt_dir=str(d), save_every_steps=4,
                           check_every=2, backoff_seconds=0.01,
                           enabled=True)

    ref_state, rep = TrainGuard(step_fn, cfg(tmp_path / "ref")).run(
        (params0, state0, res0), _make_batch, 10)
    assert rep.status == "completed"

    plan = faults.parse("preempt@6")
    d = tmp_path / "chaos"
    _, r1 = TrainGuard(step_fn, cfg(d), plan=plan).run(
        (params0, state0, res0), _make_batch, 10)
    assert r1.status == "preempted" and r1.faults_injected == 1
    state2, r2 = TrainGuard(step_fn, cfg(d), plan=plan).run(
        (params0, state0, res0), _make_batch, 10)
    assert r2.status == "completed" and r2.resumed_from is not None

    ref_leaves = jax.tree_util.tree_leaves(ref_state)
    got_leaves = jax.tree_util.tree_leaves(state2)
    assert len(ref_leaves) == len(got_leaves)
    for a, b in zip(ref_leaves, got_leaves):
        assert np.array_equal(np.asarray(a), np.asarray(b))   # bitwise
    # the sharded optimizer state is genuinely live (steps applied)
    assert int(ref_state[1].count) == 10
    res_final = jax.tree_util.tree_leaves(ref_state[2])
    assert any(float(jnp.abs(r).max()) > 0 for r in res_final)



# ---------------------------------------------------------------------------
# telemetry.memory: sharded m/v slices classify as optimizer
# ---------------------------------------------------------------------------

def test_classifier_sharded_state_fields():
    from apex_tpu.telemetry import memory
    assert memory.classify_arg("state.m") == "optimizer"
    assert memory.classify_arg("state.v") == "optimizer"
    assert memory.classify_arg(r"state[\'m\']") == "optimizer"
    assert memory.classify_arg("opt_state.master") == "optimizer"
    # no false positives on batch-ish names
    assert memory.classify_arg("m_tokens") == "batch"
    assert memory.classify_arg("vectors") == "args"
    # a genuine model param field literally named 'm' stays params —
    # the explicit param-name keys outrank the bare terminal heuristic
    # (the quoted ['m'] form was already an optimizer key pre-PR8)
    assert memory.classify_arg("model_params.m") == "params"


def test_memory_model_per_replica_optimizer_bytes(mesh):
    """The keypath classifier + memory_model report per-replica
    optimizer bytes under sharding: the sharded ``m``/``v``/``master``
    slices classify as optimizer (not temps), and
    ``optimizer_bytes_per_replica`` divides by the shard world."""
    from apex_tpu.telemetry import memory
    params = _flat_params()
    su = wu.ShardedUpdate(FusedAdam(lr=1e-2, impl="fused"),
                          axis_name="data")
    _, step_s, init_s, _ = _make_steps(
        mesh, FusedAdam(lr=1e-2, impl="fused"), su, params)
    state = init_s(params)
    fl = su._fl(params, N_DEV)

    table = memory.memory_table(step_s, state, _flat_grads(0), params)
    opt_bytes = table["by_class"].get("optimizer", 0)
    # the SPMD-compiled entry is per-partition-shaped: the sharded
    # state.m / state.v / state.master slices (total/N fp32 each) must
    # ALL classify as optimizer — without the terminal .m/.v rule the
    # moments would land in "args" and the per-replica optimizer
    # attribution would be a third of reality
    assert opt_bytes == 3 * (fl.total // N_DEV) * 4
    model = memory.memory_model(table=table, register=False)
    assert model["optimizer_bytes"] == opt_bytes
    assert model["optimizer_bytes_per_replica"] == opt_bytes
    assert model["update_sharding_world"] == 1

    # planning form: a REPLICATED-layout table + update_sharding_world
    # models the zero1 shrink (what one replica would hold)
    opt_u = FusedAdam(lr=1e-2, impl="fused")
    state_u = opt_u.init(params)
    flu = opt_u.flattener
    table_u = memory.memory_table(
        lambda s, g: opt_u.step_flat(s, flu.flatten(g)),
        state_u, jax.tree_util.tree_map(lambda x: x[0], _flat_grads(0)))
    model_u = memory.memory_model(table=table_u, register=False,
                                  update_sharding_world=N_DEV)
    assert model_u["optimizer_bytes"] >= 3 * flu.total * 4
    assert model_u["optimizer_bytes_per_replica"] == \
        model_u["optimizer_bytes"] // N_DEV
    assert model_u["update_sharding_world"] == N_DEV
