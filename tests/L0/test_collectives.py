"""Compressed + adaptive collectives (ISSUE 7) on the 8-device CPU mesh.

Covers the tentpole and its acceptance gates:

  * scheme registry / spec grammar / env knob / per-bucket routing;
  * block-scaled int8 quantization bounds and the >=3.5x wire-byte win,
    asserted via the NEW ``ddp.allreduce_compressed_bytes`` counters;
  * error feedback provably tightens vs naive quantization;
  * Adasum pairwise-merge properties vs a numpy oracle;
  * THE A/B: the flagship transformer trained on the CPU mesh with
    ``int8_blockscale`` stays within tolerance of the fp32 run while
    moving >=3.5x fewer wire bytes, per-bucket through the DDP Reducer;
  * ZeRO: compressed reduce-scatter (+ error-feedback residual,
    overflow-revert) and compressed allgather through
    ``DistributedFusedAdam``;
  * resilience: ``collective_fail`` chaos fires through the quantized
    and adasum entry points, and a TrainGuard preempt/resume mid-run
    with residual state in the step carry is bitwise-identical.
"""
import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.parallel import (DistributedDataParallel, Reducer,
                               collectives, create_mesh)
from apex_tpu.parallel.distributed import allreduce_tree
from jax import shard_map
from apex_tpu.contrib.optimizers import DistributedFusedAdam
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
    """No leaked default registry, fault plan, or env knob between
    tests."""
    prev_reg = events.set_default(None)
    prev_plan = faults.install(None)
    prev_env = os.environ.pop(collectives.ENV_KNOB, None)
    yield
    events.set_default(prev_reg)
    faults.install(prev_plan)
    os.environ.pop(collectives.ENV_KNOB, None)   # drop test-set values
    if prev_env is not None:
        os.environ[collectives.ENV_KNOB] = prev_env


# ---------------------------------------------------------------------------
# registry / spec / primitives
# ---------------------------------------------------------------------------

def test_registry_names_and_spec_grammar():
    assert set(collectives.available()) >= {"fp32", "bf16",
                                            "int8_blockscale", "adasum"}
    spec = collectives.parse_spec("int8_blockscale:block=64,min_bytes=99")
    assert spec == collectives.CollectiveSpec("int8_blockscale", 64, 99)
    assert collectives.parse_spec("adasum").scheme == "adasum"
    with pytest.raises(collectives.CollectiveError):
        collectives.parse_spec("no_such_scheme")
    with pytest.raises(collectives.CollectiveError):
        collectives.parse_spec("fp32:bogus=1")
    with pytest.raises(collectives.CollectiveError):
        collectives.get_scheme("no_such_scheme")
    # resolve precedence: explicit beats env
    os.environ[collectives.ENV_KNOB] = "bf16"
    assert collectives.resolve("adasum").scheme == "adasum"
    assert collectives.resolve(None).scheme == "bf16"
    os.environ[collectives.ENV_KNOB] = "off"
    assert collectives.resolve(None) is None


def test_wire_bytes_accounting():
    n = 1 << 16
    assert collectives.wire_bytes("fp32", n) == 4 * n
    assert collectives.wire_bytes("bf16", n) == 2 * n
    assert collectives.wire_bytes("adasum", n) == 4 * n
    int8 = collectives.wire_bytes("int8_blockscale", n)
    # 1 B/elem + one fp32 scale per 128-block: >=3.5x under fp32
    assert 4 * n / int8 >= 3.5
    # padding: a partial block still ships whole
    assert collectives.wire_bytes("int8_blockscale", 130, 128) \
        == 2 * 128 + 2 * 4


def test_quantize_roundtrip_error_bound():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(1000).astype(np.float32) * 3.0)
    q, scales = collectives.quantize_blockscale(x, 128)
    assert q.dtype == jnp.int8 and q.shape == (8, 128)
    back = collectives.dequantize_blockscale(q, scales, 1000)
    err = np.abs(np.asarray(back) - np.asarray(x))
    # error <= half a quantization step per block (scale = amax/127)
    bound = np.repeat(np.asarray(scales), 128)[:1000] * 0.5 + 1e-7
    assert (err <= bound).all()
    # all-zero blocks quantize/dequantize to exact zeros
    qz, sz = collectives.quantize_blockscale(jnp.zeros((256,)), 128)
    assert float(jnp.abs(collectives.dequantize_blockscale(
        qz, sz, 256)).max()) == 0.0


def test_adasum_pair_properties():
    rng = np.random.RandomState(1)
    g = jnp.asarray(rng.randn(64).astype(np.float32))
    # parallel gradients -> the mean (a drop-in for averaging)
    np.testing.assert_allclose(np.asarray(collectives.adasum_pair(g, g)),
                               np.asarray(g), rtol=1e-6)
    # orthogonal gradients -> the sum
    a = jnp.asarray([1.0, 0.0]); b = jnp.asarray([0.0, 2.0])
    np.testing.assert_allclose(np.asarray(collectives.adasum_pair(a, b)),
                               [1.0, 2.0], rtol=1e-6)
    # zero-norm side falls back to plain addition
    z = jnp.zeros(2)
    np.testing.assert_allclose(np.asarray(collectives.adasum_pair(a, z)),
                               np.asarray(a), rtol=1e-6)


def _adasum_oracle(stack):
    """Numpy replica of the pairwise tree (same pairing order)."""
    vals = [stack[i].astype(np.float64) for i in range(stack.shape[0])]

    def pair(a, b):
        dot = float(np.vdot(a, b))
        na = float(np.vdot(a, a)); nb = float(np.vdot(b, b))
        ca = 1.0 - dot / (2 * na) if na > 0 else 1.0
        cb = 1.0 - dot / (2 * nb) if nb > 0 else 1.0
        return ca * a + cb * b
    while len(vals) > 1:
        nxt = [pair(vals[i], vals[i + 1])
               for i in range(0, len(vals) - 1, 2)]
        if len(vals) % 2:
            nxt.append(vals[-1])
        vals = nxt
    return vals[0]


def test_adasum_mesh_matches_numpy_oracle(mesh):
    rng = np.random.RandomState(2)
    g = rng.randn(N_DEV, 96).astype(np.float32)

    @functools.partial(shard_map, mesh=mesh, in_specs=P("data"),
                       out_specs=P("data"))
    def red(x):
        return allreduce_tree({"w": x}, scheme="adasum:min_bytes=0")["w"]

    out = np.asarray(red(jnp.asarray(g)))
    expect = _adasum_oracle(g)
    # every device holds the same merged result
    for i in range(N_DEV):
        np.testing.assert_allclose(out[i], expect, rtol=1e-4, atol=1e-5)


def test_custom_scheme_registration(mesh):
    """The pluggability surface: a registered custom scheme routes
    through the same per-bucket selection as the built-ins."""
    info = collectives.SchemeInfo(
        name="_test_negate",
        reduce=lambda x, ax, blk, res: (-jax.lax.psum(x, ax), None),
        wire_bytes=lambda n, b: 4 * n)
    collectives.register_scheme(info)
    try:
        @functools.partial(shard_map, mesh=mesh, in_specs=P("data"),
                           out_specs=P("data"))
        def red(x):
            return allreduce_tree({"w": x}, scheme="_test_negate:min_bytes=0",
                                  average=False)["w"]

        out = red(jnp.ones(N_DEV, jnp.float32))
        np.testing.assert_allclose(np.asarray(out), -8.0)
    finally:
        collectives._REGISTRY.pop("_test_negate")


# ---------------------------------------------------------------------------
# allreduce_tree: schemes, thresholds, metering
# ---------------------------------------------------------------------------

def test_int8_allreduce_close_to_psum(mesh):
    rng = np.random.RandomState(3)
    g = rng.randn(N_DEV, 1024).astype(np.float32)

    def run(scheme):
        @functools.partial(shard_map, mesh=mesh, in_specs=P("data"),
                           out_specs=P("data"))
        def red(x):
            return allreduce_tree({"w": x}, scheme=scheme)["w"]
        return np.asarray(red(jnp.asarray(g)))

    ref = run(None)
    o8 = run("int8_blockscale:min_bytes=0")
    ob = run("bf16:min_bytes=0")
    of = run("fp32")
    np.testing.assert_allclose(of, ref, rtol=1e-6)
    # int8 block-scaled: error bounded by the block quantization step
    assert np.abs(o8 - ref).max() < 0.02 * np.abs(ref).max() + 1e-3
    assert np.abs(ob - ref).max() < 0.05 * np.abs(ref).max() + 1e-2


def test_small_leaves_stay_fp32_and_meter_wire_bytes(mesh):
    """Per-bucket threshold + the NEW compressed-bytes counters: the
    big leaf compresses, the small one stays fp32, and the counters
    carry the exact logical/wire split."""
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    events.set_default(reg)

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P("data"), P("data")),
                       out_specs=(P("data"), P("data")))
    def red(big, small):
        out = allreduce_tree(
            {"big": big, "small": small},
            scheme="int8_blockscale:min_bytes=1024")
        return out["big"], out["small"]

    red(jnp.ones((N_DEV, 4096), jnp.float32),
        jnp.ones((N_DEV, 8), jnp.float32))
    vals = reg.read()
    logical = (4096 + 8) * 4
    wire = collectives.wire_bytes("int8_blockscale", 4096) + 8 * 4
    assert vals["ddp.allreduce_bytes"] == logical
    assert vals["ddp.allreduce_compressed_bytes"] == wire
    assert vals["ddp.allreduce_compression_ratio"] == pytest.approx(
        logical / wire)
    assert logical / wire >= 3.5
    recs = reg.flush()
    ev = [r for r in recs if r.get("name") == "ddp.allreduce"][0]
    assert ev["fields"]["wire_bytes"] == wire
    assert ev["fields"]["scheme"] == "int8_blockscale"
    assert ev["fields"]["dtype"] == "mixed"     # int8 big + fp32 small
    assert records_violations(recs) == []


def test_env_knob_selects_scheme(mesh):
    """APEX_TPU_COLLECTIVES compresses a scheme-less allreduce_tree
    call (the A/B-in-one-process knob)."""
    os.environ[collectives.ENV_KNOB] = "int8_blockscale:min_bytes=0"
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    events.set_default(reg)

    @functools.partial(shard_map, mesh=mesh, in_specs=P("data"),
                       out_specs=P("data"))
    def red(x):
        return allreduce_tree({"w": x})["w"]

    red(jnp.ones((N_DEV, 512), jnp.float32))
    vals = reg.read()
    assert vals["ddp.allreduce_compressed_bytes"] \
        < vals["ddp.allreduce_bytes"]


def test_per_leaf_callable_routing(mesh):
    """scheme=callable(path, leaf) routes buckets individually."""
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    events.set_default(reg)

    def route(path, leaf):
        return "int8_blockscale:min_bytes=0" if "quantme" in path else None

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P("data"), P("data")),
                       out_specs=(P("data"), P("data")))
    def red(a, b):
        out = allreduce_tree({"quantme": a, "keep": b}, scheme=route)
        return out["quantme"], out["keep"]

    red(jnp.ones((N_DEV, 256), jnp.float32),
        jnp.ones((N_DEV, 256), jnp.float32))
    vals = reg.read()
    wire = collectives.wire_bytes("int8_blockscale", 256) + 256 * 4
    assert vals["ddp.allreduce_bytes"] == 2 * 256 * 4
    assert vals["ddp.allreduce_compressed_bytes"] == wire


@pytest.mark.slow   # ~21s: a 12-round constant-grad A/B; the int8+EF
# training path stays in tier-1 via test_ab_flagship_transformer_int8_
# within_tolerance (ISSUE 12 budget reclaim)
def test_error_feedback_tightens_vs_naive(mesh):
    """With a CONSTANT gradient, naive quantization repeats the same
    bias every step; error feedback carries the residual so the running
    mean converges to the true mean — the EF acceptance gate."""
    rng = np.random.RandomState(4)
    g = rng.randn(N_DEV, 512).astype(np.float32)
    true_mean = g.mean(axis=0)
    spec = "int8_blockscale:min_bytes=0"

    @functools.partial(shard_map, mesh=mesh, in_specs=P("data"),
                       out_specs=P("data"))
    def naive(x):
        return allreduce_tree({"w": x}, scheme=spec)["w"]

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P("data"), P("data")),
                       out_specs=(P("data"), P("data")))
    def ef(x, r):
        out, nr = allreduce_tree({"w": x}, scheme=spec,
                                 residuals={"w": r})
        return out["w"], nr["w"]

    K = 12
    gj = jnp.asarray(g)
    acc_naive = np.zeros_like(true_mean)
    acc_ef = np.zeros_like(true_mean)
    r = jnp.zeros((N_DEV, 512), jnp.float32)
    for _ in range(K):
        acc_naive += np.asarray(naive(gj))[0]
        out, r = ef(gj, r)
        acc_ef += np.asarray(out)[0]
    err_naive = np.abs(acc_naive / K - true_mean).max()
    err_ef = np.abs(acc_ef / K - true_mean).max()
    assert err_naive > 0
    # EF must beat naive decisively, not within noise
    assert err_ef < 0.5 * err_naive, (err_ef, err_naive)


def test_reducer_threads_scheme(mesh):
    red = Reducer(axis_name="data", collective_scheme="bf16",
                  collective_min_bytes=0)

    @functools.partial(shard_map, mesh=mesh, in_specs=P("data"),
                       out_specs=P("data"))
    def run(x):
        return red.reduce({"w": x})["w"]

    out = run(jnp.full((N_DEV, 16), 2.0, jnp.float32))
    np.testing.assert_allclose(np.asarray(out), 2.0, rtol=1e-2)


def test_noop_outside_mesh_with_residuals():
    ddp = DistributedDataParallel(axis_name="data",
                                  collective_scheme="int8_blockscale")
    g = {"w": jnp.ones((4,))}
    r = ddp.init_residuals(g)
    out, nr = ddp.allreduce_grads(g, residuals=r)
    np.testing.assert_allclose(np.asarray(out["w"]), 1.0)
    assert nr is r


# ---------------------------------------------------------------------------
# chaos: collective_fail through the new entry points
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scheme", ["int8_blockscale", "adasum"])
def test_collective_fail_fires_through_schemes(mesh, scheme):
    faults.install(faults.parse("collective_fail@0"))

    @functools.partial(shard_map, mesh=mesh, in_specs=P("data"),
                       out_specs=P("data"))
    def red(x):
        return allreduce_tree({"w": x},
                              scheme=f"{scheme}:min_bytes=0")["w"]

    with pytest.raises(faults.CollectiveFault):
        red(jnp.ones((N_DEV, 256), jnp.float32))
    # the fault is consumed: the replay traces clean
    faults.install(None)
    out = red(jnp.ones((N_DEV, 256), jnp.float32))
    assert np.isfinite(np.asarray(out)).all()


def test_collective_fail_fires_through_zero_paths():
    faults.install(faults.parse("collective_fail@0x2"))
    opt = DistributedFusedAdam(lr=1e-2, collective_scheme="int8_blockscale")
    params = {"w": jnp.ones((256,), jnp.float32)}
    mesh8 = Mesh(np.array(jax.devices()[:8]), ("data",))

    @functools.partial(shard_map, mesh=mesh8,
                       in_specs=({"w": P()},), out_specs=opt.state_pspecs())
    def init_fn(p):
        return opt.init(p)

    @functools.partial(shard_map, mesh=mesh8,
                       in_specs=(opt.state_pspecs(), {"w": P()},
                                 {"w": P()}),
                       out_specs=({"w": P()}, opt.state_pspecs()))
    def step_fn(state, g, p):
        return opt.step(state, g, p)

    state = jax.jit(init_fn)(params)
    with pytest.raises(faults.CollectiveFault):
        jax.jit(step_fn)(state, {"w": jnp.ones((256,))}, params)


# ---------------------------------------------------------------------------
# THE A/B: flagship transformer on the CPU mesh, int8 vs fp32
# ---------------------------------------------------------------------------

def _tiny_cfg():
    from apex_tpu.models import TransformerConfig
    return TransformerConfig(vocab_size=64, max_len=16, num_layers=1,
                             d_model=32, num_heads=2, d_ff=64,
                             dtype=jnp.float32)


def _make_batch(step):
    rng = np.random.RandomState(1000 + step)
    return jnp.asarray(rng.randint(0, 64, (N_DEV, 16)).astype("int32"))


def _transformer_train_fns(mesh, scheme, min_bytes=256):
    """(init_state, jitted step(params, res, tokens) ->
    (params, res, loss)) for the flagship transformer under DDP with
    ``scheme``.  Params stay replicated; grads are taken wrt a
    pcast-varying copy so the reduction actually runs (wrt replicated
    params the cotangent rule pre-sums them and no collective fires);
    the per-device residual rides a stacked leading axis."""
    from apex_tpu.models import transformer_init, transformer_loss
    cfg = _tiny_cfg()
    params0 = transformer_init(jax.random.PRNGKey(0), cfg)
    ddp = DistributedDataParallel(axis_name="data",
                                  collective_scheme=scheme,
                                  collective_min_bytes=min_bytes)
    res0 = jax.tree_util.tree_map(
        lambda p: jnp.zeros((N_DEV,) + jnp.shape(p), jnp.float32), params0)
    pspec = jax.tree_util.tree_map(lambda _: P(), params0)
    rspec = jax.tree_util.tree_map(lambda _: P("data"), params0)

    def body(params, res, tokens):
        res = jax.tree_util.tree_map(lambda r: r[0], res)
        pv = jax.tree_util.tree_map(
            lambda p: to_varying(p, ("data",)), params)
        loss, grads = jax.value_and_grad(lambda p: transformer_loss(
            p, {"tokens": tokens, "targets": tokens}, cfg))(pv)
        grads, res = ddp.allreduce_grads(grads, residuals=res)
        new_params = jax.tree_util.tree_map(
            lambda p, g: p - 0.05 * g, params, grads)
        return (new_params,
                jax.tree_util.tree_map(lambda r: r[None], res),
                jax.lax.pmean(loss, "data"))

    step = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(pspec, rspec, P("data")),
        out_specs=(pspec, rspec, P())))
    return (params0, res0), step


def test_ab_flagship_transformer_int8_within_tolerance(mesh):
    """ACCEPTANCE: N-step CPU-mesh training of the flagship transformer
    with int8_blockscale + error feedback tracks the fp32 run's loss,
    while the compressed-bytes counters prove >=3.5x fewer wire
    bytes."""
    def train(scheme):
        reg = Registry(sink=MemorySink(), flush_interval=0,
                       rank0_only=False)
        prev = events.set_default(reg)
        try:
            (params, res), step = _transformer_train_fns(mesh, scheme)
            losses = []
            for i in range(6):
                params, res, loss = step(params, res, _make_batch(i))
                losses.append(float(loss))
        finally:
            events.set_default(prev)
        vals = reg.read()
        return losses, (vals.get("ddp.allreduce_bytes") or 0,
                        vals.get("ddp.allreduce_compressed_bytes") or 0)

    losses32, (log32, wire32) = train(None)
    losses8, (log8, wire8) = train("int8_blockscale")
    # training happened, and the quantized run tracks fp32
    assert losses32[-1] < losses32[0]
    assert losses8[-1] < losses8[0]
    assert abs(losses8[-1] - losses32[-1]) < 0.05 * abs(losses32[-1]), (
        losses8, losses32)
    # wire-byte proof from the counters: fp32 shipped logical bytes,
    # int8 shipped >=3.5x less
    assert log32 == wire32 > 0
    assert log8 == log32          # same logical payload either way
    assert wire32 / wire8 >= 3.5, (wire32, wire8)


def test_guard_preempt_resume_with_residual_bitwise(mesh, tmp_path):
    """Resilience acceptance: the error-feedback residual rides the
    guard's step-state snapshot — a preempt/resume mid-run ends
    bitwise-identical to an uninterrupted run."""
    from apex_tpu.resilience import GuardConfig, TrainGuard

    (params0, res0), jstep = _transformer_train_fns(
        mesh, "int8_blockscale")

    def step_fn(state, batch):
        params, res = state
        params, res, loss = jstep(params, res, batch)
        return (params, res), loss

    def cfg(d):
        return GuardConfig(ckpt_dir=str(d), save_every_steps=4,
                           check_every=2, backoff_seconds=0.01,
                           enabled=True)

    ref_state, rep = TrainGuard(step_fn, cfg(tmp_path / "ref")).run(
        (params0, res0), _make_batch, 10)
    assert rep.status == "completed"

    plan = faults.parse("preempt@6")
    d = tmp_path / "chaos"
    _, r1 = TrainGuard(step_fn, cfg(d), plan=plan).run(
        (params0, res0), _make_batch, 10)
    assert r1.status == "preempted" and r1.faults_injected == 1
    state2, r2 = TrainGuard(step_fn, cfg(d), plan=plan).run(
        (params0, res0), _make_batch, 10)
    assert r2.status == "completed" and r2.resumed_from is not None

    ref_leaves = jax.tree_util.tree_leaves(ref_state)
    got_leaves = jax.tree_util.tree_leaves(state2)
    assert len(ref_leaves) == len(got_leaves)
    for a, b in zip(ref_leaves, got_leaves):
        assert np.array_equal(np.asarray(a), np.asarray(b))   # bitwise
    # the residual state is genuinely non-trivial (EF is active)
    res_leaves = jax.tree_util.tree_leaves(ref_state[1])
    assert any(float(jnp.abs(r).max()) > 0 for r in res_leaves)


# ---------------------------------------------------------------------------
# ZeRO: compressed reduce-scatter / allgather
# ---------------------------------------------------------------------------

SHAPES = [(33, 7), (128,), (3, 5, 11), (257,)]


def _zero_params(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), len(SHAPES))
    return {f"p{i}": jax.random.normal(k, s) * 0.5
            for i, (k, s) in enumerate(zip(ks, SHAPES))}


def _zero_grads(seed, n_dev=N_DEV):
    ks = jax.random.split(jax.random.PRNGKey(seed + 100), len(SHAPES))
    return {f"p{i}": jax.random.normal(k, (n_dev,) + s)
            for i, (k, s) in enumerate(zip(ks, SHAPES))}


def _run_zero(opt, params, iters=3, residual=False, poison_iter=None):
    mesh = Mesh(np.array(jax.devices()[:N_DEV]), ("data",))
    pspec = jax.tree_util.tree_map(lambda _: P(), params)
    gspec = jax.tree_util.tree_map(lambda _: P("data"), params)
    sspec = opt.state_pspecs()

    @functools.partial(shard_map, mesh=mesh, in_specs=(pspec,),
                       out_specs=sspec)
    def init_fn(p):
        return opt.init(p)

    @functools.partial(shard_map, mesh=mesh, in_specs=(pspec,),
                       out_specs=P("data"))
    def init_res(p):
        return opt.init_residual(p)[None]

    if residual:
        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(sspec, gspec, pspec, P("data")),
                           out_specs=(pspec, sspec, P("data")))
        def step_fn(state, gl, p, res):
            gl = jax.tree_util.tree_map(lambda g: g[0], gl)
            p2, s2, r2 = opt.step(state, gl, p, residual=res[0])
            return p2, s2, r2[None]
    else:
        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(sspec, gspec, pspec),
                           out_specs=(pspec, sspec))
        def step_fn(state, gl, p):
            gl = jax.tree_util.tree_map(lambda g: g[0], gl)
            return opt.step(state, gl, p)

    state = jax.jit(init_fn)(params)
    res = jax.jit(init_res)(params) if residual else None
    step = jax.jit(step_fn)
    p = params
    for i in range(iters):
        gl = _zero_grads(i)
        if poison_iter is not None and i == poison_iter:
            gl = jax.tree_util.tree_map(
                lambda g: g.at[0].set(jnp.inf), gl)
        if residual:
            p, state, res = step(state, gl, p, res)
        else:
            p, state = step(state, gl, p)
    return p, state, res


def test_zero_int8_reduce_scatter_tracks_fp32():
    params = _zero_params()
    p32, _, _ = _run_zero(DistributedFusedAdam(lr=1e-2), params)
    p8, _, res = _run_zero(
        DistributedFusedAdam(lr=1e-2,
                             collective_scheme="int8_blockscale"),
        params, residual=True)
    for k in p32:
        np.testing.assert_allclose(np.asarray(p32[k]), np.asarray(p8[k]),
                                   atol=3e-2, err_msg=k)
    assert float(jnp.abs(res).max()) > 0      # EF residual is live


def test_zero_adasum_runs_and_stays_finite():
    params = _zero_params()
    pa, state, _ = _run_zero(
        DistributedFusedAdam(lr=1e-2, collective_scheme="adasum"), params)
    for k in pa:
        assert np.isfinite(np.asarray(pa[k])).all()
    assert float(state.gnorm) > 0


def test_zero_allgather_schemes():
    params = _zero_params()
    # "bf16" spec must match the legacy bf16_allgather knob exactly
    p_a, _, _ = _run_zero(
        DistributedFusedAdam(lr=1e-2, bf16_allgather=True), params,
        iters=2)
    p_b, _, _ = _run_zero(
        DistributedFusedAdam(lr=1e-2, allgather_scheme="bf16"), params,
        iters=2)
    for k in p_a:
        np.testing.assert_allclose(np.asarray(p_a[k]), np.asarray(p_b[k]),
                                   atol=0, err_msg=k)
    # int8 allgather: block-quantized params stay near the fp32 gather
    p32, _, _ = _run_zero(DistributedFusedAdam(lr=1e-2), params, iters=2)
    p8, _, _ = _run_zero(
        DistributedFusedAdam(lr=1e-2,
                             allgather_scheme="int8_blockscale"),
        params, iters=2)
    for k in p32:
        np.testing.assert_allclose(np.asarray(p32[k]), np.asarray(p8[k]),
                                   atol=2e-2, err_msg=k)
    # adasum has no allgather meaning
    with pytest.raises(ValueError, match="reduction rule"):
        _run_zero(DistributedFusedAdam(lr=1e-2,
                                       allgather_scheme="adasum"),
                  params, iters=1)


def test_zero_env_knob_reaches_reduce_scatter_not_allgather():
    """APEX_TPU_COLLECTIVES A/Bs the ZeRO gradient reduce-scatter, but
    never implicitly flips the param allgather (quantizing params is a
    deliberate accuracy trade, constructor-arg only — and an ambient
    adasum knob must not crash the gather)."""
    os.environ[collectives.ENV_KNOB] = "adasum"
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    events.set_default(reg)
    params = _zero_params()
    pa, _, _ = _run_zero(DistributedFusedAdam(lr=1e-2), params, iters=1)
    for k in pa:
        assert np.isfinite(np.asarray(pa[k])).all()
    recs = reg.flush()
    evs = {r["name"]: r for r in recs if r.get("kind") == "event"}
    assert evs["zero.reduce_scatter"]["fields"]["scheme"] == "adasum"
    assert evs["zero.allgather"]["fields"].get("scheme") != "adasum"


def test_zero_overflow_reverts_residual():
    """An inf grad skips the step on ALL devices — and must also revert
    the error-feedback residual (the skipped step's quantization error
    was never applied)."""
    params = _zero_params()
    opt = DistributedFusedAdam(lr=1e-2,
                               collective_scheme="int8_blockscale")
    p1, s1, r1 = _run_zero(opt, params, iters=1, residual=True)
    p2, s2, r2 = _run_zero(opt, params, iters=2, residual=True,
                           poison_iter=1)
    assert int(s2.count) == 1
    for k in p1:
        np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(p2[k]),
                                   atol=0, err_msg=k)
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r2), atol=0)


def test_zero_collectives_metered():
    """The ZeRO reduce-scatter/allgather report through
    record_collective (op=), landing in the zero.* counters and the
    summary's folded collective line."""
    from apex_tpu.telemetry import report as treport
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    events.set_default(reg)
    params = _zero_params()
    _run_zero(DistributedFusedAdam(lr=1e-2,
                                   collective_scheme="int8_blockscale"),
              params, iters=1)
    vals = reg.read()
    assert vals["zero.reduce_scatter_calls"] >= 1
    assert 0 < vals["zero.reduce_scatter_compressed_bytes"] \
        < vals["zero.reduce_scatter_bytes"]
    assert vals["zero.allgather_bytes"] > 0
    recs = reg.flush()
    assert records_violations(recs) == []
    s = treport.summarize(recs)
    assert s["collective_bytes"] > s["collective_wire_bytes"] > 0
    line = treport.format_summary(s)
    assert "logical" in line and "wire" in line


def test_report_summary_uncompressed_line_unchanged():
    """A run with no compression keeps the classic collective line."""
    from apex_tpu.telemetry import report as treport
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    reg.counter("ddp.allreduce_bytes").add(100)
    reg.counter("ddp.allreduce_compressed_bytes").add(100)
    reg.counter("ddp.allreduce_calls").add(1)
    s = treport.summarize(reg.flush())
    assert s["collective_bytes"] == s["collective_wire_bytes"] == 100
    out = treport.format_summary(s)
    assert "collective bytes    100 (1 calls)" in out
