"""Async overlap execution (PR 16) on the 8-device CPU mesh.

Covers the tentpole and its acceptance gates:

  * bucket-partition determinism: same pytree + threshold => identical
    bucket layout (and signature) across calls, abstract-vs-concrete
    trees, and separate processes — including the non-divisible last
    bucket and the single-giant-leaf overflow;
  * mode resolution (explicit > APEX_TPU_OVERLAP env > off) and the
    ``delay_allreduce=True`` explicit-deferred pin;
  * scheme gating: adasum / callable routing cannot stream — one-time
    warning, deferred fallback with identical numerics;
  * THE A/B: ``bucketed_allreduce`` is BITWISE the deferred
    ``allreduce_tree`` for fp32/legacy (incl. predivide / sum
    semantics), tolerance-parity with identical residual layout for
    int8 + error feedback, and the per-bucket meters sum to EXACTLY the
    deferred path's logical bytes;
  * the 6-step flagship A/B: ``overlap="bucketed"`` ends bitwise equal
    to the deferred run (carry AND loss);
  * guard preempt/resume mid-run with bucket EF state in the carry is
    bitwise an uninterrupted run;
  * zero1: chunked reduce-scatter + segmented allgather are bitwise the
    whole-buffer ``ShardedUpdate`` trajectory (fp32 and block-aligned
    int8 wires);
  * the planner prices every scheme's dp wire with the one overlap
    factor it is handed;
  * the measured-drop contract: a device-trace fixture decomposed by
    ``telemetry.timeline`` shows the bucketed ``exposed_comm_fraction``
    strictly below the deferred one.
"""
import functools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex_tpu.parallel import (DistributedDataParallel, create_mesh,
                               overlap)
from apex_tpu.parallel import weight_update as wu
from apex_tpu.parallel.distributed import allreduce_tree
from jax import shard_map
from apex_tpu.optimizers import FusedAdam
from apex_tpu.telemetry import MemorySink, Registry, events
from apex_tpu.utils.pallas import to_varying

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
N_DEV = 8


@pytest.fixture(scope="module")
def mesh():
    return create_mesh({"data": N_DEV})


@pytest.fixture(autouse=True)
def _clean_hooks():
    """No leaked default registry, env knob, or warn-once memory
    between tests."""
    prev_reg = events.set_default(None)
    prev_env = os.environ.pop(overlap.ENV_KNOB, None)
    overlap._WARNED.clear()
    yield
    events.set_default(prev_reg)
    os.environ.pop(overlap.ENV_KNOB, None)
    if prev_env is not None:
        os.environ[overlap.ENV_KNOB] = prev_env


# ---------------------------------------------------------------------------
# bucket partitioning — determinism
# ---------------------------------------------------------------------------

def _shape_tree():
    return {"embed": jax.ShapeDtypeStruct((64, 32), jnp.float32),
            "layers": {"w1": jax.ShapeDtypeStruct((32, 64), jnp.float32),
                       "w2": jax.ShapeDtypeStruct((64, 32), jnp.float32)},
            "head": jax.ShapeDtypeStruct((32, 64), jnp.float32)}


def test_partition_deterministic_and_exact_cover():
    """Same pytree + threshold => identical layout and signature on
    every call; the buckets partition the leaf ids exactly (each leaf
    in exactly one bucket); reverse order puts the LAST flat leaf in
    the FIRST bucket (grad-production order)."""
    a = overlap.partition_buckets(_shape_tree(), message_size=3000)
    b = overlap.partition_buckets(_shape_tree(), message_size=3000)
    assert a == b and a.signature == b.signature
    # a concrete tree with the same (path, shape, dtype) facts agrees —
    # the layout is a pure function of static facts, never of data
    concrete = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), _shape_tree())
    c = overlap.partition_buckets(concrete, message_size=3000)
    assert c.signature == a.signature and c.buckets == a.buckets
    ids = [i for bk in a.buckets for i in bk.leaf_ids]
    assert sorted(ids) == list(range(a.num_leaves))
    assert len(ids) == len(set(ids))
    assert a.buckets[0].leaf_ids[0] == a.num_leaves - 1   # reverse order
    # a different threshold is a different layout AND signature
    d = overlap.partition_buckets(_shape_tree(), message_size=100)
    assert d.signature != a.signature


def test_partition_non_divisible_last_bucket():
    """7 x 100-element leaves at threshold 250: greedy reverse fill
    closes at >=250, so the trailing remainder bucket is UNDER the
    threshold — it must still exist and carry the leftover leaves."""
    tree = {f"l{i}": jax.ShapeDtypeStruct((100,), jnp.float32)
            for i in range(7)}
    lay = overlap.partition_buckets(tree, message_size=250)
    assert [b.elems for b in lay.buckets] == [300, 300, 100]
    assert lay.buckets[-1].elems < 250


def test_partition_single_giant_leaf_overflows_its_bucket():
    """A leaf larger than ``message_size`` is atomic — it overflows its
    bucket rather than splitting, exactly the reference's semantics."""
    tree = {"a": jax.ShapeDtypeStruct((10,), jnp.float32),
            "giant": jax.ShapeDtypeStruct((1000,), jnp.float32),
            "z": jax.ShapeDtypeStruct((10,), jnp.float32)}
    lay = overlap.partition_buckets(tree, message_size=100)
    # reverse order: z(10) then giant(1000) close bucket 0; a trails
    assert [b.elems for b in lay.buckets] == [1010, 10]
    assert any("giant" in p for p in lay.buckets[0].paths)
    with pytest.raises(ValueError):
        overlap.partition_buckets(tree, message_size=0)


def test_partition_signature_matches_across_processes():
    """The rank-0 bucket-layout broadcast invariant, established
    statically: a SEPARATE process partitioning the same static facts
    computes the identical signature."""
    here = overlap.partition_buckets(_shape_tree(), message_size=3000)
    code = (
        "import jax, jax.numpy as jnp\n"
        "from apex_tpu.parallel import overlap\n"
        "tree = {'embed': jax.ShapeDtypeStruct((64, 32), jnp.float32),\n"
        "        'layers': {'w1': jax.ShapeDtypeStruct((32, 64),"
        " jnp.float32),\n"
        "                   'w2': jax.ShapeDtypeStruct((64, 32),"
        " jnp.float32)},\n"
        "        'head': jax.ShapeDtypeStruct((32, 64), jnp.float32)}\n"
        "print(overlap.partition_buckets(tree,"
        " message_size=3000).signature)\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       env={**os.environ, "JAX_PLATFORMS": "cpu",
                            "PYTHONPATH": ROOT})
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == here.signature


# ---------------------------------------------------------------------------
# mode resolution + scheme gating
# ---------------------------------------------------------------------------

def test_resolve_mode_precedence_and_validation(monkeypatch):
    assert overlap.resolve_mode(None) == "off"          # built-in
    monkeypatch.setenv(overlap.ENV_KNOB, "bucketed")
    assert overlap.resolve_mode(None) == "bucketed"     # env
    assert overlap.resolve_mode("off") == "off"         # explicit wins
    with pytest.raises(ValueError):
        overlap.resolve_mode("stream")
    with pytest.raises(ValueError):
        DistributedDataParallel(axis_name="data", overlap="nope")


def test_delay_allreduce_pins_deferred_and_warns_once():
    """``delay_allreduce=True`` is the explicit documented deferred
    path: it wins over a requested ``overlap="bucketed"`` with a
    one-time warning, and the inert-knob warning is GONE —
    ``message_size`` is live again."""
    with pytest.warns(UserWarning, match="delay_allreduce"):
        ddp = DistributedDataParallel(axis_name="data", overlap="bucketed",
                                      delay_allreduce=True)
    assert ddp.delay_allreduce is True
    assert ddp.overlap == "bucketed"
    assert ddp.message_size == 10_000_000
    # warn-once: a second identical construction stays silent
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        DistributedDataParallel(axis_name="data", overlap="bucketed",
                                delay_allreduce=True)


def test_can_stream_gating():
    assert overlap.can_stream(None) is True
    assert overlap.can_stream("fp32") is True
    assert overlap.can_stream("int8_blockscale") is True
    assert overlap.can_stream("adasum") is False
    assert overlap.can_stream(lambda path, leaf: "fp32") is False


# ---------------------------------------------------------------------------
# bucketed_allreduce parity — synthetic pytrees under shard_map
# ---------------------------------------------------------------------------

def _grad_tree(key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 4)
    return {"a": jax.random.normal(ks[0], (33, 7)),
            "b": jax.random.normal(ks[1], (130,)),
            "c": {"w": jax.random.normal(ks[2], (64, 8)),
                  "v": jax.random.normal(ks[3], (5,))}}


def _run_reduce(mesh, fn):
    """Run ``fn(per_device_grads)`` under shard_map over stacked
    per-device grad trees (axis 'data' varying)."""
    g = _grad_tree()
    stacked = jax.tree_util.tree_map(
        lambda x: jnp.stack([x * (1.0 + 0.1 * d) for d in range(N_DEV)]),
        g)
    spec = jax.tree_util.tree_map(lambda _: P("data"), g)

    def body(gd):
        gd = jax.tree_util.tree_map(lambda x: x[0], gd)
        out = fn(gd)
        return jax.tree_util.tree_map(lambda x: x[None], out)

    return jax.jit(shard_map(body, mesh=mesh, in_specs=(spec,),
                             out_specs=spec))(stacked)


@pytest.mark.parametrize("kw", [
    dict(),
    dict(average=False),
    dict(predivide_factor=4.0),
    dict(always_fp32=True),
], ids=["avg", "sum", "predivide", "always_fp32"])
def test_bucketed_bitwise_fp32_legacy(mesh, kw):
    """fp32/legacy bucketing is BITWISE the deferred per-leaf path under
    every scaling variant — psum is elementwise and concatenation
    commutes with it."""
    ref = _run_reduce(mesh, lambda g: allreduce_tree(
        g, axis_name="data", **kw))
    got = _run_reduce(mesh, lambda g: overlap.bucketed_allreduce(
        g, axis_name="data", message_size=500, **kw))
    for r, o in zip(jax.tree_util.tree_leaves(ref),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(o))


def test_bucketed_meter_sums_to_deferred_logical_bytes(mesh):
    """ACCEPTANCE: the per-bucket ``record_collective`` calls sum to
    EXACTLY the deferred path's logical bytes (bucketing re-chunks the
    wire, never changes what is reduced)."""
    def metered(fn):
        reg = Registry(sink=MemorySink(), flush_interval=0,
                       rank0_only=False)
        prev = events.set_default(reg)
        try:
            _run_reduce(mesh, fn)
        finally:
            events.set_default(prev)
        vals = reg.read()
        return vals.get("ddp.allreduce_bytes"), vals.get(
            "ddp.allreduce_calls")

    ref_bytes, ref_calls = metered(
        lambda g: allreduce_tree(g, axis_name="data"))
    got_bytes, got_calls = metered(
        lambda g: overlap.bucketed_allreduce(g, axis_name="data",
                                             message_size=500))
    assert got_bytes == ref_bytes > 0
    # deferred meters ONE record for the whole tree; bucketed meters one
    # per bucket — and the per-bucket records sum to the same logical
    # bytes
    n_buckets = len(overlap.partition_buckets(
        _grad_tree(), message_size=500).buckets)
    assert ref_calls == 1
    assert got_calls == n_buckets > 1


def test_bucketed_int8_ef_tolerance_and_residual_layout(mesh):
    """int8 + error feedback: bucketed matches deferred to tolerance
    (blocks span bucket buffers, not leaves), the residual pytree keeps
    the deferred path's grad-shaped layout, and EF is genuinely active."""
    g0 = _grad_tree()
    res0 = jax.tree_util.tree_map(lambda x: jnp.zeros_like(x), g0)

    def run(fn):
        stacked = jax.tree_util.tree_map(
            lambda x: jnp.stack([x * (1.0 + 0.1 * d)
                                 for d in range(N_DEV)]), g0)
        rstacked = jax.tree_util.tree_map(
            lambda x: jnp.stack([x] * N_DEV), res0)
        spec = jax.tree_util.tree_map(lambda _: P("data"), g0)

        def body(gd, rd):
            gd = jax.tree_util.tree_map(lambda x: x[0], gd)
            rd = jax.tree_util.tree_map(lambda x: x[0], rd)
            out, new_res = fn(gd, rd)
            return (jax.tree_util.tree_map(lambda x: x[None], out),
                    jax.tree_util.tree_map(lambda x: x[None], new_res))

        return jax.jit(shard_map(body, mesh=mesh, in_specs=(spec, spec),
                                 out_specs=(spec, spec)))(stacked, rstacked)

    spec8 = "int8_blockscale:block=32,min_bytes=0"
    ref, ref_res = run(lambda g, r: allreduce_tree(
        g, axis_name="data", scheme=spec8, residuals=r))
    got, got_res = run(lambda g, r: overlap.bucketed_allreduce(
        g, axis_name="data", scheme=spec8, residuals=r,
        message_size=500))
    assert (jax.tree_util.tree_structure(got_res)
            == jax.tree_util.tree_structure(ref_res))
    for a, b in zip(jax.tree_util.tree_leaves(ref),
                    jax.tree_util.tree_leaves(got)):
        scale = float(jnp.abs(a).max()) or 1.0
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=0.05 * scale)
    # residual layout: leaf shapes match the grads; EF active somewhere
    for rl, gl in zip(jax.tree_util.tree_leaves(got_res),
                      jax.tree_util.tree_leaves(got)):
        assert rl.shape == gl.shape
    assert any(float(jnp.abs(r).max()) > 0
               for r in jax.tree_util.tree_leaves(got_res))


def test_adasum_falls_back_deferred_with_one_warning(mesh):
    """A scheme that cannot stream per-bucket (adasum's pairwise tree
    needs the full grad set) warns ONCE and runs the deferred path —
    numerics identical to an explicit deferred adasum reduction."""
    ddp = DistributedDataParallel(axis_name="data",
                                  collective_scheme="adasum",
                                  overlap="bucketed")
    with pytest.warns(UserWarning, match="cannot stream"):
        got = _run_reduce(mesh, ddp.allreduce_grads)
    ref = _run_reduce(mesh, lambda g: allreduce_tree(
        g, axis_name="data", scheme="adasum"))
    for r, o in zip(jax.tree_util.tree_leaves(ref),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(o))
    # the raising contract behind the gate stays enforced
    with pytest.raises(ValueError, match="cannot stream"):
        _run_reduce(mesh, lambda g: overlap.bucketed_allreduce(
            g, axis_name="data", scheme="adasum"))


# ---------------------------------------------------------------------------
# flagship A/B + guard preempt/resume
# ---------------------------------------------------------------------------

def test_flagship_6step_ab_bitwise(mesh):
    """ACCEPTANCE: the 6-step CPU-mesh flagship A/B — carry AND loss of
    the ``overlap="bucketed"`` run are BITWISE the deferred run's (fp32
    scheme)."""
    from apex_tpu.parallel import plan as planmod
    cfg = planmod._flagship_cfg(False)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(
        0, cfg.vocab_size, (8, cfg.max_len)).astype("int32"))

    def run(ddp_kwargs):
        carry, step = planmod.build_flagship_step(
            cfg, mesh, global_batch=8, ddp_kwargs=ddp_kwargs)
        loss = None
        for _ in range(6):
            carry, loss = step(carry, tokens)
        return carry, float(loss)

    carry_off, loss_off = run({"overlap": "off"})
    carry_b, loss_b = run({"overlap": "bucketed",
                           "message_size": 20_000})
    assert loss_b == loss_off
    for a, b in zip(jax.tree_util.tree_leaves(carry_off),
                    jax.tree_util.tree_leaves(carry_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _tiny_cfg():
    from apex_tpu.models import TransformerConfig
    return TransformerConfig(vocab_size=64, max_len=16, num_layers=1,
                             d_model=32, num_heads=2, d_ff=64,
                             dtype=jnp.float32)


def _make_batch(step):
    rng = np.random.RandomState(1000 + step)
    return jnp.asarray(rng.randint(0, 64, (N_DEV, 16)).astype("int32"))


def _bucketed_train_fns(mesh):
    """(init_state, jitted step) for the tiny transformer under
    bucketed int8 DDP — the EF residual (bucket state) rides the step
    carry, the layout TrainGuard snapshots."""
    from apex_tpu.models import transformer_init, transformer_loss
    cfg = _tiny_cfg()
    params0 = transformer_init(jax.random.PRNGKey(0), cfg)
    ddp = DistributedDataParallel(axis_name="data",
                                  collective_scheme="int8_blockscale",
                                  collective_min_bytes=256,
                                  overlap="bucketed", message_size=2000)
    res0 = jax.tree_util.tree_map(
        lambda p: jnp.zeros((N_DEV,) + jnp.shape(p), jnp.float32),
        params0)
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


def test_guard_preempt_resume_bucketed_bitwise(mesh, tmp_path):
    """ACCEPTANCE: a guard preempt@6 / resume with the per-bucket EF
    residual state in the carry ends BITWISE an uninterrupted bucketed
    run — bucketing changes the collective schedule, never the
    checkpoint/restore contract."""
    from apex_tpu.resilience import GuardConfig, TrainGuard, faults

    (params0, res0), jstep = _bucketed_train_fns(mesh)

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
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the EF residual (per-bucket state) is genuinely non-trivial
    assert any(float(jnp.abs(r).max()) > 0
               for r in jax.tree_util.tree_leaves(ref_state[1]))


# ---------------------------------------------------------------------------
# zero1: chunked reduce-scatter + segmented allgather
# ---------------------------------------------------------------------------

def _flat_params():
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    return {"w": 0.3 * jax.random.normal(k1, (33, 7)),
            "b": 0.1 * jax.random.normal(k2, (130,))}


def _flat_grads(i):
    ks = jax.random.split(jax.random.PRNGKey(100 + i), 2)
    return {"w": jax.random.normal(ks[0], (N_DEV, 33, 7)),
            "b": jax.random.normal(ks[1], (N_DEV, 130))}


def _zero1_steps(mesh, su, params):
    pspec = jax.tree_util.tree_map(lambda _: P(), params)
    gspec = jax.tree_util.tree_map(lambda _: P("data"), params)
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

    return jax.jit(init_s), jax.jit(step_s)


@pytest.mark.parametrize("schemes", [
    dict(),
    dict(collective_scheme="int8_blockscale:block=32,min_bytes=0",
         allgather_scheme="int8_blockscale:block=32,min_bytes=0"),
], ids=["fp32", "int8_rs_and_ag"])
def test_zero1_bucketed_bitwise_vs_whole_buffer(mesh, schemes):
    """ACCEPTANCE: ``ShardedUpdate(overlap="bucketed")`` — chunked
    reduce-scatter and segmented param-allgather — is BITWISE the
    whole-buffer trajectory for fp32 AND for block-aligned int8 wires
    (chunk bounds on quantization-block multiples preserve every code
    and scale)."""
    params = _flat_params()

    def train(overlap_mode):
        su = wu.ShardedUpdate(FusedAdam(lr=1e-2, impl="fused"),
                              axis_name="data", overlap=overlap_mode,
                              message_size=64, **schemes)
        init_s, step_s = _zero1_steps(mesh, su, params)
        state = init_s(params)
        p = params
        for i in range(3):
            p, state = step_s(state, _flat_grads(i), p)
        return p, state

    p_off, s_off = train("off")
    p_b, s_b = train("bucketed")
    for a, b in zip(jax.tree_util.tree_leaves((p_off, s_off)),
                    jax.tree_util.tree_leaves((p_b, s_b))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_shard_chunk_bounds_contract():
    """Deterministic, aligned, covering — and honest fallbacks: a
    non-align-divisible shard or a whole-shard threshold yields ONE
    chunk (quantization blocks could not be preserved otherwise)."""
    bounds = overlap.shard_chunk_bounds(1024, 256, 128)
    assert bounds == [(0, 256), (256, 512), (512, 768), (768, 1024)]
    assert all(a % 128 == 0 for a, _ in bounds)
    assert overlap.shard_chunk_bounds(1000, 256, 128) == [(0, 1000)]
    assert overlap.shard_chunk_bounds(1024, 4096, 128) == [(0, 1024)]
    assert overlap.shard_chunk_bounds(0, 256, 128) == []
    # repeated calls agree (pure function of the three ints)
    assert bounds == overlap.shard_chunk_bounds(1024, 256, 128)


# ---------------------------------------------------------------------------
# planner: the overlap factor
# ---------------------------------------------------------------------------


def test_overlap_fraction_is_one_factor_for_every_scheme(monkeypatch):
    """The planner prices every dp wire with the ONE overlap factor it
    is handed (argument > ``APEX_TPU_OVERLAP_FRACTION`` > 1.0): an int8
    dp plan and an fp32 one expose the same share of their own modeled
    comm."""
    from apex_tpu.parallel import plan as pm
    monkeypatch.setenv(pm.ENV_OVERLAP, "0.25")
    prof = pm.ModelProfile(
        name="synth", flops=1e9, bytes_accessed=1e8, params_bytes=1 << 22,
        optimizer_bytes=3 << 22, activations_bytes=8192, batch_bytes=1024,
        temps_bytes=512, output_bytes=64, args_bytes=16,
        constants_bytes=8, peak_hbm_bytes=3e7, layers=2,
        act_layer_bytes=4096, seq=64, heads=4, platform="tpu_v5e")
    p8 = pm.predict(prof, pm.Plan(dp=N_DEV,
                                  collective_scheme="int8_blockscale"),
                    platform="tpu_v5e")
    p32 = pm.predict(prof, pm.Plan(dp=N_DEV), platform="tpu_v5e")
    for p in (p8, p32):
        assert p.breakdown["dp_comm_ms"] > 0
        assert p.breakdown["overlap_fraction"] == 0.25
        assert p.breakdown["dp_comm_exposed_ms"] == pytest.approx(
            0.25 * p.breakdown["dp_comm_ms"])
    hidden = pm.predict(prof, pm.Plan(dp=N_DEV), platform="tpu_v5e",
                        overlap_fraction=0.0)
    assert hidden.breakdown["dp_comm_exposed_ms"] == 0.0


# ---------------------------------------------------------------------------
# the measured-drop contract (device-trace fixture -> timeline)
# ---------------------------------------------------------------------------

def _write_capture(root, exposed_comm_events):
    """A jax-profiler run-dir fixture (TensorBoard plugins/profile
    layout): one device with 100ms of compute and the given comm
    events."""
    import gzip
    d = os.path.join(root, "plugins", "profile", "run_1")
    os.makedirs(d)
    events_ = [
        {"ph": "M", "name": "process_name", "pid": 10,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "X", "name": "fusion.1", "ts": 0, "dur": 100_000,
         "pid": 10, "tid": 1, "args": {}},
    ] + exposed_comm_events
    with gzip.open(os.path.join(d, "host.trace.json.gz"), "wt") as f:
        f.write(json.dumps({"traceEvents": events_}))


def test_exposed_comm_drop_fixture(tmp_path):
    """ACCEPTANCE (CPU form): deferred and bucketed device-trace
    fixtures decomposed by ``telemetry.timeline`` show the bucketed
    ``exposed_comm_fraction`` STRICTLY below the deferred one on the
    same wire.  (The real on-chip drop is a chip run's job; this pins
    the measurement.)"""
    from apex_tpu.telemetry import timeline as tl
    # deferred: 50ms of all-reduce entirely AFTER compute (all exposed)
    _write_capture(str(tmp_path / "off"), [
        {"ph": "X", "name": "all-reduce.2", "ts": 100_000, "dur": 50_000,
         "pid": 10, "tid": 1, "args": {}}])
    # bucketed: same 50ms of wire, 40ms hidden under compute
    _write_capture(str(tmp_path / "bucketed"), [
        {"ph": "X", "name": "all-reduce.2", "ts": 30_000, "dur": 40_000,
         "pid": 10, "tid": 1, "args": {}},
        {"ph": "X", "name": "all-reduce.3", "ts": 100_000, "dur": 10_000,
         "pid": 10, "tid": 1, "args": {}}])
    d_off = tl.summarize(str(tmp_path / "off"))
    d_b = tl.summarize(str(tmp_path / "bucketed"))
    f_off = d_off["totals"]["exposed_comm_fraction"]
    f_b = d_b["totals"]["exposed_comm_fraction"]
    assert f_off == 1.0
    assert f_b < f_off                    # the strict drop
    assert d_b["totals"]["comm_ms"] == d_off["totals"]["comm_ms"]
