"""apex_tpu.elastic (ISSUE 11): topology-adaptive resume across chip
counts on the 8-device CPU mesh.

Covers the tentpole and its acceptance gates:

  * reshard determinism in isolation: N-way -> canonical-flat -> M-way
    -> canonical-flat round-trips BITWISE for several (N, M) pairs
    including non-divisible ones, and EF-residual re-slicing preserves
    the residual sum;
  * MANIFEST meta: world size / plan knobs / flat-shard layout recorded
    by the guard, surfaced by ``load_latest(with_meta=True)``; a
    pre-elastic manifest degrades to same-world-only with a typed
    ``ManifestCompatWarning``, never a KeyError;
  * ``resize@N:M`` in the fault grammar: one-shot like preempt,
    ``skip_until`` honored, target world in ``GuardReport.resize_to``;
  * the latent-hazard fix: an 8-way manifest resumed 4-way WITHOUT
    elastic raises the typed ``WorldSizeMismatchError`` naming both
    counts — loud, not garbage params;
  * THE chaos proof: ``resize@6:4`` kills an 8-way flagship run
    mid-epoch (zero1 update sharding + int8 EF residuals in the step
    carry); the 4-way resume through ``apex_tpu.elastic`` finishes with
    params BITWISE-identical to a clean 4-way run started from the same
    checkpoint, while ``elastic.reshard`` / ``elastic.replan`` events
    land in the registry and ``report.summarize``'s resilience line;
  * the 4 -> 8 grow path at fp32 tolerance (the reshard is exact; the
    wider axis reorders the int8 dequant-sum of the next step);
  * ``elastic.install()`` registering the process-default resharder.
"""
import functools
import json
import os
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import apex_tpu.elastic as elastic
from apex_tpu.models import (TransformerConfig, transformer_init,
                             transformer_loss)
from apex_tpu.multi_tensor_apply.flattener import LANE, TreeFlattener
from apex_tpu.optimizers import FusedAdam
from apex_tpu.parallel import collectives, create_mesh
from apex_tpu.parallel import plan as plan_mod
from apex_tpu.parallel import weight_update as wu
from jax import shard_map
from apex_tpu.resilience import (CheckpointManager, GuardConfig,
                                 ManifestCompatWarning, TrainGuard,
                                 WorldSizeMismatchError, faults, guard)
from apex_tpu.telemetry import MemorySink, Registry, events
from apex_tpu.telemetry.report import format_summary, summarize
from apex_tpu.utils.pallas import to_varying

N_DEV = 8
GLOBAL_BATCH = 8
SEQ = 20          # pos-embed 20*32 makes `used` a non-multiple of 1024,
                  # so the 8-way and 4-way canonical totals genuinely
                  # differ (13312 vs 12800) and the re-chunk is real


@pytest.fixture(autouse=True)
def _clean_hooks():
    """No leaked resharder, fault plan, or registry."""
    prev_reg = events.set_default(None)
    prev_plan = faults.install(None)
    prev_rs = guard.set_resharder(None)
    yield
    events.set_default(prev_reg)
    faults.install(prev_plan)
    guard.set_resharder(prev_rs)


# ---------------------------------------------------------------------------
# reshard determinism in isolation (satellite: property tests)
# ---------------------------------------------------------------------------

def _leaves():
    rng = np.random.RandomState(0)
    return {"w": jnp.asarray(rng.randn(33, 7).astype(np.float32)),
            "b": jnp.asarray(rng.randn(130).astype(np.float32)),
            "s": jnp.asarray(rng.randn(1).astype(np.float32))}


@pytest.mark.parametrize("n,m", [(8, 4), (4, 8), (8, 3), (3, 8), (2, 5),
                                 (8, 8)])
def test_rechunk_roundtrip_bitwise(n, m):
    """N-way canonical flat -> M-way -> back is BITWISE: the per-leaf
    content is world-independent, only the chunk padding moves —
    including non-divisible (N, M) pairs."""
    tree = _leaves()
    fl_n = TreeFlattener(tree, chunk=LANE * n)
    fl_m = TreeFlattener(tree, chunk=LANE * m)
    used = int(fl_n.offsets[-1])
    assert used == int(fl_m.offsets[-1])      # offsets are world-free
    flat_n = np.asarray(fl_n.flatten(tree))

    flat_m = collectives.rechunk_flat(flat_n, used=used, total=fl_m.total)
    # every leaf unpacks bitwise from the re-chunked buffer
    got = fl_m.unflatten(jnp.asarray(flat_m))
    for k in tree:
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(tree[k]), err_msg=k)
    # and the round trip reproduces the N-way buffer bitwise
    back = collectives.rechunk_flat(flat_m, used=used, total=fl_n.total)
    np.testing.assert_array_equal(back, flat_n)


def test_rechunk_refuses_nonzero_tail():
    buf = np.arange(1, 9, dtype=np.float32)
    with pytest.raises(ValueError, match="nonzero data beyond"):
        collectives.rechunk_flat(buf, used=4, total=16)
    with pytest.raises(ValueError, match="exceeds"):
        collectives.rechunk_flat(buf, used=12, total=16)


@pytest.mark.parametrize("n,m", [(8, 4), (4, 8), (8, 3)])
def test_ef_residual_reslice_preserves_sum(n, m):
    """An EF residual built over the N-way canonical buffer is zero in
    the padding (all-zero blocks quantize with scale 0), so the M-way
    re-slice carries exactly the same residual mass."""
    tree = _leaves()
    fl_n = TreeFlattener(tree, chunk=LANE * n)
    fl_m = TreeFlattener(tree, chunk=LANE * m)
    used = int(fl_n.offsets[-1])
    flat = fl_n.flatten(tree)
    q, scales = collectives.quantize_blockscale(flat, 128)
    res = np.asarray(
        flat - collectives.dequantize_blockscale(q, scales, flat.shape[0]))
    assert np.abs(res).max() > 0              # the residual is live
    assert not np.any(res[used:])             # padding residual is zero
    out = collectives.rechunk_flat(res, used=used, total=fl_m.total)
    # element-identity on the used prefix (zeros elsewhere) IS sum
    # preservation; the f64 check makes it order-independent (a 24-bit
    # mantissa summed 640 times spans < 52 bits — exact in f64)
    np.testing.assert_array_equal(out[:used], res[:used])
    assert not np.any(out[used:])
    assert np.sum(out, dtype=np.float64) == np.sum(res, dtype=np.float64)


def test_layout_meta_contents():
    su = wu.ShardedUpdate(FusedAdam(lr=1e-2, impl="fused"),
                          axis_name="data")
    tree = _leaves()
    meta = su.layout_meta(tree, 8)
    fl = su._fl(tree, 8)
    assert meta["flat_total"] == fl.total and meta["chunk"] == LANE * 8
    assert meta["used"] == int(fl.offsets[-1]) <= fl.total
    per = fl.total // 8
    assert meta["shard_offsets"] == [i * per for i in range(8)]
    assert meta["kind"] == "zero1_flat" and meta["lane"] == LANE


# ---------------------------------------------------------------------------
# stage/expert lattice resharding (ISSUE 17: pp/ep resize@N:M)
# ---------------------------------------------------------------------------

def _lattice_layout(length, rows, lane=LANE):
    """Contiguous-fill row lattice for a canonical flat sequence of
    ``length`` elements: row_total rounded up to whole lanes, full rows
    then one partial tail row — padding only at the global tail (the
    layout the elastic stacked rule reproduces)."""
    per = -(-length // rows)
    row_total = -(-per // lane) * lane
    row_used = [max(min(length - i * row_total, row_total), 0)
                for i in range(rows)]
    return row_total, row_used


def _pack_lattice(flat, rows):
    """(lattice, stacked-block) — the contiguous fill IS the zero-padded
    flat reshaped row-major, so pack/unpack are shape games only."""
    flat = np.asarray(flat)
    row_total, row_used = _lattice_layout(flat.shape[0], rows)
    lat = np.zeros((rows * row_total,), flat.dtype)
    lat[:flat.shape[0]] = flat
    return lat.reshape(rows, row_total), {
        "rows": rows, "row_total": row_total, "row_used": row_used}


def _stacked_meta(world, length, block):
    return {"world_size": world,
            "layout": {"flat_total": block["rows"] * block["row_total"],
                       "used": length, "stacked": dict(block)}}


@pytest.mark.parametrize("n,m", [(2, 4), (4, 2), (2, 3), (3, 2), (8, 3)])
def test_stacked_lattice_reshard_roundtrip_bitwise(n, m):
    """Property: per-stage/per-expert flat lattices re-slice N -> M -> N
    BITWISE through the canonical-flat path, including non-divisible
    row counts (real padding on both sides of the trip)."""
    rng = np.random.RandomState(7)
    flat = rng.randn(1000).astype(np.float32)   # 1000: no lane alignment
    lat_n, blk_n = _pack_lattice(flat, n)
    lat_m_ref, blk_m = _pack_lattice(flat, m)

    tmpl_m = {"lat": jnp.zeros(lat_m_ref.shape, jnp.float32)}
    out = elastic.reshard_payload(
        tmpl_m, {"step": 1, "leaves": [lat_n]},
        _stacked_meta(n, flat.shape[0], blk_n), m)
    got = np.asarray(out["leaves"][0])
    np.testing.assert_array_equal(got, lat_m_ref)

    tmpl_n = {"lat": jnp.zeros(lat_n.shape, jnp.float32)}
    back = elastic.reshard_payload(
        tmpl_n, {"step": 1, "leaves": [got]},
        _stacked_meta(m, flat.shape[0], blk_m), n)
    np.testing.assert_array_equal(np.asarray(back["leaves"][0]), lat_n)


def test_stacked_lattice_int_row_used_and_typed_errors():
    """The scalar ``row_used`` broadcast (every row full), and the
    typed failure modes: a live lattice too small for the content is a
    model change, a nonzero tail beyond ``row_used`` is refused rather
    than silently dropped, and a ``row_used`` arity mismatch names the
    counts."""
    flat = np.arange(1, 513, dtype=np.float32)        # 512 = 4 lanes
    lat, blk = _pack_lattice(flat, 4)
    assert blk["row_used"] == [128] * 4
    meta = _stacked_meta(4, 512, blk)
    meta["layout"]["stacked"]["row_used"] = 128       # int broadcast
    tmpl = {"lat": jnp.zeros((2, 256), jnp.float32)}
    out = elastic.reshard_payload(tmpl, {"step": 0, "leaves": [lat]},
                                  meta, 2)
    np.testing.assert_array_equal(np.asarray(out["leaves"][0]).ravel(),
                                  flat)

    small = {"lat": jnp.zeros((2, 128), jnp.float32)}
    with pytest.raises(WorldSizeMismatchError, match="resize"):
        elastic.reshard_payload(small, {"step": 0, "leaves": [lat]},
                                meta, 2)
    dirty = _stacked_meta(4, 484, dict(blk, row_used=[100, 128, 128, 128]))
    with pytest.raises(WorldSizeMismatchError, match="resize"):
        elastic.reshard_payload(tmpl, {"step": 0, "leaves": [lat]},
                                dirty, 2)
    bad = _stacked_meta(4, 512, dict(blk, row_used=[128, 128]))
    with pytest.raises(WorldSizeMismatchError, match="row_used"):
        elastic.reshard_payload(tmpl, {"step": 0, "leaves": [lat]},
                                bad, 2)


# ---------------------------------------------------------------------------
# manifest meta (satellite: ckpt.py)
# ---------------------------------------------------------------------------

def test_manifest_meta_roundtrip_and_degrade(tmp_path):
    mgr = CheckpointManager(str(tmp_path), meta={"world_size": 8,
                                                 "plan": {"dp": 8}})
    mgr.save(3, {"step": 3, "leaves": [np.zeros(4, np.float32)]})
    assert mgr.manifest_meta()["world_size"] == 8
    found = mgr.load_latest(with_meta=True)
    assert found[0] == 3 and found[2]["plan"] == {"dp": 8}
    # the 2-tuple protocol is unchanged for existing callers
    assert mgr.load_latest()[0] == 3

    # a pre-elastic manifest (no meta) degrades to {} — never KeyError
    doc = json.loads((tmp_path / "MANIFEST.json").read_text())
    doc.pop("meta")
    (tmp_path / "MANIFEST.json").write_text(json.dumps(doc))
    old = CheckpointManager(str(tmp_path))
    assert old.manifest_meta() == {}
    assert old.load_latest(with_meta=True)[2] == {}


# ---------------------------------------------------------------------------
# resize fault grammar (satellite: faults.py)
# ---------------------------------------------------------------------------

def test_resize_fault_grammar():
    assert "resize" in faults.KINDS
    p = faults.parse("resize@40:4;seed=3")
    assert p.specs[0] == faults.FaultSpec(kind="resize", step=40, arg=4.0)
    with pytest.raises(faults.FaultError, match="positive integer"):
        faults.parse("resize@40")
    with pytest.raises(faults.FaultError, match="positive integer"):
        faults.parse("resize@40:0")
    # one-shot: consumed firings never re-fire
    p = faults.parse("resize@6:4")
    assert p.fire("resize", 6) is not None
    assert p.fire("resize", 6) is None
    # skip_until: like preempt, a resize at exactly the resume step
    # already fired in the interrupted run
    p = faults.parse("resize@6:4")
    p.skip_until(6)
    assert p.fire("resize", 6) is None
    p = faults.parse("resize@7:4")
    p.skip_until(6)
    assert p.fire("resize", 7) is not None    # still armed ahead


# ---------------------------------------------------------------------------
# the CPU-mesh harness: flagship transformer, zero1 + int8 EF residual
# ---------------------------------------------------------------------------

def _tiny_cfg():
    return TransformerConfig(vocab_size=64, max_len=SEQ, num_layers=1,
                             d_model=32, num_heads=2, d_ff=64,
                             dtype=jnp.float32)


def _make_batch(step):
    rng = np.random.RandomState(1000 + step)
    return jnp.asarray(
        rng.randint(0, 64, (GLOBAL_BATCH, SEQ)).astype("int32"))


def _build_harness(world):
    """(state0, step_fn, layout) for a ``world``-way zero1 + int8-EF
    DDP training step over the first ``world`` CPU devices.  The GLOBAL
    batch is fixed at 8 rows, so 8-way and 4-way runs see the same data
    stream — the elastic contract."""
    mesh = create_mesh({"data": world}, jax.devices()[:world])
    cfg = _tiny_cfg()
    params0 = transformer_init(jax.random.PRNGKey(0), cfg)
    su = wu.ShardedUpdate(FusedAdam(lr=1e-2, impl="fused"),
                          axis_name="data",
                          collective_scheme="int8_blockscale:min_bytes=0")
    pspec = jax.tree_util.tree_map(lambda _: P(), params0)
    sspec = su.state_pspecs(params0, world)

    def grads_of(params, tokens):
        pv = jax.tree_util.tree_map(
            lambda p: to_varying(p, ("data",)), params)
        return jax.value_and_grad(lambda p: transformer_loss(
            p, {"tokens": tokens, "targets": tokens}, cfg))(pv)

    @functools.partial(shard_map, mesh=mesh, in_specs=(pspec,),
                       out_specs=(sspec, P("data")))
    def init_s(p):
        return su.init(p), su.init_residual(p)[None]

    def body(params, state, res, tokens):
        loss, grads = grads_of(params, tokens)
        params, state, r2 = su.step(state, grads, params, residual=res[0])
        return params, state, r2[None], jax.lax.pmean(loss, "data")

    jstep = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(pspec, sspec, P("data"), P("data")),
        out_specs=(pspec, sspec, P("data"), P())))
    state0, res0 = jax.jit(init_s)(params0)

    def step_fn(state, batch):
        params, opt_state, res = state
        params, opt_state, res, loss = jstep(params, opt_state, res,
                                             batch)
        return (params, opt_state, res), loss

    return (params0, state0, res0), step_fn, su.layout_meta(params0, world)


@pytest.fixture(scope="module")
def harnesses():
    return {w: _build_harness(w) for w in (8, 4)}


def _gcfg(d, world, layout, **kw):
    return GuardConfig(ckpt_dir=str(d), save_every_steps=2, check_every=2,
                       backoff_seconds=0.01, enabled=True,
                       world_size=world,
                       ckpt_meta={"plan": {"dp": world},
                                  "layout": layout}, **kw)


def _import_canonical(template_state, payload, saved_world, layout):
    """The INDEPENDENT canonical-flat import the comparator run uses:
    inline numpy re-chunk + replica-0 residual collapse, no elastic
    code — what 'a clean run started from the same checkpoint' means."""
    used, tot = int(layout["used"]), int(layout["flat_total"])
    tmpl_leaves, treedef = jax.tree_util.tree_flatten(template_state)
    out = []
    for t, h in zip(tmpl_leaves, payload["leaves"]):
        h = np.asarray(h)
        if h.shape == tuple(t.shape):
            v = h
        elif h.ndim == 1 and h.shape[0] == tot:
            assert not np.any(h[used:])
            v = np.zeros((t.shape[0],), h.dtype)
            v[:used] = h[:used]
        elif h.ndim == 2 and h.shape == (saved_world, tot):
            acc = np.zeros((t.shape[1],), h.dtype)
            for row in h:
                r = np.zeros((t.shape[1],), h.dtype)
                r[:used] = row[:used]
                acc = acc + r
            v = np.zeros(tuple(t.shape), h.dtype)
            v[0] = acc
        else:
            raise AssertionError((h.shape, tuple(t.shape)))
        sh = t.sharding if isinstance(t.sharding, NamedSharding) else None
        out.append(jax.device_put(v.astype(t.dtype), sh))
    return jax.tree_util.tree_unflatten(treedef, out)


def _tiny_profile():
    """A hand-built cost-model profile (test_plan's oracle idiom) so the
    re-plan search runs without an AOT compile."""
    return plan_mod.ModelProfile(
        name="tiny", flops=1e9, bytes_accessed=1e8,
        params_bytes=1 << 20, optimizer_bytes=3 << 20,
        activations_bytes=1 << 20, batch_bytes=1 << 16,
        temps_bytes=1 << 18, output_bytes=1 << 10, platform="cpu")


# ---------------------------------------------------------------------------
# the latent-hazard fix + THE chaos proof
# ---------------------------------------------------------------------------

def test_chaos_resize_8_to_4_bitwise(harnesses, tmp_path):
    """ACCEPTANCE: resize@6:4 kills the 8-way zero1+int8-EF run
    mid-epoch; WITHOUT elastic the 4-way resume raises the typed
    WorldSizeMismatchError naming both counts; WITH elastic it
    reshards, replans, and finishes BITWISE-identical to a clean 4-way
    run started from the same checkpoint."""
    state8, step8, layout8 = harnesses[8]
    state4, step4, layout4 = harnesses[4]
    d = tmp_path / "ckpts"

    plan = faults.parse("resize@6:4")
    _, r1 = TrainGuard(step8, _gcfg(d, 8, layout8), plan=plan).run(
        state8, _make_batch, 10)
    assert r1.status == "preempted" and r1.final_step == 6
    assert r1.resize_to == 4 and r1.faults_injected == 1

    # the latent hazard, fixed: a 4-way resume of the 8-way manifest
    # without elastic is a LOUD typed error, not garbage params
    with pytest.raises(WorldSizeMismatchError,
                       match="world size 8.*world size 4") as ei:
        TrainGuard(step4, _gcfg(d, 4, layout4), plan=plan).run(
            state4, _make_batch, 10)
    assert ei.value.saved_world == 8 and ei.value.live_world == 4

    # the clean comparator: import the SAME checkpoint into 4-way
    # shapes independently and run the remaining steps plain
    ck_step, payload, meta = CheckpointManager(str(d)).load_latest(
        with_meta=True)
    assert ck_step == 6 and meta["world_size"] == 8
    assert meta["plan"] == {"dp": 8}
    assert meta["layout"]["flat_total"] == layout8["flat_total"]
    assert layout8["flat_total"] != layout4["flat_total"]   # real re-chunk
    state_b = _import_canonical(state4, payload, 8, meta["layout"])
    for i in range(ck_step, 10):
        state_b, _ = step4(state_b, _make_batch(i))

    # the elastic resume: reshard + replan + continue, metered
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    er = elastic.ElasticResume(profile=_tiny_profile())
    state_a, r2 = TrainGuard(step4, _gcfg(d, 4, layout4), plan=plan,
                             registry=reg, elastic=er).run(
        state4, _make_batch, 10)
    assert r2.status == "completed" and r2.final_step == 10
    assert r2.resumed_from == 6 and r2.resharded_from == 8

    # BITWISE: params and the full carry (opt state + EF residual)
    for a, b in zip(jax.tree_util.tree_leaves(state_a),
                    jax.tree_util.tree_leaves(state_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(state_a[1].count) == 10        # the update really ran
    assert float(jnp.abs(state_a[2]).max()) > 0   # EF residual live

    # the replan ran for the NEW chip count
    assert er.last_plan is not None and er.last_plan.chips == 4

    # events: elastic.reshard + elastic.replan through the pinned
    # registry, folded into the report's resilience line
    recs = reg.flush()
    evs = {r["name"]: r for r in recs if r.get("kind") == "event"}
    assert evs["elastic.reshard"]["fields"]["from_world"] == 8
    assert evs["elastic.reshard"]["fields"]["to_world"] == 4
    assert evs["elastic.reshard"]["fields"]["fields_resharded"] >= 4
    assert evs["elastic.replan"]["fields"]["chips"] == 4
    assert evs["elastic.replan"]["fields"]["new_knobs"]["dp"] == 4
    summary = summarize(recs)
    assert summary["reshards"] == 1 and summary["replans"] == 1
    text = format_summary(summary)
    assert "reshards 1" in text and "replans 1" in text


@pytest.mark.slow   # the grow direction re-runs both harnesses' guard
def test_grow_4_to_8_fp32_tolerance(harnesses, tmp_path):
    """The reverse path: a 4-way run resized to 8 chips resumes through
    the same reshard.  The elastic resume is BITWISE the independent
    canonical import continued 8-way (the machinery adds nothing), and
    matches the would-have-been 4-way continuation only at fp32
    tolerance — the wider axis changes which local grads each replica
    quantizes, so the int8 EF noise differs (the documented grow-path
    caveat)."""
    state8, step8, layout8 = harnesses[8]
    state4, step4, layout4 = harnesses[4]
    d = tmp_path / "grow"

    plan = faults.parse("resize@5:8")
    _, r1 = TrainGuard(step4, _gcfg(d, 4, layout4), plan=plan).run(
        state4, _make_batch, 10)
    assert r1.status == "preempted" and r1.resize_to == 8

    ck_step, payload, meta = CheckpointManager(str(d)).load_latest(
        with_meta=True)
    assert ck_step == 5 and meta["world_size"] == 4

    er = elastic.ElasticResume()
    state_a, r2 = TrainGuard(step8, _gcfg(d, 8, layout8), plan=plan,
                             elastic=er).run(state8, _make_batch, 10)
    assert r2.status == "completed" and r2.resharded_from == 4

    # (a) bitwise vs the independent 8-way canonical import
    state_c = _import_canonical(state8, payload, 4, meta["layout"])
    for i in range(ck_step, 10):
        state_c, _ = step8(state_c, _make_batch(i))
    for (kp, a), (_, c) in zip(
            jax.tree_util.tree_leaves_with_path(state_a[0]),
            jax.tree_util.tree_leaves_with_path(state_c[0])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c),
                                      err_msg=str(kp))

    # (b) tolerance vs the clean 4-way continuation: same data, same
    # math, but 8 replicas quantize different local grad buffers than
    # 4 did, so the int8+EF noise differs — the documented caveat.
    # Adam normalization amplifies that noise on near-zero params, so
    # the bound is absolute-dominated (empirically ~1e-2 after 5 steps)
    state_d = _import_canonical(state4, payload, 4, meta["layout"])
    for i in range(ck_step, 10):
        state_d, _ = step4(state_d, _make_batch(i))
    for (kp, a), (_, dd) in zip(
            jax.tree_util.tree_leaves_with_path(state_a[0]),
            jax.tree_util.tree_leaves_with_path(state_d[0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(dd),
                                   rtol=0.25, atol=2e-2,
                                   err_msg=str(kp))


def _moe_lattice_harness(rows):
    """An ep-flagship training step whose per-expert FFN weights live
    in a ``(rows, row_total)`` stacked flat lattice — the storage
    layout an expert-sharded run checkpoints, and exactly what the
    elastic stacked rule reshards across widths.  The step itself is
    world-independent (unpack -> same params -> same SGD update), so a
    resized resume must continue BITWISE."""
    from apex_tpu.models.moe_transformer import (MoETransformerConfig,
                                                 moe_transformer_init,
                                                 moe_transformer_loss)
    cfg = MoETransformerConfig(vocab_size=64, max_len=8, num_layers=1,
                               d_model=16, num_heads=2, d_ff=32,
                               num_experts=8)
    full0 = moe_transformer_init(jax.random.PRNGKey(0), cfg)
    shapes = [(l["w_in"].shape, l["w_out"].shape)
              for l in full0["layers"]]
    canon = sum(int(np.prod(si)) + int(np.prod(so))
                for si, so in shapes)
    row_total, row_used = _lattice_layout(canon, rows)

    def split(full):
        pieces, layers = [], []
        for l in full["layers"]:
            l = dict(l)
            pieces.append(l.pop("w_in").ravel())
            pieces.append(l.pop("w_out").ravel())
            layers.append(l)
        flat = jnp.concatenate(pieces)
        lat = jnp.zeros((rows * row_total,), flat.dtype)
        return ({**full, "layers": layers},
                lat.at[:canon].set(flat).reshape(rows, row_total))

    def join(dense, lat):
        flat = lat.reshape(-1)[:canon]
        off, layers = 0, []
        for l, (si, so) in zip(dense["layers"], shapes):
            ni, no = int(np.prod(si)), int(np.prod(so))
            layers.append({**l,
                           "w_in": flat[off:off + ni].reshape(si),
                           "w_out": flat[off + ni:off + ni + no]
                           .reshape(so)})
            off += ni + no
        return {**dense, "layers": layers}

    lr = 0.05

    @jax.jit
    def jstep(dense, lat, tokens):
        def loss_fn(dn, lt):
            return moe_transformer_loss(
                join(dn, lt), {"tokens": tokens, "targets": tokens}, cfg)
        loss, (gd, gl) = jax.value_and_grad(
            loss_fn, argnums=(0, 1))(dense, lat)
        dense = jax.tree_util.tree_map(lambda p, g: p - lr * g,
                                       dense, gd)
        # the lattice padding gets exact-zero grads (the loss reads
        # only the used prefix), so it stays zero — re-resizable
        return dense, lat - lr * gl, loss

    def step_fn(state, batch):
        dense, lat = state
        dense, lat, loss = jstep(dense, lat, batch)
        return (dense, lat), loss

    layout = {"flat_total": rows * row_total, "used": canon,
              "stacked": {"rows": rows, "row_total": row_total,
                          "row_used": row_used}}
    return split(full0), step_fn, layout


def _moe_batch(step):
    rng = np.random.RandomState(500 + step)
    return jnp.asarray(rng.randint(0, 64, (4, 8)).astype("int32"))


def test_chaos_resize_ep_lattice_2_to_3_bitwise(tmp_path):
    """ACCEPTANCE (ISSUE 17): resize@4:3 kills a 2-shard ep-flagship
    run mid-epoch; the 3-shard resume reshards the expert lattice
    through elastic (non-divisible 2 -> 3, real tail padding) and
    finishes BITWISE-identical to a clean 3-shard run started from the
    same checkpoint via an independent numpy import."""
    state2, step2, layout2 = _moe_lattice_harness(2)
    state3, step3, layout3 = _moe_lattice_harness(3)
    assert layout2["stacked"]["row_total"] * 3 != layout3["flat_total"]
    d = tmp_path / "ep"

    plan = faults.parse("resize@4:3")
    _, r1 = TrainGuard(step2, _gcfg(d, 2, layout2), plan=plan).run(
        state2, _moe_batch, 8)
    assert r1.status == "preempted" and r1.final_step == 4
    assert r1.resize_to == 3 and r1.faults_injected == 1

    # the independent comparator: numpy re-slice of the lattice leaf
    # (no elastic code), then the remaining steps plain 3-shard
    ck_step, payload, meta = CheckpointManager(str(d)).load_latest(
        with_meta=True)
    assert ck_step == 4 and meta["world_size"] == 2
    _, treedef2 = jax.tree_util.tree_flatten(state2)
    dense_s, lat_s = jax.tree_util.tree_unflatten(treedef2,
                                                  payload["leaves"])
    blk = meta["layout"]["stacked"]
    flat = np.concatenate([np.asarray(lat_s)[i, :u]
                           for i, u in enumerate(blk["row_used"]) if u])
    lat3_ref, _ = _pack_lattice(flat, 3)
    state_b = (jax.tree_util.tree_map(jnp.asarray, dense_s),
               jnp.asarray(lat3_ref))
    for i in range(ck_step, 8):
        state_b, _ = step3(state_b, _moe_batch(i))

    er = elastic.ElasticResume()
    state_a, r2 = TrainGuard(step3, _gcfg(d, 3, layout3), plan=plan,
                             elastic=er).run(state3, _moe_batch, 8)
    assert r2.status == "completed" and r2.final_step == 8
    assert r2.resumed_from == 4 and r2.resharded_from == 2

    for (kp, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(state_a),
            jax.tree_util.tree_leaves_with_path(state_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(kp))
    # the resized lattice kept its padding exactly zero
    lat_a = np.asarray(state_a[1])
    assert lat_a.shape == (3, layout3["stacked"]["row_total"])
    assert not np.any(lat_a.reshape(-1)[layout3["used"]:])


def test_old_manifest_degrades_with_typed_warning(harnesses, tmp_path):
    """A manifest written by an older PR (no meta): same-world resume
    still works, with a ManifestCompatWarning — and never a KeyError."""
    state4, step4, layout4 = harnesses[4]
    d = tmp_path / "old"
    _, r1 = TrainGuard(step4, _gcfg(d, 4, layout4),
                       plan=faults.parse("preempt@4")).run(
        state4, _make_batch, 8)
    assert r1.status == "preempted"
    # strip the meta, as an old-version manifest would look
    mpath = d / "MANIFEST.json"
    doc = json.loads(mpath.read_text())
    doc.pop("meta", None)
    mpath.write_text(json.dumps(doc))

    er = elastic.ElasticResume()
    with pytest.warns(ManifestCompatWarning, match="same-world"):
        _, r2 = TrainGuard(step4, _gcfg(d, 4, layout4), elastic=er).run(
            state4, _make_batch, 8)
    assert r2.status == "completed" and r2.resumed_from == 4
    assert r2.resharded_from is None


# ---------------------------------------------------------------------------
# install / uninstall: the process-default resharder
# ---------------------------------------------------------------------------

def test_install_registers_the_process_default_resharder():
    er = elastic.install(profile=_tiny_profile(), capacity_bytes=1 << 40)
    assert elastic.installed() is er and guard.get_resharder() is er
    assert er.search_kw == {"capacity_bytes": 1 << 40}
    elastic.uninstall()
    assert elastic.installed() is None


def test_reshard_payload_rejects_model_change():
    """A leaf-count or incompatible-shape difference is a model change,
    not a world change — typed error with detail, never a mis-slice."""
    meta = {"world_size": 8,
            "layout": {"flat_total": 1024, "used": 512, "chunk": 1024,
                       "lane": 128}}
    tmpl = {"a": jnp.zeros((512,), jnp.float32)}
    payload = {"step": 1, "leaves": [np.zeros((1024,), np.float32),
                                     np.zeros((4,), np.float32)]}
    with pytest.raises(WorldSizeMismatchError, match="leaves"):
        elastic.reshard_payload(tmpl, payload, meta, 4)
    payload = {"step": 1, "leaves": [np.zeros((768,), np.float32)]}
    with pytest.raises(WorldSizeMismatchError, match="cannot be resharded"):
        elastic.reshard_payload(tmpl, payload, meta, 4)
    # missing layout -> typed error, not KeyError
    with pytest.raises(WorldSizeMismatchError, match="layout"):
        elastic.reshard_payload(tmpl, {"step": 1, "leaves": []},
                                {"world_size": 8}, 4)
