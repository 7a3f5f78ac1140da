#!/usr/bin/env python
"""The elastic kill-N-resume-M proof as a one-shot artifact (ISSUE 11).

Trains the flagship-shaped transformer
N-way under TrainGuard with zero1 update-sharding + int8 error-feedback
residuals, kill it mid-epoch with an injected ``resize@K:M`` fault,
resume M-way through ``apex_tpu.elastic`` (manifest world-size detect →
re-plan → canonical-flat reshard), and verify the final params are
BITWISE-identical to a clean M-way run started from the same
checkpoint (independent canonical import, no elastic code).

Prints exactly ONE JSON line on stdout::

    {"metric": "elastic_proof", "backend": "tpu", "from_world": 8,
     "to_world": 4, "ckpt_step": 6, "steps": 12, "bitwise": true,
     "resharded_from": 8, "flat_total_from": 13312,
     "flat_total_to": 12800, "elapsed_s": 31.2}

exit 0 iff the proof holds (bitwise + typed-error gate).  CPU runs the
same logic on the forced 8-device host platform, which is what
``tests/L0/test_elastic.py`` asserts piece-by-piece — this tool exists
to capture the SAME proof on real silicon.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _build(world, cfg, su, global_batch):
    import jax
    import numpy as np  # noqa: F401
    from jax.sharding import PartitionSpec as P
    from apex_tpu.models import transformer_init, transformer_loss
    from apex_tpu.parallel import create_mesh
    from jax import shard_map
    from apex_tpu.utils.pallas import to_varying

    mesh = create_mesh({"data": world}, jax.devices()[:world])
    params0 = transformer_init(jax.random.PRNGKey(0), cfg)
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

    return ((params0, state0, res0), step_fn,
            su.layout_meta(params0, world))


def _import_canonical(template_state, payload, saved_world, layout):
    """Independent canonical-flat import (inline numpy — deliberately
    NOT elastic.reshard_payload, so the proof compares two separate
    implementations of the re-slice)."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding
    used, tot = int(layout["used"]), int(layout["flat_total"])
    tmpl_leaves, treedef = jax.tree_util.tree_flatten(template_state)
    out = []
    for t, h in zip(tmpl_leaves, payload["leaves"]):
        h = np.asarray(h)
        if h.shape == tuple(t.shape):
            v = h
        elif h.ndim == 1 and h.shape[0] == tot:
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
            raise RuntimeError(f"unexpected leaf {h.shape} vs "
                               f"{tuple(t.shape)}")
        sh = t.sharding if isinstance(t.sharding, NamedSharding) else None
        out.append(jax.device_put(v.astype(t.dtype), sh))
    return jax.tree_util.tree_unflatten(treedef, out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--from-world", type=int, default=None,
                    help="chip count of the killed run (default: all "
                         "visible devices, max 8)")
    ap.add_argument("--to-world", type=int, default=None,
                    help="chip count of the resumed run (default: "
                         "from_world // 2)")
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--kill-at", type=int, default=6)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--real-data", action="store_true",
                    help="feed a REAL on-disk npz shard set through the "
                         "seekable shard-addressed loader (apex_tpu."
                         "data.sharded) instead of a synthetic "
                         "callable: the kill-N-resume-M proof then "
                         "covers the data plane too — manifest cursor, "
                         "checksum verify, N->M shard re-partition")
    ap.add_argument("--data-dir", default=None,
                    help="existing token-shard dir for --real-data "
                         "(default: a tiny generated set in a temp dir)")
    args = ap.parse_args(argv)

    t0 = time.time()
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    import apex_tpu.elastic as elastic
    from apex_tpu.models import TransformerConfig
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.parallel import weight_update as wu
    from apex_tpu.resilience import (CheckpointManager, GuardConfig,
                                     TrainGuard, WorldSizeMismatchError,
                                     faults)

    backend = jax.default_backend()
    n_dev = len(jax.devices())
    from_world = args.from_world or min(8, n_dev)
    to_world = args.to_world or max(1, from_world // 2)
    if from_world > n_dev or to_world > n_dev or from_world == to_world:
        print(json.dumps({"metric": "elastic_proof", "backend": backend,
                          "error": f"need >= 2 devices with distinct "
                                   f"worlds (have {n_dev})"}))
        return 1

    # pos-embed length keeps `used` off the chunk lattice so the two
    # canonical totals actually differ (a real re-chunk, not a no-op)
    cfg = TransformerConfig(vocab_size=64, max_len=20, num_layers=1,
                            d_model=32, num_heads=2, d_ff=64,
                            dtype=jnp.float32)
    # the global batch must shard over BOTH worlds
    global_batch = int(np.lcm(from_world, to_world))

    data_meta = {}
    if args.real_data:
        # a real shard-addressed dataset: non-divisible shard sizes so
        # the (shard, offset) addressing is genuinely exercised, enough
        # records that the kill lands MID-EPOCH (epoch > 0)
        from apex_tpu.data import ShardedLoader, open_dataset
        ddir = args.data_dir
        if ddir is None:
            ddir = tempfile.mkdtemp(prefix="apex_tpu_shards_")
            n0 = 0
            for i, sz in enumerate((global_batch * 2 - 3,
                                    global_batch + 5,
                                    global_batch * 2 - 2)):
                rng = np.random.RandomState(77 + i)
                np.savez(os.path.join(ddir, f"tokens-{i:03d}.npz"),
                         tokens=rng.randint(
                             0, 64, (sz, 20)).astype(np.int32))
                n0 += sz
        dataset = open_dataset(ddir)
        dataset.verify()        # the eager checksum sweep, on record
        make_batch = ShardedLoader(
            dataset, global_batch=global_batch, seed=1,
            num_steps=args.steps,
            transform=lambda b, s: jnp.asarray(b["tokens"]))
        data_meta = {"real_data": True, "data_dir": ddir,
                     "index_digest": dataset.index.digest,
                     "n_records": dataset.n_records,
                     "steps_per_epoch": make_batch.steps_per_epoch}
    else:
        def make_batch(step):
            rng = np.random.RandomState(1000 + step)
            return jnp.asarray(
                rng.randint(0, 64, (global_batch, 20)).astype("int32"))

    def mk_su():
        return wu.ShardedUpdate(
            FusedAdam(lr=1e-2, impl="fused"), axis_name="data",
            collective_scheme="int8_blockscale:min_bytes=0")

    state_n, step_n, layout_n = _build(from_world, cfg, mk_su(),
                                       global_batch)
    state_m, step_m, layout_m = _build(to_world, cfg, mk_su(),
                                       global_batch)

    d = args.ckpt_dir or tempfile.mkdtemp(prefix="apex_tpu_elastic_")

    def gcfg(world, layout):
        return GuardConfig(ckpt_dir=d, save_every_steps=2, check_every=2,
                           backoff_seconds=0.01, enabled=True,
                           world_size=world,
                           ckpt_meta={"plan": {"dp": world},
                                      "layout": layout})

    plan = faults.parse(f"resize@{args.kill_at}:{to_world}")
    _, r1 = TrainGuard(step_n, gcfg(from_world, layout_n),
                       plan=plan).run(state_n, make_batch, args.steps)
    ok_kill = (r1.status == "preempted" and r1.resize_to == to_world)

    # without elastic the mismatch must be the typed, loud error
    try:
        TrainGuard(step_m, gcfg(to_world, layout_m), plan=plan).run(
            state_m, make_batch, args.steps)
        typed_error = False
    except WorldSizeMismatchError:
        typed_error = True

    ck_step, payload, meta = CheckpointManager(d).load_latest(
        with_meta=True)
    state_b = _import_canonical(state_m, payload, from_world,
                                meta["layout"])
    for i in range(ck_step, args.steps):
        state_b, _ = step_m(state_b, make_batch(i))

    er = elastic.ElasticResume()
    state_a, r2 = TrainGuard(step_m, gcfg(to_world, layout_m), plan=plan,
                             elastic=er).run(
        state_m, make_batch, args.steps)

    # real-data gate: the manifest carried the data-plane cursor for
    # THIS dataset, and the elastic resume re-partitioned the shard
    # assignment alongside the optimizer reshard
    data_ok = True
    if args.real_data:
        mdata = meta.get("data") or {}
        data_ok = (mdata.get("index_digest") == data_meta["index_digest"]
                   and isinstance(mdata.get("cursor"), dict)
                   and er.last_data is not None
                   and er.last_data["to_world"] == to_world)

    bitwise = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves(state_a),
                        jax.tree_util.tree_leaves(state_b)))
    out = {
        "metric": "elastic_proof", "backend": backend,
        "from_world": from_world, "to_world": to_world,
        "ckpt_step": int(ck_step), "steps": args.steps,
        "kill_status": r1.status, "resize_to": r1.resize_to,
        "typed_error_without_elastic": typed_error,
        "resumed_from": r2.resumed_from,
        "resharded_from": r2.resharded_from,
        "flat_total_from": layout_n["flat_total"],
        "flat_total_to": layout_m["flat_total"],
        "bitwise": bool(bitwise),
        "elapsed_s": round(time.time() - t0, 2),
    }
    if args.real_data:
        out.update(data_meta)
        out["data_cursor_ok"] = bool(data_ok)
        out["data_repartition"] = er.last_data
    print(json.dumps(out))
    return 0 if (bitwise and ok_kill and typed_error and data_ok
                 and r2.resharded_from == from_world) else 1


if __name__ == "__main__":
    raise SystemExit(main())
