#!/usr/bin/env python
"""The run-controller chaos acceptance as a one-shot artifact (ISSUE 19).

Trains the flagship-shaped transformer
N-way under TrainGuard with a ``straggler@K:F`` fault armed and an
``apex_tpu.control.RunController`` riding the health-check window.  The
leave-one-out z-score must name the slowed device persistently, the
controller's quarantine policy must fire a synthesized ``resize@N:N-1``
through the guard, the run must come back up (N-1)-way through the
elastic reshard, and the final params must be BITWISE-identical to an
independent import of the post-quarantine checkpoint stepped forward
without any controller/elastic code.  The decision trail must survive
as a schema-valid ``CONTROL.json`` with >= 1 quarantine decision.

Prints exactly ONE JSON line on stdout::

    {"metric": "control_chaos", "backend": "cpu", "from_world": 8,
     "to_world": 7, "quarantine_decisions": 1, "control_valid": true,
     "quarantined_device": "d0", "bitwise": true, "elapsed_s": 41.0}

exit 0 iff the acceptance holds.  CPU runs the same logic on the forced
8-device host platform (``XLA_FLAGS=
--xla_force_host_platform_device_count=8``); on a multi-chip host the same
command runs on the chips — one process, the backend jax brings up.
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
    # the elastic_proof zero1 harness: flat sharded optimizer state so
    # the 8->7 reshard crosses a genuinely non-divisible chunk lattice
    import jax
    from jax.sharding import PartitionSpec as P
    from apex_tpu.models import transformer_init, transformer_loss
    from apex_tpu.parallel import create_mesh
    from jax import shard_map
    from apex_tpu.utils.pallas import to_varying

    mesh = create_mesh({"data": world}, jax.devices()[:world])
    params0 = transformer_init(jax.random.PRNGKey(0), cfg)
    pspec = jax.tree_util.tree_map(lambda _: P(), params0)
    sspec = su.state_pspecs(params0, world)

    @functools.partial(shard_map, mesh=mesh, in_specs=(pspec,),
                       out_specs=sspec)
    def init_s(p):
        return su.init(p)

    def body(params, state, tokens):
        pv = jax.tree_util.tree_map(
            lambda p: to_varying(p, ("data",)), params)
        loss, grads = jax.value_and_grad(lambda p: transformer_loss(
            p, {"tokens": tokens, "targets": tokens}, cfg))(pv)
        params, state = su.step(state, grads, params)
        return params, state, jax.lax.pmean(loss, "data")

    jstep = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(pspec, sspec, P("data")),
        out_specs=(pspec, sspec, P())))
    state0 = jax.jit(init_s)(params0)

    def step_fn(state, batch):
        params, opt_state = state
        params, opt_state, loss = jstep(params, opt_state, batch)
        return (params, opt_state), loss

    return (params0, state0), step_fn, su.layout_meta(params0, world)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--from-world", type=int, default=None,
                    help="chip count of the straggler-afflicted run "
                         "(default: all visible devices, max 8)")
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--straggle-at", type=int, default=2,
                    help="first step the straggler fault is armed at")
    ap.add_argument("--factor", type=float, default=4.0,
                    help="straggler slowdown factor F")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args(argv)

    t0 = time.time()
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    import apex_tpu.elastic as elastic
    from apex_tpu.control import (ControlConfig, RunController,
                                  control_violations)
    from apex_tpu.models import TransformerConfig
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.parallel import weight_update as wu
    from apex_tpu.resilience import (CheckpointManager, GuardConfig,
                                     TrainGuard, faults)
    from apex_tpu.telemetry import trace as ttrace

    backend = jax.default_backend()
    n_dev = len(jax.devices())
    from_world = args.from_world or min(8, n_dev)
    to_world = from_world - 1
    if from_world > n_dev or from_world < 2:
        print(json.dumps({"metric": "control_chaos", "backend": backend,
                          "error": f"need >= 2 devices (have {n_dev})"}))
        return 1

    cfg = TransformerConfig(vocab_size=64, max_len=20, num_layers=1,
                            d_model=32, num_heads=2, d_ff=64,
                            dtype=jnp.float32)
    global_batch = int(np.lcm(from_world, to_world))

    def make_batch(step):
        rng = np.random.RandomState(1000 + step)
        return jnp.asarray(
            rng.randint(0, 64, (global_batch, 20)).astype("int32"))

    def mk_su():
        return wu.ShardedUpdate(FusedAdam(lr=1e-2, impl="fused"),
                                axis_name="data")

    state_n, step_n, layout_n = _build(from_world, cfg, mk_su(),
                                       global_batch)
    state_m, step_m, layout_m = _build(to_world, cfg, mk_su(),
                                       global_batch)

    d = args.ckpt_dir or tempfile.mkdtemp(prefix="apex_tpu_control_")

    def gcfg(world, layout):
        return GuardConfig(ckpt_dir=d, save_every_steps=2, check_every=2,
                           backoff_seconds=0.01, enabled=True,
                           world_size=world,
                           ckpt_meta={"plan": {"dp": world},
                                      "layout": layout})

    # phase 1: the afflicted run — a persistent straggler the
    # controller must quarantine (the fault stays armed for the whole
    # run; the z-score needs >= 2 consecutive windows to name it)
    plan = faults.parse(
        f"straggler@{args.straggle_at}x{args.steps}:{args.factor}")
    tracer = ttrace.Tracer(enabled=True, flight_dir=d)
    prev_tracer = ttrace.set_tracer(tracer)
    try:
        ctl = RunController(ControlConfig(enabled=True, max_actions=2))
        _, r1 = TrainGuard(step_n, gcfg(from_world, layout_n), plan=plan,
                           controller=ctl).run(state_n, make_batch,
                                               args.steps)
    finally:
        ttrace.set_tracer(prev_tracer)

    doc = r1.control or {}
    quarantines = [dec for dec in doc.get("decisions", ())
                   if dec.get("action") == "quarantine"
                   and dec.get("outcome") == "acted"]
    control_valid = bool(doc) and not control_violations(doc)
    artifact_ok = bool(r1.control_path
                       and os.path.basename(r1.control_path)
                       == "CONTROL.json" and os.path.exists(r1.control_path))
    ok_quarantine = (r1.status == "preempted"
                     and r1.resize_to == to_world and len(quarantines) >= 1)
    quarantined = (quarantines[0]["detail"].get("device")
                   if quarantines and isinstance(
                       quarantines[0].get("detail"), dict) else None)

    # independent import of the post-quarantine checkpoint: reshard
    # through elastic ONCE into the (N-1)-way template, then step it
    # forward with plain engine code — no guard, no controller
    ck_step, payload, meta = CheckpointManager(d).load_latest(
        with_meta=True)
    payload_b = elastic.reshard_payload(state_m, payload, meta, to_world)
    import apex_tpu.resilience.guard as guard_mod
    state_b = guard_mod.TrainGuard(step_m, GuardConfig(enabled=True),
                                   )._restore(state_m, payload_b)
    for i in range(ck_step, args.steps):
        state_b, _ = step_m(state_b, make_batch(i))

    # phase 2: the real resumed run through the guard's elastic path
    state_a, r2 = TrainGuard(step_m, gcfg(to_world, layout_m),
                             elastic=elastic.ElasticResume()).run(
        state_m, make_batch, args.steps)

    bitwise = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves(state_a),
                        jax.tree_util.tree_leaves(state_b)))
    out = {
        "metric": "control_chaos", "backend": backend,
        "from_world": from_world, "to_world": to_world,
        "steps": args.steps, "ckpt_step": int(ck_step),
        "kill_status": r1.status, "resize_to": r1.resize_to,
        "quarantine_decisions": len(quarantines),
        "quarantined_device": quarantined,
        "control_valid": bool(control_valid),
        "control_artifact": r1.control_path,
        "artifact_ok": bool(artifact_ok),
        "windows": doc.get("windows", 0),
        "resumed_from": r2.resumed_from,
        "resharded_from": r2.resharded_from,
        "bitwise": bool(bitwise),
        "elapsed_s": round(time.time() - t0, 2),
    }
    print(json.dumps(out))
    return 0 if (ok_quarantine and control_valid and artifact_ok
                 and bitwise and r2.resharded_from == from_world) else 1


if __name__ == "__main__":
    raise SystemExit(main())
