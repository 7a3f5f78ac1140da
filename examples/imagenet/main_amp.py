"""ImageNet training with apex_tpu amp — the flagship example.

TPU-native rebuild of ``examples/imagenet/main_amp.py`` in the reference
(ResNet-50 + amp + DDP + optional SyncBN; the ``images/sec`` Speed print at
main_amp.py:391 is the reference's own metric).  Differences by design:

- SPMD instead of process-per-GPU: one process drives every visible device
  through a ``jax.sharding.Mesh``; ``--distributed`` shards the batch over
  the ``data`` axis (the DistributedDataParallel analog — gradient reduction
  is inserted by XLA from the shardings).  With a sharded batch, batch-norm
  statistics computed over the global batch dim ARE synchronized batch norm,
  so ``--sync-bn`` semantics come free under pjit.
- Synthetic ImageNet-shaped data by default (``--data`` accepts a directory
  of ``.npz`` shards with ``images``/``labels`` arrays): the container has
  no dataset, and the metric is step throughput, not input pipelines.

Usage (CPU smoke):
    PYTHONPATH=. JAX_PLATFORMS=cpu python examples/imagenet/main_amp.py \
        --arch resnet18 --batch-size 8 --steps 10 --print-freq 2

TPU (single chip):
    python examples/imagenet/main_amp.py --arch resnet50 --batch-size 128 \
        --opt-level O2 --steps 100

Multi-device (on CPU use
XLA_FLAGS=--xla_force_host_platform_device_count=8):
    python examples/imagenet/main_amp.py --distributed --sync-bn ...
"""
from __future__ import annotations

import argparse
import functools
import os
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu import amp, checkpoint, telemetry
from apex_tpu.models import (resnet18_config, resnet50_config, resnet_init,
                             resnet_apply)
from apex_tpu.optimizers import FusedAdam, FusedSGD, FusedLAMB
from apex_tpu.parallel import create_mesh, use_mesh


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="apex_tpu imagenet example")
    p.add_argument("--arch", default="resnet50",
                   choices=["resnet18", "resnet50"])
    p.add_argument("--data", default=None,
                   help="dir of .npz shards (images NHWC uint8/float, labels "
                        "int); default: synthetic data")
    p.add_argument("--batch-size", type=int, default=128,
                   help="GLOBAL batch size")
    p.add_argument("--steps", type=int, default=100, help="steps per epoch")
    p.add_argument("--epochs", type=int, default=1,
                   help="total steps trained = epochs * steps")
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--optimizer", default="adam",
                   choices=["adam", "sgd", "lamb"])
    p.add_argument("--opt-level", default="O2",
                   choices=["O0", "O1", "O2", "O3", "O4", "O5"])
    p.add_argument("--loss-scale", default=None,
                   help='"dynamic" or a number (preset default otherwise)')
    p.add_argument("--keep-batchnorm-fp32", default=None,
                   choices=[None, "True", "False"])
    p.add_argument("--distributed", action="store_true",
                   help="shard the batch over all visible devices")
    p.add_argument("--sync-bn", action="store_true",
                   help="documented no-op under pjit: global-batch BN stats "
                        "are already synchronized when the batch is sharded")
    p.add_argument("--print-freq", type=int, default=10)
    p.add_argument("--validate", type=int, default=0, metavar="N",
                   help="run an N-step eval pass after training (synthetic "
                        "val set; prints eval Speed + Prec@1/@5 like the "
                        "reference validate())")
    p.add_argument("--deterministic", action="store_true")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--resume", default=None, help="checkpoint to resume from")
    p.add_argument("--save", default=None, help="checkpoint path to write")
    p.add_argument("--auto-resume", action="store_true",
                   help="drive training through apex_tpu.resilience."
                        "TrainGuard: rotating checkpoints under --save "
                        "(required, used as a directory), SIGTERM -> "
                        "snapshot + clean exit, NaN-streak rollback, and "
                        "resume from the newest checkpoint on restart — "
                        "an interrupted run makes incremental progress "
                        "instead of restarting from step 0.  Exits "
                        "non-zero unless all steps completed.")
    p.add_argument("--save-every", type=int, default=50,
                   help="guard checkpoint cadence in steps (--auto-resume)")
    p.add_argument("--prof", action="store_true",
                   help="capture a profiler trace of steps 5-10 "
                        "(apex_tpu.pyprof)")
    p.add_argument("--prof-dir", default="/tmp/apex_tpu_trace")
    p.add_argument("--loader", default="python",
                   choices=["python", "native"],
                   help="'native': assemble batches on the C++ prefetch "
                        "engine (csrc/prefetch.cpp), the data_prefetcher/"
                        "DALI-stage analog; works with synthetic data or "
                        "with --data pointing at images.npy+labels.npy "
                        "(memmapped)")
    return p.parse_args(argv)


class AverageMeter:
    """Running averages for the Speed/Loss prints (reference AverageMeter)."""

    def __init__(self):
        self.reset()

    def reset(self):
        self.val = self.sum = self.count = 0.0

    def update(self, val, n=1):
        self.val = val
        self.sum += val * n
        self.count += n

    @property
    def avg(self):
        return self.sum / max(self.count, 1)


_SYN_CLASSES = 64        # distinct learnable classes in the synthetic pool
_SYN_PROTOS = None       # lazy: built once per process (38 MB, ~100 ms)


def _syn_protos():
    global _SYN_PROTOS
    if _SYN_PROTOS is None:
        proto_rng = np.random.RandomState(1234)  # pool shared across seeds
        _SYN_PROTOS = proto_rng.rand(
            _SYN_CLASSES, 224, 224, 3).astype(np.float32)
    return _SYN_PROTOS


def synthetic_batches(batch, seed, steps):
    """Host-side synthetic ImageNet-shaped data: a fixed pool of class
    prototypes (one random image per class, pool seed independent of the
    batch seed) sampled with per-step noise.  A new array is built every
    step so the input feed is exercised (like the reference's
    data_prefetcher), but the image->label mapping is LEARNABLE — loss
    falls and Prec@1 moves off floor, which is what the on-hardware
    numerics proof checks.  (Fresh noise with fresh random labels, the
    r1-r4 form, bounds loss below at ln(1000) and proves nothing.)

    Train and eval callers pass different ``seed``s but share the
    prototype pool, so eval accuracy measures real generalization to
    unseen noise draws.

    ``--loader native``'s no-data mode instead uses the C++
    ``SyntheticSource`` (uniform noise, uniform labels) — a loader
    THROUGHPUT vehicle, not a learnability proof; train on real/memmap
    data (``--data``) when using the native loader for numerics."""
    protos = _syn_protos()
    rng = np.random.Generator(np.random.PCG64(seed))
    for _ in range(steps):
        labels = rng.integers(0, _SYN_CLASSES, size=(batch,))
        # native f32 draw: no double-sized f64 temporary on the feed path
        images = protos[labels] + 0.08 * rng.standard_normal(
            (batch, 224, 224, 3), dtype=np.float32)
        yield images, labels.astype(np.int32)


def synthetic_batch_at(batch, seed, step):
    """Step-addressable synthetic batch for the guard path (--auto-resume):
    same prototype pool + noise model as :func:`synthetic_batches`, but
    seeded per (seed, step) so resume and rollback replay the EXACT batch
    for any global step — the property the bitwise-resume proof needs."""
    protos = _syn_protos()
    rng = np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([seed, step])))
    labels = rng.integers(0, _SYN_CLASSES, size=(batch,))
    images = protos[labels] + 0.08 * rng.standard_normal(
        (batch, 224, 224, 3), dtype=np.float32)
    return images, labels.astype(np.int32)


def native_batches(args, batch, steps):
    """Batches via the native prefetch engine (apex_tpu.data): C++ worker
    threads assemble batches in a ring while the step runs; yields numpy so
    the training loop's sharded device_put stays in charge of placement."""
    from apex_tpu.data import (ArraySource, NativeLoader, SyntheticSource,
                               native_available)
    if not native_available():
        raise RuntimeError(
            "--loader native: the native prefetch engine could not be "
            "built (its warning says why); use --loader python")
    if args.data:
        img = os.path.join(args.data, "images.npy")
        lab = os.path.join(args.data, "labels.npy")
        if not (os.path.exists(img) and os.path.exists(lab)):
            raise FileNotFoundError(
                f"--loader native with --data needs {img} + {lab} "
                "(fp32 NHWC + int32; np.memmap-ed without loading)")
        src = ArraySource(data=np.load(img, mmap_mode="r"),
                          labels=np.load(lab, mmap_mode="r"))
    else:
        src = SyntheticSource(shape=(224, 224, 3), n_classes=1000)
    return iter(NativeLoader(src, batch_size=batch, steps=steps,
                             seed=args.seed, device_put=False))


def _has_npz_shards(data_dir):
    try:
        return any(f.endswith(".npz") for f in os.listdir(data_dir))
    except OSError:
        return False


def sharded_npz_loader(args, batch, steps, sharding=None):
    """Seekable shard-addressed loader (``apex_tpu.data.sharded``) over
    a directory of ``.npz`` shards with ``images``/``labels`` arrays:
    checksummed shards, pure (seed, epoch, step) addressing, prefetched
    iteration.  Calling it — ``loader(step)`` — replays any global
    step's batch bitwise, which is what lets ``--auto-resume`` record
    the data-plane cursor in the checkpoint manifest and seek the
    stream on resume instead of restarting it (docs/data.md)."""
    from apex_tpu.data import ShardedLoader, open_dataset

    def tf(b, step):
        x = b["images"]
        x = (x.astype(np.float32) / 255.0 if x.dtype == np.uint8
             else x.astype(np.float32))
        y = b["labels"].astype(np.int32)
        if sharding is not None:
            return jax.device_put(x, sharding), jax.device_put(y, sharding)
        return x, y

    return ShardedLoader(open_dataset(args.data), global_batch=batch,
                         seed=args.seed, num_steps=steps, transform=tf)


def validate(args, cfg, state, bn_state, mesh, batch_sharding):
    """Eval pass (reference validate(), main_amp.py:457 Speed/Prec prints):
    train=False BN (running stats), top-1/top-5 on synthetic data."""
    @jax.jit
    def eval_step(state, bn_state, images, labels):
        logits, _ = resnet_apply(state.model_params, bn_state, images, cfg,
                                 train=False)
        logits = logits.astype(jnp.float32)
        top1 = jnp.mean(
            (jnp.argmax(logits, axis=1) == labels).astype(jnp.float32))
        top5_idx = jax.lax.top_k(logits, 5)[1]
        top5 = jnp.mean(jnp.any(top5_idx == labels[:, None],
                                axis=1).astype(jnp.float32))
        return top1, top5

    m1, m5, speed = AverageMeter(), AverageMeter(), AverageMeter()
    t0 = time.perf_counter()
    with use_mesh(mesh):
        for step, (np_images, np_labels) in enumerate(
                synthetic_batches(args.batch_size, args.seed + 1,
                                  args.validate)):
            images = jax.device_put(np_images, batch_sharding)
            labels = jax.device_put(np_labels, batch_sharding)
            top1, top5 = eval_step(state, bn_state, images, labels)
            m1.update(float(top1))          # host sync = timing boundary
            m5.update(float(top5))
            dt = time.perf_counter() - t0
            if step > 0:                    # skip compile step
                speed.update(args.batch_size / dt)
            t0 = time.perf_counter()
    print(f"=> eval: Speed {speed.avg:.1f} img/s  "
          f"Prec@1 {m1.avg:.3f} Prec@5 {m5.avg:.3f}")
    return m1.avg


def main(argv=None, report=None):
    """Train; returns the average speed print.  ``report``, a dict the
    caller owns, is filled (plain loop only) with what a check of the run
    needs: the printed ``losses``, the number of ``optimizer_steps``
    actually applied (a step the scaler skipped does not count), and the
    final ``state``."""
    args = parse_args(argv)
    if args.deterministic:
        np.random.seed(args.seed)

    devices = jax.devices()
    n_dev = len(devices) if args.distributed else 1
    if args.batch_size % n_dev:
        raise ValueError(f"global batch {args.batch_size} must divide over "
                         f"{n_dev} devices")
    mesh = create_mesh({"data": n_dev}, devices=devices[:n_dev])
    print(f"=> devices: {n_dev} ({jax.default_backend()}), "
          f"global batch {args.batch_size}")

    cfg_fn = resnet50_config if args.arch == "resnet50" else resnet18_config
    compute_dtype = (jnp.bfloat16 if args.opt_level in
                     ("O1", "O2", "O3", "O4", "O5") else jnp.float32)
    cfg = cfg_fn(dtype=compute_dtype)
    opt_cls = {"adam": functools.partial(FusedAdam, lr=args.lr),
               "sgd": functools.partial(FusedSGD, lr=args.lr, momentum=0.9),
               "lamb": functools.partial(FusedLAMB, lr=args.lr)}[args.optimizer]
    opt = opt_cls()

    loss_scale = args.loss_scale
    if loss_scale not in (None, "dynamic"):
        loss_scale = float(loss_scale)
    kbn = {None: None, "True": True, "False": False}[args.keep_batchnorm_fp32]
    # the host's time in building the state, for the set-up record
    # (docs/telemetry.md): nobody waits for the device here
    with telemetry.trace.setup_tracer().span("setup.state"):
        params, bn_state = jax.jit(
            lambda: resnet_init(jax.random.PRNGKey(args.seed), cfg))()
        state = amp.initialize(params, opt, opt_level=args.opt_level,
                               loss_scale=loss_scale, keep_batchnorm_fp32=kbn)

    start_step = 0
    if args.resume:
        ckpt = checkpoint.load(args.resume)
        state = state._replace(
            model_params=checkpoint.restore_like(state.model_params,
                                                 ckpt["model"]),
            master_params=(checkpoint.restore_like(state.master_params,
                                                   ckpt["masters"])
                           if ckpt.get("masters") is not None else None),
            opt_state=checkpoint.restore_like(state.opt_state, ckpt["opt"]))
        state = amp.load_state_dict(state, ckpt["amp"])
        bn_state = checkpoint.restore_like(bn_state, ckpt["bn"])
        start_step = int(ckpt["step"])
        print(f"=> resumed from {args.resume} at step {start_step}")

    batch_sharding = NamedSharding(mesh, P("data"))
    # replicate over the mesh up front: left on the default device, the
    # state would be re-laid-out by the first step and the step traced
    # and compiled a second time for the new input shardings
    state, bn_state = jax.device_put((state, bn_state),
                                     NamedSharding(mesh, P()))

    @jax.jit
    def train_step(state, bn_state, images, labels):
        def loss_fn(p):
            logits, new_bn = resnet_apply(p, bn_state, images, cfg,
                                          train=True)
            lp = jax.nn.log_softmax(logits.astype(jnp.float32))
            loss = -jnp.mean(jnp.take_along_axis(lp, labels[:, None], axis=1))
            acc = jnp.mean(
                (jnp.argmax(logits, axis=1) == labels).astype(jnp.float32))
            return amp.scale_loss(loss, state), (new_bn, loss, acc)

        grads, (new_bn, loss, acc) = jax.grad(
            loss_fn, has_aux=True)(state.model_params)
        return amp.amp_step(state, grads), new_bn, loss, acc

    total_steps = args.steps * args.epochs
    end_step = start_step + total_steps

    if args.auto_resume:
        if not args.save:
            raise SystemExit("--auto-resume requires --save DIR (used as "
                             "the rotating checkpoint directory)")
        from apex_tpu.resilience import GuardConfig, TrainGuard

        if args.data and _has_npz_shards(args.data):
            # the seekable shard-addressed path (docs/data.md): the
            # loader IS batches(step), so resume and rollback replay
            # bitwise, and the guard records the data-plane cursor
            # (epoch/shard position + index digest) in the manifest
            batch_src = sharded_npz_loader(args, args.batch_size,
                                           total_steps,
                                           sharding=batch_sharding)
        elif args.data or args.loader == "native":
            # non-seekable sources (memmapped .npy via the native ring):
            # resume continues from the iterator's current position;
            # rollback is unavailable (the guard aborts with a clear
            # error if it would be needed)
            src = native_batches(args, args.batch_size, total_steps)
            batch_src = ((jax.device_put(x, batch_sharding),
                          jax.device_put(y, batch_sharding))
                         for x, y in src)
        else:
            def batch_src(step):
                x, y = synthetic_batch_at(args.batch_size, args.seed, step)
                return (jax.device_put(x, batch_sharding),
                        jax.device_put(y, batch_sharding))

        def gstep(carry, batch):
            st, bn = carry
            st, bn, loss, acc = train_step(st, bn, *batch)
            return (st, bn), loss, acc

        t_check = [time.perf_counter()]

        def on_check(step, losses):
            now = time.perf_counter()
            ips = len(losses) * args.batch_size / max(now - t_check[0], 1e-9)
            t_check[0] = now
            print(f"Step [{step}/{total_steps}]  Speed {ips:.1f} img/s  "
                  f"Loss {losses[-1]:.4f}", flush=True)

        gcfg = GuardConfig(ckpt_dir=args.save,
                           save_every_steps=args.save_every,
                           check_every=max(1, args.print_freq),
                           floor_patience=3)
        guard = TrainGuard(gstep, gcfg, on_check=on_check)
        with use_mesh(mesh):
            (state, bn_state), rep = guard.run((state, bn_state), batch_src,
                                               total_steps)
        if rep.resumed_from is not None:
            print(f"=> guard resumed from step {rep.resumed_from}")
        print(f"=> guard: {rep.status} at step {rep.final_step}/{total_steps}"
              f"  (rollbacks {rep.rollbacks}, faults {rep.faults_injected}, "
              f"checkpoints {rep.checkpoints})", flush=True)
        if args.validate and rep.status == "completed":
            validate(args, cfg, state, bn_state, mesh, batch_sharding)
        if rep.status != "completed":
            # a caller keys "done" on a zero exit: an interrupted run
            # must read as retryable
            raise SystemExit(3)
        return None

    if args.loader == "native":
        batches = native_batches(args, args.batch_size, total_steps)
    elif args.data:
        # shard-addressed loader with prefetch (docs/data.md); same
        # (x, y) numpy contract as the native path
        batches = iter(sharded_npz_loader(args, args.batch_size,
                                          total_steps))
    else:
        batches = synthetic_batches(args.batch_size, args.seed, total_steps)

    losses, top1, speed = AverageMeter(), AverageMeter(), AverageMeter()
    history = []
    prof = None
    if args.prof:
        from apex_tpu import pyprof
        prof = pyprof

    with use_mesh(mesh):
        t0 = time.perf_counter()
        window = 0                      # steps since the last speed print
        for step, (np_images, np_labels) in enumerate(batches, start_step):
            if prof and step == start_step + 5:
                prof.start_trace(args.prof_dir)
            images = jax.device_put(np_images, batch_sharding)
            labels = jax.device_put(np_labels, batch_sharding)
            state, bn_state, loss, acc = train_step(state, bn_state,
                                                    images, labels)
            window += 1
            if prof and step == start_step + 10:
                prof.stop_trace()
                print(f"=> profiler trace written to {args.prof_dir}")
            if (step + 1) % args.print_freq == 0:
                loss = float(loss)      # host sync — the timing boundary
                dt = time.perf_counter() - t0
                ips = window * args.batch_size / dt
                losses.update(loss, window)
                history.append(loss)
                top1.update(float(acc), window)
                if step - start_step + 1 > args.print_freq:  # skip compile
                    speed.update(ips)
                print(f"Step [{step + 1}/{end_step}]  "
                      f"Speed {ips:.1f} ({speed.avg:.1f}) img/s  "
                      f"Loss {losses.val:.4f} ({losses.avg:.4f})  "
                      f"Prec@1 {top1.val:.3f}", flush=True)
                t0 = time.perf_counter()
                window = 0

    if report is not None:
        report.update(losses=history, state=state,
                      optimizer_steps=int(state.opt_state.count))

    if args.validate:
        validate(args, cfg, state, bn_state, mesh, batch_sharding)

    if args.save:
        checkpoint.save(args.save, step=end_step, model=state.model_params,
                        masters=state.master_params, opt=state.opt_state,
                        amp=amp.state_dict(state), bn=bn_state)
        print(f"=> saved checkpoint to {args.save}")
    print(f"=> done. avg speed {speed.avg:.1f} images/sec "
          f"(global batch {args.batch_size})")
    return speed.avg


if __name__ == "__main__":
    from apex_tpu.utils.platform import enable_compile_cache
    enable_compile_cache()
    main()
