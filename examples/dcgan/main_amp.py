"""DCGAN with two optimizers and per-loss scalers.

TPU-native rebuild of the reference's ``examples/dcgan/main_amp.py``, the one
example that exercises ``amp.initialize(..., num_losses=3)`` and
``scale_loss(..., loss_id=i)``: the discriminator accumulates TWO separately
-scaled backward passes (real, fake) into one optimizer step
(``amp.amp_step_multi``), and the generator uses its own third scaler.

Synthetic 64x64 "dataset" (the container ships no CIFAR/LSUN); the training
dynamics (D/G losses, multi-scaler bookkeeping, bf16 compute) are what the
example demonstrates.

    PYTHONPATH=. JAX_PLATFORMS=cpu python examples/dcgan/main_amp.py \
        --steps 5 --batch-size 8
"""
from __future__ import annotations

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from apex_tpu import amp
from apex_tpu.models import (DCGANConfig, dcgan_init, generator_apply,
                             discriminator_apply)
from apex_tpu.optimizers import FusedAdam


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--latent", type=int, default=100)
    p.add_argument("--lr", type=float, default=2e-4)
    p.add_argument("--beta1", type=float, default=0.5)
    p.add_argument("--opt-level", default="O4",
                   help="bf16 cast-insertion; O0 for pure fp32")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--print-freq", type=int, default=10)
    return p.parse_args(argv)


def bce_logits(logits, target):
    """BCE with logits (numerically safe form of the reference's
    sigmoid+BCELoss)."""
    return jnp.mean(jnp.maximum(logits, 0) - logits * target
                    + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def main(argv=None):
    args = parse_args(argv)
    cfg = DCGANConfig(latent_dim=args.latent,
                      dtype=jnp.bfloat16 if args.opt_level != "O0"
                      else jnp.float32)
    params, bn_state = jax.jit(
        lambda: dcgan_init(jax.random.PRNGKey(args.seed), cfg))()

    # two models, two optimizers, three loss scalers (reference
    # amp.initialize([netD, netG], [optD, optG], num_losses=3)
    optD = FusedAdam(lr=args.lr, betas=(args.beta1, 0.999))
    optG = FusedAdam(lr=args.lr, betas=(args.beta1, 0.999))
    stateD = amp.initialize(params["disc"], optD, opt_level=args.opt_level,
                            num_losses=2, verbosity=0)
    stateG = amp.initialize(params["gen"], optG, opt_level=args.opt_level,
                            num_losses=1, verbosity=0)

    real_label, fake_label = 1.0, 0.0

    @jax.jit
    def train_step(stateD, stateG, bn_state, real_images, z):
        P = lambda sD, sG: {"disc": sD.model_params, "gen": sG.model_params}

        # --- D step: two separately-scaled losses, one optimizer step ----
        fake_images, bn1 = generator_apply(P(stateD, stateG), bn_state, z,
                                           cfg, train=True)
        fake_images = jax.lax.stop_gradient(fake_images)

        def d_real_loss(dp):
            logits, bn_r = discriminator_apply(
                {"disc": dp, "gen": stateG.model_params}, bn1,
                real_images, cfg, train=True)
            return amp.scale_loss(bce_logits(logits, real_label), stateD,
                                  loss_id=0), (logits, bn_r)

        gr, (logits_real, bn_r) = jax.grad(d_real_loss, has_aux=True)(
            stateD.model_params)

        def d_fake_loss(dp):
            # running BN stats chain through the real pass (bn_r), as two
            # sequential forward passes would in the reference
            logits, bn2 = discriminator_apply(
                {"disc": dp, "gen": stateG.model_params}, bn_r,
                fake_images, cfg, train=True)
            return amp.scale_loss(bce_logits(logits, fake_label), stateD,
                                  loss_id=1), bn2

        gf, bn2 = jax.grad(d_fake_loss, has_aux=True)(stateD.model_params)
        errD_real = bce_logits(logits_real, real_label)
        new_stateD = amp.amp_step_multi(stateD, [(gr, 0), (gf, 1)])

        # --- G step: third scaler ---------------------------------------
        def g_loss(gp):
            imgs, bn3 = generator_apply(
                {"disc": new_stateD.model_params, "gen": gp}, bn2, z, cfg,
                train=True)
            logits, bn4 = discriminator_apply(
                {"disc": new_stateD.model_params, "gen": gp}, bn3, imgs,
                cfg, train=True)
            loss = bce_logits(logits, real_label)
            return amp.scale_loss(loss, stateG, loss_id=0), (loss, bn4)

        gg, (errG, bn4) = jax.grad(g_loss, has_aux=True)(stateG.model_params)
        new_stateG = amp.amp_step(stateG, gg, loss_id=0)
        return new_stateD, new_stateG, bn4, errD_real, errG

    rng = np.random.RandomState(args.seed)
    t0 = time.perf_counter()
    for step in range(args.steps):
        real = jnp.asarray(rng.rand(args.batch_size, 64, 64, cfg.channels)
                           .astype(np.float32) * 2.0 - 1.0)
        z = jnp.asarray(rng.randn(args.batch_size, args.latent)
                        .astype(np.float32))
        stateD, stateG, bn_state, errD, errG = train_step(
            stateD, stateG, bn_state, real, z)
        if (step + 1) % args.print_freq == 0 or step == args.steps - 1:
            dt = time.perf_counter() - t0
            print(f"[{step + 1}/{args.steps}] Loss_D {float(errD):.4f} "
                  f"Loss_G {float(errG):.4f}  scales "
                  f"D0={float(stateD.scalers[0].loss_scale):.0f} "
                  f"D1={float(stateD.scalers[1].loss_scale):.0f} "
                  f"G={float(stateG.loss_scale):.0f}  "
                  f"{(step % args.print_freq + 1) * args.batch_size / dt:.0f}"
                  " img/s", flush=True)
            t0 = time.perf_counter()
    print("=> done")
    return float(errD), float(errG)


if __name__ == "__main__":
    from apex_tpu.utils.platform import enable_compile_cache
    enable_compile_cache()
    main()
