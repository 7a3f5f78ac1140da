"""BERT-style masked-LM pretraining — the ``bert_large`` benchmark's
workload (FusedLAMB with its global-norm gradient clip).

The reference has no BERT example (its LAMB cites "BERT in 76 minutes");
this harness makes it runnable end-to-end: transformer encoder + amp
O5 (bf16 + fp32 masters) + FusedLAMB with global-norm clipping, leaf by
leaf where the update is replicated and on the flat engine where it is
sharded (--zero), on synthetic MLM batches.  Distributed options:

  --distributed    shard the batch over all devices (DP: shard_map +
                   DistributedDataParallel gradient averaging)
  --zero           ZeRO sharded optimizer states (DistributedFusedLAMB
                   inside shard_map: psum_scatter grads -> sharded update
                   -> bf16 all_gather)

(For the long-context sequence-parallel path see
``apex_tpu.parallel.sequence`` and ``SelfMultiheadAttn(impl='ring')``.)

CPU smoke:
    PYTHONPATH=. JAX_PLATFORMS=cpu python examples/bert/pretrain.py \
        --steps 4 --batch-size 2
"""
from __future__ import annotations

import argparse
import functools
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu import amp, telemetry
from apex_tpu.models import (TransformerConfig, bert_large_config,
                             transformer_init, transformer_loss,
                             MoETransformerConfig, moe_transformer_init,
                             moe_transformer_loss, Lfm2Config,
                             lfm2_24b_a2b_config, lfm2_cut_layer_types,
                             lfm2_init, lfm2_loss, NemotronHConfig,
                             nemotron3_super_120b_a12b_config,
                             nemotron_h_cut_pattern, nemotron_h_init,
                             nemotron_h_loss, Qwen3NextConfig,
                             qwen3_next_80b_a3b_config, qwen3_next_init,
                             qwen3_next_loss, Glm4MoeLiteConfig,
                             glm47_flash_config, glm4_moe_lite_init,
                             glm4_moe_lite_loss, LagunaConfig,
                             laguna_s_2_1_config, laguna_init, laguna_loss)
from apex_tpu.optimizers import FusedLAMB
from apex_tpu.parallel import create_mesh, use_mesh
from apex_tpu.utils.logging import AverageMeter, Throughput


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="apex_tpu BERT pretrain example")
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--data", default=None,
                   help="dir of .npz token shards (a 'tokens' int32 "
                        "array, rows >= --seq-len wide) fed through the "
                        "seekable shard-addressed loader (apex_tpu.data."
                        "sharded): checksummed shards, bitwise "
                        "seek-to-step — with --auto-resume the manifest "
                        "records the data-plane cursor; default: "
                        "synthetic MLM batches")
    p.add_argument("--batch-size", type=int, default=8, help="global batch")
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--d-model", type=int, default=256)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--vocab", type=int, default=8192)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--opt-level", default="O5")
    p.add_argument("--bert-large", action="store_true",
                   help="full bert-large config (TPU-sized)")
    p.add_argument("--lfm2", type=int, nargs=3, default=None,
                   metavar=("DENSE", "PERIODS", "HELD"),
                   help="LFM2-24B-A2B at its published widths (causal LM on "
                        "next-token batches), cut to a chip's share: DENSE "
                        "leading dense layers (published 2), PERIODS whole "
                        "periods of (attention, conv, conv, conv) after "
                        "them (published 9 and a half), the first HELD of "
                        "the 64 experts of each layer, --vocab rows of the "
                        "65536 of the embedding; the router still scores "
                        "all 64 (docs/lfm2.md)")
    p.add_argument("--nemotron-h", type=int, nargs=3, default=None,
                   metavar=("TP", "EP", "PERIODS"),
                   help="NVIDIA-Nemotron-3-Super-120B-A12B at its published "
                        "widths (causal LM on next-token batches), cut to "
                        "one chip's share of a tensor-parallel-TP x "
                        "expert-parallel-EP stage: the first 1/TP of the 128 "
                        "Mamba heads (whole B/C groups) and of the 32 query "
                        "heads with the key-value heads they read, the first "
                        "1/EP of the 512 routed experts of each layer, "
                        "PERIODS whole periods (MEMEMEMEM*E) of the 88-layer "
                        "pattern, --vocab rows of the 131072 of embedding "
                        "and head; the router still scores all 512 "
                        "(docs/nemotron_h.md)")
    p.add_argument("--qwen3-next", type=int, nargs=2, default=None,
                   metavar=("EP", "PERIODS"),
                   help="Qwen3-Next-80B-A3B-Instruct at its published widths "
                        "(causal LM on next-token batches), cut to one "
                        "chip's share of an EP-way expert-parallel group: "
                        "the first 1/EP of the 512 routed experts of each "
                        "layer, PERIODS whole periods (three Gated DeltaNet "
                        "layers, one gated attention layer) of the 48, "
                        "--vocab rows of the 151936 of embedding and head; "
                        "mixers, router (all 512 outputs) and shared expert "
                        "whole (docs/qwen3_next.md)")
    p.add_argument("--glm4-moe-lite", type=int, nargs=2, default=None,
                   metavar=("EP", "LAYERS"),
                   help="GLM-4.7-Flash at its published widths (causal LM on "
                        "next-token batches, the multi-token-prediction "
                        "module and its loss term included), cut to one "
                        "chip's share of an EP-way expert-parallel group: "
                        "the first 1/EP of the 64 routed experts of each "
                        "sparse layer, the dense layer and the first LAYERS "
                        "sparse layers of the 47, --vocab rows of the 154880 "
                        "of embedding and head; latent-attention mixers, "
                        "router (all 64 outputs) and shared expert whole "
                        "(docs/glm4_moe_lite.md)")
    p.add_argument("--laguna", type=int, default=None, metavar="EP",
                   help="Laguna-S-2.1 at its published widths (causal LM on "
                        "next-token batches), cut to one chip's share of an "
                        "EP-way expert-parallel group: the first 1/EP of the "
                        "256 routed experts of each sparse layer, the dense "
                        "layer 0 and one whole period after it (three "
                        "sliding-window layers, one full YaRN layer) of the "
                        "48, --vocab rows of the 100352 of embedding and "
                        "head; attention, gates, router (all 256 outputs) "
                        "and shared expert whole (docs/laguna.md)")
    p.add_argument("--distributed", action="store_true")
    p.add_argument("--zero", action="store_true",
                   help="ZeRO sharded optimizer (DistributedFusedLAMB)")
    p.add_argument("--moe", type=int, default=0, metavar="E",
                   help="use a Mixture-of-Experts FFN with E experts "
                        "(single-device MoE here; for SHARDED expert "
                        "parallelism use --plan, which materializes the "
                        "ep engine)")
    p.add_argument("--plan", action="store_true",
                   help="planner-driven parallelism: cost-model search "
                        "(plan.search) over this config's own profiled "
                        "step, then run the winner through "
                        "spmd.build_plan_step — dp/tp/sp/pp/ep as engine "
                        "families instead of hand-wired sharding flags")
    p.add_argument("--attn", default="default",
                   choices=("default", "fast"),
                   help="attention impl: 'fast' = the contrib flash "
                        "Pallas kernel (the reference examples' "
                        "fast_self_multihead_attn switch)")
    p.add_argument("--state-dtype", default=None, choices=[None, "bf16"],
                   help="store optimizer moments in bf16 (fp32 math; "
                        "26->18 B/param of step traffic — "
                        "docs/performance.md)")
    p.add_argument("--remat", action="store_true",
                   help="jax.checkpoint each layer (recompute activations "
                        "in backward) — O(1)-in-depth activation memory "
                        "for long sequences / deep stacks")
    p.add_argument("--print-freq", type=int, default=10)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--auto-resume", default=None, metavar="DIR",
                   help="drive the standard path through apex_tpu."
                        "resilience.TrainGuard: rotating checkpoints in "
                        "DIR, SIGTERM -> snapshot + clean exit, resume "
                        "from the newest checkpoint on restart (not "
                        "supported with --zero)")
    p.add_argument("--save-every", type=int, default=50,
                   help="guard checkpoint cadence in steps (--auto-resume)")
    return p.parse_args(argv)


_SYN_POOL = 64           # distinct token ids the synthetic corpus uses


@functools.lru_cache(maxsize=4)
def _syn_pool(vocab):
    """The corpus's token ids: fixed seed, spread over the vocabulary."""
    return np.random.RandomState(1234).choice(
        np.arange(1, vocab), size=min(_SYN_POOL, vocab - 1), replace=False)


def synthetic_mlm(rng, batch, seq, vocab):
    """Synthetic MLM batch over a fixed pool of ``_SYN_POOL`` token ids
    spread across the vocabulary (pool seed independent of the batch
    seed).  The pool makes the corpus LEARNABLE — its unigram prior takes
    the loss from ln(vocab) towards ln(pool) — which is what a numerics
    proof on hardware checks; tokens uniform over the whole vocabulary
    bound the loss at ln(vocab) from the first step and prove nothing
    (the same posture as the imagenet example's class prototypes)."""
    pool = _syn_pool(vocab)
    tokens = pool[rng.randint(0, len(pool), size=(batch, seq))].astype(
        np.int32)
    targets = tokens.copy()
    mask = rng.rand(batch, seq) < 0.15
    tokens[mask] = 0                      # [MASK]
    weights = mask.astype(np.float32)
    return tokens, targets, weights


_SYN_COMMON = (8, 0.9)   # an eighth of the ids is common: 90% of fresh draws
_SYN_FOLLOW = 0.5        # share of positions that follow the rule


@functools.lru_cache(maxsize=4)
def _syn_rule(vocab):
    """The next-token corpus's fixed rule (fixed seed, like the MLM pool's):
    which ids are common, and every id's successor — a permutation that
    keeps common ids common, so following it leaves the frequencies alone."""
    ids = np.random.RandomState(1234).permutation(vocab).astype(np.int32)
    common, rare = ids[: vocab // _SYN_COMMON[0]], ids[vocab // _SYN_COMMON[0]:]
    successor = np.empty(vocab, np.int32)
    successor[common] = np.roll(common, 1)
    successor[rare] = np.roll(rare, 1)
    return common, rare, successor


def synthetic_next_token(rng, batch, seq, vocab):
    """Synthetic causal-LM batch over the WHOLE vocabulary.  A position is a
    fresh draw — a common id with probability 0.9, else a rare one, uniform
    within each kind — or, with probability ``_SYN_FOLLOW``, the fixed
    successor of the id before it.  LEARNABLE: the common ids' prior is a
    nat and a half under ln(vocab), and half the targets are a function of
    the token before.  No id carries more than 0.9 / (vocab / 8) of the
    tokens, so what a batch does to the model — the load of a routed expert,
    say — is an average over thousands of ids and does not hang on which few
    a seed drew (the MLM corpus's 64-id pool would).  ``targets`` are the
    tokens shifted by one; the last position has none and weighs 0."""
    common, rare, successor = _syn_rule(vocab)
    fresh = np.where(rng.rand(batch, seq) < _SYN_COMMON[1],
                     common[rng.randint(0, len(common), size=(batch, seq))],
                     rare[rng.randint(0, len(rare), size=(batch, seq))])
    follow = rng.rand(batch, seq) < _SYN_FOLLOW
    tokens = fresh.astype(np.int32)
    for t in range(1, seq):
        tokens[:, t] = np.where(follow[:, t], successor[tokens[:, t - 1]],
                                tokens[:, t])
    targets = np.roll(tokens, -1, axis=1)
    weights = np.ones((batch, seq), np.float32)
    weights[:, -1] = 0.0
    return tokens, targets, weights


def _mask_mlm(tokens, seed, step_idx):
    """MLM masking pure in ``(seed, step)`` — applied to REAL token
    shards so resume/rollback replay the exact masked batch for any
    global step (the same seeding contract as ``batch_at``)."""
    rs = np.random.RandomState((seed * 1000003 + step_idx) % (2 ** 31 - 1))
    targets = tokens.copy()
    mask = rs.rand(*tokens.shape) < 0.15
    tokens = tokens.copy()
    tokens[mask] = 0                      # [MASK]
    return {"tokens": tokens, "targets": targets,
            "weights": mask.astype(np.float32)}


def sharded_mlm_loader(args, steps):
    """Seekable shard-addressed MLM loader over ``--data``'s ``.npz``
    token shards (``apex_tpu.data.sharded``): checksummed shards, pure
    addressing, deterministic per-step masking — ``loader(step)``
    replays bitwise, which is what ``--auto-resume``'s manifest cursor
    and the elastic resize guarantee need (docs/data.md)."""
    from apex_tpu.data import ShardedLoader, open_dataset

    def tf(b, step_idx):
        toks = b["tokens"]
        if toks.shape[1] < args.seq_len:
            raise ValueError(
                f"token shards are {toks.shape[1]} wide < --seq-len "
                f"{args.seq_len}")
        return _mask_mlm(toks[:, :args.seq_len].astype(np.int32),
                         args.seed, step_idx)

    return ShardedLoader(open_dataset(args.data),
                         global_batch=args.batch_size, seed=args.seed,
                         num_steps=steps, transform=tf)


def _device_batch(np_batch, sharding):
    return {k: jax.device_put(v, sharding) for k, v in np_batch.items()}


def run_standard(args, cfg, mesh):
    """amp O5 + FusedLAMB, data-parallel over the mesh's ``data`` axis: each
    device runs the step on its shard of the batch inside ``shard_map`` and
    the gradients are averaged by ``DistributedDataParallel``.  The update
    is replicated — every device applies the whole of it — so it runs leaf
    by leaf in the leaves' own layouts (``impl="xla"``: masters and moments
    are trees shaped like the parameters); one flat buffer a field is what
    a SHARDED update slices (``run_zero``, ``parallel.weight_update``), and
    packing a tiled leaf into it is a relayout each way (PERF.md section 6,
    PR 37).  (Not GSPMD auto-partitioning: the Pallas kernels — flash
    attention, xentropy — have no partitioning rule, so on a TPU "Mosaic kernels cannot be
    automatically partitioned"; under ``shard_map`` each device simply
    runs them on its shard.)"""
    from jax import shard_map
    from apex_tpu.parallel import DistributedDataParallel
    init_fn, loss_impl = {
        MoETransformerConfig: (moe_transformer_init, moe_transformer_loss),
        Lfm2Config: (lfm2_init, lfm2_loss),
        NemotronHConfig: (nemotron_h_init, nemotron_h_loss),
        Qwen3NextConfig: (qwen3_next_init, qwen3_next_loss),
        Glm4MoeLiteConfig: (glm4_moe_lite_init, glm4_moe_lite_loss),
        LagunaConfig: (laguna_init, laguna_loss),
    }.get(type(cfg), (transformer_init, transformer_loss))
    opt = FusedLAMB(lr=args.lr, weight_decay=0.01, max_grad_norm=1.0,
                    impl="xla",
                    state_dtype=jnp.bfloat16 if args.state_dtype else None)
    # ONE program makes the float32 parameters and the amp state that owns
    # its copies of them, replicated over the mesh where it is written: no
    # second copy is placed (init, then amp.initialize and a device_put,
    # peaked at 26 bytes a parameter; the step's footprint is 16) and the
    # first step neither re-lays-out the state nor compiles twice.  The key
    # is an argument, so every seed runs the same cached program.  The span
    # is the host's time in the call: nobody waits for the device here.
    with telemetry.trace.setup_tracer().span("setup.state"):
        state = jax.jit(
            lambda key: amp.initialize(init_fn(key, cfg), opt,
                                       opt_level=args.opt_level, verbosity=0),
            out_shardings=NamedSharding(mesh, P()))(
                jax.random.PRNGKey(args.seed))
    sharding = NamedSharding(mesh, P("data"))

    # donate the amp state: every leaf of master / m / v and of the model
    # copy is written anew by its apply fusion, so in-place HBM reuse must
    # happen here at the jit boundary — at BERT-large scale the un-donated
    # transient would be an extra ~4 GB of fp32 state.  Safe: amp.initialize
    # never aliases buffers between the model and master trees for this
    # param family.
    ddp = DistributedDataParallel(axis_name="data")

    @functools.partial(jax.jit, donate_argnums=0)
    @functools.partial(
        shard_map, mesh=mesh, in_specs=(P(), P("data")),
        out_specs=(P(), P()),
        check_vma=False)            # interpret-mode pallas limitation
    def train_step(state, batch):
        def loss_fn(p):
            loss = loss_impl(p, batch, cfg)
            return amp.scale_loss(loss, state), loss
        g, loss = jax.grad(loss_fn, has_aux=True)(state.model_params)
        g = ddp.allreduce_grads(g)
        return amp.amp_step(state, g), jax.lax.pmean(loss, "data")

    def step(state, np_batch):
        return train_step(state, _device_batch(np_batch, sharding))

    step.trace = lambda state, np_batch: train_step.trace(
        state, _device_batch(np_batch, sharding))
    step.optimizer_steps = lambda state: int(state.opt_state.count)
    return state, step


def run_zero(args, cfg, mesh):
    """ZeRO: DistributedFusedLAMB inside shard_map (sharded opt state)."""
    from jax import shard_map
    from apex_tpu.contrib.optimizers import DistributedFusedLAMB

    params = jax.jit(
        lambda: transformer_init(jax.random.PRNGKey(args.seed), cfg))()
    opt = DistributedFusedLAMB(
        lr=args.lr, weight_decay=0.01, max_grad_norm=1.0,
        bf16_allgather=True,
        state_dtype=jnp.bfloat16 if args.state_dtype else None)
    rep = jax.tree_util.tree_map(lambda _: P(), params)
    sspec = opt.state_pspecs()

    @functools.partial(shard_map, mesh=mesh, in_specs=(rep,),
                       out_specs=sspec)
    def init_fn(p):
        return opt.init(p)

    opt_state = jax.jit(init_fn)(params)
    sharding = NamedSharding(mesh, P("data"))

    # donate the (params, sharded opt state) carry: the stage-1 kernels
    # write fresh buffers (PERF_NOTES §2), so in-place HBM reuse happens
    # at this jit boundary
    @functools.partial(jax.jit, donate_argnums=0)
    def train_step(carry, batch):
        params, opt_state = carry

        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(rep, sspec,
                      jax.tree_util.tree_map(lambda _: P("data"), batch)),
            out_specs=(rep, sspec, P()),
            check_vma=False)        # interpret-mode pallas limitation
        def inner(p, s, local_batch):
            local = {k: v for k, v in local_batch.items()}
            loss, g = jax.value_and_grad(
                lambda p_: transformer_loss(p_, local, cfg))(p)
            new_p, new_s = opt.step(s, g, p)
            return new_p, new_s, jax.lax.pmean(loss, "data")

        new_p, new_s, loss = inner(params, opt_state, batch)
        return (new_p, new_s), loss

    carry = (jax.device_put(params, NamedSharding(mesh, P())), opt_state)

    class _State:            # match run_standard's (state, step) shape
        pass

    holder = _State()
    holder.carry = carry

    def step(holder_state, np_batch):
        holder.carry, loss = train_step(
            holder.carry, _device_batch(np_batch, sharding))
        return holder, loss

    step.trace = lambda holder_state, np_batch: train_step.trace(
        holder.carry, _device_batch(np_batch, sharding))
    step.optimizer_steps = lambda holder_state: int(holder.carry[1].count)
    return holder, step


def run_plan(args, cfg):
    """Planner-driven parallelism (``--plan``): the cost-model search
    (``plan.search``) over a profile of THIS config's train step; the
    chosen plan is materialized through
    ``spmd.build_plan_step``.  This replaces hand-wired sharding flags
    for the model-parallel families: tp, sp, pipeline (GPipe stages x
    microbatches) and expert parallelism all arrive as plannable,
    measurable engines — an ep winner builds the sharded switch-MoE
    step the old single-device ``--moe`` wiring could not."""
    from apex_tpu.parallel import plan as planmod
    from apex_tpu.parallel import spmd as spmdmod

    n_dev = len(jax.devices())
    prof, _, _ = planmod.flagship_profile(
        cfg=cfg, global_batch=args.batch_size)
    ranked = planmod.search(prof, n_dev)
    if not ranked:
        raise SystemExit(f"--plan: no feasible plan at {n_dev} chips "
                         f"for batch {args.batch_size}")
    chosen = ranked[0]
    print(f"=> plan [cost-model search ({len(ranked)} feasible)]: "
          f"{chosen.describe()}")

    rng = np.random.RandomState(args.seed)
    losses, tput = AverageMeter("mlm_loss"), Throughput()
    with chosen.apply(jax.devices()[: chosen.chips]) as mesh:
        carry, step, info = spmdmod.build_plan_step(
            cfg, mesh, chosen, global_batch=args.batch_size, lr=args.lr,
            meter=False)
        print(f"=> engine {info.get('engine')} (family "
              f"{info.get('family')}) on {chosen.chips} device(s)")
        for i in range(args.steps):
            tokens = rng.randint(0, cfg.vocab_size,
                                 size=(args.batch_size, cfg.max_len)
                                 ).astype(np.int32)
            carry, loss = step(carry, jnp.asarray(tokens))
            if (i + 1) % args.print_freq == 0 or i == args.steps - 1:
                losses.update(float(loss))
                rate = tput.tick(args.print_freq * args.batch_size)
                print(f"step {i + 1:4d}  {losses}  "
                      f"{rate:.1f} sequences/sec", flush=True)
    print(f"=> done: final loss {losses.val:.4f}")
    return losses.val


def lfm2_config(args):
    """``--lfm2 DENSE PERIODS HELD``: the published widths, the cut's depth,
    experts and ``--vocab``."""
    dense, periods, held = args.lfm2
    return lfm2_24b_a2b_config(
        vocab_size=args.vocab, num_dense_layers=dense,
        layer_types=lfm2_cut_layer_types(dense, periods),
        experts_held=(0, held), dtype=jnp.bfloat16, remat=args.remat,
        attn_impl=args.attn)


def nemotron_h_config(args):
    """``--nemotron-h TP EP PERIODS``: the published widths, and one chip's
    share of heads, experts, depth and ``--vocab``."""
    tp, ep, periods = args.nemotron_h
    whole = nemotron3_super_120b_a12b_config()
    return nemotron3_super_120b_a12b_config(
        vocab_size=args.vocab,
        hybrid_override_pattern=nemotron_h_cut_pattern(periods),
        mamba_heads_held=(0, whole.mamba_num_heads // tp),
        attention_heads_held=(0, whole.num_attention_heads // tp),
        experts_held=(0, whole.n_routed_experts // ep),
        dtype=jnp.bfloat16, remat=args.remat, attn_impl=args.attn)


def qwen3_next_config(args):
    """``--qwen3-next EP PERIODS``: the published widths, and one chip's
    share of experts, depth and ``--vocab``."""
    ep, periods = args.qwen3_next
    whole = qwen3_next_80b_a3b_config()
    return qwen3_next_80b_a3b_config(
        vocab_size=args.vocab,
        num_hidden_layers=periods * whole.full_attention_interval,
        experts_held=(0, whole.num_experts // ep),
        dtype=jnp.bfloat16, remat=args.remat, attn_impl=args.attn)


def glm4_moe_lite_config(args):
    """``--glm4-moe-lite EP LAYERS``: the published widths, and one chip's
    share of experts, depth and ``--vocab``."""
    ep, layers = args.glm4_moe_lite
    whole = glm47_flash_config()
    return glm47_flash_config(
        vocab_size=args.vocab,
        num_hidden_layers=whole.first_k_dense_replace + layers,
        experts_held=(0, whole.num_experts // ep), dtype=jnp.bfloat16,
        remat=args.remat, attn_impl=args.attn)


#: the leading dense layer and one whole period of (full, window, window,
#: window) after it: the least a cut keeps (layers 0-4)
LAGUNA_CUT_LAYERS = 5


def laguna_config(args):
    """``--laguna EP``: the published widths, and one chip's share of
    experts, depth and ``--vocab``."""
    whole = laguna_s_2_1_config()
    return laguna_s_2_1_config(
        LAGUNA_CUT_LAYERS, vocab_size=args.vocab,
        experts_held=(0, whole.num_experts // args.laguna),
        dtype=jnp.bfloat16, remat=args.remat, attn_impl=args.attn)


def main(argv=None, report=None):
    """Train; returns the last printed loss.  ``report``, a dict the
    caller owns, is filled (standard and ``--zero`` paths) with what a
    check of the run needs: the printed ``losses``, the number of
    ``optimizer_steps`` actually applied (a step the scaler skipped does
    not count), and ``state`` / ``step`` / ``batch`` so the compiled
    step can be inspected through ``step.trace(state, batch)``."""
    args = parse_args(argv)
    if args.moe and (args.bert_large or args.zero):
        raise SystemExit("--moe combines with the standard path only")
    if args.lfm2 and (args.bert_large or args.moe or args.zero or args.plan
                      or args.data):
        raise SystemExit("--lfm2 is a model preset of the standard path on "
                         "synthetic next-token batches")
    if args.nemotron_h and (args.bert_large or args.lfm2 or args.moe
                            or args.zero or args.plan or args.data):
        raise SystemExit("--nemotron-h is a model preset of the standard "
                         "path on synthetic next-token batches")
    if args.qwen3_next and (args.bert_large or args.lfm2 or args.nemotron_h
                            or args.moe or args.zero or args.plan
                            or args.data):
        raise SystemExit("--qwen3-next is a model preset of the standard "
                         "path on synthetic next-token batches")
    if args.glm4_moe_lite and (args.bert_large or args.lfm2 or args.nemotron_h
                               or args.qwen3_next or args.moe or args.zero
                               or args.plan or args.data):
        raise SystemExit("--glm4-moe-lite is a model preset of the standard "
                         "path on synthetic next-token batches")
    if args.laguna and (args.bert_large or args.lfm2 or args.nemotron_h
                        or args.qwen3_next or args.glm4_moe_lite or args.moe
                        or args.zero or args.plan or args.data):
        raise SystemExit("--laguna is a model preset of the standard path on "
                         "synthetic next-token batches")
    if args.plan and (args.moe or args.zero or args.distributed
                      or args.auto_resume):
        raise SystemExit("--plan owns the parallelism decision — it does "
                         "not combine with --moe/--zero/--distributed/"
                         "--auto-resume")
    if args.bert_large:
        cfg = bert_large_config(dtype=jnp.bfloat16, remat=args.remat,
                                attn_impl=args.attn)
    elif args.lfm2:
        cfg = lfm2_config(args)
    elif args.nemotron_h:
        cfg = nemotron_h_config(args)
    elif args.qwen3_next:
        cfg = qwen3_next_config(args)
    elif args.glm4_moe_lite:
        cfg = glm4_moe_lite_config(args)
    elif args.laguna:
        cfg = laguna_config(args)
    elif args.moe:
        cfg = MoETransformerConfig(
            vocab_size=args.vocab, max_len=args.seq_len,
            num_layers=args.layers, d_model=args.d_model,
            num_heads=args.heads, d_ff=4 * args.d_model,
            num_experts=args.moe, dtype=jnp.bfloat16, remat=args.remat,
            attn_impl=args.attn)
    else:
        cfg = TransformerConfig(
            vocab_size=args.vocab, max_len=args.seq_len,
            num_layers=args.layers, d_model=args.d_model,
            num_heads=args.heads, d_ff=4 * args.d_model,
            dtype=jnp.bfloat16, remat=args.remat, attn_impl=args.attn)
    if args.plan:
        return run_plan(args, cfg)
    n_dev = len(jax.devices()) if (args.distributed or args.zero) else 1
    if args.batch_size % n_dev:
        raise ValueError(f"batch {args.batch_size} must divide {n_dev}")
    mesh = create_mesh({"data": n_dev}, devices=jax.devices()[:n_dev])
    causal_lm = bool(args.lfm2 or args.nemotron_h or args.qwen3_next
                     or args.glm4_moe_lite or args.laguna)
    print(f"=> {n_dev} device(s), {'ZeRO' if args.zero else 'standard'} "
          f"optimizer, layers="
          f"{cfg.num_hidden_layers if causal_lm else cfg.num_layers} d="
          f"{cfg.hidden_size if causal_lm else cfg.d_model} "
          f"seq={args.seq_len}")

    rng = np.random.RandomState(args.seed)
    losses, tput = AverageMeter("mlm_loss"), Throughput()
    synthetic = synthetic_next_token if causal_lm else synthetic_mlm

    if args.auto_resume:
        if args.zero:
            raise SystemExit("--auto-resume drives the standard path only "
                             "(the ZeRO holder carry is not a pure pytree)")
        from apex_tpu.resilience import GuardConfig, TrainGuard

        if args.data:
            # real token shards through the seekable data plane: the
            # loader IS batches(step), and the guard records its
            # data-plane cursor (index digest + epoch/shard position)
            # in the checkpoint manifest
            batch_at = sharded_mlm_loader(args, args.steps)
        else:
            def batch_at(step_idx):
                # per-step seeding: resume and rollback replay the
                # exact batch for any global step (the sequential-rng
                # path below cannot be re-entered mid-stream)
                rs = np.random.RandomState(
                    (args.seed * 1000003 + step_idx) % (2 ** 31 - 1))
                tokens, targets, weights = synthetic(
                    rs, args.batch_size, args.seq_len, cfg.vocab_size)
                return {"tokens": tokens, "targets": targets,
                        "weights": weights}

        def on_check(step_idx, window):
            losses.update(window[-1])
            rate = tput.tick(len(window) * args.batch_size)
            print(f"step {step_idx:4d}  {losses}  "
                  f"{rate:.1f} sequences/sec", flush=True)

        with use_mesh(mesh):
            state, step = run_standard(args, cfg, mesh)
            guard = TrainGuard(step, GuardConfig(
                ckpt_dir=args.auto_resume,
                save_every_steps=args.save_every,
                check_every=max(1, args.print_freq),
                floor_patience=3), on_check=on_check)
            state, rep = guard.run(state, batch_at, args.steps)
        if rep.resumed_from is not None:
            print(f"=> guard resumed from step {rep.resumed_from}")
        print(f"=> guard: {rep.status} at step {rep.final_step}/"
              f"{args.steps}  (rollbacks {rep.rollbacks}, checkpoints "
              f"{rep.checkpoints})", flush=True)
        if rep.status != "completed":
            raise SystemExit(3)
        print(f"=> done: final loss {losses.val:.4f}")
        return losses.val

    data_it = (iter(sharded_mlm_loader(args, args.steps)) if args.data
               else None)
    with use_mesh(mesh):
        state, step = (run_zero if args.zero else run_standard)(args, cfg,
                                                                mesh)
        history = []
        for i in range(args.steps):
            if data_it is not None:
                batch = next(data_it)      # prefetched shard-addressed
            else:
                tokens, targets, weights = synthetic(
                    rng, args.batch_size, args.seq_len, cfg.vocab_size)
                batch = {"tokens": tokens, "targets": targets,
                         "weights": weights}
            state, loss = step(state, batch)
            if (i + 1) % args.print_freq == 0 or i == args.steps - 1:
                losses.update(float(loss))
                history.append(losses.val)
                rate = tput.tick(args.print_freq * args.batch_size)
                print(f"step {i + 1:4d}  {losses}  "
                      f"{rate:.1f} sequences/sec", flush=True)
        if report is not None:
            report.update(losses=history, state=state, step=step,
                          batch=batch,
                          optimizer_steps=step.optimizer_steps(state))
    print(f"=> done: final loss {losses.val:.4f}")
    return losses.val


if __name__ == "__main__":
    from apex_tpu.utils.platform import enable_compile_cache
    enable_compile_cache()
    main()
