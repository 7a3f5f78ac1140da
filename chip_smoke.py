#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Runs, in ONE process, the library's main path through the entry points a
user would call, at the full width of the models the repo supports:

  native      the csrc helpers build from the committed sources
  kernels     every pallas_call compiled by Mosaic at a production shape
              and compared with its XLA twin
  serve       InferenceEngine + ContinuousBatcher at BERT-large width
  resnet50    examples/imagenet/main_amp.py, b128, amp O2 + FusedAdam
  bert_large  examples/bert/pretrain.py, 24L b8 x s512, flash + remat,
              amp O5 + per-leaf FusedLAMB; the compiled step is shown to
              contain the Pallas kernels; then the optimizer alone on that
              tree, per-leaf beside the flat engine: the masters' largest
              difference and, on the chip, both times
  lfm2        the same example with --lfm2 1 1 8: LFM2-24B-A2B's widths,
              one dense layer and one period, 8 of 64 experts, b8 x s4096
              (the benchmark cell's shapes); the step holds the kernels and
              the grouped products, and loss and gradients match the
              XLA-attention twin on one sequence; then one expert layer
              at one walk of its buffer and at a forced three against
              its float32 XLA twin, gradients included
  nemotron_h  one Mamba-2 layer and one LatentMoE layer of
              NVIDIA-Nemotron-3-Super-120B-A12B at the published widths and
              the benchmark cell's share (16 heads, 8 of 512 experts top-22
              in the 1024-wide latent), each against its float32 XLA twin,
              forward and every gradient; the expert layer at one walk of
              its buffer and at a forced three
  qwen3_next  Qwen3-Next-80B-A3B-Instruct at the published widths and the
              benchmark cell's share (32 of 512 experts top-10): the gated
              delta rule — at the published shape the Pallas kernel pair,
              which the phase insists on — against the sequential
              recurrence at S 4096 x 32 heads and timed beside its
              jax.numpy twin, a Gated DeltaNet mixer and the gated attention
              mixer (flash at D 256) against float32 twins, the sparse FFN
              at one walk of its buffer and at a forced three, forward and
              every gradient
  glm4_moe_lite
              GLM-4.7-Flash at the published widths and the benchmark
              cell's share (--glm4-moe-lite 8 4: the dense layer, four
              sparse layers with 8 of 64 experts top-4, the MTP module):
              the whole cut on one sequence of 4096 through flash against
              the same model through XLA attention, loss and every
              parameter leaf's gradient; one latent-attention mixer through
              flash at S 4096 against its float32 twin, output and every
              gradient
  multichip   (when jax finds >= 4 devices) the trainers --distributed /
              --zero / --sync-bn and one step of every plan family on a
              4-device mesh, each device holding its share

    python chip_smoke.py                # on a TPU; anything else exits 2
    python chip_smoke.py --rehearse     # the same phases, tiny, on the CPU
    python chip_smoke.py --only kernels,serve

Weights are random from a seed; nothing is read from the network.  Per
phase it prints wall / compile / run seconds, the device allocator's
high-water mark and the compile cache's hits — facts about this run,
not benchmark metrics.  A phase that raises prints its traceback, the
other phases still run, and the exit code is 1.  The last line of stdout
is one JSON object: ``{"ok": true, "device": {"platform": ..., "kind":
..., "count": ...}}``; the full report goes to
``chiprun_out/chip_smoke.json``.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def load_example(rel_path: str):
    """The shipped example at ``rel_path`` as a module (its ``main(argv)``
    is the entry point a user runs)."""
    name = "chip_smoke_" + os.path.basename(rel_path)[:-3]
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, rel_path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# what jax spent compiling, and whether the persistent cache answered
# ---------------------------------------------------------------------------

class CompileMeter:
    """Sums jax's own compile-time and compile-cache events."""

    _COMPILE = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, name, secs, **_):
        if name in self._COMPILE:
            self.compile_s += secs

    def _on_event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.compile_s, self.hits, self.misses


def memory_fact(devices) -> dict:
    """Allocator counters of each device, where the backend reports them
    (the CPU backend does not)."""
    stats = [d.memory_stats() for d in devices]
    if not all(stats):
        return {"bytes_in_use": None, "peak_bytes_in_use": None}
    return {"bytes_in_use": [int(s["bytes_in_use"]) for s in stats],
            "peak_bytes_in_use": [int(s["peak_bytes_in_use"]) for s in stats]}


def attempt(label: str, fn) -> dict:
    """Run one phase / leg / check: its facts with ``ok: True``, or — after
    printing the traceback — ``ok: False`` and the error; seconds either
    way.  A failure never stops the ones after it."""
    t0 = time.monotonic()
    try:
        rec = {"ok": True, **fn()}
    except Exception:
        say(f"{label} FAILED:")
        traceback.print_exc(file=sys.stdout)
        rec = {"ok": False, "error": traceback.format_exc()[-1500:]}
    rec["s"] = round(time.monotonic() - t0, 2)
    gc.collect()
    return rec


# ---------------------------------------------------------------------------
# small numeric helpers
# ---------------------------------------------------------------------------

def rel_err(got, want) -> float:
    """max |got - want| over max |want|, over every leaf, in float32."""
    import jax
    import jax.numpy as jnp
    worst = 0.0
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        g = jnp.asarray(g, jnp.float32)
        w = jnp.asarray(w, jnp.float32)
        if g.shape != w.shape:
            raise AssertionError(f"shape {g.shape} != reference {w.shape}")
        if not bool(jnp.all(jnp.isfinite(g))):
            return float("inf")
        denom = float(jnp.max(jnp.abs(w))) or 1.0
        worst = max(worst, float(jnp.max(jnp.abs(g - w))) / denom)
    return worst


def norm_rel_err(got, want) -> float:
    """|got - want| over |want| (2-norms), in float32."""
    import jax.numpy as jnp
    got, want = (jnp.asarray(t, jnp.float32) for t in (got, want))
    return float(jnp.linalg.norm(got - want)) / (
        float(jnp.linalg.norm(want)) or 1.0)


def median_ms(fn, *args, runs: int = 10) -> float:
    """Median wall milliseconds of ``fn(*args)`` run to its end, after one
    warm-up: a fact about this run on this device, not a benchmark metric."""
    import jax
    jax.block_until_ready(fn(*args))
    took = []
    for _ in range(runs):
        start = time.perf_counter()
        jax.block_until_ready(fn(*args))
        took.append(time.perf_counter() - start)
    return round(sorted(took)[len(took) // 2] * 1e3, 3)


def pallas_kernel_names(traced) -> set:
    """Names of every ``pallas_call`` in a traced step (``jit(f).trace``),
    walking into scan / remat / custom-vjp / pjit sub-jaxprs."""
    found = set()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.add(str(eqn.params["name"]))
            for val in eqn.params.values():
                for sub in (val if isinstance(val, (tuple, list)) else (val,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)

    walk(traced.jaxpr.jaxpr)
    return found


def check_trainer_report(report: dict, steps: int) -> dict:
    """The facts a trainer run must show: every printed loss finite, the
    loss falling (last third of the run below the first third, which a
    noisy small batch cannot fake), and every step applied (none skipped
    by the loss scaler)."""
    import math
    losses = [float(x) for x in report["losses"]]
    if len(losses) != steps:
        raise AssertionError(f"{len(losses)} losses printed for {steps} steps")
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"non-finite loss: {losses}")
    third = max(1, steps // 3)
    if not sum(losses[-third:]) < sum(losses[:third]):
        raise AssertionError(f"loss did not fall: {losses}")
    if report["optimizer_steps"] != steps:
        raise AssertionError(
            f"{report['optimizer_steps']} optimizer steps applied of "
            f"{steps}: the loss scaler skipped some")
    return {"losses": [round(x, 4) for x in losses],
            "optimizer_steps": report["optimizer_steps"]}


def check_holds_share(tree, devices, what: str, balance=False) -> None:
    """Every array of ``tree`` lives on all of ``devices`` (replicated or
    sharded) — nothing left behind on device 0.  ``balance`` (for states
    of gigabytes, where stray kilobytes cannot tip it): where the
    allocator reports, no device holds less than half of what the
    fullest holds."""
    import jax
    for path, x in jax.tree_util.tree_leaves_with_path(tree):
        if hasattr(x, "sharding") and x.sharding.device_set != set(devices):
            raise AssertionError(
                f"{what}{jax.tree_util.keystr(path)} is on "
                f"{len(x.sharding.device_set)} device(s), not "
                f"{len(devices)}")
    in_use = balance and memory_fact(devices)["bytes_in_use"]
    if in_use and min(in_use) < 0.5 * max(in_use):
        raise AssertionError(f"{what}: bytes in use per device {in_use}")


# ---------------------------------------------------------------------------
# phases.  Each takes the run context and returns a dict of facts; it
# raises on any failure.
# ---------------------------------------------------------------------------

def phase_native(ctx) -> dict:
    """Both csrc libraries build from the committed sources (or are found
    already built) in csrc/_build/."""
    from apex_tpu.utils import native
    out = {}
    for src, lib in (("host_pack.cpp", "libapex_tpu_host"),
                     ("prefetch.cpp", "libapex_tpu_prefetch")):
        path, built_now = native.build(src, lib)
        out[src] = {"built_now": built_now,
                    "lib": os.path.relpath(path, REPO)}
        say(f"  {src}: {'built' if built_now else 'found'} {out[src]['lib']}")
    from apex_tpu.data import native_available as loader_native
    from apex_tpu.utils.host_pack import native_available as pack_native
    if not (loader_native() and pack_native()):
        raise AssertionError("a native library built but did not load")
    return out


def _kernel_checks(full: bool):
    """(name, tolerance, thunk -> rel_err) for every pallas_call in the
    repo.  ``full``: production shapes (BERT-large: B8 H16 D64, vocab
    30592, d 1024, d_ff 4096; flash also at S=2048 so the default blocks
    are reached un-clamped); else tiny interpret-mode shapes."""
    import jax
    import jax.numpy as jnp
    from apex_tpu.contrib.multihead_attn import flash as F
    from apex_tpu.contrib.xentropy import softmax_xentropy_loss
    from apex_tpu.multi_tensor_apply import kernels as K
    from apex_tpu.normalization import fused_layer_norm_affine
    from apex_tpu.ops.fused_mlp import dense_act

    bf16, f32 = jnp.bfloat16, jnp.float32
    key = jax.random.PRNGKey(0)

    def rnd(i, shape, dtype=f32, scale=1.0):
        return (scale * jax.random.normal(jax.random.fold_in(key, i), shape,
                                          f32)).astype(dtype)

    checks = []

    # -- flash attention: forward; the backward as the shape's rule tiles
    #    it (whole_key: one k block a head, dq finished in the kernel;
    #    resident at S 4096: the head's keys in VMEM, walked in pieces) and
    #    the three backwards forced apart --
    def flash_inputs(S, BH):
        D = 64
        q, k, v, do = (rnd(i, (BH, S, D), bf16, 0.5) for i in range(4))
        return q, k, v, do, jnp.zeros((1, 1, S), f32)

    def flash_fwd(S, BH, causal, rate=0.0):
        q, k, v, _, bias = flash_inputs(S, BH)
        got = jax.jit(lambda a, b, c: F.flash_attention(
            a, b, c, bias, 7, causal, rate, 1))(q, k, v)
        want = jax.jit(lambda a, b, c: F._xla_reference(
            a, b, c, bias, causal, rate, 7, 1))(q, k, v)
        return rel_err(got, want)

    def flash_bwd(S, BH, causal, impl, rate=0.0):
        q, k, v, do, bias = flash_inputs(S, BH)

        def ref(a, b, c):      # autodiff of the plain-XLA attention
            _, vjp = jax.vjp(lambda a_, b_, c_: F._xla_reference(
                a_, b_, c_, bias, causal, rate, 7, 1), a, b, c)
            return vjp(do)

        if impl == "xla":      # the public switch
            def run(a, b, c):
                _, vjp = jax.vjp(lambda a_, b_, c_: F.flash_attention(
                    a_, b_, c_, bias, 7, causal, rate, 1, "xla"), a, b, c)
                return vjp(do)
        else:
            # whole_key: nothing forced, the tile the rule gives this shape
            # (S 2048 must still compile, whichever path that is); split
            # (dq + dkv) forced; fused forced onto the 128x128 grid whose dq
            # leaves as f32 partials (nk > 1 wherever S > 128)
            forced = {"whole_key": {}, "resident": {},
                      "split": {"fuse": False},
                      "fused": {"fuse": True, "bq": 128, "bk": 128}}[impl]

            def run(a, b, c):
                out, lse = F._flash_fwd(a, b, c, bias, causal, rate, 7, 1)
                return F._flash_bwd(a, b, c, bias, causal, rate, 7, 1, out,
                                    lse, do, **forced)
        if impl == "whole_key" and S <= 512:
            tile = F._clamp_blocks(None, None, 64, 2, False, bwd="fused",
                                   sq=S, sk=S, causal=causal)
            if tile[1] < S:
                raise AssertionError(f"S {S}: the rule gave {tile}, nk > 1")
        if impl == "resident" and (
                F._whole_key_blocks(S, S, 64, 2, False, causal) is not None
                or F._resident_blocks(S, S, 64, 2, False) is None):
            raise AssertionError(f"S {S}: the rule does not answer resident")
        return rel_err(jax.jit(run)(q, k, v), jax.jit(ref)(q, k, v))

    for S, BH in (((512, 128), (2048, 32)) if full else ((128, 2),)):
        for causal in (True, False):
            tag = f"S{S}{'c' if causal else ''}"
            checks.append((f"flash_fwd[{tag}]", 3e-2,
                           lambda S=S, BH=BH, c=causal: flash_fwd(S, BH, c)))
            for impl in ("whole_key", "split", "fused", "xla"):
                checks.append((
                    f"flash_bwd_{impl}[{tag}]", 5e-2,
                    lambda S=S, BH=BH, c=causal, i=impl: flash_bwd(
                        S, BH, c, i)))
    if full:
        # the LFM2 cell's attention shape (BH small: the twin's scores are
        # BH x 64 MiB): nothing forced, the resident kernel must be what
        # the rule answers and what Mosaic compiles, with and without mask
        checks.append(("flash_fwd[S4096c]", 3e-2,
                       lambda: flash_fwd(4096, 4, True)))
        checks.append(("flash_bwd_resident[S4096c]", 5e-2,
                       lambda: flash_bwd(4096, 4, True, "resident")))
        checks.append(("flash_bwd_resident[S4096]", 5e-2,
                       lambda: flash_bwd(4096, 4, False, "resident")))
        checks.append(("flash_bwd_resident_dropout[S4096c]", 5e-2,
                       lambda: flash_bwd(4096, 4, True, "resident", 0.1)))
    S, BH = (512, 128) if full else (128, 2)
    # dropout: the in-kernel uint32 hash must rebuild the XLA mask bit for bit
    checks.append((f"flash_fwd_dropout[S{S}c]", 3e-2,
                   lambda: flash_fwd(S, BH, True, 0.1)))
    for impl in ("whole_key", "fused"):
        checks.append((f"flash_bwd_{impl}_dropout[S{S}c]", 5e-2,
                       lambda i=impl: flash_bwd(S, BH, True, i, 0.1)))

    # -- flash in the projection's layout (BERT's): qkv (B, S, 3·H·64) in,
    #    the context (B, S, H·64) out, two heads a 128-lane block; only the
    #    chip sees a lane or row the kernels leave unwritten --
    def flash_qkv(S, B, H, causal, rate, padded):
        qkv = rnd(5, (B, S, 3 * H * 64), bf16, 0.5)
        do = rnd(6, (B, S, H * 64), bf16)
        bias = jnp.zeros((1, 1, S), f32)
        if padded:      # the model's key padding, one sequence wholly dead
            pad = jnp.arange(S)[None, :] >= S - (S // 8) * jnp.arange(B)[:, None]
            bias = jnp.where(pad[:, None, :], -1e9, 0.0).astype(f32) \
                .at[1].set(F.NEG_INF)
        if F._packed_tile(S, H, 64, 2, False) is None:
            raise AssertionError(f"S {S}, {H} heads: not the projection layout")

        def twin(x):    # the heads transposed around plain-XLA attention
            q, k, v = (t.reshape(B, S, H, 64).transpose(0, 2, 1, 3)
                       .reshape(B * H, S, 64) for t in jnp.split(x, 3, -1))
            q = (q.astype(f32) / 8.0).astype(bf16)
            o = F._xla_reference(q, k, v, bias, causal, rate, 7, H)
            return o.reshape(B, H, S, 64).transpose(0, 2, 1, 3) \
                .reshape(B, S, H * 64)

        def both(fn):
            def run(x):
                o, vjp = jax.vjp(fn, x)
                return o, vjp(do)[0]
            return jax.jit(run)(qkv)
        return rel_err(both(lambda x: F.flash_attention_qkv(
            x, bias, 7, causal, rate, H)), both(twin))

    B, H = (8, 16) if full else (2, 2)
    for tag, causal, rate, padded in (("", False, 0.0, False),
                                      ("_padded", False, 0.0, True),
                                      ("_dropout", True, 0.1, False)):
        checks.append((f"flash_qkv{tag}[B{B}xS{S}xH{H}]", 5e-2,
                       lambda c=causal, r=rate, p=padded: flash_qkv(
                           S, B, H, c, r, p)))

    # -- flash's band (a sliding window: the Laguna cell's window layers),
    #    forward and both gradients through the public entry against the
    #    XLA band, at the one shape the band kernels admit: S 8192 x D 128
    #    at W 512 in bfloat16 (BH small: the twin's scores are BH x 256
    #    MiB).  The rehearsal leaves it out: interpret mode cannot reach
    #    that shape, and tests/L0/test_laguna.py holds the kernels to the
    #    XLA band at small ones --
    def flash_band(S, BH, W):
        q, k, v, do = (rnd(10 + i, (BH, S, 128), bf16, 0.5)
                       for i in range(4))
        bias = jnp.zeros((1, 1, S), f32)
        if not F._band_admitted(q, k, bias, W, 0.0):
            raise AssertionError(f"S {S}, W {W}: not the band kernels")

        def both(fn):
            def run(a, b, c):
                out, vjp = jax.vjp(fn, a, b, c)
                return out, vjp(do)
            return jax.jit(run)(q, k, v)
        return rel_err(
            both(lambda *t: F.flash_attention(*t, bias, 0, True, 0.0, BH,
                                              "auto", W)),
            both(lambda *t: F._xla_reference(*t, bias, True, 0.0, 0, BH, W)))

    if full:
        checks.append(("flash_band[S8192xW512]", 5e-2,
                       lambda: flash_band(8192, 4, 512)))

    # -- xentropy, forward and backward (ragged last vocabulary block) ----
    N, V = (4096, 30592) if full else (64, 1000)

    def xent(dtype):
        logits = rnd(10, (N, V), dtype, 2.0)
        labels = jax.random.randint(jax.random.fold_in(key, 11), (N,), 0, V)

        def run(impl):
            return jax.jit(jax.value_and_grad(
                lambda lg: softmax_xentropy_loss(
                    lg, labels, 0.1, 0, False, impl).sum()))(logits)
        return rel_err(run("pallas"), run("xla"))

    checks.append((f"xentropy[{N}x{V},f32]", 1e-4, lambda: xent(f32)))
    checks.append((f"xentropy[{N}x{V},bf16]", 2e-2, lambda: xent(bf16)))

    # -- layer norm, forward and backward ----------------------------------
    R, H = (4096, 1024) if full else (64, 256)

    def layer_norm(dtype):
        x = rnd(20, (R, H), dtype)
        w, b = 1.0 + rnd(21, (H,), f32, 0.1), rnd(22, (H,), f32, 0.1)

        def run(use_pallas):
            return jax.jit(jax.value_and_grad(
                lambda x_, w_, b_: fused_layer_norm_affine(
                    x_, w_, b_, (H,), use_pallas=use_pallas
                ).astype(f32).sum(), argnums=(0, 1, 2)))(x, w, b)
        return rel_err(run(True), run(False))

    checks.append((f"layer_norm[{R}x{H},f32]", 1e-4, lambda: layer_norm(f32)))
    checks.append((f"layer_norm[{R}x{H},bf16]", 2e-2,
                   lambda: layer_norm(bf16)))

    # -- fused GEMM + bias + activation --------------------------------------
    M, Kd, Nd = (4096, 1024, 4096) if full else (64, 128, 256)

    def mlp(dtype):
        x, w = rnd(30, (M, Kd), dtype, 0.1), rnd(31, (Kd, Nd), dtype, 0.1)
        b = rnd(32, (Nd,), dtype, 0.1)
        got = jax.jit(lambda a, c, d: dense_act(a, c, d, "relu"))(x, w, b)
        want = jax.jit(lambda a, c, d: jnp.maximum(jnp.dot(
            a, c, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=f32) + d.astype(f32), 0.0))(x, w, b)
        return rel_err(got, want)

    checks.append((f"dense_act[{M}x{Kd}x{Nd},f32]", 2e-2, lambda: mlp(f32)))
    checks.append((f"dense_act[{M}x{Kd}x{Nd},bf16]", 2e-2, lambda: mlp(bf16)))

    # -- the flat multi-tensor kernels ---------------------------------------
    total = 32 * 1024 * 1024 if full else 8192

    def multi_tensor():
        x, y = rnd(40, (total,)), rnd(41, (total,))
        scaled, _ = jax.jit(lambda a: K.multi_tensor_scale(a, 0.5))(x)
        axpby, _ = jax.jit(
            lambda a, b: K.multi_tensor_axpby(a, b, 2.0, -0.5))(x, y)
        norm = jax.jit(K.multi_tensor_l2norm)(x)
        return max(rel_err(scaled, x * 0.5),
                   rel_err(axpby, 2.0 * x - 0.5 * y),
                   rel_err(norm, jnp.sqrt(jnp.sum(x * x))))

    def moments():
        g, p = rnd(50, (total,), f32, 0.01), rnd(51, (total,))
        m, v = rnd(52, (total,), f32, 0.01), jnp.abs(rnd(53, (total,))) * 1e-4
        return g, p, m, v

    def adam_flat():
        g, p, m, v = moments()
        lr, b1, b2, eps, wd, rc1, rc2, inv = (1e-3, 0.9, 0.999, 1e-8, 0.01,
                                              1.2, 1.1, 0.5)
        scalars = jnp.asarray([[lr, b1, b2, eps, wd, rc1, rc2, inv]], f32)
        got = jax.jit(lambda *a: K.fused_adam_flat(
            *a, model_dtype=bf16))(g, p, m, v, scalars)

        def twin(g, p, m, v):
            g = g * inv
            m2 = b1 * m + (1.0 - b1) * g
            v2 = b2 * v + (1.0 - b2) * g * g
            p2 = p - lr * ((m2 * rc1) / (jnp.sqrt(v2 * rc2) + eps) + wd * p)
            return [p2, m2, v2, p2.astype(bf16)]
        want = jax.jit(twin)(g, p, m, v)
        # the bf16 model copy may round a last-bit-different fp32 value the
        # other way: one bf16 ulp (2^-8) there, fp32 agreement elsewhere
        return max(rel_err(got[:3], want[:3]),
                   rel_err(got[3], want[3]) * (1e-5 / 2 ** -8))

    def lamb_stage1_flat():
        g, p, m, v = moments()
        b1, b2, eps, wd, rc1, rc2, clip, inv, b3 = (
            0.9, 0.999, 1e-6, 0.01, 1.2, 1.1, 0.7, 0.5, 0.1)
        scalars = jnp.asarray(
            [[b1, b2, eps, wd, rc1, rc2, clip, inv, b3]], f32)
        got = jax.jit(K.fused_lamb_stage1_flat)(g, p, m, v, scalars)

        def twin(g, p, m, v):
            g = g * inv * clip
            m2 = b1 * m + b3 * g
            v2 = b2 * v + (1.0 - b2) * g * g
            return [(m2 * rc1) / (jnp.sqrt(v2 * rc2) + eps) + wd * p, m2, v2]
        return rel_err(got, jax.jit(twin)(g, p, m, v))

    checks.append((f"multi_tensor_scale_axpby_l2norm[{total}]", 1e-4,
                   multi_tensor))
    checks.append((f"fused_adam_flat[{total}]", 1e-5, adam_flat))
    checks.append((f"fused_lamb_stage1_flat[{total}]", 1e-5,
                   lamb_stage1_flat))
    return checks


def phase_kernels(ctx) -> dict:
    """Every pallas_call against its XLA twin.  All checks run; the phase
    fails when any raised or missed its tolerance."""
    def checked(tol, thunk):
        def run():
            err = thunk()
            if not err <= tol:
                raise AssertionError(f"rel_err {err:.3e} > tol {tol:.0e}")
            return {"rel_err": float(f"{err:.3e}"), "tol": tol}
        return run

    results = {}
    for name, tol, thunk in _kernel_checks(ctx["full"]):
        results[name] = attempt(f"kernel check {name}", checked(tol, thunk))
        say(f"  {name}: {results[name]}")
    bad = [n for n, r in results.items() if not r["ok"]]
    if bad:
        raise AssertionError(f"kernel checks failed: {bad}")
    return {"checks": results}


def phase_serve(ctx) -> dict:
    """A server that answers a few requests: BERT-large width, causal,
    bf16 weights, requests of different prompt lengths submitted together;
    then the engine's logits against the trainer's forward and against
    its own one-shot prefill (the paged cache must be invisible)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from apex_tpu.models import (TransformerConfig, bert_large_config,
                                 transformer_apply, transformer_init)
    from apex_tpu.serve import (CacheConfig, ContinuousBatcher,
                                InferenceEngine, PagePool, Request)

    if ctx["full"]:
        cfg = bert_large_config(causal=True, xent_impl="xla")
        cache = CacheConfig(page_size=16, num_pages=160, max_ctx=512)
        width, prompt_lens, new_tokens = 8, (5, 37, 128, 300), 8
    else:
        cfg = TransformerConfig(vocab_size=128, max_len=32, num_layers=2,
                                d_model=32, num_heads=2, d_ff=64,
                                causal=True, xent_impl="xla")
        cache = CacheConfig(page_size=8, num_pages=24, max_ctx=32)
        width, prompt_lens, new_tokens = 2, (3, 5, 9, 14), 3
    params = jax.jit(lambda: transformer_init(jax.random.PRNGKey(0), cfg))()
    eng = InferenceEngine(params, cfg, cache=cache, olevel="bf16",
                          decode_width=width)

    rng = np.random.RandomState(0)
    requests = [Request(rid=f"r{n}", max_new_tokens=new_tokens,
                        prompt=[int(t) for t in
                                rng.randint(1, cfg.vocab_size, size=n)])
                for n in prompt_lens]
    batcher = ContinuousBatcher(eng)
    for r in requests:
        batcher.submit(r)
    batcher.run(max_steps=16 * new_tokens)
    served = {}
    for r in requests:
        res = batcher.results[r.rid]
        toks = [int(t) for t in res.tokens]
        if res.status != "done" or len(toks) != new_tokens or not all(
                0 <= t < cfg.vocab_size for t in toks):
            raise AssertionError(f"request {r.rid}: status {res.status}, "
                                 f"tokens {toks}")
        served[r.rid] = {"prompt_len": len(r.prompt), "tokens": toks}

    # numerics, on the shortest request: prefill's last-row logits vs the
    # trainer forward (two separately compiled programs, bf16), then each
    # paged decode step vs the engine's one-shot prefill of the sequence
    prompt = requests[0].prompt
    S, PPR = cache.max_ctx, cache.pages_per_request
    pool = PagePool(cache)

    def padded(seq):
        toks = np.zeros(S, np.int32)
        toks[:len(seq)] = seq
        return toks

    def table_of(pages):
        table = np.zeros(PPR, np.int32)
        table[:len(pages)] = pages
        return table

    pages = pool.alloc(cache.pages_for(len(prompt)))
    first, logits = eng.prefill(padded(prompt), len(prompt),
                                table_of(pages), 0)
    bf16_params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16), params)
    trainer = jax.jit(lambda p, t: transformer_apply(
        p, t, eng.cfg))(bf16_params, jnp.asarray(padded(prompt))[None])
    err_trainer = rel_err(logits, trainer[0, len(prompt) - 1])

    scratch = pool.alloc(PPR)      # the oracle's own pages
    seq, err_paged = list(prompt) + [int(first)], 0.0
    for _ in range(3):
        pos = len(seq) - 1
        need = cache.pages_for(pos + 1)
        if need > len(pages):
            pages = pages + pool.alloc(need - len(pages))
        toks_w, positions = np.zeros(width, np.int32), np.zeros(width, np.int32)
        toks_w[0], positions[0] = seq[-1], pos
        tables = np.zeros((width, PPR), np.int32)
        tables[0] = table_of(pages)
        zeros = np.zeros(width, np.int32)
        nxt, dec_logits = eng.decode_step(
            toks_w, positions, tables, zeros, np.zeros(width, np.float32),
            zeros)
        _, oracle = eng.prefill(padded(seq), pos + 1, table_of(scratch), 0)
        err_paged = max(err_paged, rel_err(dec_logits[0], oracle))
        seq.append(int(np.asarray(nxt)[0]))
    tol = 3e-2                     # bf16 through every layer
    if not (err_trainer <= tol and err_paged <= tol):
        raise AssertionError(
            f"logits off: vs trainer forward {err_trainer:.3e}, paged "
            f"decode vs one-shot {err_paged:.3e} (tol {tol})")
    return {"layers": cfg.num_layers, "d_model": cfg.d_model,
            "max_ctx": cache.max_ctx, "decode_width": width,
            "weights_compression": eng.compression_ratio, "served": served,
            "logits_rel_err_vs_trainer": float(f"{err_trainer:.3e}"),
            "logits_rel_err_paged_vs_oneshot": float(f"{err_paged:.3e}"),
            "tol": tol}


def _resnet_argv(ctx, steps, extra=()):
    size = (["--arch", "resnet50", "--batch-size", "128"] if ctx["full"]
            else ["--arch", "resnet18", "--batch-size", "8"])
    return size + ["--opt-level", "O2", "--steps", str(steps),
                   "--print-freq", "1", *extra]


def phase_resnet50(ctx) -> dict:
    """A trainer that takes a few steps: ResNet-50 b128, amp O2 +
    FusedAdam + batch norm, on learnable synthetic images."""
    steps = 12
    report = {}
    load_example("examples/imagenet/main_amp.py").main(
        _resnet_argv(ctx, steps), report=report)
    return check_trainer_report(report, steps)


def _bert_argv(ctx, steps, batch, extra=()):
    # the tiny model needs a large step to move its loss within a few
    # steps; at full width the example's default learning rate does
    size = (["--bert-large", "--seq-len", "512"] if ctx["full"] else
            ["--layers", "2", "--d-model", "64", "--heads", "2", "--vocab",
             "512", "--seq-len", "128", "--lr", "5e-2"])
    return size + ["--batch-size", str(batch), "--attn", "fast", "--remat",
                   "--steps", str(steps), "--print-freq", "1", *extra]


def _flash_bwd_present(names: set) -> bool:
    return ("apex_flash_bwd_fused" in names
            or {"apex_flash_bwd_dq", "apex_flash_bwd_dkv"} <= names)


def _lamb_paths(ctx) -> dict:
    """The optimizer leg: ``amp.amp_step`` under O5 on BERT-large's tree
    (bfloat16 gradients), the per-leaf LAMB a replicated update runs beside
    the flat engine a sharded one slices — three steps each from the same
    state and gradients, the masters' largest difference relative to a
    leaf's size, and on the chip the milliseconds of a call.  The only place
    a fault of either path that exists only in the TPU's layouts would
    show: the CPU tests hold the two to 1e-6."""
    import jax
    import numpy as np
    from apex_tpu import amp
    from apex_tpu.models import (TransformerConfig, bert_large_config,
                                 transformer_init)
    from apex_tpu.optimizers import FusedLAMB
    cfg = bert_large_config() if ctx["full"] else TransformerConfig(
        vocab_size=512, max_len=128, num_layers=2, d_model=64, num_heads=2,
        d_ff=256)

    def gradients(params):
        leaves, treedef = jax.tree_util.tree_flatten(params)
        keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
        return jax.tree_util.tree_unflatten(treedef, [
            (1e-2 * jax.random.normal(k, x.shape)).astype(x.dtype)
            for k, x in zip(keys, leaves)])

    facts, masters = {}, {}
    for path, impl in (("leafwise", "xla"), ("flat", "fused")):
        opt = FusedLAMB(lr=1e-3, weight_decay=0.01, max_grad_norm=1.0,
                        impl=impl)
        state = jax.jit(lambda key: amp.initialize(
            transformer_init(key, cfg), opt, opt_level="O5", verbosity=0))(
                jax.random.PRNGKey(0))
        grads = jax.jit(gradients)(state.model_params)
        update = jax.jit(amp.amp_step, donate_argnums=0)
        for _ in range(3):
            state = update(state, grads)
        masters[path] = [np.asarray(x) for x in amp.master_params(state)]
        if ctx["on_tpu"]:
            def once():
                nonlocal state
                state = update(state, grads)
                return state.scalers
            facts[f"{path}_ms"] = median_ms(once)
        del state, grads, update
    worst = max(float(np.abs(a - b).max() / np.abs(a).max())
                for a, b in zip(masters["leafwise"], masters["flat"]))
    facts["masters_rel_diff"] = float(f"{worst:.3e}")
    facts["parameters"] = int(sum(x.size for x in masters["flat"]))
    if not facts["masters_rel_diff"] < 1e-4:
        raise AssertionError(f"per-leaf and flat LAMB part ways: {facts}")
    return facts


def phase_bert_large(ctx) -> dict:
    """The required leg: BERT-large 24L b8 x s512, flash attention +
    remat, amp O5, FusedLAMB leaf by leaf with the global-norm clip — the
    compiled step shown to contain the Pallas kernels and no flat engine —
    then the optimizer alone, per-leaf beside flat (:func:`_lamb_paths`)."""
    import re
    steps = 9
    report = {}
    load_example("examples/bert/pretrain.py").main(
        _bert_argv(ctx, steps, batch=8 if ctx["full"] else 2), report=report)
    facts = check_trainer_report(report, steps)

    traced = report["step"].trace(report["state"], report["batch"])
    names = pallas_kernel_names(traced)
    if "apex_l2norm" in names:
        raise AssertionError("the replicated update took the flat engine: "
                             "its l2norm kernel is in the traced step")
    required = {"apex_flash_fwd"}
    if ctx["on_tpu"]:
        required.add("apex_xentropy_fwd")     # "auto" is XLA off the chip
    missing = sorted(required - names)
    if missing or not _flash_bwd_present(names):
        raise AssertionError(
            f"traced step lacks Pallas kernels {missing or 'flash bwd'}; "
            f"found {sorted(names)}")
    facts["pallas_calls_traced"] = sorted(names)
    if ctx["on_tpu"]:
        # and in what Mosaic was handed: one tpu_custom_call per kernel
        text = traced.lower().as_text()
        lowered = set(re.findall(r'kernel_name = "([^"]+)"', text))
        if not (required <= lowered and _flash_bwd_present(lowered)):
            raise AssertionError(
                f"lowered step has tpu_custom_calls {sorted(lowered)}, "
                f"expected {sorted(required)} and a flash backward")
        facts["tpu_custom_calls_lowered"] = sorted(lowered)
    del traced
    report.clear()                  # the trainer's state, before two more
    gc.collect()
    facts["lamb"] = _lamb_paths(ctx)
    return facts


def phase_lfm2(ctx) -> dict:
    """LFM2-24B-A2B's share of the benchmark cell (``--lfm2 1 1 8 --vocab
    8192``, b8 x s4096, flash + remat, amp O5, per-leaf FusedLAMB)
    through ``parse_args`` -> ``run_standard``: trains, the step holds the
    Pallas kernels and the grouped products, and on one sequence the loss
    and its gradient agree with the XLA-attention twin.  The rehearsal
    keeps the pattern and the path at width 64."""
    import dataclasses
    import re
    import jax
    import jax.numpy as jnp
    import numpy as np
    from apex_tpu.models import lfm2_loss
    from apex_tpu.parallel import create_mesh, use_mesh
    pretrain = load_example("examples/bert/pretrain.py")
    batch, seq = (8, 4096) if ctx["full"] else (2, 128)
    args = pretrain.parse_args([
        "--lfm2", "1", "1", "8", "--vocab", "8192", "--seq-len", str(seq),
        "--batch-size", str(batch), "--attn", "fast", "--remat",
        *([] if ctx["full"] else ["--lr", "2e-2"])])
    cfg = pretrain.lfm2_config(args)
    if not ctx["full"]:
        cfg = dataclasses.replace(
            cfg, vocab_size=256, hidden_size=64, intermediate_size=160,
            moe_intermediate_size=32, num_experts=16, num_attention_heads=8,
            num_key_value_heads=2)
    steps, rng = 9, np.random.RandomState(0)
    report = {"losses": []}
    mesh = create_mesh({"data": 1}, devices=jax.devices()[:1])
    with use_mesh(mesh):
        state, step = pretrain.run_standard(args, cfg, mesh)
        for _ in range(steps):
            tokens, targets, weights = pretrain.synthetic_next_token(
                rng, batch, seq, cfg.vocab_size)
            np_batch = {"tokens": tokens, "targets": targets,
                        "weights": weights}
            state, loss = step(state, np_batch)
            report["losses"].append(float(loss))
        report["optimizer_steps"] = step.optimizer_steps(state)
        facts = check_trainer_report(report, steps)
        traced = step.trace(state, np_batch)
    names = pallas_kernel_names(traced)
    # ONE backward kernel at S 4096 (resident), not the dq / dkv pair
    required = {"apex_flash_fwd", "apex_flash_bwd_fused"}
    if ctx["on_tpu"]:
        required.add("apex_xentropy_fwd")
    if not required <= names or names & {"apex_flash_bwd_dq",
                                         "apex_flash_bwd_dkv",
                                         "apex_l2norm"}:
        raise AssertionError(f"traced step has Pallas kernels {sorted(names)}"
                             f", expected {sorted(required)}, no split "
                             "flash backward and no flat engine")
    grouped = len(re.findall(r"ragged_dot", str(traced.jaxpr)))
    if not grouped:
        raise AssertionError("the traced step holds no ragged_dot")
    facts.update(pallas_calls_traced=sorted(names), ragged_dots=grouped)

    # the twin: the same parameters, XLA attention, one sequence (its scores
    # are 2 GiB in float32 at S 4096, so the optimizer state goes first)
    params = state.model_params
    del state, traced
    one = {k: jnp.asarray(v[:1]) for k, v in np_batch.items()}

    def loss_and_norm(cfg):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: lfm2_loss(p, one, cfg)))(params)
        return float(loss), float(jnp.sqrt(sum(
            jnp.sum(jnp.square(g.astype(jnp.float32)))
            for g in jax.tree_util.tree_leaves(grads))))
    fast = loss_and_norm(cfg)
    twin = loss_and_norm(dataclasses.replace(cfg, attn_impl="default"))
    errors = [abs(a - b) / abs(b) for a, b in zip(fast, twin)]
    facts.update(loss_and_grad_norm=fast, xla_attention_twin=twin,
                 rel_err=[float(f"{e:.3e}") for e in errors])
    if not (errors[0] < 2e-3 and errors[1] < 2e-2):
        raise AssertionError(f"flash step against its XLA-attention twin: "
                             f"{fast} vs {twin}")
    del params
    facts["expert_layer"] = _expert_layer_checks(ctx)
    return facts


def _sum_facts(tokens, top_k, held, c):
    """The form a walk's sum back to the tokens takes through a buffer of
    ``c`` rows, and the rows its gathers write."""
    from apex_tpu.parallel import expert
    in_rows = expert.sums_in_row_space(tokens, top_k, held, c)
    return {"sum_in_row_space": in_rows,
            "sum_rows": c + tokens if in_rows else top_k * tokens}


def _check_routing(label, routing, walks, most_slots):
    """A routed layer's record: the walks it was made to take, nothing
    dropped, no token with more held assignments than there can be."""
    if int(routing["walks"]) != walks or int(routing["dropped"]):
        raise AssertionError(f"{label}: {routing['walks']} walks, "
                             f"{routing['dropped']} dropped")
    if not 1 <= int(routing["slots"]) <= most_slots:
        raise AssertionError(f"{label}: a token with {routing['slots']} "
                             f"held assignments")


def _expert_layer_checks(ctx) -> dict:
    """One expert layer (8 of 64 held, top-4, the model's widths) at one
    walk of its buffer and at a forced three, output and every gradient
    against its float32 XLA twin: the same routing, then each held expert
    over ALL tokens, weighed — no sort, no buffer, no grouped product.  The
    TPU's grouped product leaves rows past the groups unwritten where the
    CPU zero-fills, so only here can a masking fault show."""
    import jax
    import jax.numpy as jnp
    from apex_tpu.parallel import expert
    tokens, d, f = (8192, 2048, 1536) if ctx["full"] else (96, 64, 32)
    experts, held, top_k, first = 64, 8, 4, 16
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    bf16 = jnp.bfloat16

    def normal(key, shape, fan_in, dtype):
        return (jax.random.normal(key, shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)
    args = (normal(keys[0], (tokens, d), 1, bf16),
            normal(keys[1], (d, experts), d, jnp.float32),
            normal(keys[2], (held, d, 2 * f), d, bf16),
            normal(keys[3], (held, f, d), f, bf16))
    probe = jax.random.normal(keys[4], (tokens, d), jnp.float32)
    bias = jnp.zeros((experts,), jnp.float32)
    names = ("out", "d_x", "d_router", "d_w13", "d_w2")

    def system(rows_a_walk):
        def loss(x, router, w13, w2):
            out, routing = expert._routed_experts(
                x, router, bias, w13, w2, top_k=top_k, first=first,
                axis_name=None, rows_a_walk=rows_a_walk)
            return jnp.sum(out.astype(jnp.float32) * probe), (out, routing)
        (_, (out, routing)), grads = jax.jit(jax.value_and_grad(
            jax.checkpoint(loss), argnums=(0, 1, 2, 3), has_aux=True))(*args)
        return (out, *grads), routing

    def twin(x, router, w13, w2):
        ids, weights = expert.route_top_k(x, router, bias, top_k)
        x, out = x.astype(jnp.float32), 0.0
        for e in range(held):
            weight = jnp.sum(jnp.where(ids == first + e, weights, 0.0), -1)
            gate, up = jnp.split(x @ w13[e].astype(jnp.float32), 2, axis=-1)
            out = out + weight[:, None] * (
                (jax.nn.silu(gate) * up) @ w2[e].astype(jnp.float32))
        return jnp.sum(out * probe), out

    with jax.default_matmul_precision("highest"):
        (_, want_out), want_grads = jax.jit(jax.value_and_grad(
            twin, argnums=(0, 1, 2, 3), has_aux=True))(*args)
    want = (want_out, *want_grads)

    runs = {"one_walk": system(None)}
    sent = int(runs["one_walk"][1]["rows"].sum())
    runs["three_walks"] = system(-(-sent // 3))     # a third of the load
    buffer = expert.buffer_rows(tokens, top_k, experts, held)
    facts = {"rows_sent": sent, "buffer_rows": buffer}
    for (label, (got, routing)), walks in zip(runs.items(), (1, 3)):
        errors = {name: float(f"{rel_err(g, w):.3e}")
                  for name, g, w in zip(names, got, want)}
        facts[label] = {
            "walks": int(routing["walks"]), "slots": int(routing["slots"]),
            **_sum_facts(tokens, top_k, held,
                         buffer if walks == 1 else -(-sent // 3)),
            "rel_err": errors}
        _check_routing(label, routing, walks, min(top_k, held))
        if not max(errors.values()) < 3e-2:
            raise AssertionError(f"{label} against the float32 twin: "
                                 f"{errors}")
    return facts


def phase_nemotron_h(ctx) -> dict:
    """One ``M`` layer and one ``E`` layer of the ``--nemotron-h 8 64 1``
    share at the published widths (the rehearsal: width 64), bfloat16 against
    float32 twins at the highest matmul precision, output and every
    gradient.  The Mamba-2 twin is the same block in float32 (the chunked
    scan against the sequential recurrence is the CPU tests'); the expert
    twin routes alike, then runs each held expert over ALL tokens, weighed —
    no sort, no buffer, no grouped product: on a TPU the grouped product
    leaves rows past the groups unwritten, so only here can a masking fault
    at squared-ReLU experts on latent rows show."""
    import dataclasses
    import functools
    import jax
    import jax.numpy as jnp
    from apex_tpu.models import nemotron_h, nemotron_h_init
    from apex_tpu.parallel import expert
    pretrain = load_example("examples/bert/pretrain.py")
    cfg = pretrain.nemotron_h_config(pretrain.parse_args(
        ["--nemotron-h", "8", "64", "1", "--vocab", "16384"]))
    batch, seq = (1, 8192) if ctx["full"] else (2, 40)
    if not ctx["full"]:
        cfg = dataclasses.replace(
            cfg, hidden_size=64, mamba_num_heads=16, mamba_head_dim=8,
            mamba_heads_held=(0, 2), ssm_state_size=16, chunk_size=16,
            n_routed_experts=64, num_experts_per_tok=6, moe_latent_size=32,
            moe_intermediate_size=48, moe_shared_expert_intermediate_size=96)
    cfg = dataclasses.replace(cfg, hybrid_override_pattern="ME", vocab_size=8,
                              experts_held=(16, 8))
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    m_layer, e_layer = nemotron_h_init(keys[0], cfg)["layers"]
    u = jax.random.normal(keys[1], (batch, seq, cfg.hidden_size))
    probe = jax.random.normal(keys[2], u.shape)
    half = functools.partial(jax.tree_util.tree_map,
                             lambda x: x.astype(jnp.bfloat16))
    # the values the system sees, in float32: what a twin is given
    seen = functools.partial(jax.tree_util.tree_map, lambda x: x.astype(
        jnp.bfloat16).astype(jnp.float32))
    facts = {}

    def graded(fn, *args):
        # the probe is an argument: closed over it would be a constant of
        # the program, 134 MB of it at the published widths
        (_, aux), grads = jax.jit(jax.value_and_grad(
            jax.checkpoint(fn), argnums=(0, 1), has_aux=True))(*args)
        return aux, grads

    def judge(label, got, want, **more):
        errors = {name: float(f"{rel_err(g, w):.3e}")
                  for name, g, w in zip(("out", "d_x"), got, want)}
        errors.update({"d_" + name: float(f"{rel_err(g, want[2][name]):.3e}")
                       for name, g in got[2].items()})
        facts[label] = dict(rel_err=errors, **more)
        # a per-head scalar's gradient is a sum of signed terms over every
        # position: bfloat16's rounding does not average out of it
        loose = ("d_A_log", "d_dt_bias", "d_D")
        if not all(e < (0.15 if name in loose else 3e-2)
                   for name, e in errors.items()):
            raise AssertionError(f"{label} against the float32 twin: "
                                 f"{errors}")

    # -- the Mamba-2 block: norm, W_in, conv, chunked scan, gated norm, W_out
    def ssm(dtype):
        def loss(x, lp, probe):
            y, _ = nemotron_h._block(
                x, lp, cfg=dataclasses.replace(cfg, dtype=dtype), kind="M")
            return jnp.sum(y.astype(jnp.float32) * probe), y
        return loss
    with jax.default_matmul_precision("highest"):
        want, want_grads = graded(ssm(jnp.float32), seen(u), seen(m_layer),
                                  probe)
    got, grads = graded(ssm(jnp.bfloat16), half(u), half(m_layer), probe)
    judge("ssm_layer", (got, *grads), (want, *want_grads),
          heads=cfg.mamba_heads_held[1], chunks=-(-seq // cfg.chunk_size))

    # -- the latent expert layer, at one walk and at a forced three
    first, held = cfg.experts_held
    flat, flat_probe = (t.reshape(-1, t.shape[-1]) for t in (u, probe))

    def shared(x, lp):
        return jnp.square(jax.nn.relu(x @ lp["shared_w1"])) @ lp["shared_w2"]

    def system(rows_a_walk):
        def loss(x, lp, probe):
            routed, routing = expert._routed_experts(
                x, lp["router"], lp["expert_bias"], lp["w1"], lp["w2"],
                top_k=cfg.num_experts_per_tok, first=first,
                routed_scaling_factor=cfg.routed_scaling_factor,
                form="relu2", rows=x @ lp["latent_down"], axis_name=None,
                rows_a_walk=rows_a_walk)
            out = routed @ lp["latent_up"] + shared(x, lp)
            return jnp.sum(out.astype(jnp.float32) * probe), (out, routing)
        lp = dict(half(e_layer), router=e_layer["router"],
                  expert_bias=e_layer["expert_bias"])
        (out, routing), grads = graded(loss, half(flat), lp, flat_probe)
        return (out, *grads), routing

    def twin(x, lp, probe):
        ids, weights = expert.route_top_k(
            x.astype(jnp.bfloat16), lp["router"], lp["expert_bias"],
            cfg.num_experts_per_tok,
            routed_scaling_factor=cfg.routed_scaling_factor)
        latent, routed = x @ lp["latent_down"], 0.0
        for e in range(held):
            weight = jnp.sum(jnp.where(ids == first + e, weights, 0.0), -1)
            routed = routed + weight[:, None] * (
                jnp.square(jax.nn.relu(latent @ lp["w1"][e])) @ lp["w2"][e])
        out = routed @ lp["latent_up"] + shared(x, lp)
        return jnp.sum(out * probe), out

    with jax.default_matmul_precision("highest"):
        want, want_grads = graded(twin, seen(flat), dict(
            seen(e_layer), router=e_layer["router"]), flat_probe)
    one, routing = system(None)
    sent = int(routing["rows"].sum())
    facts["rows_sent"], facts["buffer_rows"] = sent, expert.buffer_rows(
        flat.shape[0], cfg.num_experts_per_tok, cfg.n_routed_experts, held)
    for label, walks, (got, routing) in (
            ("expert_layer_one_walk", 1, (one, routing)),
            ("expert_layer_three_walks", 3, system(-(-sent // 3)))):
        _check_routing(label, routing, walks,
                       min(cfg.num_experts_per_tok, held))
        judge(label, got, (want, *want_grads), walks=walks,
              slots=int(routing["slots"]), **_sum_facts(
                  flat.shape[0], cfg.num_experts_per_tok, held,
                  facts["buffer_rows"] if walks == 1 else -(-sent // 3)))
    return facts


def phase_qwen3_next(ctx) -> dict:
    """The ``--qwen3-next 16 1`` share at the published widths (the
    rehearsal: width 64), bfloat16 against float32 twins at the highest
    matmul precision, output and every gradient: the gated delta rule —
    the Pallas kernel pair at the published shape (``facts["rule"]["path"]``
    from the program's own record; anything else fails the phase), timed
    alone beside its ``jax.numpy`` twin — against the reference's sequential
    recurrence at S 4096 x 32 heads; a Gated DeltaNet mixer and the gated
    attention mixer (the flash kernel at D 256 — no other configuration has a head wider than 128)
    against the same block in float32 with XLA attention; and the sparse FFN
    — softmax scores, gated-SiLU experts, the gated shared expert — at one
    walk of its buffer and at a forced three against a twin that routes
    alike, then runs each held expert over ALL tokens, weighed: on a TPU the
    grouped product leaves rows past the groups unwritten, so only here can
    a masking fault show."""
    import dataclasses
    import functools
    import jax
    import jax.numpy as jnp
    from apex_tpu.models import qwen3_next, qwen3_next_init
    from apex_tpu.parallel import expert
    from apex_tpu.telemetry import MemorySink, Registry, events
    pretrain = load_example("examples/bert/pretrain.py")
    reference = load_example("benchmarks/reference/qwen3_next_80b_a3b.py")
    cfg = pretrain.qwen3_next_config(pretrain.parse_args(
        ["--qwen3-next", "16", "1", "--vocab", "19072", "--attn", "fast"]))
    batch, seq = (2, 4096) if ctx["full"] else (2, 40)
    if not ctx["full"]:
        cfg = dataclasses.replace(
            cfg, hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, linear_num_key_heads=2, linear_key_head_dim=8,
            linear_num_value_heads=4, linear_value_head_dim=8, chunk_size=16,
            num_experts=64, num_experts_per_tok=6, moe_intermediate_size=32,
            shared_expert_intermediate_size=32)
    cfg = dataclasses.replace(cfg, vocab_size=8, experts_held=(
        cfg.num_experts // 16, cfg.num_experts // 16))
    keys = jax.random.split(jax.random.PRNGKey(7), 9)
    gdn_layer, _, _, full_layer = qwen3_next_init(keys[0], cfg)["layers"]
    u = jax.random.normal(keys[1], (batch, seq, cfg.hidden_size))
    probe = jax.random.normal(keys[2], u.shape)
    half = functools.partial(jax.tree_util.tree_map,
                             lambda x: x.astype(jnp.bfloat16))
    # the values the system sees, in float32: what a twin is given
    seen = functools.partial(jax.tree_util.tree_map, lambda x: x.astype(
        jnp.bfloat16).astype(jnp.float32))
    facts = {}

    def graded(fn, *args, argnums=(0, 1)):
        # the probe is an argument: closed over it would be a constant of
        # the program
        (_, aux), grads = jax.jit(jax.value_and_grad(
            jax.checkpoint(fn), argnums=argnums, has_aux=True))(*args)
        return aux, grads

    def judge(label, got, want, loose=(), **more):
        errors = {name: float(f"{rel_err(g, w):.3e}")
                  for name, g, w in zip(("out", "d_x"), got, want)}
        errors.update({"d_" + name: float(f"{rel_err(g, want[2][name]):.3e}")
                       for name, g in got[2].items()})
        facts[label] = dict(rel_err=errors, **more)
        # a per-head scalar's gradient (and that of the 64 columns that make
        # the decays) is a sum of signed terms over every position:
        # bfloat16's rounding does not average out of it
        if not all(e < (0.15 if name in loose else 3e-2)
                   for name, e in errors.items()):
            raise AssertionError(f"{label} against the float32 twin: "
                                 f"{errors}")

    # -- the chunked rule against the sequential recurrence ------------------
    groups, heads = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    rule_in = {
        "q": qwen3_next._l2_norm(jax.random.normal(
            keys[3], (1, seq, groups, dk))) * dk ** -0.5,
        "k": qwen3_next._l2_norm(jax.random.normal(
            keys[4], (1, seq, groups, dk))),
        "v": jax.random.normal(keys[5], (1, seq, heads, dv)),
        "g": -jax.nn.softplus(jax.random.normal(keys[6], (1, seq, heads))),
        "beta": jax.nn.sigmoid(jax.random.normal(keys[7], (1, seq, heads)))}
    rule_probe = jax.random.normal(keys[8], (1, seq, heads, dv))
    rounded = dict(seen(rule_in), g=rule_in["g"], beta=rule_in["beta"])

    def chunked(t, probe):
        o = qwen3_next.gated_delta_rule(
            *(t[n].astype(jnp.bfloat16) for n in "qkv"), t["g"], t["beta"],
            cfg.chunk_size)
        return jnp.sum(o.astype(jnp.float32) * probe), o

    def sequential(t, probe):
        per = heads // groups
        o = reference._delta_recurrence(
            jnp.repeat(t["q"], per, axis=2), jnp.repeat(t["k"], per, axis=2),
            t["v"], t["g"], t["beta"])
        return jnp.sum(o * probe), o

    with jax.default_matmul_precision("highest"):
        want, (want_grads,) = graded(sequential, rounded, rule_probe,
                                     argnums=(0,))
    # which form a traced call of the rule takes is the program's own record
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    prev = events.set_default(reg)
    try:
        got, (grads,) = graded(chunked, rounded, rule_probe, argnums=(0,))
    finally:
        events.set_default(prev)
    paths = sorted({r["fields"]["path"] for r in reg.flush()
                    if r.get("name") == "gdn.rule"})
    errors = {"out": float(f"{rel_err(got, want):.3e}"), **{
        "d_" + n: float(f"{rel_err(grads[n], want_grads[n]):.3e}")
        for n in grads}}
    facts["rule"] = {"seq": seq, "heads": heads, "path": paths,
                     "chunks": -(-seq // cfg.chunk_size), "rel_err": errors}
    if not max(errors.values()) < 3e-2:
        raise AssertionError(f"the chunked rule against the recurrence: "
                             f"{errors}")
    if paths != (["kernel"] if ctx["full"] else ["jnp"]):
        raise AssertionError(
            f"the rule at d_k {dk}, d_v {dv}, chunk {cfg.chunk_size} took "
            f"{paths}: the published shape is the kernel pair's, the "
            "rehearsal's 8-wide heads the jax.numpy form's")
    if ctx["full"]:
        # the pair beside its jax.numpy twin, one sequence alone: a forward
        # pass, and the gradient program (the pair's sweep and reverse
        # kernel; the twin's forward and reverse)
        operands = tuple(rounded[n].astype(jnp.bfloat16) for n in "qkv") \
            + (rounded["g"], rounded["beta"])
        for label, rule in (("kernel", qwen3_next.gated_delta_rule),
                            ("jnp", qwen3_next._chunked_rule)):
            rule = functools.partial(rule, chunk=cfg.chunk_size)
            facts["rule"][label + "_ms"] = {
                "forward": median_ms(jax.jit(rule), *operands),
                "backward": median_ms(jax.jit(jax.grad(
                    lambda *a, rule=rule: jnp.sum(
                        rule(*a[:5]).astype(jnp.float32) * a[5]),
                    argnums=range(5))), *operands, rule_probe)}

    # -- the two mixers: norm, projections, (conv, rule, gated norm | q/k
    #    norms, quarter rotary, flash, gate), output projection
    def mixer(fn, dtype, attn):
        def loss(x, lp, probe):
            y = fn(x, lp, dataclasses.replace(cfg, dtype=dtype,
                                              attn_impl=attn))
            return jnp.sum(y.astype(jnp.float32) * probe), y
        return loss
    for label, fn, lp, names in (
            ("gdn_mixer", qwen3_next._gdn_mixer, gdn_layer,
             ("in_proj_qkvz", "in_proj_ba", "conv_w", "dt_bias", "A_log",
              "gate_norm", "out_proj")),
            ("attention_mixer", qwen3_next._attention_mixer, full_layer,
             ("wq", "wk", "wv", "wo", "q_norm", "k_norm"))):
        lp = {n: lp[n] for n in names}
        with jax.default_matmul_precision("highest"):
            want, want_grads = graded(mixer(fn, jnp.float32, "default"),
                                      seen(u), seen(lp), probe)
        got, grads = graded(mixer(fn, jnp.bfloat16, "fast"), half(u),
                            half(lp), probe)
        judge(label, (got, *grads), (want, *want_grads),
              loose=("d_A_log", "d_dt_bias", "d_in_proj_ba"), batch=batch,
              seq=seq)

    # -- the sparse FFN, at one walk and at a forced three --------------------
    first, held = cfg.experts_held
    e_names = ("router", "w13", "w2", "shared_w13", "shared_w2",
               "shared_gate")
    e_layer = {n: gdn_layer[n] for n in e_names}
    flat, flat_probe = (t.reshape(-1, t.shape[-1]) for t in (u, probe))

    def system(rows_a_walk):
        def loss(x, lp, probe):
            routed, routing = expert._routed_experts(
                x, lp["router"], None, lp["w13"], lp["w2"],
                top_k=cfg.num_experts_per_tok, first=first, score="softmax",
                axis_name=None, rows_a_walk=rows_a_walk)
            out = routed + qwen3_next._shared_expert(x, lp)
            return jnp.sum(out.astype(jnp.float32) * probe), (out, routing)
        lp = dict(half(e_layer), router=e_layer["router"])
        (out, routing), grads = graded(loss, half(flat), lp, flat_probe)
        return (out, *grads), routing

    def twin(x, lp, probe):
        ids, weights = expert.route_top_k(
            x.astype(jnp.bfloat16), lp["router"], None,
            cfg.num_experts_per_tok, score="softmax")
        out = qwen3_next._shared_expert(x, lp)
        for e in range(held):
            weight = jnp.sum(jnp.where(ids == first + e, weights, 0.0), -1)
            gate, up = jnp.split(x @ lp["w13"][e], 2, axis=-1)
            out = out + weight[:, None] * (
                (jax.nn.silu(gate) * up) @ lp["w2"][e])
        return jnp.sum(out * probe), out

    with jax.default_matmul_precision("highest"):
        want, want_grads = graded(twin, seen(flat), dict(
            seen(e_layer), router=e_layer["router"]), flat_probe)
    one, routing = system(None)
    sent = int(routing["rows"].sum())
    facts["rows_sent"], facts["buffer_rows"] = sent, expert.buffer_rows(
        flat.shape[0], cfg.num_experts_per_tok, cfg.num_experts, held)
    # a third of the load — in whole 128s on the chip, as the rule's buffers
    # are whole 512s: at 1707 rows and ten neighbours to add XLA's own
    # gather fusion asked for 16.26 MiB of its 16 MiB of scoped VMEM and
    # the program was refused (PR 34)
    third = -(-sent // 3)
    if ctx["full"]:
        third = -(-third // 128) * 128
    facts["forced_buffer_rows"] = third
    for label, walks, (got, routing) in (
            ("expert_layer_one_walk", 1, (one, routing)),
            ("expert_layer_three_walks", 3, system(third))):
        _check_routing(label, routing, walks,
                       min(cfg.num_experts_per_tok, held))
        judge(label, got, (want, *want_grads), walks=walks,
              slots=int(routing["slots"]), **_sum_facts(
                  flat.shape[0], cfg.num_experts_per_tok, held,
                  facts["buffer_rows"] if walks == 1 else third))
    return facts


def phase_glm4_moe_lite(ctx) -> dict:
    """The ``--glm4-moe-lite 8 4`` share at the published widths (the
    rehearsal: width 64), bfloat16: the whole cut — MTP module and its loss
    term included — on one sequence through flash (``--attn fast``) against
    the same parameters through XLA's ``attention_core`` (``--attn
    default``), the loss and every parameter leaf's gradient; then one
    latent-attention mixer through flash against its float32 twin through
    ``attention_core`` at the highest matmul precision, output and every
    gradient.  A leaf's error is the norm of its difference over its norm;
    the routed leaves (router, experts) are reported and not limited, beside
    the assignments the two attentions route differently (a score a hair
    from the fourth largest falls either way in bfloat16, and moves a
    token's whole share of those leaves); the others are held to 0.15, which
    a wrong leaf (an error near 1) cannot meet and bfloat16 on both sides
    at the rehearsal's width 64 does (up to 0.11)."""
    import dataclasses
    import functools
    import jax
    import jax.numpy as jnp
    import numpy as np
    from apex_tpu.models import glm4_moe_lite, glm4_moe_lite_init
    from benchmarks.job import global_norm
    pretrain = load_example("examples/bert/pretrain.py")
    cfg = pretrain.glm4_moe_lite_config(pretrain.parse_args(
        ["--glm4-moe-lite", "8", "4", "--vocab", "19456", "--attn", "fast",
         "--remat"]))
    seq = 4096 if ctx["full"] else 40
    if not ctx["full"]:
        cfg = dataclasses.replace(
            cfg, vocab_size=256, hidden_size=64, intermediate_size=96,
            num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
            qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16,
            num_experts=32, moe_intermediate_size=24, experts_held=(0, 4))
    half = functools.partial(jax.tree_util.tree_map,
                             lambda x: x.astype(jnp.bfloat16))
    params = jax.jit(lambda key: half(glm4_moe_lite_init(key, cfg)))(
        jax.random.PRNGKey(7))
    tokens, targets, weights = pretrain.synthetic_next_token(
        np.random.RandomState(0), 1, seq, cfg.vocab_size)
    one = {"tokens": jnp.asarray(tokens), "targets": jnp.asarray(targets),
           "weights": jnp.asarray(weights)}

    def loss_and_grads(attn):
        c = dataclasses.replace(cfg, attn_impl=attn)
        loss = functools.partial(glm4_moe_lite.glm4_moe_lite_loss, cfg=c)
        ids = jax.jit(functools.partial(glm4_moe_lite.glm4_moe_lite_routing,
                                        cfg=c))(params, one)["ids"]
        return jax.jit(jax.value_and_grad(loss))(params, one) + (ids,)
    fast, twin = loss_and_grads("fast"), loss_and_grads("default")
    leaves = {jax.tree_util.keystr(path): float(f"{norm_rel_err(g, w):.3e}")
              for (path, w), g in zip(
                  jax.tree_util.tree_leaves_with_path(twin[1]),
                  jax.tree_util.tree_leaves(fast[1]))}
    routed = ("'router'", "'w13'", "'w2'")
    limited = {n: e for n, e in leaves.items()
               if not any(r in n for r in routed)}
    loss_err = abs(float(fast[0]) - float(twin[0])) / abs(float(twin[0]))
    norm_err = rel_err(global_norm(fast[1]), global_norm(twin[1]))
    worst = max(limited, key=limited.get)
    facts = {"seq": seq, "loss": float(fast[0]),
             "xla_attention_twin_loss": float(twin[0]),
             "loss_rel_err": float(f"{loss_err:.3e}"),
             "grad_norm_rel_err": float(f"{norm_err:.3e}"),
             "routed_differently": int(jnp.sum(jnp.sort(fast[2], -1)
                                               != jnp.sort(twin[2], -1))),
             "assignments": int(fast[2].size), "leaves": len(leaves),
             "worst_unrouted_leaf": [worst, limited[worst]],
             "leaf_rel_err": leaves}
    del fast, twin
    if not (loss_err < 2e-3 and norm_err < 2e-2
            and limited[worst] < 0.15):
        raise AssertionError(f"the cut through flash against XLA attention: "
                             f"loss {loss_err:.3e}, gradient norm "
                             f"{norm_err:.3e}, worst {worst} "
                             f"{limited[worst]}")

    # -- one latent-attention mixer against its float32 twin ------------------
    names = ("q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b", "o")
    lp = {n: params["layers"][1][n] for n in names}
    keys = jax.random.split(jax.random.PRNGKey(8), 2)
    u = jax.random.normal(keys[0], (1, seq, cfg.hidden_size))
    probe = jax.random.normal(keys[1], u.shape)
    seen = functools.partial(jax.tree_util.tree_map,
                             lambda x: x.astype(jnp.float32))

    def mixer(dtype, attn):
        def loss(x, lp, probe):
            y = glm4_moe_lite._mla_mixer(x, lp, dataclasses.replace(
                cfg, dtype=dtype, attn_impl=attn))
            return jnp.sum(y.astype(jnp.float32) * probe), y
        # the probe is an argument: closed over it would be a constant
        return jax.jit(jax.value_and_grad(jax.checkpoint(loss),
                                          argnums=(0, 1), has_aux=True))
    with jax.default_matmul_precision("highest"):
        (_, want), want_grads = mixer(jnp.float32, "default")(
            seen(half(u)), seen(lp), probe)
    (_, got), grads = mixer(jnp.bfloat16, "fast")(half(u), lp, probe)
    errors = {"out": float(f"{rel_err(got, want):.3e}"),
              "d_u": float(f"{rel_err(grads[0], want_grads[0]):.3e}"),
              **{"d_" + n: float(f"{rel_err(grads[1][n], want_grads[1][n]):.3e}")
                 for n in names}}
    facts["mla_mixer"] = {"seq": seq, "heads": cfg.num_attention_heads,
                          "head_dim": cfg.qk_head_dim, "rel_err": errors}
    if not max(errors.values()) < 3e-2:
        raise AssertionError(f"the latent-attention mixer against its "
                             f"float32 twin: {errors}")
    return facts


def _plan_families():
    from apex_tpu.parallel import plan as pm
    return [("dp2xtp2", pm.Plan(dp=2, tp=2)),
            ("dp2xsp2_ring", pm.Plan(dp=2, sp=2, sp_strategy="ring")),
            ("dp2xsp2_ulysses", pm.Plan(dp=2, sp=2, sp_strategy="ulysses")),
            ("dp2xpp2", pm.Plan(dp=2, pp_stages=2, pp_microbatches=2)),
            ("dp2xep2", pm.Plan(dp=2, ep=2)),
            ("dp4_zero1", pm.Plan(dp=4, update_sharding="zero1")),
            ("dp4_zero", pm.Plan(dp=4, zero=True))]


def phase_multichip(ctx) -> dict:
    """The same trainers data-parallel over every device, and one step of
    each plan family on a 4-device mesh; each device holds its share."""
    import jax
    import jax.numpy as jnp
    from apex_tpu.models import TransformerConfig
    from apex_tpu.parallel import spmd

    devices = jax.devices()
    n = len(devices)
    out = {}

    def leg(name, fn):
        out[name] = attempt(f"multichip leg {name}", fn)
        out[name]["memory"] = memory_fact(devices)
        say(f"  {name}: {out[name]}")

    steps = 9
    bert_batch = (8 if ctx["full"] else 1) * n

    def bert(extra):
        def run():
            report = {}
            load_example("examples/bert/pretrain.py").main(
                _bert_argv(ctx, steps, bert_batch, extra), report=report)
            facts = check_trainer_report(report, steps)
            state = report["state"]
            check_holds_share(getattr(state, "carry", state), devices,
                              "state", balance=ctx["full"])
            return facts
        return run

    def resnet():
        report = {}
        argv = _resnet_argv(ctx, steps, ("--distributed", "--sync-bn"))
        if not ctx["full"]:
            argv[argv.index("--batch-size") + 1] = str(2 * n)
        load_example("examples/imagenet/main_amp.py").main(
            argv, report=report)
        facts = check_trainer_report(report, steps)
        check_holds_share(report["state"], devices, "state",
                          balance=ctx["full"])
        return facts

    leg("bert_large_distributed", bert(("--distributed",)))
    leg("bert_large_zero", bert(("--zero",)))
    leg("resnet50_distributed_syncbn", resnet)

    # two layers so a 2-stage pipeline divides evenly; the loss kernel
    # stays "auto" so that on the chip the engines' shard_maps carry the
    # Pallas xentropy, as a production config would
    cfg = TransformerConfig(vocab_size=64, max_len=16, num_layers=2,
                            d_model=32, num_heads=2, d_ff=64)
    tokens = jnp.zeros((4, cfg.max_len), jnp.int32)

    def family(plan):
        def run():
            with plan.apply(devices[: plan.chips]) as mesh:
                carry, step, info = spmd.build_plan_step(
                    cfg, mesh, plan, global_batch=4, meter=False)
                carry, loss = step(carry, tokens)
                if not bool(jnp.isfinite(loss)):
                    raise AssertionError(f"loss {loss}")
                check_holds_share(carry, devices[: plan.chips], "carry")
            return {"engine": info.get("engine"), "loss": float(loss)}
        return run

    for name, plan in _plan_families():
        leg(f"family_{name}", family(plan))
    bad = [name for name, rec in out.items() if not rec["ok"]]
    if bad:
        raise AssertionError(f"multichip legs failed: {bad}")
    return {"devices": n, "legs": out}


PHASES = {
    "native": phase_native,
    "kernels": phase_kernels,
    "serve": phase_serve,
    "resnet50": phase_resnet50,
    "bert_large": phase_bert_large,
    "lfm2": phase_lfm2,
    "nemotron_h": phase_nemotron_h,
    "qwen3_next": phase_qwen3_next,
    "glm4_moe_lite": phase_glm4_moe_lite,
    "multichip": phase_multichip,
}
MULTICHIP_DEVICES = 4


def run_phases(ctx, names) -> dict:
    import jax
    meter = ctx["meter"]
    devices = jax.devices()
    report = {}
    for name in names:
        if name == "multichip" and len(devices) < MULTICHIP_DEVICES:
            say(f"phase multichip: skipped — jax found {len(devices)} "
                f"device(s), the leg needs {MULTICHIP_DEVICES}")
            report[name] = {"ok": True, "skipped":
                            f"{len(devices)} device(s) < {MULTICHIP_DEVICES}"}
            continue
        say(f"phase {name} ...")
        c0, h0, m0 = meter.snapshot()
        rec = attempt(f"phase {name}", lambda: PHASES[name](ctx))
        c1, h1, m1 = meter.snapshot()
        wall = rec.pop("s")
        rec.update(wall_s=wall, compile_s=round(c1 - c0, 2),
                   run_s=round(wall - (c1 - c0), 2),
                   compile_cache={"hits": h1 - h0, "misses": m1 - m0},
                   memory=memory_fact(devices))
        report[name] = rec
        say(f"phase {name}: {'ok' if rec['ok'] else 'FAILED'}  "
            f"wall {rec['wall_s']} s = compile {rec['compile_s']} s + "
            f"run {rec['run_s']} s; cache {rec['compile_cache']}; "
            f"peak bytes in use (process high-water) "
            f"{rec['memory']['peak_bytes_in_use']}")
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="run the same phases at tiny shapes on the CPU "
                         "(kernels interpreted); never chosen for you")
    ap.add_argument("--only", default=None,
                    help=f"comma-separated phases of {list(PHASES)}")
    args = ap.parse_args(argv)
    names = list(PHASES) if not args.only else args.only.split(",")
    unknown = [n for n in names if n not in PHASES]
    if unknown:
        ap.error(f"unknown phases {unknown}; known: {list(PHASES)}")

    import jax
    from apex_tpu.utils import platform as plat
    if args.rehearse:
        plat.force_cpu(MULTICHIP_DEVICES)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    say(f"jax {jax.__version__}  platform {device['platform']}  "
        f"device_kind {device['kind']}  devices {device['count']}")
    if args.rehearse:
        say("REHEARSAL: tiny shapes, platform: cpu — proves the script, "
            "not the chip")
    elif not plat.found_tpu("chip_smoke.py"):
        print("nothing was run (--rehearse runs the phases tiny on the "
              "CPU)", file=sys.stderr)
        return 2

    meter = CompileMeter()
    cache_dir = plat.enable_compile_cache()
    say(f"compile cache: {cache_dir}")
    ctx = {"full": not args.rehearse, "on_tpu": dev.platform == "tpu",
           "meter": meter}
    t0 = time.monotonic()
    report = run_phases(ctx, names)
    failed = [n for n, r in report.items() if not r["ok"]]
    _, hits, misses = meter.snapshot()
    summary = {"ok": not failed, "device": device}
    if args.rehearse:
        summary["rehearsal"] = True
    if failed:
        summary["failed"] = failed
    full_report = {**summary, "jax": jax.__version__,
                   "wall_s": round(time.monotonic() - t0, 1),
                   "compile_cache": {"dir": cache_dir, "hits": hits,
                                     "misses": misses},
                   "phases": report}
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "chip_smoke.json"), "w") as f:
        json.dump(full_report, f, indent=1)
    say(f"done in {full_report['wall_s']} s; compile cache hits {hits}, "
        f"misses {misses}; failed phases: {failed or 'none'}")
    print(json.dumps(summary))
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
