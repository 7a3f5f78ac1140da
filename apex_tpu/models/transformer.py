"""BERT-style transformer encoder LM — the flagship model for the BERT-large
FusedLAMB pretrain benchmark (BASELINE config[3]; the workload behind the
reference's "BERT in 76 minutes" LAMB citation, ``apex/optimizers/fused_lamb.py:32``)
and for the contrib multihead-attn perf harness
(``apex/contrib/examples/multihead_attn/perf_test_multihead_attn.py``).

TPU-first design decisions:
  - pure functional ``init``/``apply`` over a param pytree; layers are
    *stacked* (leading ``num_layers`` dim) and iterated with ``lax.scan`` so
    compile time is O(1) in depth and pipeline/tensor shardings are a
    PartitionSpec away;
  - every matmul is laid out for the MXU (model dims multiples of 128,
    bf16 activations under amp);
  - ``transformer_pspecs`` gives a Megatron-style tensor-parallel sharding
    (QKV/ff1 column-split over heads, out-proj/ff2 row-split) expressed as
    PartitionSpecs — XLA inserts the psums; no hand-written collectives;
  - attention is the fused-by-XLA jnp reference path (``_attention``); it is
    the correctness oracle the contrib fast-attention kernel must match.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..normalization.fused_layer_norm import fused_layer_norm_affine
from ..pyprof import annotate


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32768
    max_len: int = 512
    num_layers: int = 4
    d_model: int = 256
    num_heads: int = 4
    d_ff: int = 1024
    dropout: float = 0.0          # inference/bench default; train passes rng
    causal: bool = False          # BERT-style bidirectional by default
    dtype: Any = jnp.float32      # activation dtype (amp casts params)
    tie_embeddings: bool = True
    remat: bool = False           # jax.checkpoint each layer: recompute
                                  # activations in backward instead of
                                  # saving them — O(1) layer activations
                                  # in memory, the long-context enabler
    attn_impl: str = "default"    # "default": jnp reference path (the
                                  # numerics oracle); "fast": the contrib
                                  # flash Pallas kernel (O(S) memory,
                                  # online softmax) — the analog of
                                  # running the reference's examples with
                                  # fast_*_multihead_attn extensions
    xent_impl: str = "auto"       # loss kernel: "auto" (pallas on TPU,
                                  # xla elsewhere) / "pallas" / "xla".
                                  # Explicit so harnesses can pin the XLA
                                  # path per-config instead of mutating
                                  # APEX_TPU_XENT_IMPL (trace-time env
                                  # reads don't survive retraces)
    scan_unroll: int = 1          # layer-scan unroll factor.  >1 clones
                                  # the layer body so consecutive
                                  # layers' grads become SEPARATE ops a
                                  # bucketed dp reduction can interleave
                                  # with (parallel.overlap) — the TPU
                                  # overlap enabler.  Explicit opt-in:
                                  # unrolling changes XLA fusion
                                  # boundaries, so the fp32 bitwise
                                  # parity contract only covers runs
                                  # comparing like against like (same
                                  # unroll both legs)

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.num_heads == 0
        return self.d_model // self.num_heads


def bert_large_config(**overrides) -> TransformerConfig:
    base = dict(vocab_size=30592, max_len=512, num_layers=24, d_model=1024,
                num_heads=16, d_ff=4096)
    base.update(overrides)
    return TransformerConfig(**base)


def _dense_init(key, shape, scale=0.02):
    return scale * jax.random.normal(key, shape, jnp.float32)


def transformer_init(key, cfg: TransformerConfig):
    """Param pytree.  Per-layer weights are stacked on a leading L axis."""
    keys = jax.random.split(key, 8)
    L, D, F, V = cfg.num_layers, cfg.d_model, cfg.d_ff, cfg.vocab_size
    params = {
        "embed": {
            "tok": _dense_init(keys[0], (V, D)),
            "pos": _dense_init(keys[1], (cfg.max_len, D)),
            "ln_g": jnp.ones((D,), jnp.float32),
            "ln_b": jnp.zeros((D,), jnp.float32),
        },
        "layers": {
            "wqkv": _dense_init(keys[2], (L, D, 3 * D)),
            "bqkv": jnp.zeros((L, 3 * D), jnp.float32),
            "wo": _dense_init(keys[3], (L, D, D)),
            "bo": jnp.zeros((L, D), jnp.float32),
            "ln1_g": jnp.ones((L, D), jnp.float32),
            "ln1_b": jnp.zeros((L, D), jnp.float32),
            "w1": _dense_init(keys[4], (L, D, F)),
            "b1": jnp.zeros((L, F), jnp.float32),
            "w2": _dense_init(keys[5], (L, F, D)),
            "b2": jnp.zeros((L, D), jnp.float32),
            "ln2_g": jnp.ones((L, D), jnp.float32),
            "ln2_b": jnp.zeros((L, D), jnp.float32),
        },
        "head": {
            "ln_g": jnp.ones((D,), jnp.float32),
            "ln_b": jnp.zeros((D,), jnp.float32),
        },
    }
    if not cfg.tie_embeddings:
        params["head"]["out"] = _dense_init(keys[6], (D, V))
    return params


def transformer_pspecs(cfg: TransformerConfig, *, dp="data", tp="model"):
    """Megatron-style tensor-parallel PartitionSpec tree matching
    ``transformer_init``'s structure.  Column-parallel: QKV / ff1 (shard the
    output feature dim over ``tp``); row-parallel: out-proj / ff2 (shard the
    input dim).  Embeddings shard the vocab dim; norms replicate.
    XLA derives the all-reduces from these specs (scaling-book recipe)."""
    del dp  # params are replicated over the data axis
    head = {"ln_g": P(), "ln_b": P()}
    if not cfg.tie_embeddings:
        head["out"] = P(None, tp)
    return {
        "embed": {"tok": P(tp, None), "pos": P(), "ln_g": P(), "ln_b": P()},
        "layers": {
            "wqkv": P(None, None, tp), "bqkv": P(None, tp),
            "wo": P(None, tp, None), "bo": P(None, None),
            "ln1_g": P(None, None), "ln1_b": P(None, None),
            "w1": P(None, None, tp), "b1": P(None, tp),
            "w2": P(None, tp, None), "b2": P(None, None),
            "ln2_g": P(None, None), "ln2_b": P(None, None),
        },
        "head": head,
    }


def _attention(x, wqkv, bqkv, wo, bo, cfg: TransformerConfig, mask,
               dropout_rng=None, attn_override=None):
    """Self-attention reference path (jnp; XLA fuses).  The contrib fast
    Pallas kernel slots in behind the same signature.

    ``attn_override``: a callable ``(q, k, v, *, causal) -> ctx`` over the
    (B, H, S, D) head layout that replaces the score/softmax core — the
    hook the sequence-parallel step engine (``parallel.spmd``) uses to
    route attention through ``ring_attention``/``ulysses_attention``
    inside shard_map.  The override owns the 1/sqrt(D) scaling (both
    sequence collectives scale internally); masks are not supported
    through the hook (the sp engine trains unpadded batches)."""
    B, S, D = x.shape
    H, hd = cfg.num_heads, cfg.head_dim
    qkv = jnp.einsum("bsd,de->bse", x, wqkv.astype(x.dtype)) + bqkv.astype(x.dtype)
    if attn_override is None and cfg.attn_impl == "fast":
        # the kernel reads q, k and v from the projection and writes the
        # context in the layout ``wo`` reads (flash_attention_qkv picks the
        # layout from the shape)
        from ..contrib.multihead_attn.flash import flash_attention_qkv
        from ..contrib.multihead_attn.modules import _rng_seed_from
        if mask is not None:   # (B, S) nonzero = PAD -> additive key bias
            bias = jnp.where(mask[:, None, :] != 0, -1e9, 0.0) \
                .astype(jnp.float32)
        else:
            bias = jnp.zeros((1, 1, S), jnp.float32)
        rate = cfg.dropout if dropout_rng is not None else 0.0
        ctx = flash_attention_qkv(qkv, bias,
                                  seed=_rng_seed_from(dropout_rng),
                                  causal=cfg.causal, dropout_rate=rate,
                                  heads=H)
        return jnp.einsum("bsd,de->bse", ctx, wo.astype(x.dtype)) \
            + bo.astype(x.dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, S, H, hd).transpose(0, 2, 1, 3)
    k = k.reshape(B, S, H, hd).transpose(0, 2, 1, 3)
    v = v.reshape(B, S, H, hd).transpose(0, 2, 1, 3)
    if attn_override is not None:
        if mask is not None:
            raise ValueError(
                "attn_override does not compose with a key-padding mask "
                "(the sequence-parallel collectives carry no mask plumbing)")
        ctx = attn_override(q, k, v, causal=cfg.causal)
        ctx = ctx.astype(x.dtype).transpose(0, 2, 1, 3).reshape(B, S, D)
        return jnp.einsum("bsd,de->bse", ctx, wo.astype(x.dtype)) \
            + bo.astype(x.dtype)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(
        jnp.asarray(hd, x.dtype))
    if cfg.causal:
        causal = jnp.tril(jnp.ones((S, S), bool))
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
    if mask is not None:
        # key padding mask (B, S), nonzero = PAD — the repo-wide polarity
        # (contrib.multihead_attn / reference apex convention); round 1 used
        # the inverted True=keep here, silently flipping masks shared with
        # the contrib modules
        scores = jnp.where(mask[:, None, None, :] != 0,
                           jnp.asarray(-1e9, scores.dtype), scores)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(x.dtype)
    if dropout_rng is not None and cfg.dropout > 0.0:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - cfg.dropout,
                                    probs.shape)
        probs = probs * keep.astype(probs.dtype) / (1.0 - cfg.dropout)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, D)
    return jnp.einsum("bsd,de->bse", ctx, wo.astype(x.dtype)) + bo.astype(x.dtype)


def _layer(x, lp, cfg: TransformerConfig, mask, dropout_rng,
           attn_override=None):
    """Pre-LN transformer block (the contrib norm-add layout,
    ``apex/contrib/multihead_attn/self_multihead_attn.py`` norm-add variant)."""
    dt = x.dtype
    with annotate("apex.attn"):
        h = fused_layer_norm_affine(x, lp["ln1_g"].astype(dt),
                                    lp["ln1_b"].astype(dt), (cfg.d_model,))
        r1 = None
        if dropout_rng is not None:
            dropout_rng, r1 = jax.random.split(dropout_rng)
        x = x + _attention(h, lp["wqkv"], lp["bqkv"], lp["wo"], lp["bo"],
                           cfg, mask, r1, attn_override)
    with annotate("apex.mlp"):
        h = fused_layer_norm_affine(x, lp["ln2_g"].astype(dt),
                                    lp["ln2_b"].astype(dt), (cfg.d_model,))
        h = jnp.einsum("bsd,df->bsf", h, lp["w1"].astype(dt)) \
            + lp["b1"].astype(dt)
        h = jax.nn.gelu(h)
        h = jnp.einsum("bsf,fd->bsd", h, lp["w2"].astype(dt)) \
            + lp["b2"].astype(dt)
        return x + h


def transformer_apply(params, tokens, cfg: TransformerConfig, *,
                      mask=None, dropout_rng=None, attn_override=None,
                      pos_offset=None):
    """tokens (B, S) int32 -> logits (B, S, V).  Layers run under lax.scan
    over the stacked L axis.  ``mask``: optional key-padding mask (B, S),
    nonzero = PAD (same polarity as contrib.multihead_attn).

    ``attn_override``/``pos_offset`` are the sequence-parallel hooks
    (``parallel.spmd``): the override replaces every layer's attention
    core (see :func:`_attention`), and ``pos_offset`` (a traced int, the
    device's global position of its first local token) shifts the
    position-embedding slice so a sequence-sharded device reads ITS
    positions, not [0, S_local)."""
    if cfg.attn_impl not in ("default", "fast"):
        raise ValueError(
            f"attn_impl must be 'default' or 'fast', got {cfg.attn_impl!r}")
    emb = params["embed"]
    dt = cfg.dtype
    with annotate("apex.embed"):
        if pos_offset is None:
            pos = emb["pos"][: tokens.shape[1]]
        else:
            pos = jax.lax.dynamic_slice_in_dim(emb["pos"], pos_offset,
                                               tokens.shape[1])
        x = emb["tok"][tokens].astype(dt) + pos[None].astype(dt)
        x = fused_layer_norm_affine(x, emb["ln_g"].astype(dt),
                                    emb["ln_b"].astype(dt), (cfg.d_model,))

    n_layers = params["layers"]["wqkv"].shape[0]
    if dropout_rng is not None:
        layer_rngs = jax.random.split(dropout_rng, n_layers)
    else:
        layer_rngs = None

    def body(carry, layer_in):
        lp = layer_in[0] if layer_rngs is not None else layer_in
        rng = layer_in[1] if layer_rngs is not None else None
        layer = _layer
        if cfg.remat:
            # recompute this layer's activations in the backward pass
            # (saves only the between-layer carry); under scan this gives
            # O(1)-in-depth activation memory at ~1/3 extra FLOPs
            # prevent_cse=False: scan already blocks the CSE that the
            # default barriers defend against (per the jax.checkpoint docs)
            layer = jax.checkpoint(
                functools.partial(_layer, cfg=cfg, mask=mask,
                                  attn_override=attn_override),
                prevent_cse=False)
            return layer(carry, lp, dropout_rng=rng), None
        return layer(carry, lp, cfg, mask, rng, attn_override), None

    xs = (params["layers"], layer_rngs) if layer_rngs is not None \
        else params["layers"]
    # unroll>1 (cfg.scan_unroll) threads the layer carry through cloned
    # bodies, turning the one-op-for-all-layers scan grad into per-layer
    # ops the bucketed dp reduction (parallel.overlap) can launch
    # between — XLA cannot schedule a collective into the middle of a
    # single scan op
    x, _ = jax.lax.scan(body, x, xs, unroll=int(cfg.scan_unroll))

    hd = params["head"]
    with annotate("apex.head"):
        x = fused_layer_norm_affine(x, hd["ln_g"].astype(dt),
                                    hd["ln_b"].astype(dt), (cfg.d_model,))
        w_out = (emb["tok"].T if cfg.tie_embeddings else hd["out"]).astype(dt)
        return jnp.einsum("bsd,dv->bsv", x, w_out)


def transformer_loss(params, batch, cfg: TransformerConfig, *,
                     dropout_rng=None, smoothing=0.0, attn_override=None,
                     pos_offset=None):
    """Masked-LM style cross-entropy via the contrib fused xentropy kernel.
    batch: dict(tokens (B,S) int32, targets (B,S) int32,
    weights optional (B,S) f32).  ``attn_override``/``pos_offset``
    thread through to :func:`transformer_apply` (sequence parallelism)."""
    from ..contrib.xentropy import softmax_xentropy_loss
    logits = transformer_apply(params, batch["tokens"], cfg,
                               mask=batch.get("mask"),
                               dropout_rng=dropout_rng,
                               attn_override=attn_override,
                               pos_offset=pos_offset)
    B, S, V = logits.shape
    with annotate("apex.loss"):
        # padding_idx=-1: padding is expressed through ``weights``, and vocab
        # id 0 is a legitimate target here (unlike the reference's seq2seq
        # pad=0)
        nll = softmax_xentropy_loss(logits.reshape(B * S, V),
                                    batch["targets"].reshape(B * S),
                                    smoothing, -1, False,
                                    cfg.xent_impl).reshape(B, S)
        w = batch.get("weights")
        if w is None:
            return nll.mean()
        return (nll * w).sum() / jnp.maximum(w.sum(), 1.0)
