"""Nemotron-H decoder (NVIDIA ``nemotron_h``; docs/nemotron_h.md): every layer
is ONE pre-norm residual block, ``x <- x + mixer(rms(x))``, the mixer chosen
by a character of ``hybrid_override_pattern``; then a final RMSNorm and an
untied head.

- ``M``, Mamba-2: ``[z | xBC | dt] = u W_in``; ``xBC <- silu(conv(xBC) + b)``,
  a causal depthwise convolution over ``conv_kernel`` steps; ``Δ =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; ``h_t = exp(Δ_t A) h_{t-1} +
  Δ_t x_t ⊗ B_t``, ``y_t = C_t·h_t + D x_t`` (a head reads the B, C of its
  group) as a **chunked scan** (:func:`ssd_scan`); ``y <- rms_group(y ⊙
  silu(z)) g``; ``out = y W_out``.
- ``*``, attention: grouped-query heads, causal softmax at ``head_dim^-½``,
  NO position embedding; under ``attn_impl="fast"`` the flash kernel with
  every key/value head repeated for its query heads.
- ``E``, LatentMoE: the sigmoid router over ALL ``n_routed_experts`` reads
  the full-width input and :func:`apex_tpu.parallel.expert.routed_experts`
  runs squared-ReLU experts on its ``moe_latent_size``-wide projection;
  ``out = r W_up + W2ˢ relu(W1ˢ u)²`` with the shared expert on the full
  width.

A chip's share of a layer is part of the configuration — ``mamba_heads_held``
(whole groups), ``attention_heads_held`` (with the key/value heads they
read), ``experts_held``, each ``(first, count)``, and ``vocab_size`` rows of
embedding and head: it computes ITS part of each layer's sum (its columns of
``W_in`` / ``W_q``, its rows of ``W_out`` / ``W_o``, its experts' part of
``r``) and nothing stands in for the absent chips.  The defaults hold
everything, so the uncut model is the same code; :func:`nemotron_h_share`
cuts a share's parameters out of the whole model's.

Layers are a python loop (they differ in shape), each under
``jax.checkpoint`` where ``remat``.  Plain ``jax.numpy`` around the flash
kernel, the grouped products and the loss kernel; XLA fuses the rest.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from ..parallel.expert import routed_experts
from ..pyprof import annotate
from ..telemetry import events as _tel_events
from .lfm2 import _normal, _rms_norm, causal_attention, causal_lm_loss

_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
            "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
_PERIOD = "MEMEMEMEM*E"       # layers 28-38, and three more times after them


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072            # the rows of embedding and head held
    hidden_size: int = 4096
    hybrid_override_pattern: str = _PATTERN
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8                   # B / C groups of the Mamba heads
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 1e-3
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    n_routed_experts: int = 512         # what the router scores
    num_experts_per_tok: int = 22
    moe_latent_size: int = 1024
    moe_intermediate_size: int = 2688   # one routed expert's, in the latent
    moe_shared_expert_intermediate_size: int = 5376
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 5.0
    layer_norm_epsilon: float = 1e-5
    # rescale_prenorm_residual: the matrices that write into the residual
    # stream start 1/sqrt(this) smaller — the PUBLISHED depth, whatever
    # part of the pattern is kept
    rescale_layers: int = 88
    # (first, count) of what lives here; the defaults hold everything
    mamba_heads_held: Tuple[int, int] = (0, 128)
    attention_heads_held: Tuple[int, int] = (0, 32)
    experts_held: Tuple[int, int] = (0, 512)
    dtype: Any = jnp.float32
    remat: bool = False
    attn_impl: str = "default"    # as TransformerConfig's
    xent_impl: str = "auto"       # as TransformerConfig's

    @property
    def num_hidden_layers(self) -> int:
        return len(self.hybrid_override_pattern)

    @property
    def heads_a_group(self) -> int:
        assert self.mamba_num_heads % self.n_groups == 0
        return self.mamba_num_heads // self.n_groups

    @property
    def groups_held(self) -> Tuple[int, int]:
        """(first, count) of the B / C groups the held Mamba heads read."""
        first, count = self.mamba_heads_held
        if first % self.heads_a_group or count % self.heads_a_group:
            raise ValueError(f"mamba_heads_held {self.mamba_heads_held} is "
                             f"not whole groups of {self.heads_a_group}")
        return first // self.heads_a_group, count // self.heads_a_group

    @property
    def kv_heads_held(self) -> Tuple[int, int]:
        """(first, count) of the key/value heads the held query heads read."""
        first, count = self.attention_heads_held
        serves = self.num_attention_heads // self.num_key_value_heads
        kv_first = first // serves
        return kv_first, (first + count - 1) // serves - kv_first + 1


def nemotron3_super_120b_a12b_config(**overrides) -> NemotronHConfig:
    """NVIDIA-Nemotron-3-Super-120B-A12B as published (the defaults above),
    without its multi-token-prediction module (docs/nemotron_h.md).  A cut
    keeps whole periods of the pattern and a share of heads, experts and
    vocabulary: see ``examples/bert/pretrain.py --nemotron-h``."""
    return NemotronHConfig(**overrides)


def nemotron_h_cut_pattern(periods: int) -> str:
    """``periods`` whole periods (five ``M``, one ``*``, five ``E``) of the
    published pattern."""
    return _PERIOD * periods


def _widths(cfg: NemotronHConfig):
    """(Mamba inner width, convolved width — x beside B and C) of the held
    share."""
    inner = cfg.mamba_heads_held[1] * cfg.mamba_head_dim
    return inner, inner + 2 * cfg.groups_held[1] * cfg.ssm_state_size


def nemotron_h_init(key, cfg: NemotronHConfig):
    """Parameter tree of the held share: ``embed/tok``, a list of layers and
    ``head/norm``, ``head/out``.  Matrices are N(0, 1/fan_in) — those that
    write into the residual stream (``out_proj``, ``wo``, ``latent_up``,
    ``shared_w2``) 1/sqrt(``rescale_layers``) smaller, the config's
    ``rescale_prenorm_residual`` — and the embedding N(0, 1): a token's own
    embedding carries the stream at initialisation, not the sum of the
    blocks' outputs, whose common part (a squared ReLU's mean) would make
    every token choose the same experts.  ``A_log`` = log U(1, 16);
    ``dt_bias`` the inverse softplus of a log-uniform step in
    [``time_step_min``, ``time_step_max``] floored at ``time_step_floor``;
    ``D`` and the norm gains 1."""
    d, ell = cfg.hidden_size, cfg.moe_latent_size
    f, fs = cfg.moe_intermediate_size, cfg.moe_shared_expert_intermediate_size
    heads = cfg.mamba_heads_held[1]
    inner, convolved = _widths(cfg)
    q = cfg.attention_heads_held[1] * cfg.head_dim
    kv = cfg.kv_heads_held[1] * cfg.head_dim
    held = cfg.experts_held[1]
    key, k_tok, k_out = jax.random.split(key, 3)

    def writer(key, shape, fan_in):      # into the residual stream
        return _normal(key, shape, fan_in * cfg.rescale_layers)
    layers = []
    for i, kind in enumerate(cfg.hybrid_override_pattern):
        key, k1, k2, k3, k4, k5, k6, k7 = jax.random.split(key, 8)
        layer = {"norm": jnp.ones((d,), jnp.float32)}
        if kind == "M":
            step = jnp.exp(jax.random.uniform(k4, (heads,), jnp.float32)
                           * math.log(cfg.time_step_max / cfg.time_step_min)
                           + math.log(cfg.time_step_min))
            step = jnp.maximum(step, cfg.time_step_floor)
            layer.update(
                in_proj=_normal(k1, (d, inner + convolved + heads), d),
                conv_w=_normal(k2, (cfg.conv_kernel, convolved),
                               cfg.conv_kernel),
                conv_b=jnp.zeros((convolved,), jnp.float32),
                dt_bias=step + jnp.log(-jnp.expm1(-step)),
                A_log=jnp.log(jax.random.uniform(
                    k5, (heads,), jnp.float32, 1.0, 16.0)),
                D=jnp.ones((heads,), jnp.float32),
                gate_norm=jnp.ones((inner,), jnp.float32),
                out_proj=writer(k3, (inner, d), inner))
        elif kind == "*":
            layer.update(
                wq=_normal(k1, (d, q), d), wk=_normal(k2, (d, kv), d),
                wv=_normal(k3, (d, kv), d), wo=writer(k4, (q, d), q))
        elif kind == "E":
            layer.update(
                router=_normal(k1, (d, cfg.n_routed_experts), d),
                # e_score_correction_bias: chooses, never weighs, and no
                # gradient reaches it: zero, and it stays zero
                expert_bias=jnp.zeros((cfg.n_routed_experts,), jnp.float32),
                latent_down=_normal(k2, (d, ell), d),
                latent_up=writer(k3, (ell, d), ell),
                w1=_normal(k4, (held, ell, f), ell),
                w2=_normal(k5, (held, f, ell), f),
                shared_w1=_normal(k6, (d, fs), d),
                shared_w2=writer(k7, (fs, d), fs))
        else:
            raise ValueError(f"hybrid_override_pattern[{i}] = {kind!r}")
        layers.append(layer)
    return {"embed": {"tok": _normal(k_tok, (cfg.vocab_size, d), 1)},
            "layers": layers,
            "head": {"norm": jnp.ones((d,), jnp.float32),
                     "out": _normal(k_out, (d, cfg.vocab_size), d)}}


def nemotron_h_share(params, whole: NemotronHConfig, cfg: NemotronHConfig):
    """The parameters ``cfg``'s share holds, cut out of ``whole``'s (which
    holds everything): its heads' columns of ``W_in`` / ``W_q`` and rows of
    ``W_out`` / ``W_o``, its experts, the first ``vocab_size`` ids.  What
    every chip holds alike (norms, router, latent projections, the shared
    expert) is copied."""
    p, n = whole.mamba_head_dim, whole.ssm_state_size
    h0, h = cfg.mamba_heads_held
    g0, g = cfg.groups_held
    inner, groups_n = whole.mamba_num_heads * p, whole.n_groups * n
    # where [z | x | B | C | dt] start in the whole model's W_in
    heads_cols = jnp.arange(h0 * p, (h0 + h) * p)
    group_cols = jnp.arange(g0 * n, (g0 + g) * n)
    conv_cols = jnp.concatenate([heads_cols, inner + group_cols,
                                 inner + groups_n + group_cols])
    in_cols = jnp.concatenate([
        heads_cols, inner + conv_cols,
        2 * inner + 2 * groups_n + jnp.arange(h0, h0 + h)])
    q0, q = cfg.attention_heads_held
    kv0, kv = cfg.kv_heads_held
    hd = whole.head_dim
    q_cols = slice(q0 * hd, (q0 + q) * hd)
    kv_cols = slice(kv0 * hd, (kv0 + kv) * hd)
    e0, e = cfg.experts_held

    def cut(kind, lp):
        if kind == "M":
            return dict(
                lp, in_proj=lp["in_proj"][:, in_cols],
                conv_w=lp["conv_w"][:, conv_cols],
                conv_b=lp["conv_b"][conv_cols],
                dt_bias=lp["dt_bias"][h0:h0 + h], A_log=lp["A_log"][h0:h0 + h],
                D=lp["D"][h0:h0 + h], gate_norm=lp["gate_norm"][heads_cols],
                out_proj=lp["out_proj"][heads_cols])
        if kind == "*":
            return dict(lp, wq=lp["wq"][:, q_cols], wk=lp["wk"][:, kv_cols],
                        wv=lp["wv"][:, kv_cols], wo=lp["wo"][q_cols])
        return dict(lp, w1=lp["w1"][e0:e0 + e], w2=lp["w2"][e0:e0 + e])

    return {"embed": {"tok": params["embed"]["tok"][:cfg.vocab_size]},
            "layers": [cut(kind, lp) for kind, lp in zip(
                cfg.hybrid_override_pattern, params["layers"])],
            "head": {"norm": params["head"]["norm"],
                     "out": params["head"]["out"][:, :cfg.vocab_size]}}


def _causal_conv(x, w, b):
    """``b + Σ_j w_j ⊙ x_{t-(K-1)+j}`` over x (B, S, C), zeros left of the
    sequence; ``w`` (K, C)."""
    taps, seq = w.shape[0], x.shape[1]
    x = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return b + sum(w[j] * x[:, j:j + seq] for j in range(taps))


def ssd_scan(x, delta, a, b_in, c_out, chunk: int):
    """``y_t = C_t·h_t`` of ``h_t = exp(Δ_t A) h_{t-1} + Δ_t x_t ⊗ B_t`` in
    the chunked (state-space-dual) form: within a chunk of ``chunk`` steps
    the masked products ``(C Bᵀ ∘ decay ∘ Δ) x``, across chunks a recurrence
    over the S / chunk states.

    ``x`` (B, S, H, P); ``delta`` (B, S, H) float32; ``a`` (H,) float32,
    negative; ``b_in`` / ``c_out`` (B, S, G, N), a head reading group
    ``h // (H // G)``.  Returns (B, S, H, P) float32.  Δ·A, its cumulative
    sums, every decay and the chunk states are float32; the four products
    take operands of ``x``'s dtype and accumulate in float32."""
    bsz, seq, heads, p = x.shape
    groups, n = b_in.shape[2:]
    per = heads // groups
    dt = x.dtype
    pad = -seq % chunk          # Δ = 0: the state passes a step unchanged
    if pad:
        x, delta, b_in, c_out = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, delta, b_in, c_out))
    nc = (seq + pad) // chunk
    x = x.reshape(bsz, nc, chunk, groups, per, p)
    delta = delta.reshape(bsz, nc, chunk, groups, per)
    b_in = b_in.reshape(bsz, nc, chunk, groups, n)
    c_out = c_out.reshape(bsz, nc, chunk, groups, n)
    product = functools.partial(jnp.einsum,
                                preferred_element_type=jnp.float32)

    # log of the decay from a chunk's start to the end of step i
    cum = jnp.cumsum(delta * a.reshape(groups, per), axis=2)
    # -- within a chunk: y_i += Σ_{j<=i} (C_i·B_j) exp(cum_i - cum_j) Δ_j x_j
    cum_t = cum.transpose(0, 1, 3, 4, 2)                    # (B, c, G, k, i)
    later = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(later, cum_t[..., :, None] - cum_t[..., None, :],
                              -jnp.inf))                    # (B,c,G,k,i,j)
    scores = product("bcign,bcjgn->bcgij", c_out, b_in)
    mixed = (scores[:, :, :, None] * decay
             * delta.transpose(0, 1, 3, 4, 2)[..., None, :]).astype(dt)
    y = product("bcgkij,bcjgkp->bcigkp", mixed, x)
    # -- a chunk's own state at its end: Σ_j exp(cum_last - cum_j) Δ_j x_j ⊗ B_j
    to_end = jnp.exp(cum[:, :, -1:] - cum) * delta          # (B, c, j, G, k)
    states = product("bcjgn,bcjgkp->bcgkpn", b_in,
                     (x * to_end[..., None]).astype(dt))
    # -- across chunks: the state a chunk starts from
    whole = jnp.exp(cum[:, :, -1])                          # (B, c, G, k)

    def carry_on(state, chunk_in):
        own, decayed = chunk_in
        return state * decayed[..., None, None] + own, state

    _, entering = jax.lax.scan(
        carry_on, jnp.zeros_like(states[:, 0]),
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(whole, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1).astype(dt)      # (B,c,G,k,P,N)
    y = y + product("bcign,bcgkpn->bcigkp", c_out, entering) \
        * jnp.exp(cum)[..., None]
    return y.reshape(bsz, nc * chunk, heads, p)[:, :seq]


def _mamba_mixer(u, lp, cfg: NemotronHConfig):
    dt = u.dtype
    bsz, seq, _ = u.shape
    heads, p, n = cfg.mamba_heads_held[1], cfg.mamba_head_dim, \
        cfg.ssm_state_size
    groups = cfg.groups_held[1]
    inner, convolved = _widths(cfg)
    _tel_events.record_ssm_layout(heads=heads, chunk=cfg.chunk_size,
                                  chunks=-(-seq // cfg.chunk_size))
    z, xbc, step = jnp.split(u @ lp["in_proj"].astype(dt),
                             [inner, inner + convolved], axis=-1)
    xbc = jax.nn.silu(_causal_conv(xbc, lp["conv_w"].astype(dt),
                                   lp["conv_b"].astype(dt)))
    x, b_in, c_out = jnp.split(xbc, [inner, inner + groups * n], axis=-1)
    x = x.reshape(bsz, seq, heads, p)
    with annotate("apex.ssm_scan"):
        delta = jax.nn.softplus(step.astype(jnp.float32) + lp["dt_bias"])
        y = ssd_scan(x, delta, -jnp.exp(lp["A_log"]),
                     b_in.reshape(bsz, seq, groups, n),
                     c_out.reshape(bsz, seq, groups, n), cfg.chunk_size)
        y = y + lp["D"][:, None] * x.astype(jnp.float32)
    # y <- rms(y ⊙ silu(z)) g over each group of heads, in float32
    y = y.reshape(bsz, seq, inner) * jax.nn.silu(z.astype(jnp.float32))
    y = y.reshape(bsz, seq, groups, inner // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + cfg.layer_norm_epsilon)
    y = (y.reshape(bsz, seq, inner) * lp["gate_norm"]).astype(dt)
    return y @ lp["out_proj"].astype(dt)


def _attention_mixer(u, lp, cfg: NemotronHConfig):
    dt = u.dtype
    bsz, seq, _ = u.shape
    (first, heads), (kv_first, _) = cfg.attention_heads_held, cfg.kv_heads_held
    hd = cfg.head_dim
    serves = cfg.num_attention_heads // cfg.num_key_value_heads
    q = (u @ lp["wq"].astype(dt)).reshape(bsz, seq, heads, hd)
    k = (u @ lp["wk"].astype(dt)).reshape(bsz, seq, -1, hd)
    v = (u @ lp["wv"].astype(dt)).reshape(bsz, seq, -1, hd)
    # (B, H, S, hd); query head h reads key/value head h // serves
    reads = jnp.asarray([(first + h) // serves - kv_first
                         for h in range(heads)])
    q = (q * hd ** -0.5).astype(dt).transpose(0, 2, 1, 3)
    k = k.transpose(0, 2, 1, 3)[:, reads]
    v = v.transpose(0, 2, 1, 3)[:, reads]
    return causal_attention(q, k, v, cfg.attn_impl) @ lp["wo"].astype(dt)


def _latent_moe(u, lp, cfg: NemotronHConfig):
    """``(out, routing)``: the held experts' part of ``r`` through the latent
    projections, plus the shared expert."""
    dt = u.dtype
    flat = u.reshape(-1, u.shape[-1])
    with annotate("apex.latent"):
        latent = flat @ lp["latent_down"].astype(dt)
    routed, routing = routed_experts(
        flat, lp["router"], lp["expert_bias"], lp["w1"], lp["w2"],
        top_k=cfg.num_experts_per_tok, first=cfg.experts_held[0],
        norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling_factor, form="relu2",
        rows=latent)
    with annotate("apex.latent"):
        out = routed @ lp["latent_up"].astype(dt)
    with annotate("apex.shared_expert"):
        hidden = jnp.square(jax.nn.relu(flat @ lp["shared_w1"].astype(dt)))
        out = out + hidden @ lp["shared_w2"].astype(dt)
    return out.reshape(u.shape), routing


_SCOPE = {"M": "apex.ssm", "*": "apex.attn", "E": "apex.moe"}


def _block(x, lp, *, cfg: NemotronHConfig, kind: str):
    """One layer: ``kind`` picks the mixer.  Returns ``(y, routing)``:
    ``routed_experts``' record from an ``E`` layer, else None."""
    with annotate(_SCOPE[kind]):
        u = _rms_norm(x, lp["norm"], cfg.layer_norm_epsilon)
        if kind == "E":
            out, routing = _latent_moe(u, lp, cfg)
            return x + out, routing
        mixer = _mamba_mixer if kind == "M" else _attention_mixer
        return x + mixer(u, lp, cfg), None


def _forward(params, tokens, cfg: NemotronHConfig):
    """``(logits, routing)``: ``routing`` stacks every ``E`` layer's record
    (``ids`` (L, T, k), ``rows`` (L, held), ``dropped`` (L,), ``walks``
    (L,), ``slots`` (L,))."""
    if cfg.attn_impl not in ("default", "fast"):
        raise ValueError(
            f"attn_impl must be 'default' or 'fast', got {cfg.attn_impl!r}")
    if len(params["layers"]) != cfg.num_hidden_layers:
        raise ValueError(f"{len(params['layers'])} layers of parameters for "
                         f"a pattern of {cfg.num_hidden_layers}")
    dt = cfg.dtype
    with annotate("apex.embed"):
        x = params["embed"]["tok"].astype(dt)[tokens]
    records = []
    for kind, lp in zip(cfg.hybrid_override_pattern, params["layers"]):
        block = functools.partial(_block, cfg=cfg, kind=kind)
        if cfg.remat:
            block = jax.checkpoint(block)
        x, record = block(x, lp)
        if record is not None:
            records.append(record)
    routing = jax.tree_util.tree_map(lambda *r: jnp.stack(r), *records) \
        if records else None
    if records and _tel_events.active():
        # the routing meter, as models.lfm2 has it: once a forward pass
        jax.debug.callback(_tel_events.record_expert_rows, routing["rows"],
                           jnp.sum(routing["dropped"]), routing["walks"],
                           slots=routing["slots"])
    with annotate("apex.head"):
        x = _rms_norm(x, params["head"]["norm"], cfg.layer_norm_epsilon)
        return x @ params["head"]["out"].astype(dt), routing


def nemotron_h_apply(params, tokens, cfg: NemotronHConfig):
    """tokens (B, S) int32 -> logits (B, S, V) over the held vocabulary."""
    return _forward(params, tokens, cfg)[0]


def nemotron_h_routing(params, tokens, cfg: NemotronHConfig):
    """What the forward pass over ``tokens`` routed, every ``E`` layer
    stacked: ``models.lfm2.lfm2_routing``'s record."""
    return _forward(params, tokens, cfg)[1]


def nemotron_h_loss(params, batch, cfg: NemotronHConfig):
    """Next-token cross entropy over the held vocabulary, as ``lfm2_loss``."""
    return causal_lm_loss(nemotron_h_apply(params, batch["tokens"], cfg),
                          batch, cfg.xent_impl)
