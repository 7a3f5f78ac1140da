"""Plain float32 reference of the decoder the ``laguna_s_2_1`` configuration
trains: forward pass and next-token loss in straightforward ``jax.numpy``.

No kernels, no flash, no sort-and-gather dispatch, no grouped product, no
mixed precision; matrix products at ``precision="highest"`` (set by the
caller through ``jax.default_matmul_precision``), so on a TPU they are true
float32.  Gradients are ``jax.grad`` of :func:`loss`.  Attention is
written out head by head, each head's softmax a block of ``QUERY_BLOCK``
queries against every key at a time under a dense mask — the causal one, or
for a window layer the band's; the expert layer is a loop (``lax.scan``)
over the held experts with a weight: every token goes through every held
expert and the weight, 0 where the router did not choose it, keeps what it
chose.

It follows the ``laguna`` configuration of Laguna-S-2.1
(https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json) and,
for what the configuration does not state, the convention of the family
whose keys it uses (Qwen2-MoE / Qwen3-MoE / Qwen3-Next:
``decoder_sparse_step``, ``mlp_only_layers``, ``norm_topk_prob``,
``shared_expert_intermediate_size``), each such choice listed under
``assumed`` in ``configs/laguna_s_2_1.json``.  With ``rms(x; w) = w ⊙ x /
sqrt(mean x² + eps)`` every layer is ``x <- x + attn(rms(x)); x <- x +
ffn(rms(x))``; then a final ``rms`` and an untied head.

    attention, H query heads (48 full, 72 window), 8 key-value heads of 128
       q = rms_head(u W_q), k = rms_head(u W_k), v = u W_v, a head each;
       query head h reads key-value head h // (H / 8)
       full layer:   RoPE (rotate-half) on dims 0..63 of q and k with YaRN's
                     frequencies (θ 5e5, factor 128 over 8192, β 32 / 1),
                     cos and sin times attention_factor; i sees j <= i
       window layer: RoPE on all 128 dims at θ 1e4; i sees i - 512 < j <= i
       ctx_h = softmax(q_h k_hᵀ / sqrt(128), masked) v_h
       out = (ctx_h · sigmoid(u W_g)_h)_h W_o      one gate a head
    dense FFN (layer 0)   W2(silu(W1 x) ⊙ W3 x)
    sparse FFN (the others)
       s = softmax(x W_r) over all 256;  S = top-10(s);  w_e = 2.5 s_e / Σ_S s
       out = Σ_{e ∈ S} w_e W2ᵉ(silu(W1ᵉ x) ⊙ W3ᵉ x)
             + sigmoid(x w_s) W2ˢ(silu(W1ˢ x) ⊙ W3ˢ x)
    L = CE(logits, t_{i+1}), a weighted mean

Departures, each of them the benchmark configuration's and stated in
``configs/laguna_s_2_1.json``:

- the share: only the routed experts ``experts_held`` = (first, count) exist
  here.  A share computes ITS experts' part of the routed sum; what the
  absent chips would add is left out, and that partial result goes on to the
  next layer.  The router scores all ``num_experts``.  The vocabulary is the
  held slice: logits and loss are over it;
- the depth: the layers of ``layer_types`` (the published first five);
- RoPE rotates halves (the family's layout);
- ``jax.checkpoint`` around each block, each head of attention and each
  block of queries: memory only, no value changes.

Parameters arrive in the program's own tree (they are data): ``embed/tok``;
``layers[i]`` with ``input_norm, ffn_norm, wq, wk, wv, wo, w_gate (d, H),
q_norm, k_norm`` and ``w13, w2`` (dense) | ``router, w13 (held, d, 2f), w2
(held, f, d), shared_w13, shared_w2, shared_gate (d, 1)``; ``head/norm``,
``head/out`` (d, V).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512     # queries of one head whose scores exist at once


def _rms(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _silu(x):
    return x * _sigmoid(x)


def yarn(model):
    """(inverse frequencies of the full layers' rotary dims, scale of cos
    and sin): the YaRN formula (arXiv:2309.00071 §3.2-3.3), with the ramp's
    ends rounded outward to whole dims as the public implementations do."""
    dim = int(model["head_dim"] * model["partial_rotary_factor"])
    base, factor = model["rope_theta"], model["yarn_factor"]
    length = model["original_max_position_embeddings"]
    # the dim whose wavelength fits ``turns`` times into the trained length
    at = [dim * math.log(length / (turns * 2 * math.pi)) / (2 * math.log(base))
          for turns in (model["beta_fast"], model["beta_slow"])]
    low, high = max(math.floor(at[0]), 0), min(math.ceil(at[1]), dim - 1)
    high = high if high > low else low + 0.001     # a ramp of one dim
    theta = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    interpolate = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return (theta * (1 - interpolate) + theta / factor * interpolate,
            model["attention_factor"])


def _frequencies(model, kind):
    if kind == "full_attention":
        return yarn(model)
    dim = model["head_dim"]
    return (model["sliding_rope_theta"]
            ** (-np.arange(0, dim, 2, dtype=np.float64) / dim), 1.0)


def _rope(x, inv_freq, scale):
    """Rotate-half RoPE on the first 2·len(inv_freq) dims of x (B, S, H, hd)
    at positions 0..S-1; the rest pass."""
    half = inv_freq.shape[0]
    angle = np.arange(x.shape[1], dtype=np.float64)[:, None] * inv_freq[None]
    cos = jnp.asarray(np.cos(angle) * scale, jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(angle) * scale, jnp.float32)[None, :, None, :]
    first, second = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin, x[..., 2 * half:]],
                           axis=-1)


def _attention(u, p, model, kind, heads):
    batch, seq, _ = u.shape
    hd, kv, eps = (model["head_dim"], model["num_key_value_heads"],
                   model["rms_norm_eps"])
    q = _rms((u @ p["wq"]).reshape(batch, seq, heads, hd), p["q_norm"], eps)
    k = _rms((u @ p["wk"]).reshape(batch, seq, kv, hd), p["k_norm"], eps)
    v = (u @ p["wv"]).reshape(batch, seq, kv, hd)
    gate = _sigmoid(u @ p["w_gate"])                          # (B, S, H)
    inv_freq, scale = _frequencies(model, kind)
    q, k = _rope(q, inv_freq, scale), _rope(k, inv_freq, scale)
    k, v = (jnp.repeat(t, heads // kv, axis=2) for t in (k, v))
    window = model["sliding_window"] if kind == "sliding_attention" else seq
    blocks = -(-seq // QUERY_BLOCK)
    pad = blocks * QUERY_BLOCK - seq

    @jax.checkpoint
    def attend(head):
        q_h, k_h, v_h = head                  # (B, S, hd) of one head
        q_h = jnp.pad(q_h, ((0, 0), (0, pad), (0, 0)))

        @jax.checkpoint
        def a_block(i):
            at = i * QUERY_BLOCK
            rows = jax.lax.dynamic_slice_in_dim(q_h, at, QUERY_BLOCK, 1)
            scores = jnp.einsum("bqd,bkd->bqk", rows, k_h) / math.sqrt(hd)
            # the padding's rows read as the last query (and are dropped)
            query = jnp.minimum(at + jnp.arange(QUERY_BLOCK), seq - 1)[:, None]
            key = jnp.arange(seq)[None, :]
            seen = (key <= query) & (key > query - window)
            scores = jnp.where(seen[None], scores, -jnp.inf)
            scores = scores - jnp.max(scores, axis=-1, keepdims=True)
            probs = jnp.exp(scores)
            probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
            return jnp.einsum("bqk,bkd->bqd", probs, v_h)

        out = jax.lax.map(a_block, jnp.arange(blocks))   # (n, B, Q, hd)
        return jnp.moveaxis(out, 0, 1).reshape(batch, -1, hd)[:, :seq]

    ctx = jax.lax.map(attend, tuple(jnp.moveaxis(t, 2, 0)
                                    for t in (q, k, v)))  # (H, B, S, hd)
    ctx = jnp.moveaxis(ctx, 0, 2) * gate[..., None]
    return ctx.reshape(batch, seq, heads * hd) @ p["wo"]


def _gated(x, w13, w2):
    gate, up = jnp.split(x @ w13, 2, axis=-1)
    return (_silu(gate) * up) @ w2


def _route(x, p, model):
    """``(chosen (T, E) bool, weights (T, E))`` over all experts."""
    logits = x @ p["router"]
    scores = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    scores = scores / jnp.sum(scores, axis=-1, keepdims=True)
    kth = jnp.sort(jax.lax.stop_gradient(scores),
                   axis=-1)[:, -model["num_experts_per_tok"]]
    chosen = scores >= kth[:, None]
    weights = jnp.where(chosen, scores, 0.0)
    if model["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return chosen, weights * model["moe_routed_scaling_factor"]


def _sparse_ffn(x, p, model):
    """``(the layer's output with the held experts' part of the routed sum,
    chosen)``."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    chosen, weights = _route(x, p, model)
    first, count = model["experts_held"]

    def add_expert(routed, expert):
        w13, w2, weight = expert                # weight (T,): 0 where not chosen
        return routed + weight[:, None] * _gated(x, w13, w2), None

    routed, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(x),
        (p["w13"], p["w2"], weights[:, first:first + count].T))
    shared = _sigmoid(x @ p["shared_gate"]) * _gated(
        x, p["shared_w13"], p["shared_w2"])
    return (routed + shared).reshape(shape), chosen


def _block(x, p, model, kind, heads, dense):
    """``(y, chosen | None)``."""
    eps = model["rms_norm_eps"]
    x = x + jax.checkpoint(lambda x, p: _attention(
        _rms(x, p["input_norm"], eps), p, model, kind, heads))(x, p)
    if dense:
        return x + _gated(_rms(x, p["ffn_norm"], eps), p["w13"], p["w2"]), \
            None
    out, chosen = jax.checkpoint(lambda x, p: _sparse_ffn(
        _rms(x, p["ffn_norm"], eps), p, model))(x, p)
    return x + out, chosen


def _forward(params, batch, model):
    """``(logits, [chosen] of every sparse layer)``."""
    x = params["embed"]["tok"][batch["tokens"]]
    chosen = []
    for kind, heads, mlp, p in zip(model["layer_types"],
                                   model["num_attention_heads_per_layer"],
                                   model["mlp_layer_types"],
                                   params["layers"]):
        x, picked = _block(x, p, model, kind, heads, mlp == "dense")
        if picked is not None:
            chosen.append(picked)
    head = params["head"]
    return _rms(x, head["norm"], model["rms_norm_eps"]) @ head["out"], chosen


def _nll(logits, targets):
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    log_probs = logits - jnp.log(jnp.sum(jnp.exp(logits), axis=-1,
                                         keepdims=True))
    return -jnp.take_along_axis(log_probs, targets[..., None], axis=-1)[..., 0]


def loss(params, batch, model):
    """Next-token cross entropy over ``batch``, a weighted mean."""
    logits, _ = _forward(params, batch, model)
    w = batch["weights"]
    return jnp.sum(_nll(logits, batch["targets"]) * w) / jnp.maximum(
        jnp.sum(w), 1.0)


def routing(params, batch, model):
    """(sparse layers, B·S, num_experts) bool: the experts every token
    chose."""
    return jnp.stack(_forward(params, batch, model)[1])
