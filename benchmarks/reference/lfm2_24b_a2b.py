"""Plain float32 reference of the decoder the ``lfm2_24b_a2b`` configuration
trains: forward pass and next-token loss in straightforward ``jax.numpy``.

No kernels, no sort, no grouped product, no remat, no mixed precision;
matrix products at ``precision="highest"`` (set by the caller through
``jax.default_matmul_precision``), so on a TPU they are true float32.
Gradients are ``jax.grad`` of :func:`loss`.  The expert layer is a loop over
the held experts with a mask: every token goes through every held expert and
the mask keeps what the router chose.

It follows the ``lfm2_moe`` configuration of LFM2-24B-A2B
(https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json):

    h = x + mixer(rms(x));  y = h + ffn(rms(h));  rms(x) = x rsqrt(mean x² + eps) g
    conv mixer:   [B, C, X] = split3(W_in u); z = B ⊙ X;
                  c_t = Σ_j w_j ⊙ z_{t-(L-1)+j}  (zeros left of the sequence);
                  out = W_out (C ⊙ c)
    attention:    q = rope(rms_head(W_q u)), k = rope(rms_head(W_k u)),
                  causal softmax(q kᵀ / sqrt(hd)) v, a key/value head serving
                  heads / kv_heads query heads; out = W_o ctx
    dense FFN:    W2 (silu(W1 h) ⊙ W3 h)
    expert FFN:   s = sigmoid(W_g h); S = top-k(s + b); w_e = s_e / (Σ_S s + 1e-6)
                  · routed_scaling_factor; Σ_{e ∈ S} w_e · W2ᵉ(silu(W1ᵉ h) ⊙ W3ᵉ h)

with these departures, each of them the benchmark configuration's and stated
in ``configs/lfm2_24b_a2b.json``:

- the head is the token embedding, transposed (the row gives no key; the
  family ties);
- the expert bias ``b`` is a fixed buffer that chooses and does not weigh;
  the program initialises it to zero and no gradient moves it;
- the share: only the experts ``experts_held = (first, count)`` exist here.
  The router scores all ``num_experts``, and an assignment to an absent
  expert adds nothing — what the other chips of the expert-parallel group
  would add is left out, and that partial sum goes on to the next layer.
  The vocabulary is the held slice: logits and loss are over it.

Parameters arrive in the program's own tree (they are data): ``embed/tok``;
``layers[i]`` with ``op_norm``, ``ffn_norm`` and ``in_proj, conv (L, d),
out_proj`` or ``wq, wk, wv, wo, q_norm, k_norm``, then ``w13 (d, 2f), w2``
or ``router, expert_bias, w13 (held, d, 2f), w2 (held, f, d)`` — ``w13`` is
W1 beside W3; ``head/norm``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _rms(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _rope(x, theta):
    """x (B, S, H, hd): rotate-half convention, positions 0..S-1."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / theta ** (np.arange(half, dtype=np.float64) / half)
    angle = np.arange(x.shape[1], dtype=np.float64)[:, None] * inv_freq[None]
    cos = jnp.asarray(np.cos(angle), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(angle), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _conv_mixer(u, p, model):
    b, c, x = jnp.split(u @ p["in_proj"], 3, axis=-1)
    z = b * x
    taps = model["conv_L_cache"]
    conv = jnp.zeros_like(z)
    for j in range(taps):
        back = taps - 1 - j                      # w_j multiplies z_{t-back}
        shifted = jnp.pad(z, ((0, 0), (back, 0), (0, 0)))[:, : z.shape[1]]
        conv = conv + p["conv"][j] * shifted
    return (c * conv) @ p["out_proj"]


def _attention_mixer(u, p, model):
    batch, seq, d = u.shape
    heads, kv_heads = model["num_attention_heads"], model["num_key_value_heads"]
    hd = d // heads
    q = (u @ p["wq"]).reshape(batch, seq, heads, hd)
    k = (u @ p["wk"]).reshape(batch, seq, kv_heads, hd)
    v = (u @ p["wv"]).reshape(batch, seq, kv_heads, hd)
    q = _rope(_rms(q, p["q_norm"], model["norm_eps"]), model["rope_theta"])
    k = _rope(_rms(k, p["k_norm"], model["norm_eps"]), model["rope_theta"])
    group = heads // kv_heads
    causal = np.tril(np.ones((seq, seq), bool))
    out = []
    for h in range(heads):
        kh, vh = k[:, :, h // group], v[:, :, h // group]
        scores = jnp.einsum("bqd,bkd->bqk", q[:, :, h], kh) / np.sqrt(hd)
        scores = jnp.where(causal[None], scores, -jnp.inf)
        scores = scores - jnp.max(scores, axis=-1, keepdims=True)
        probs = jnp.exp(scores)
        probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
        out.append(jnp.einsum("bqk,bkd->bqd", probs, vh))
    return jnp.concatenate(out, axis=-1) @ p["wo"]


def _dense_ffn(h, p):
    gate, up = jnp.split(h @ p["w13"], 2, axis=-1)
    return (_silu(gate) * up) @ p["w2"]


def _route(h, p, model):
    """``(chosen (T, E) bool, weights (T, E))`` over all experts."""
    scores = 1.0 / (1.0 + jnp.exp(-(h @ p["router"])))
    bias = p["expert_bias"] if model["use_expert_bias"] else 0.0
    choose = jax.lax.stop_gradient(scores + bias)
    kth = jnp.sort(choose, axis=-1)[:, -model["num_experts_per_tok"]]
    chosen = choose >= kth[:, None]
    weights = jnp.where(chosen, scores, 0.0)
    if model["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-6)
    return chosen, weights * model["routed_scaling_factor"]


def _expert_ffn(h, p, model):
    """``(the held experts' part of the sum, chosen (T, E) bool)``."""
    shape = h.shape
    h = h.reshape(-1, shape[-1])
    chosen, weights = _route(h, p, model)
    first, count = model["experts_held"]
    out = jnp.zeros_like(h)
    for e in range(count):
        gate, up = jnp.split(h @ p["w13"][e], 2, axis=-1)
        out = out + weights[:, first + e, None] * (
            (_silu(gate) * up) @ p["w2"][e])
    return out.reshape(shape), chosen


def _forward(params, tokens, model):
    """``(hidden state after the last layer, [chosen] per expert layer)``."""
    x = params["embed"]["tok"][tokens]
    chosen = []
    for i, kind in enumerate(model["layer_types"]):
        p = params["layers"][i]
        u = _rms(x, p["op_norm"], model["norm_eps"])
        x = x + (_conv_mixer if kind == "conv" else _attention_mixer)(
            u, p, model)
        h = _rms(x, p["ffn_norm"], model["norm_eps"])
        if i < model["num_dense_layers"]:
            x = x + _dense_ffn(h, p)
        else:
            out, picked = _expert_ffn(h, p, model)
            x = x + out
            chosen.append(picked)
    return x, chosen


def logits(params, tokens, model):
    """tokens (B, S) int32 -> logits (B, S, V) float32."""
    x, _ = _forward(params, tokens, model)
    x = _rms(x, params["head"]["norm"], model["norm_eps"])
    return x @ params["embed"]["tok"].T


def routing(params, tokens, model):
    """(expert layers, B·S, E) bool: the experts every token chose."""
    return jnp.stack(_forward(params, tokens, model)[1])


def loss_sum(params, batch, model):
    """``Σ nll · weights`` over the batch — so that a caller can take a
    batch a sequence at a time and divide by the whole weight."""
    lg = logits(params, batch["tokens"], model)
    lg = lg - jnp.max(lg, axis=-1, keepdims=True)
    log_probs = lg - jnp.log(jnp.sum(jnp.exp(lg), axis=-1, keepdims=True))
    nll = -jnp.take_along_axis(log_probs, batch["targets"][..., None],
                               axis=-1)[..., 0]
    return jnp.sum(nll * batch["weights"])


def loss(params, batch, model):
    """Mean negative log-likelihood of ``targets`` (the tokens shifted by
    one) over the positions whose ``weights`` are 1."""
    return loss_sum(params, batch, model) / jnp.maximum(
        jnp.sum(batch["weights"]), 1.0)
