"""Plain float32 reference of the decoder the ``nemotron3_super_120b_a12b``
configuration trains: forward pass and next-token loss in straightforward
``jax.numpy``.

No kernels, no chunked scan, no sort, no grouped product, no mixed precision;
matrix products at ``precision="highest"`` (set by the caller through
``jax.default_matmul_precision``), so on a TPU they are true float32.
Gradients are ``jax.grad`` of :func:`loss_sum`.  The state-space layer is the
**sequential recurrence** over time, attention is the dense softmax over all
keys, and the expert layer is a loop (``lax.scan``) over the held experts with a mask:
every token goes through every held expert and the mask keeps what the router
chose.

It follows the ``nemotron_h`` configuration of NVIDIA-Nemotron-3-Super-120B-A12B
(https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json);
every layer is one block ``x <- x + mixer(rms(x))``, the mixer chosen by a
character of ``hybrid_override_pattern``; then a final RMSNorm and an untied
head.  ``rms(x) = x rsqrt(mean x² + eps) g``.

    M  [z | xBC | dt] = u W_in;  xBC <- silu(conv(xBC) + b), conv causal and
       depthwise over ``conv_kernel`` steps;  [x | B | C] = xBC
       Δ = softplus(dt + dt_bias);  A = -exp(A_log)   (a scalar a head)
       h_t = exp(Δ_t A) h_{t-1} + Δ_t x_t ⊗ B_t;  y_t = C_t·h_t + D x_t
       (a head reads the B, C of its group)
       y <- rms_group(y ⊙ silu(z)) g   over groups of (heads / n_groups)·P
       out = y W_out
    *  q, k, v = u W_q, u W_k, u W_v;  causal softmax(q kᵀ / sqrt(head_dim)) v,
       a key/value head serving heads / kv_heads query heads;  out = ctx W_o.
       NO rotary or other position embedding (the Nemotron-H family,
       arXiv:2504.03624).
    E  s = sigmoid(u W_g);  S = top-k(s + b);  w_e = s_e / Σ_S s · scale
       ℓ = u W_down;  r = Σ_{e ∈ S} w_e W2ᵉ relu(W1ᵉ ℓ)²
       out = r W_up + W2ˢ relu(W1ˢ u)²          (the shared expert, on u)

Departures, each of them the benchmark configuration's and stated in
``configs/nemotron3_super_120b_a12b.json``:

- the share: only the Mamba heads ``mamba_heads_held``, the query heads
  ``attention_heads_held`` (with the key/value heads they read) and the routed
  experts ``experts_held`` — each ``(first, count)`` — exist here.  A share
  computes ITS part of each layer's sum (its columns of W_in / W_q, its rows
  of W_out / W_o, its experts' part of ``r``); what the absent chips would add
  is left out, and that partial result goes on to the next layer.  The router
  scores all ``n_routed_experts``.  The vocabulary is the held slice: logits
  and loss are over it;
- the selection bias ``b`` (``e_score_correction_bias``) is a fixed buffer
  that chooses and does not weigh; no gradient moves it;
- the multi-token-prediction module is left out (docs/nemotron_h.md);
- ``jax.checkpoint`` around each layer and around each block of
  ``TIME_BLOCK`` steps of the recurrence: memory only (one sequence of 8192
  would otherwise keep 4 GiB of per-step states a layer), no value changes.

Parameters arrive in the program's own tree (they are data): ``embed/tok``;
``layers[i]`` with ``norm`` and, by kind, ``in_proj, conv_w (K, c), conv_b,
dt_bias, A_log, D, gate_norm, out_proj`` | ``wq, wk, wv, wo`` | ``router,
expert_bias, latent_down, latent_up, w1 (held, ℓ, f), w2 (held, f, ℓ),
shared_w1, shared_w2``; ``head/norm``, ``head/out`` (d, V).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

TIME_BLOCK = 128     # steps of the recurrence between two kept states


def _rms(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def _causal_conv(x, w, b):
    """``c_t = b + Σ_j w_j ⊙ x_{t-(K-1)+j}`` with zeros left of the sequence;
    x (B, S, C), w (K, C)."""
    taps, seq = w.shape[0], x.shape[1]
    out = jnp.zeros_like(x) + b
    for j in range(taps):
        back = taps - 1 - j                      # w_j multiplies x_{t-back}
        out = out + w[j] * jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :seq]
    return out


def _recurrence(x, delta, a, b_in, c_out):
    """``h_t = exp(Δ_t A) h_{t-1} + Δ_t x_t ⊗ B_t;  y_t = C_t·h_t``, one step
    at a time.  x (B, S, H, P), delta (B, S, H), a (H,), b_in / c_out
    (B, S, H, N) -> y (B, S, H, P)."""
    batch, seq, heads, p = x.shape
    n = b_in.shape[-1]
    pad = -seq % TIME_BLOCK       # Δ = 0, x = 0: the state passes unchanged

    def blocks(t):                # (B, S, ...) -> (blocks, TIME_BLOCK, B, ...)
        t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape(-1, TIME_BLOCK, *t.shape[1:])

    def step(h, inputs):
        x_t, d_t, b_t, c_t = inputs
        h = (jnp.exp(d_t * a)[..., None, None] * h
             + (d_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return h, jnp.sum(h * c_t[:, :, None, :], axis=-1)

    @jax.checkpoint
    def block(h, inputs):
        return jax.lax.scan(step, h, inputs)

    _, y = jax.lax.scan(block, jnp.zeros((batch, heads, p, n), x.dtype),
                        tuple(map(blocks, (x, delta, b_in, c_out))))
    return jnp.moveaxis(y.reshape(-1, batch, heads, p), 0, 1)[:, :seq]


def _mamba_mixer(u, p, model):
    batch, seq, _ = u.shape
    heads, hp, n = (model["mamba_heads_held"][1], model["mamba_head_dim"],
                    model["ssm_state_size"])
    per_group = model["mamba_num_heads"] // model["n_groups"]
    groups, inner = heads // per_group, heads * hp
    z, xbc, dt = jnp.split(u @ p["in_proj"],
                           [inner, 2 * inner + 2 * groups * n], axis=-1)
    xbc = _silu(_causal_conv(xbc, p["conv_w"], p["conv_b"]))
    x, b_in, c_out = jnp.split(xbc, [inner, inner + groups * n], axis=-1)
    x = x.reshape(batch, seq, heads, hp)
    # a head reads the B and C of its group
    b_in = jnp.repeat(b_in.reshape(batch, seq, groups, n), per_group, axis=2)
    c_out = jnp.repeat(c_out.reshape(batch, seq, groups, n), per_group, axis=2)
    y = _recurrence(x, _softplus(dt + p["dt_bias"]), -jnp.exp(p["A_log"]),
                    b_in, c_out)
    y = (y + p["D"][:, None] * x).reshape(batch, seq, inner) * _silu(z)
    y = _rms(y.reshape(batch, seq, groups, per_group * hp),
             p["gate_norm"].reshape(groups, per_group * hp),
             model["layer_norm_epsilon"])
    return y.reshape(batch, seq, inner) @ p["out_proj"]


def _attention_mixer(u, p, model):
    batch, seq, _ = u.shape
    hd = model["head_dim"]
    first, heads = model["attention_heads_held"]
    group = model["num_attention_heads"] // model["num_key_value_heads"]
    kv_first = first // group
    q = (u @ p["wq"]).reshape(batch, seq, heads, hd)
    k = (u @ p["wk"]).reshape(batch, seq, -1, hd)
    v = (u @ p["wv"]).reshape(batch, seq, -1, hd)
    # computed, not a literal: 8192 x 8192 flags would be 64 MiB of program
    causal = jnp.arange(seq)[:, None] >= jnp.arange(seq)[None, :]
    out = []
    for h in range(heads):
        kv = (first + h) // group - kv_first
        scores = jnp.einsum("bqd,bkd->bqk", q[:, :, h], k[:, :, kv]) \
            / np.sqrt(hd)
        scores = jnp.where(causal[None], scores, -jnp.inf)
        scores = scores - jnp.max(scores, axis=-1, keepdims=True)
        probs = jnp.exp(scores)
        probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
        out.append(jnp.einsum("bqk,bkd->bqd", probs, v[:, :, kv]))
    return jnp.concatenate(out, axis=-1) @ p["wo"]


def _route(u, p, model):
    """``(chosen (T, E) bool, weights (T, E))`` over all experts."""
    scores = 1.0 / (1.0 + jnp.exp(-(u @ p["router"])))
    choose = jax.lax.stop_gradient(scores + p["expert_bias"])
    kth = jnp.sort(choose, axis=-1)[:, -model["num_experts_per_tok"]]
    chosen = choose >= kth[:, None]
    weights = jnp.where(chosen, scores, 0.0)
    if model["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return chosen, weights * model["routed_scaling_factor"]


def _latent_moe(u, p, model):
    """``(the layer's output with the held experts' part of r, chosen)``."""
    shape = u.shape
    u = u.reshape(-1, shape[-1])
    chosen, weights = _route(u, p, model)
    latent = u @ p["latent_down"]
    first, count = model["experts_held"]

    def add_expert(routed, expert):
        w1, w2, weight = expert                 # weight (T,): 0 where not chosen
        return routed + weight[:, None] * (_relu2(latent @ w1) @ w2), None

    routed, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(latent),
        (p["w1"], p["w2"], weights[:, first:first + count].T))
    out = routed @ p["latent_up"] + _relu2(u @ p["shared_w1"]) @ p["shared_w2"]
    return out.reshape(shape), chosen


def _forward(params, tokens, model):
    """``(hidden state after the last layer, [chosen] per E layer)``."""
    x = params["embed"]["tok"][tokens]
    eps = model["layer_norm_epsilon"]
    chosen = []
    for kind, p in zip(model["hybrid_override_pattern"], params["layers"]):
        if kind == "E":
            out, picked = jax.checkpoint(lambda x, p: _latent_moe(
                _rms(x, p["norm"], eps), p, model))(x, p)
            chosen.append(picked)
        else:
            mixer = {"M": _mamba_mixer, "*": _attention_mixer}[kind]
            out = jax.checkpoint(lambda x, p, mixer=mixer: mixer(
                _rms(x, p["norm"], eps), p, model))(x, p)
        x = x + out
    return x, chosen


def logits(params, tokens, model):
    """tokens (B, S) int32 -> logits (B, S, V) float32."""
    x, _ = _forward(params, tokens, model)
    x = _rms(x, params["head"]["norm"], model["layer_norm_epsilon"])
    return x @ params["head"]["out"]


def routing(params, tokens, model):
    """(E layers, B·S, n_routed_experts) bool: the experts every token
    chose."""
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
