"""Plain float32 reference of the decoder the ``qwen3_next_80b_a3b``
configuration trains: forward pass and next-token loss in straightforward
``jax.numpy``.

No kernels, no chunked rule, no sort, no grouped product, no mixed precision;
matrix products at ``precision="highest"`` (set by the caller through
``jax.default_matmul_precision``), so on a TPU they are true float32.
Gradients are ``jax.grad`` of :func:`loss_sum`.  The Gated DeltaNet layer is
the **sequential recurrence** over time (one position a step, NOT the chunked
form the program runs), attention is a masked softmax a head at a time,
and the expert layer is a loop (``lax.scan``) over the held experts with
a mask: every token goes through every held expert and the mask keeps what the
router chose.

It follows the ``qwen3_next`` configuration of Qwen3-Next-80B-A3B-Instruct
(https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json)
and, for what the configuration does not state, the public ``transformers``
implementation ``modeling_qwen3_next.py``.  With ``rms0(x; w) = (1 + w) ⊙ x /
sqrt(mean x² + eps)`` every layer is ``x <- x + mixer(rms0(x)); x <- x +
ffn(rms0(x))``; then a final ``rms0`` and an untied head.

    linear_attention (layers with (i + 1) mod full_attention_interval ≠ 0)
       [q | k | v | z] = u W_qkvz;  [b | a] = u W_ba
       [q | k | v] <- silu(conv([q | k | v])), conv causal and depthwise over
       ``linear_conv_kernel_dim`` steps, no bias
       β = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)   (a value head)
       q <- q / ‖q‖ · d_k^-½;  k <- k / ‖k‖   (a head; eps 1e-6 under the root)
       S_t = e^{g_t} S_{t-1} + k_t ⊗ β_t (v_t - (e^{g_t} S_{t-1})ᵀ k_t)
       o_t = S_tᵀ q_t        (a key head serves H_v / H_k value heads)
       out = (rms(o; w_n) ⊙ silu(z)) W_out,  rms over each head, gain w_n
    full_attention
       [q | gate] = u W_q a head;  k, v = u W_k, u W_v
       q <- rms0(q), k <- rms0(k) a head;  rotate-half RoPE on the first
       ``partial_rotary_factor`` of a head, the rest pass
       ctx = causal softmax(q kᵀ / sqrt(head_dim)) v, a key/value head serving
       heads / kv_heads query heads;  out = (ctx ⊙ sigmoid(gate)) W_o
    sparse FFN (every layer)
       p = softmax(x W_g) over all experts;  S = top-k(p);  w_e = p_e / Σ_S p
       out = Σ_{e ∈ S} w_e W2ᵉ(silu(W1ᵉ x) ⊙ W3ᵉ x)
             + sigmoid(x w_s) · W2ˢ(silu(W1ˢ x) ⊙ W3ˢ x)

Departures, each of them the benchmark configuration's and stated in
``configs/qwen3_next_80b_a3b.json``:

- the share: only the routed experts ``experts_held`` = (first, count) exist
  here.  A share computes ITS experts' part of the routed sum; what the
  absent chips would add is left out, and that partial result goes on to the
  next layer.  The router scores all ``num_experts``.  The vocabulary is the
  held slice: logits and loss are over it;
- the columns of ``W_qkvz`` / ``W_ba`` lie q beside k beside v beside z and b
  beside a (the published checkpoint interleaves them a key head; with random
  weights the order of columns is not part of the equations);
- the multi-token-prediction module is left out (docs/qwen3_next.md);
- ``jax.checkpoint`` around each layer, around each block of ``TIME_BLOCK``
  steps of the recurrence and around each head of attention: memory only (one
  sequence of 4096 would otherwise keep 8 GiB of per-step states a layer), no
  value changes.

Parameters arrive in the program's own tree (they are data): ``embed/tok``;
``layers[i]`` with ``input_norm``, ``ffn_norm``, by kind ``in_proj_qkvz,
in_proj_ba, conv_w (K, c), dt_bias, A_log, gate_norm, out_proj`` | ``wq, wk,
wv, wo, q_norm, k_norm``, and ``router, w13 (held, d, 2f), w2 (held, f, d),
shared_w13, shared_w2, shared_gate``; ``head/norm``, ``head/out`` (d, V).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

TIME_BLOCK = 64      # steps of the recurrence between two kept states


def _rms(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _rms0(x, w, eps):
    return _rms(x, 1.0 + w, eps)


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _silu(x):
    return x * _sigmoid(x)


def _softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def _l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def layer_types(model):
    return ["linear_attention" if (i + 1) % model["full_attention_interval"]
            else "full_attention" for i in range(model["num_hidden_layers"])]


def _causal_conv(x, w):
    """``c_t = Σ_j w_j ⊙ x_{t-(K-1)+j}`` with zeros left of the sequence;
    x (B, S, C), w (K, C)."""
    taps, seq = w.shape[0], x.shape[1]
    out = jnp.zeros_like(x)
    for j in range(taps):
        back = taps - 1 - j                      # w_j multiplies x_{t-back}
        out = out + w[j] * jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :seq]
    return out


def _delta_recurrence(q, k, v, g, beta):
    """``S_t = e^{g_t} S_{t-1} + k_t ⊗ β_t (v_t - (e^{g_t} S_{t-1})ᵀ k_t)``,
    ``o_t = S_tᵀ q_t``, one step at a time.  q, k (B, S, H, d_k) — every key
    head already repeated for its value heads —, v (B, S, H, d_v), g, beta
    (B, S, H) -> o (B, S, H, d_v)."""
    batch, seq, heads, dk = q.shape
    dv = v.shape[-1]
    pad = -seq % TIME_BLOCK       # β = 0, g = 0: the state passes unchanged

    def blocks(t):                # (B, S, ...) -> (blocks, TIME_BLOCK, B, ...)
        t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape(-1, TIME_BLOCK, *t.shape[1:])

    def step(state, inputs):                     # state (B, H, d_k, d_v)
        q_t, k_t, v_t, g_t, beta_t = inputs
        state = jnp.exp(g_t)[..., None, None] * state
        read = jnp.sum(state * k_t[..., None], axis=-2)         # Sᵀ k
        write = beta_t[..., None] * (v_t - read)
        state = state + k_t[..., None] * write[..., None, :]
        return state, jnp.sum(state * q_t[..., None], axis=-2)  # Sᵀ q

    @jax.checkpoint
    def block(state, inputs):
        return jax.lax.scan(step, state, inputs)

    _, out = jax.lax.scan(block, jnp.zeros((batch, heads, dk, dv), q.dtype),
                          tuple(map(blocks, (q, k, v, g, beta))))
    return jnp.moveaxis(out.reshape(-1, batch, heads, dv), 0, 1)[:, :seq]


def _gdn_mixer(u, p, model):
    batch, seq, _ = u.shape
    kh, dk = model["linear_num_key_heads"], model["linear_key_head_dim"]
    vh, dv = model["linear_num_value_heads"], model["linear_value_head_dim"]
    key_w, value_w = kh * dk, vh * dv
    qkv, z = jnp.split(u @ p["in_proj_qkvz"], [2 * key_w + value_w], axis=-1)
    b, a = jnp.split(u @ p["in_proj_ba"], 2, axis=-1)
    qkv = _silu(_causal_conv(qkv, p["conv_w"]))
    q, k, v = jnp.split(qkv, [key_w, 2 * key_w], axis=-1)
    q = _l2(q.reshape(batch, seq, kh, dk)) / np.sqrt(dk)
    k = _l2(k.reshape(batch, seq, kh, dk))
    # a key head serves vh // kh value heads
    q = jnp.repeat(q, vh // kh, axis=2)
    k = jnp.repeat(k, vh // kh, axis=2)
    o = _delta_recurrence(
        q, k, v.reshape(batch, seq, vh, dv),
        -jnp.exp(p["A_log"]) * _softplus(a + p["dt_bias"]), _sigmoid(b))
    y = _rms(o, p["gate_norm"], model["rms_norm_eps"]) \
        * _silu(z.reshape(batch, seq, vh, dv))
    return y.reshape(batch, seq, value_w) @ p["out_proj"]


def _partial_rope(x, model):
    """Rotate-half RoPE over the first ``partial_rotary_factor`` of every head
    of x (B, S, H, hd) at positions 0..S-1; the rest pass."""
    rot = int(model["head_dim"] * model["partial_rotary_factor"])
    half = rot // 2
    freq = model["rope_theta"] ** (-np.arange(half, dtype=np.float32) / half)
    angle = np.arange(x.shape[1], dtype=np.float32)[:, None] * freq[None]
    cos = jnp.asarray(np.cos(angle))[None, :, None, :]
    sin = jnp.asarray(np.sin(angle))[None, :, None, :]
    first, second, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin, rest], axis=-1)


def _attention_mixer(u, p, model):
    batch, seq, _ = u.shape
    heads, kv_heads, hd = (model["num_attention_heads"],
                           model["num_key_value_heads"], model["head_dim"])
    eps = model["rms_norm_eps"]
    q, gate = jnp.split((u @ p["wq"]).reshape(batch, seq, heads, 2 * hd), 2,
                        axis=-1)
    k = (u @ p["wk"]).reshape(batch, seq, kv_heads, hd)
    v = (u @ p["wv"]).reshape(batch, seq, kv_heads, hd)
    q = _partial_rope(_rms0(q, p["q_norm"], eps), model)
    k = _partial_rope(_rms0(k, p["k_norm"], eps), model)
    causal = jnp.arange(seq)[:, None] >= jnp.arange(seq)[None, :]

    @jax.checkpoint
    def attend(head):                           # q, k, v of one head (B, S, hd)
        q_h, k_h, v_h = head
        scores = jnp.einsum("bqd,bkd->bqk", q_h, k_h) / np.sqrt(hd)
        scores = jnp.where(causal[None], scores, -jnp.inf)
        scores = scores - jnp.max(scores, axis=-1, keepdims=True)
        probs = jnp.exp(scores)
        probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
        return jnp.einsum("bqk,bkd->bqd", probs, v_h)

    # a head at a time: query head h reads key/value head h // (heads / kv)
    reads = np.arange(heads) // (heads // kv_heads)
    ctx = jax.lax.map(attend, (jnp.moveaxis(q, 2, 0),
                               jnp.moveaxis(k, 2, 0)[reads],
                               jnp.moveaxis(v, 2, 0)[reads]))
    ctx = jnp.moveaxis(ctx, 0, 2).reshape(batch, seq, heads * hd)
    return (ctx * _sigmoid(gate.reshape(batch, seq, heads * hd))) @ p["wo"]


def _route(x, p, model):
    """``(chosen (T, E) bool, weights (T, E))`` over all experts."""
    logits = x @ p["router"]
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    probs = jnp.exp(logits)
    probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    kth = jnp.sort(jax.lax.stop_gradient(probs),
                   axis=-1)[:, -model["num_experts_per_tok"]]
    chosen = probs >= kth[:, None]
    weights = jnp.where(chosen, probs, 0.0)
    if model["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return chosen, weights


def _gated(x, w13, w2):
    gate, up = jnp.split(x @ w13, 2, axis=-1)
    return (_silu(gate) * up) @ w2


def _shared_expert(x, p):
    return _sigmoid(x @ p["shared_gate"]) * _gated(x, p["shared_w13"],
                                                   p["shared_w2"])


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
    return (routed + _shared_expert(x, p)).reshape(shape), chosen


def _forward(params, tokens, model):
    """``(hidden state after the last layer, [chosen] per layer)``."""
    x = params["embed"]["tok"][tokens]
    eps = model["rms_norm_eps"]
    chosen = []
    for kind, p in zip(layer_types(model), params["layers"]):
        mixer = {"linear_attention": _gdn_mixer,
                 "full_attention": _attention_mixer}[kind]
        x = x + jax.checkpoint(lambda x, p, mixer=mixer: mixer(
            _rms0(x, p["input_norm"], eps), p, model))(x, p)
        out, picked = jax.checkpoint(lambda x, p: _sparse_ffn(
            _rms0(x, p["ffn_norm"], eps), p, model))(x, p)
        chosen.append(picked)
        x = x + out
    return x, chosen


def logits(params, tokens, model):
    """tokens (B, S) int32 -> logits (B, S, V) float32."""
    x, _ = _forward(params, tokens, model)
    x = _rms0(x, params["head"]["norm"], model["rms_norm_eps"])
    return x @ params["head"]["out"]


def routing(params, tokens, model):
    """(layers, B·S, num_experts) bool: the experts every token chose."""
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
