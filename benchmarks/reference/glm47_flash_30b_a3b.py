"""Plain float32 reference of the decoder the ``glm47_flash_30b_a3b``
configuration trains: forward pass, multi-token-prediction module and the
two-term loss in straightforward ``jax.numpy``.

No kernels, no flash, no sort, no grouped product, no mixed precision;
matrix products at ``precision="highest"`` (set by the caller through
``jax.default_matmul_precision``), so on a TPU they are true float32.
Gradients are ``jax.grad`` of :func:`loss_part`.  Latent attention is
written out head by head from the latents, each head's causal softmax a
block of ``QUERY_BLOCK`` queries at a time; the expert layer is a loop
(``lax.scan``) over the held experts with a mask: every token goes through
every held expert and the mask keeps what the router chose.

It follows the ``glm4_moe_lite`` configuration of GLM-4.7-Flash
(https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json) and,
for what the configuration does not state, the public ``transformers``
implementations of ``glm4_moe_lite`` / DeepSeek-V3 and arXiv:2412.19437
§2.2.  With ``rms(x; w) = w ⊙ x / sqrt(mean x² + eps)`` every layer is ``x
<- x + MLA(rms(x)); x <- x + ffn(rms(x))``; then a final ``rms`` and an
untied head.

    MLA (every layer), H heads
       c_q = rms(u W_qa);  [q_nope | q_pe] = c_q W_qb          a head
       [c_kv | k_pe] = u W_kva;  [k_nope | v] = rms(c_kv) W_kvb a head
       q_pe, k_pe <- RoPE (rotate-half, positions 0..S-1); k_pe is ONE
       vector a position, the same for every head
       ctx_h = causal softmax(q_h k_hᵀ / sqrt(nope + rope)) v_h  with
       q_h = q_nope_h ‖ q_pe_h, k_h = k_nope_h ‖ k_pe;  out = ctx W_o
    FFN of the first ``first_k_dense_replace`` layers
       W2(silu(W1 x) ⊙ W3 x)
    FFN of the others
       s = sigmoid(x W_g) over all experts;  S = top-k(s + b), b the
       correction bias (chooses, never weighs);  w_e = 1.8 s_e / Σ_S s
       out = Σ_{e ∈ S} w_e W2ᵉ(silu(W1ᵉ x) ⊙ W3ᵉ x) + W2ˢ(silu(W1ˢ x) ⊙ W3ˢ x)
    MTP (depth 1), h the trunk's last hidden state before the final norm
       m = [rms(Emb(t_{i+1}); w_e) ‖ rms(h_i; w_h)] W_eh;  m <- block(m)
       logits_mtp = rms(m; w_sh) W_head   (the trunk's head)
    L = CE(logits, t_{i+1}) + λ CE(logits_mtp, t_{i+2}), each a weighted
    mean, the second weighing 0 where t_{i+1} or t_{i+2} does not exist

Departures, each of them the benchmark configuration's and stated in
``configs/glm47_flash_30b_a3b.json``:

- the share: only the routed experts ``experts_held`` = (first, count) exist
  here.  A share computes ITS experts' part of the routed sum; what the
  absent chips would add is left out, and that partial result goes on to the
  next layer.  The router scores all ``num_experts``.  The vocabulary is the
  held slice: logits and loss are over it;
- RoPE rotates halves, where the checkpoint pairs neighbours: with random
  weights that is a permutation of ``W_qb``'s and ``W_kva``'s rotary
  columns;
- λ = 0.3, ``[e ‖ h]`` in that order and ``h`` before the final norm
  (DeepSeek-V3's; the configuration has no key for them);
- the weights' sum has a floor of 1e-6 (the public code's is 1e-20: four
  sigmoids sum to about 2);
- ``jax.checkpoint`` around each block, each head of attention and each
  block of queries: memory only, no value changes.

Parameters arrive in the program's own tree (they are data): ``embed/tok``;
``layers[i]`` with ``input_norm, ffn_norm, q_a, q_a_norm, q_b, kv_a,
kv_a_norm, kv_b, o`` and ``w13, w2`` (dense) | ``router, expert_bias, w13
(held, d, 2f), w2 (held, f, d), shared_w13, shared_w2``; ``head/norm``,
``head/out`` (d, V); ``mtp[0]`` a sparse layer's leaves and ``enorm, hnorm,
eh_proj (2d, d), head_norm``.
"""
from __future__ import annotations

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


def _rope(x, theta):
    """Rotate-half RoPE over the last axis of x (B, S, ..., r) at positions
    0..S-1."""
    half = x.shape[-1] // 2
    freq = theta ** (-np.arange(half, dtype=np.float32) / half)
    angle = np.arange(x.shape[1], dtype=np.float32)[:, None] * freq[None]
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (half,)
    cos = jnp.asarray(np.cos(angle)).reshape(shape)
    sin = jnp.asarray(np.sin(angle)).reshape(shape)
    first, second = x[..., :half], x[..., half:]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin], axis=-1)


def _mla(u, p, model):
    batch, seq, _ = u.shape
    heads, eps = model["num_attention_heads"], model["rms_norm_eps"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    vd, kl = model["v_head_dim"], model["kv_lora_rank"]
    q = (_rms(u @ p["q_a"], p["q_a_norm"], eps) @ p["q_b"]).reshape(
        batch, seq, heads, nope + rope)
    latent = u @ p["kv_a"]
    c_kv, k_pe = latent[..., :kl], latent[..., kl:]
    kv = (_rms(c_kv, p["kv_a_norm"], eps) @ p["kv_b"]).reshape(
        batch, seq, heads, nope + vd)
    q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], model["rope_theta"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k_pe = _rope(k_pe, model["rope_theta"])          # (B, S, rope): ONE
    scale = 1.0 / np.sqrt(nope + rope)
    blocks = -(-seq // QUERY_BLOCK)
    pad = blocks * QUERY_BLOCK - seq

    @jax.checkpoint
    def attend(head):
        qn, qp, kn, v_h = head            # (B, S, ·) of one head
        qn, qp = (jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (qn, qp))

        @jax.checkpoint
        def a_block(i):
            at = i * QUERY_BLOCK
            rows = jax.lax.dynamic_slice_in_dim(qn, at, QUERY_BLOCK, 1)
            rows_pe = jax.lax.dynamic_slice_in_dim(qp, at, QUERY_BLOCK, 1)
            # q_h · k_h = q_nope · k_nope + q_pe · k_pe: the shared key part
            scores = (jnp.einsum("bqd,bkd->bqk", rows, kn)
                      + jnp.einsum("bqd,bkd->bqk", rows_pe, k_pe)) * scale
            causal = (at + jnp.arange(QUERY_BLOCK))[:, None] \
                >= jnp.arange(seq)[None, :]
            scores = jnp.where(causal[None], scores, -jnp.inf)
            scores = scores - jnp.max(scores, axis=-1, keepdims=True)
            probs = jnp.exp(scores)
            probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
            return jnp.einsum("bqk,bkd->bqd", probs, v_h)

        out = jax.lax.map(a_block, jnp.arange(blocks))   # (n, B, Q, vd)
        return jnp.moveaxis(out, 0, 1).reshape(batch, -1, vd)[:, :seq]

    ctx = jax.lax.map(attend, tuple(jnp.moveaxis(t, 2, 0) for t in (
        q_nope, q_pe, k_nope, v)))                       # (H, B, S, vd)
    return jnp.moveaxis(ctx, 0, 2).reshape(batch, seq, heads * vd) @ p["o"]


def _gated(x, w13, w2):
    gate, up = jnp.split(x @ w13, 2, axis=-1)
    return (_silu(gate) * up) @ w2


def _route(x, p, model):
    """``(chosen (T, E) bool, weights (T, E))`` over all experts."""
    scores = _sigmoid(x @ p["router"])
    choose = scores + jax.lax.stop_gradient(p["expert_bias"])
    kth = jnp.sort(jax.lax.stop_gradient(choose),
                   axis=-1)[:, -model["num_experts_per_tok"]]
    chosen = choose >= kth[:, None]
    weights = jnp.where(chosen, scores, 0.0)
    if model["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-6)
    return chosen, weights * model["routed_scaling_factor"]


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
    shared = _gated(x, p["shared_w13"], p["shared_w2"])
    return (routed + shared).reshape(shape), chosen


def _block(x, p, model, dense):
    """``(y, chosen | None)``."""
    eps = model["rms_norm_eps"]
    x = x + jax.checkpoint(lambda x, p: _mla(_rms(x, p["input_norm"], eps),
                                             p, model))(x, p)
    if dense:
        return x + _gated(_rms(x, p["ffn_norm"], eps), p["w13"], p["w2"]), \
            None
    out, chosen = jax.checkpoint(lambda x, p: _sparse_ffn(
        _rms(x, p["ffn_norm"], eps), p, model))(x, p)
    return x + out, chosen


def _forward(params, batch, model):
    """``(logits, mtp logits | None, [chosen] of every sparse layer, the MTP
    module's last)``."""
    eps = model["rms_norm_eps"]
    embed, head = params["embed"]["tok"], params["head"]
    x = embed[batch["tokens"]]
    chosen = []
    for i, p in enumerate(params["layers"]):
        x, picked = _block(x, p, model, i < model["first_k_dense_replace"])
        if picked is not None:
            chosen.append(picked)
    logits = _rms(x, head["norm"], eps) @ head["out"]
    mtp = None
    for p in params["mtp"]:
        m = jnp.concatenate([_rms(embed[batch["targets"]], p["enorm"], eps),
                             _rms(x, p["hnorm"], eps)], axis=-1) @ p["eh_proj"]
        m, picked = _block(m, p, model, False)
        chosen.append(picked)
        mtp = _rms(m, p["head_norm"], eps) @ head["out"]
    return logits, mtp, chosen


def _nll(logits, targets):
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    log_probs = logits - jnp.log(jnp.sum(jnp.exp(logits), axis=-1,
                                         keepdims=True))
    return -jnp.take_along_axis(log_probs, targets[..., None], axis=-1)[..., 0]


def mtp_weights(weights):
    """Position i's weight in the second term: ``weights[i] ·
    weights[i + 1]``, 0 at the last position."""
    return jnp.concatenate([weights[:, :-1] * weights[:, 1:],
                            jnp.zeros_like(weights[:, :1])], axis=1)


def weight_totals(batch):
    """The two terms' denominators over ``batch``: (Σ weights, Σ MTP
    weights)."""
    return (jnp.sum(batch["weights"]), jnp.sum(mtp_weights(batch["weights"])))


def loss_part(params, batch, model, totals):
    """``batch``'s part of the loss of a sample whose :func:`weight_totals`
    are ``totals`` — so that a caller can take a sample a sequence at a time
    and add the parts."""
    logits, mtp, _ = _forward(params, batch, model)
    w = batch["weights"]
    part = jnp.sum(_nll(logits, batch["targets"]) * w) / jnp.maximum(
        totals[0], 1.0)
    if mtp is None:
        return part
    # position i's MTP target is t_{i+2} = targets[i + 1]
    ahead = jnp.concatenate([batch["targets"][:, 1:],
                             jnp.zeros_like(batch["targets"][:, :1])], axis=1)
    return part + model["mtp_loss_weight"] * jnp.sum(
        _nll(mtp, ahead) * mtp_weights(w)) / jnp.maximum(totals[1], 1.0)


def loss(params, batch, model):
    """``CE(next) + λ CE(two ahead)`` over the whole of ``batch``."""
    return loss_part(params, batch, model, weight_totals(batch))


def routing(params, batch, model):
    """(sparse layers, B·S, num_experts) bool: the experts every token
    chose, the MTP module's layer last."""
    return jnp.stack(_forward(params, batch, model)[2])
