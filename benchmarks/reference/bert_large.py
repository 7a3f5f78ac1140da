"""Plain float32 reference of the encoder the ``bert_large`` configuration
trains: forward pass and masked-LM loss in straightforward ``jax.numpy``.

No kernels, no scan, no remat, no mixed precision; matrix products at
``precision="highest"`` (set by the caller through
``jax.default_matmul_precision``), so on a TPU they are true float32.
Gradients are ``jax.grad`` of :func:`loss`.

It follows BERT (Devlin et al., arXiv:1810.04805) at BERT-large's sizes with
the departures the program makes, each of them the program's choice and not
the benchmark's:

- pre-LN blocks (norm before attention and before the MLP, a final norm
  before the head) where the paper has post-LN;
- GELU in its tanh form;
- no segment embeddings, no pooler and no next-sentence head: the loss is the
  masked-LM term alone, logits are taken at every position and the 15% masked
  positions are selected by ``weights``;
- the output head is the token embedding, transposed, without a bias or a
  transform layer in front of it.

Parameters arrive in the program's own tree (they are data):
``embed/{tok,pos,ln_g,ln_b}``, ``layers/{wqkv,bqkv,wo,bo,ln1_g,ln1_b,w1,b1,
w2,b2,ln2_g,ln2_b}`` stacked on a leading layer axis, ``head/{ln_g,ln_b}``.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

_EPS = 1e-5


def _layer_norm(x, gain, bias):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + _EPS) * gain + bias


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def _attention(x, p, i, heads):
    batch, seq, d = x.shape
    qkv = x @ p["wqkv"][i] + p["bqkv"][i]
    q, k, v = (t.reshape(batch, seq, heads, d // heads).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, axis=-1))
    scores = q @ k.transpose(0, 1, 3, 2) / np.sqrt(d // heads)
    scores = scores - jnp.max(scores, axis=-1, keepdims=True)
    probs = jnp.exp(scores)
    probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    ctx = (probs @ v).transpose(0, 2, 1, 3).reshape(batch, seq, d)
    return ctx @ p["wo"][i] + p["bo"][i]


def logits(params, tokens, model):
    """tokens (B, S) int32 -> logits (B, S, V) float32."""
    emb, p, head = params["embed"], params["layers"], params["head"]
    x = emb["tok"][tokens] + emb["pos"][: tokens.shape[1]][None]
    x = _layer_norm(x, emb["ln_g"], emb["ln_b"])
    for i in range(model["num_layers"]):
        h = _layer_norm(x, p["ln1_g"][i], p["ln1_b"][i])
        x = x + _attention(h, p, i, model["num_heads"])
        h = _layer_norm(x, p["ln2_g"][i], p["ln2_b"][i])
        h = _gelu_tanh(h @ p["w1"][i] + p["b1"][i])
        x = x + h @ p["w2"][i] + p["b2"][i]
    x = _layer_norm(x, head["ln_g"], head["ln_b"])
    return x @ emb["tok"].T


def loss(params, batch, model):
    """Mean negative log-likelihood of ``targets`` over the positions whose
    ``weights`` are 1."""
    lg = logits(params, batch["tokens"], model)
    lg = lg - jnp.max(lg, axis=-1, keepdims=True)
    log_probs = lg - jnp.log(jnp.sum(jnp.exp(lg), axis=-1, keepdims=True))
    nll = -jnp.take_along_axis(log_probs, batch["targets"][..., None],
                               axis=-1)[..., 0]
    w = batch["weights"]
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)
