"""The next-token generator of the ``lfm2_pretrain`` job: a copy of
``examples/bert/pretrain.py:synthetic_next_token`` driven by a cell's data
file, for the reasons ``inputs.py`` gives for its own copies (a cell trains
on what a user of the example trains on, and a later change to the example
cannot move the yardstick; ``benchmarks/tests`` holds the copy to the
original).

The corpus is LEARNABLE and covers the whole vocabulary slice: a fixed rule
(its seed does not depend on ``--seed``) says which ids are common and what
every id's successor is; ``--seed`` draws the batches.  It is not
``inputs.mlm_batches``' 64-id pool on purpose: with a few ids the load of
the held experts would hang on how a seed's router happens to treat them,
and ``samples_per_s`` would swing with ``--seed``.
"""
from __future__ import annotations

import numpy as np

_RULE_SEED = 1234


def next_token_batches(seed: int, steps: int, batch: int, seq: int,
                       vocab: int, common_share: int, common_mass: float,
                       follow: float):
    """``steps`` causal-LM batches: ``vocab // common_share`` ids are common
    and take ``common_mass`` of the fresh draws, uniform within each kind; a
    position follows the fixed successor of the id before it with
    probability ``follow``; ``targets`` are the tokens shifted by one and
    the last position weighs 0."""
    ids = np.random.RandomState(_RULE_SEED).permutation(vocab).astype(
        np.int32)
    common, rare = ids[: vocab // common_share], ids[vocab // common_share:]
    successor = np.empty(vocab, np.int32)
    successor[common] = np.roll(common, 1)
    successor[rare] = np.roll(rare, 1)
    rng = np.random.RandomState(seed % 2 ** 32)
    out = []
    for _ in range(steps):
        fresh = np.where(
            rng.rand(batch, seq) < common_mass,
            common[rng.randint(0, len(common), size=(batch, seq))],
            rare[rng.randint(0, len(rare), size=(batch, seq))])
        follows = rng.rand(batch, seq) < follow
        tokens = fresh.astype(np.int32)
        for t in range(1, seq):
            tokens[:, t] = np.where(follows[:, t],
                                    successor[tokens[:, t - 1]], tokens[:, t])
        weights = np.ones((batch, seq), np.float32)
        weights[:, -1] = 0.0
        out.append({"tokens": tokens, "targets": np.roll(tokens, -1, axis=1),
                    "weights": weights})
    return out


def make_batches(traffic: dict, model: dict, seed: int):
    """The ring of ``traffic["ring"]`` batches a cell cycles through."""
    if traffic["generator"] != "next_token":
        raise ValueError(f"unknown input generator {traffic['generator']!r}")
    return next_token_batches(
        seed, traffic["ring"], traffic["batch"], traffic["seq"],
        model["vocab_size"], traffic["common_share"], traffic["common_mass"],
        traffic["follow"])
