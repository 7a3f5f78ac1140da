"""The benchmark's input generator: one function, driven by a cell's data file.

A cell's file (``workloads/<cell>.json``) names a ``generator`` and its
parameters; everything is drawn from ``--seed``, so the same seed gives the
same batches.  The two generators are copies of the examples' own
(``examples/bert/pretrain.py:synthetic_mlm``,
``examples/imagenet/main_amp.py:synthetic_batches``) so that a cell trains on
what a user of the example trains on, and a later change to the examples
cannot move the yardstick; ``benchmarks/tests`` checks the copies against the
originals.

Both corpora are LEARNABLE (a fixed pool of token ids / class prototypes whose
seed does not depend on ``--seed``): the loss must fall during a run, which is
part of what ``correct`` means.
"""
from __future__ import annotations

import numpy as np

_POOL_SEED = 1234
_MLM_POOL = 64        # distinct token ids in the synthetic corpus
_IMAGE_CLASSES = 64   # distinct class prototypes


def mlm_batches(seed: int, steps: int, batch: int, seq: int, vocab: int):
    """``steps`` masked-LM batches: tokens from a 64-id pool spread over the
    vocabulary, 15% of positions replaced by id 0 ([MASK]) and weighted 1."""
    pool = np.random.RandomState(_POOL_SEED).choice(
        np.arange(1, vocab), size=min(_MLM_POOL, vocab - 1), replace=False)
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(steps):
        tokens = pool[rng.randint(0, len(pool), size=(batch, seq))].astype(
            np.int32)
        targets = tokens.copy()
        mask = rng.rand(batch, seq) < 0.15
        tokens[mask] = 0
        out.append({"tokens": tokens, "targets": targets,
                    "weights": mask.astype(np.float32)})
    return out


def image_batches(seed: int, steps: int, batch: int, image: int):
    """``steps`` batches of ``(images NHWC float32, labels int32)``: one
    random prototype per class plus fresh noise of 0.08 sigma."""
    protos = np.random.RandomState(_POOL_SEED).rand(
        _IMAGE_CLASSES, image, image, 3).astype(np.float32)
    rng = np.random.Generator(np.random.PCG64(seed))
    out = []
    for _ in range(steps):
        labels = rng.integers(0, _IMAGE_CLASSES, size=(batch,))
        images = protos[labels] + 0.08 * rng.standard_normal(
            (batch, image, image, 3), dtype=np.float32)
        out.append((images, labels.astype(np.int32)))
    return out


def make_batches(traffic: dict, model: dict, seed: int):
    """The ring of ``traffic["ring"]`` batches a cell cycles through."""
    kind = traffic["generator"]
    if kind == "mlm":
        return mlm_batches(seed, traffic["ring"], traffic["batch"],
                           traffic["seq"], model["vocab_size"])
    if kind == "image_prototypes":
        return image_batches(seed, traffic["ring"], traffic["batch"],
                             model["image_size"])
    raise ValueError(f"unknown input generator {kind!r}")
