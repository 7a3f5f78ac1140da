"""The job adapters and the yardstick's arithmetic against the program.

- the copied input generators are the examples' generators;
- the ``resnet_train`` job's copied step is ``main_amp.main``'s step: same
  seed, same batches, the same losses step for step;
- ``flops.py`` agrees with the dot / convolution FLOPs that
  ``telemetry.attrib.op_table`` reads out of the compiled HLO.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import flops, inputs, run
from benchmarks.job import load_example, load_module

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "tiny", "BENCHMARK.json")


def test_mlm_generator_is_the_examples():
    pretrain = load_example("examples/bert/pretrain.py")
    rng = np.random.RandomState(7)
    ours = inputs.mlm_batches(7, 3, batch=4, seq=32, vocab=512)
    for batch in ours:
        tokens, targets, weights = pretrain.synthetic_mlm(rng, 4, 32, 512)
        np.testing.assert_array_equal(batch["tokens"], tokens)
        np.testing.assert_array_equal(batch["targets"], targets)
        np.testing.assert_array_equal(batch["weights"], weights)


def test_image_generator_is_the_examples():
    main_amp = load_example("examples/imagenet/main_amp.py")
    ours = inputs.image_batches(5, 2, batch=3, image=224)
    for (images, labels), (ex_images, ex_labels) in zip(
            ours, main_amp.synthetic_batches(3, 5, 2)):
        np.testing.assert_array_equal(images, ex_images)
        np.testing.assert_array_equal(labels, ex_labels)


def test_resnet_job_step_is_the_examples_step():
    steps, seed = 4, 3
    report = {}
    load_example("examples/imagenet/main_amp.py").main(
        ["--arch", "resnet18", "--batch-size", "8", "--opt-level", "O2",
         "--steps", str(steps), "--print-freq", "1", "--seed", str(seed)],
        report=report)
    manifest = run.Manifest(TINY)
    with open(os.path.join(manifest.root, "cells", "configs",
                           "tiny_resnet.json")) as f:
        config = json.load(f)
    traffic = dict(manifest.load_json("workloads", "tiny_resnet.b8.json"),
                   ring=steps)
    adapter = load_module(manifest.find("jobs", "resnet_train.py"),
                          "resnet_train_under_test")
    job = adapter.build(config, traffic, seed, jax.devices()[:1],
                        manifest.find("reference", "resnet50.py"))
    assert job.reference["ok"], job.reference
    state, losses = job.state, []
    with job.scope():
        for batch in job.batches:
            state, loss = job.step(state, batch)
            losses.append(float(loss))
    # one program with and one without the example's unused host plumbing:
    # the arithmetic is the same, only XLA's fusion choices may differ
    np.testing.assert_allclose(losses, report["losses"], rtol=1e-3)
    assert job.applied_steps(state) == report["optimizer_steps"]


def test_dp_optimizer_probe_keeps_its_gradients_on_every_device():
    """``optimizer_step_ms`` times ``amp_step`` alone.  A stand-in gradient
    tree made on one device is sent to the others inside every timed call:
    that was ``bert_large.dp4_s512``'s first reading on the chip (85.6 ms
    against the one-chip cells' 52, PR 22)."""
    manifest = run.Manifest(TINY)
    with open(os.path.join(manifest.root, "cells", "configs",
                           "tiny_bert.json")) as f:
        config = json.load(f)
    adapter = load_module(manifest.find("jobs", "bert_pretrain.py"),
                          "bert_pretrain_under_test")
    devices = jax.devices()[:4]
    job = adapter.build(
        config, manifest.load_json("workloads", "tiny_bert.dp4_s128.json"),
        0, devices, manifest.find("reference", "bert_large.py"))
    update, state, grads = job.optimizer_probe(job.state)
    for leaf in jax.tree_util.tree_leaves(grads):
        assert leaf.sharding.is_fully_replicated
        assert {s.device for s in leaf.addressable_shards} == set(devices)
    with job.scope():
        assert job.applied_steps(update(state, grads)) == 1


def _matmul_flops(table):
    return sum(table["by_class"].get(c, {"flops": 0.0})["flops"]
               for c in ("blas", "conv"))


def test_transformer_flops_match_the_compiled_program():
    """One layer (a scan body is counted once by ``op_table``, whatever its
    trip count), XLA attention, no remat: what the HLO's dots add up to is
    what ``flops.py`` says forward + backward need.  Margin 2%: XLA may fold
    a transpose into a dot but does not add or drop one."""
    from apex_tpu.models import (TransformerConfig, transformer_init,
                                 transformer_loss)
    from apex_tpu.telemetry.attrib import op_table
    model = {"vocab_size": 512, "max_len": 64, "num_layers": 1,
             "d_model": 128, "num_heads": 2, "d_ff": 512}
    cfg = TransformerConfig(xent_impl="xla", **model)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    batch = inputs.mlm_batches(0, 1, batch=4, seq=64, vocab=512)[0]
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    table = op_table(jax.grad(lambda p: transformer_loss(p, batch, cfg)),
                     params)
    want = 4 * flops.transformer_train_flops_per_sample(model, 64)
    assert _matmul_flops(table) == pytest.approx(want, rel=0.02)


def test_resnet_flops_match_the_compiled_program():
    """ResNet-18 on 64x64 images.  Margin 3%: the compiler may realise a
    strided 1x1 projection's input gradient over the kept positions only."""
    from apex_tpu.models import resnet18_config, resnet_apply, resnet_init
    from apex_tpu.telemetry.attrib import op_table
    model = {"block": "basic", "stage_sizes": [2, 2, 2, 2], "width": 64,
             "num_classes": 1000}
    cfg = resnet18_config()
    params, bn_state = resnet_init(jax.random.PRNGKey(0), cfg)
    images = jnp.ones((2, 64, 64, 3), jnp.float32)

    def loss(p):
        logits, _ = resnet_apply(p, bn_state, images, cfg, train=True)
        return jnp.mean(logits ** 2)

    table = op_table(jax.grad(loss), params)
    want = 2 * flops.resnet_train_flops_per_sample(model, 64)
    assert _matmul_flops(table) == pytest.approx(want, rel=0.03)


def test_resnet50_flops_are_the_published_count():
    """ResNet-50 at 224x224 is 4.09 G multiply-adds forward (the figure
    quoted for the torchvision layout); training is three passes less the
    stem's input gradient."""
    model = {"block": "bottleneck", "stage_sizes": [3, 4, 6, 3], "width": 64,
             "num_classes": 1000}
    forward = sum(2.0 * hw * hw * k * k * cin * cout
                  for hw, k, cin, cout, _ in flops.resnet_convs(model, 224))
    assert forward / 2 == pytest.approx(4.09e9, rel=0.01)
    total = flops.resnet_train_flops_per_sample(model, 224)
    assert total == pytest.approx(3 * forward - 2.0 * 112 * 112 * 49 * 3 * 64)


def test_bert_large_flops_per_token():
    """6·N + 12·L·S·d with N = 24·12·1024² + 30592·1024."""
    model = {"vocab_size": 30592, "max_len": 512, "num_layers": 24,
             "d_model": 1024, "num_heads": 16, "d_ff": 4096}
    n = 24 * 12 * 1024 ** 2 + 30592 * 1024
    assert flops.transformer_matmul_params(model) == n
    assert flops.transformer_train_flops_per_token(model, 512) == \
        6 * n + 12 * 24 * 512 * 1024
