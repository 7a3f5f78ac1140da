"""Example-script smoke tests: every shipped example must run end to end
on CPU (the BASELINE configs' measurement vehicles — guarded here so they
cannot rot).  Each runs in-process with tiny shapes via its main(argv)."""
import importlib.util
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _load(rel_path, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, rel_path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_simple_distributed_example():
    ex = _load("examples/simple/distributed/distributed_data_parallel.py",
               "ex_simple")
    final = ex.main(["--steps", "40", "--batch-size", "16",
                     "--print-freq", "20"])
    assert np.isfinite(final) and final < 1.0


@pytest.mark.slow   # ~60-100s each: the imagenet example trains a
# real (tiny) model through the full main(argv) path — far beyond
# the tier-1 time budget; the other example smoke tests keep the
# entry-point surface covered there
def test_imagenet_example_resume_roundtrip(tmp_path):
    ex = _load("examples/imagenet/main_amp.py", "ex_imagenet")
    ck = str(tmp_path / "rn.ckpt")
    ex.main(["--arch", "resnet18", "--batch-size", "4", "--steps", "3",
             "--print-freq", "3", "--save", ck])
    speed = ex.main(["--arch", "resnet18", "--batch-size", "4",
                     "--steps", "3", "--print-freq", "3", "--resume", ck])
    assert speed >= 0


@pytest.mark.slow   # ~26s: a full GAN D+G train loop through main(argv);
# test_models.test_dcgan_shapes_and_training_signal keeps the model
# surface in tier-1 (ISSUE 12 budget reclaim)
def test_dcgan_example():
    ex = _load("examples/dcgan/main_amp.py", "ex_dcgan")
    errD, errG = ex.main(["--steps", "3", "--batch-size", "4",
                          "--print-freq", "3"])
    assert np.isfinite(errD) and np.isfinite(errG)


def test_bert_example():
    ex = _load("examples/bert/pretrain.py", "ex_bert")
    loss = ex.main(["--steps", "3", "--batch-size", "2", "--seq-len", "32",
                    "--d-model", "64", "--layers", "1", "--vocab", "256",
                    "--print-freq", "3"])
    assert np.isfinite(loss)


@pytest.mark.slow   # ~17s: the base test_bert_example keeps the
# entry point in tier-1; the flash-kernel numerics this variant adds
# are covered by chip_smoke --rehearse and the multihead_attn suite
# (ISSUE 12 budget reclaim)
def test_bert_example_fast_attention():
    """--attn fast trains through the contrib flash kernel (interpret
    mode on CPU) — the reference examples' fast_self_multihead_attn
    switch, exercised e2e inside a training step."""
    ex = _load("examples/bert/pretrain.py", "ex_bert_fast")
    loss = ex.main(["--steps", "3", "--batch-size", "2", "--seq-len", "32",
                    "--d-model", "64", "--layers", "1", "--vocab", "256",
                    "--attn", "fast", "--print-freq", "3"])
    assert np.isfinite(loss)


def test_bert_example_plan_smoke():
    """--plan resolves the parallel plan through the cost-model search
    (its ``=> plan [cost-model search ...]`` line says so) and
    materializes the winner through
    spmd.build_plan_step — at these tiny dims the search picks a
    sharded expert-parallel plan, so this smoke drives the ep engine
    end to end through the example entry point (the path that replaced
    the hand-wired single-device --moe wiring for sharded runs)."""
    ex = _load("examples/bert/pretrain.py", "ex_bert_plan")
    loss = ex.main(["--steps", "2", "--batch-size", "8", "--seq-len", "16",
                    "--d-model", "32", "--heads", "2", "--layers", "1",
                    "--vocab", "64", "--print-freq", "2", "--plan"])
    assert np.isfinite(loss)
    # --plan owns the parallelism decision: hand-wired flags refuse
    with pytest.raises(SystemExit):
        ex.main(["--steps", "1", "--plan", "--moe", "4"])


@pytest.mark.slow   # ~30s: the tier-1 plan smoke above keeps the
# entry point + ep engine covered; this variant re-runs the search at
# pipeline-capable dims (2 layers, larger batch) for full coverage
def test_bert_example_plan_full():
    ex = _load("examples/bert/pretrain.py", "ex_bert_plan_full")
    loss = ex.main(["--steps", "4", "--batch-size", "16", "--seq-len",
                    "32", "--d-model", "64", "--heads", "2", "--layers",
                    "2", "--vocab", "256", "--print-freq", "4", "--plan"])
    assert np.isfinite(loss)


@pytest.mark.slow   # ~60-100s each: the imagenet example trains a
# real (tiny) model through the full main(argv) path — far beyond
# the tier-1 time budget; the other example smoke tests keep the
# entry-point surface covered there
def test_imagenet_example_native_loader(tmp_path):
    """--loader native drives the C++ prefetch engine end to end, both
    synthetic and memmapped-npy data."""
    ex = _load("examples/imagenet/main_amp.py", "ex_imagenet_native")
    speed = ex.main(["--arch", "resnet18", "--batch-size", "4",
                     "--steps", "3", "--print-freq", "3",
                     "--loader", "native"])
    assert speed >= 0
    # memmap path: tiny fp32 dataset on disk
    n = 16
    np.save(tmp_path / "images.npy",
            np.random.rand(n, 224, 224, 3).astype(np.float32))
    np.save(tmp_path / "labels.npy",
            np.random.randint(0, 1000, n).astype(np.int32))
    speed = ex.main(["--arch", "resnet18", "--batch-size", "4",
                     "--steps", "3", "--print-freq", "3",
                     "--loader", "native", "--data", str(tmp_path)])
    assert speed >= 0


@pytest.mark.slow   # ~60-100s each: the imagenet example trains a
# real (tiny) model through the full main(argv) path — far beyond
# the tier-1 time budget; the other example smoke tests keep the
# entry-point surface covered there
def test_imagenet_example_distributed():
    """--distributed + --sync-bn over the 8-device mesh (the DDP+SyncBN
    BASELINE config shape), with the native loader feeding it."""
    ex = _load("examples/imagenet/main_amp.py", "ex_imagenet_dist")
    speed = ex.main(["--arch", "resnet18", "--batch-size", "16",
                     "--steps", "2", "--print-freq", "2",
                     "--distributed", "--sync-bn", "--loader", "native"])
    assert speed >= 0


@pytest.mark.slow   # ~20s: the base test_bert_example keeps the entry
# point in tier-1; the zero/moe internals are covered first-class by
# test_distributed_optimizers and test_expert_parallel/test_spmd
# (ISSUE 12 budget reclaim)
def test_bert_example_zero_and_moe():
    """The --zero (DistributedFusedLAMB shard_map) leg runs on the mesh;
    the --moe leg runs the MoE FFN single-device (pretrain.py keeps MoE
    local unless sharded — the mesh-sharded MoE path is exercised by
    dryrun_multichip leg 4 and test_expert_parallel)."""
    ex = _load("examples/bert/pretrain.py", "ex_bert_flags")
    loss = ex.main(["--steps", "2", "--batch-size", "8", "--seq-len", "32",
                    "--d-model", "64", "--layers", "1", "--vocab", "256",
                    "--print-freq", "2", "--zero"])
    assert np.isfinite(loss)
    loss = ex.main(["--steps", "2", "--batch-size", "8", "--seq-len", "32",
                    "--d-model", "64", "--layers", "1", "--vocab", "256",
                    "--print-freq", "2", "--moe", "4"])
    assert np.isfinite(loss)
