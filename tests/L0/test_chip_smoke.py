"""chip_smoke.py — the on-chip smoke — exercised where there is no chip:
the rehearsal mode runs every phase tiny on the CPU (kernels interpreted),
so the script, its exit codes and its one-JSON-line contract are tier-1
tested; without the flag the script must refuse the CPU before doing any
work."""
import importlib.util
import json
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SMOKE = os.path.join(ROOT, "chip_smoke.py")
ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def compile_cache_config():
    """chip_smoke turns the persistent compile cache on for its
    process; put this process's settings back afterwards."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_rehearsal_runs_every_phase_on_cpu():
    """``--rehearse``: every phase at tiny shapes on the CPU, exit 0, and
    the last stdout line is the JSON summary labelled platform cpu."""
    r = subprocess.run([sys.executable, SMOKE, "--rehearse"],
                       capture_output=True, text=True, timeout=600,
                       cwd=ROOT, env=ENV)
    assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-1500:])
    summary = json.loads(r.stdout.strip().splitlines()[-1])
    assert summary["ok"] is True and summary["rehearsal"] is True
    assert summary["device"]["platform"] == "cpu"
    assert summary["device"]["count"] >= 4
    with open(os.path.join(ROOT, "chiprun_out", "chip_smoke.json")) as f:
        report = json.load(f)
    smoke = _load_smoke()
    assert list(report["phases"]) == list(smoke.PHASES)
    for name, rec in report["phases"].items():
        assert rec["ok"] and "skipped" not in rec, (name, rec)
        assert rec["compile_s"] >= 0 and rec["wall_s"] >= rec["compile_s"]
    # the BERT step was shown to hold the flash kernels and no flat engine
    # (its l2norm kernel); the optimizer leg held per-leaf LAMB to the flat
    # one on the same tree — and timed neither: a time is the chip's
    traced = report["phases"]["bert_large"]["pallas_calls_traced"]
    assert "apex_flash_fwd" in traced and "apex_l2norm" not in traced
    lamb = report["phases"]["bert_large"]["lamb"]
    assert lamb["masters_rel_diff"] < 1e-5 and lamb["parameters"] > 0
    assert not any(k.endswith("_ms") for k in lamb)
    # the Qwen3-Next leg held the chunked rule to the recurrence and its
    # sparse FFN to the twin at one walk of the buffer and at three
    qwen = report["phases"]["qwen3_next"]
    assert qwen["rule"]["chunks"] == 3 and max(
        qwen["rule"]["rel_err"].values()) < 3e-2
    # ... by the form its 8-wide heads take (the program's own record; on
    # the chip the phase insists on the kernel pair, and times it)
    assert qwen["rule"]["path"] == ["jnp"] and "kernel_ms" not in qwen["rule"]
    assert (qwen["expert_layer_one_walk"]["walks"],
            qwen["expert_layer_three_walks"]["walks"]) == (1, 3)
    # the GLM-4.7-Flash leg held every parameter leaf of the cut, the MTP
    # module's among them, through flash to XLA attention, and one latent
    # mixer to its float32 twin
    glm = report["phases"]["glm4_moe_lite"]
    assert glm["leaves"] == len(glm["leaf_rel_err"]) == 93
    assert any(name.startswith("['mtp'][0]") for name in glm["leaf_rel_err"])
    assert glm["loss_rel_err"] < 2e-3 and glm["assignments"] == 5 * 40 * 4
    assert max(glm["mla_mixer"]["rel_err"].values()) < 3e-2
    # every plan family took its step on the 4-device mesh
    legs = report["phases"]["multichip"]["legs"]
    assert sum(k.startswith("family_") for k in legs) == 7
    assert all(leg["ok"] for leg in legs.values())


def test_without_the_flag_the_cpu_is_refused():
    """No TPU and no ``--rehearse``: non-zero exit before any work, the
    platform named, and no result line."""
    r = subprocess.run([sys.executable, SMOKE], capture_output=True,
                       text=True, timeout=120, cwd=ROOT, env=ENV)
    assert r.returncode == 2
    assert "'cpu'" in r.stderr and "TPU" in r.stderr
    assert not any(ln.startswith("{") for ln in r.stdout.splitlines())
    assert "phase" not in r.stdout


def test_a_failing_phase_fails_the_run(monkeypatch, capsys,
                                       compile_cache_config):
    """A phase that raises prints its traceback, the remaining phases
    still run, and the exit code and summary say failed."""
    smoke = _load_smoke()

    def boom(ctx):
        raise RuntimeError("Mosaic lowering exploded")
    monkeypatch.setitem(smoke.PHASES, "kernels", boom)
    rc = smoke.main(["--rehearse", "--only", "kernels,native"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "Traceback" in out and "Mosaic lowering exploded" in out
    assert "phase native: ok" in out              # later phases still ran
    summary = json.loads(out.strip().splitlines()[-1])
    assert summary["ok"] is False and summary["failed"] == ["kernels"]

    with pytest.raises(SystemExit):               # a typo'd phase name
        smoke.main(["--rehearse", "--only", "kernals"])


def test_compile_cache_is_placed_from_outside_or_in_the_checkout(
        monkeypatch, compile_cache_config):
    from apex_tpu.utils import platform as plat
    # unset: the fixed in-checkout directory, set in code
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert plat.enable_compile_cache() == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == os.path.join(
        ROOT, ".jax_cache")
    # set: the variable's directory is reported and no code sets one
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    updated = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: updated.append(key))
    assert plat.enable_compile_cache() == "/some/dir"
    assert "jax_compilation_cache_dir" not in updated
    # ... because jax reads the variable itself
    r = subprocess.run(
        [sys.executable, "-c",
         "import jax; print(jax.config.jax_compilation_cache_dir)"],
        capture_output=True, text=True, timeout=120,
        env={**ENV, "JAX_COMPILATION_CACHE_DIR": "/some/dir"})
    assert r.stdout.strip() == "/some/dir", r.stderr[-1500:]


def test_force_cpu_after_another_backend_is_an_error(monkeypatch):
    """A process keeps the backend it initialised: asking for more CPU
    devices than the live backend has cannot be granted, and says so."""
    from apex_tpu.utils import platform as plat
    assert plat.backends_initialized()
    plat.force_cpu(jax.device_count())            # already satisfied
    with pytest.raises(RuntimeError, match="before the first jax"):
        plat.force_cpu(jax.device_count() + 1)
