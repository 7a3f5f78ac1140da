"""The step names its blocks from inside (``apex_tpu.pyprof.SCOPES``), and
the persistent compile cache cannot hand a profile an executable that was
compiled before the names existed.

The compiled text of a tiny BERT step (remat, flash attention, amp O5,
FusedLAMB on the flat engine — the benchmark's path) is what a device trace
shows: every instruction's ``op_name`` is the path ``benchmarks/scopes.py``
reads.  The step is lowered and compiled once per module.
"""
import functools
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu import amp, pyprof
from apex_tpu.models import (Glm4MoeLiteConfig, LagunaConfig, Lfm2Config,
                             NemotronHConfig, Qwen3NextConfig,
                             TransformerConfig, glm4_moe_lite_init,
                             glm4_moe_lite_loss, laguna_init, laguna_loss,
                             laguna_s_2_1_config, lfm2_cut_layer_types,
                             lfm2_init, lfm2_loss,
                             nemotron_h_init, nemotron_h_loss,
                             qwen3_next_init, qwen3_next_loss,
                             transformer_init, transformer_loss)
from apex_tpu.optimizers import FusedLAMB
from apex_tpu.parallel import DistributedDataParallel

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CFG = TransformerConfig(vocab_size=256, max_len=128, num_layers=2,
                        d_model=64, num_heads=2, d_ff=128,
                        dtype=jnp.bfloat16, remat=True, attn_impl="fast")
# the other model of the benchmark: one dense layer and one period of LFM2
LFM2 = Lfm2Config(vocab_size=256, hidden_size=64, intermediate_size=160,
                  moe_intermediate_size=32, num_experts=16,
                  num_experts_per_tok=4, num_dense_layers=1,
                  layer_types=lfm2_cut_layer_types(1, 1),
                  num_attention_heads=8, num_key_value_heads=2,
                  experts_held=(0, 4), dtype=jnp.bfloat16, remat=True,
                  attn_impl="fast")
# ... and the third: one layer of each kind of Nemotron-H, a share held
NEMOTRON_H = NemotronHConfig(
    vocab_size=256, hidden_size=64, hybrid_override_pattern="ME*",
    mamba_num_heads=16, mamba_head_dim=8, n_groups=8, ssm_state_size=16,
    chunk_size=16, num_attention_heads=8, num_key_value_heads=2, head_dim=16,
    n_routed_experts=32, num_experts_per_tok=4, moe_latent_size=32,
    moe_intermediate_size=48, moe_shared_expert_intermediate_size=96,
    mamba_heads_held=(0, 4), attention_heads_held=(0, 2),
    experts_held=(0, 8), dtype=jnp.bfloat16, remat=True, attn_impl="fast")
# ... and the fourth: a Gated DeltaNet and a gated attention layer of
# Qwen3-Next, a share of the experts held
QWEN3_NEXT = Qwen3NextConfig(
    vocab_size=256, hidden_size=64, num_hidden_layers=2,
    full_attention_interval=2, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, linear_num_key_heads=2, linear_key_head_dim=8,
    linear_num_value_heads=4, linear_value_head_dim=8, chunk_size=16,
    num_experts=32, num_experts_per_tok=4, moe_intermediate_size=24,
    shared_expert_intermediate_size=24, experts_held=(0, 8),
    dtype=jnp.bfloat16, remat=True, attn_impl="fast")
# ... and the fifth: GLM-4.7-Flash's dense layer, a sparse layer and its
# MTP module, a share of the experts held
GLM4_MOE_LITE = Glm4MoeLiteConfig(
    vocab_size=256, hidden_size=64, num_hidden_layers=2,
    intermediate_size=96, num_attention_heads=4, q_lora_rank=24,
    kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16,
    num_experts=32, num_experts_per_tok=4, moe_intermediate_size=24,
    experts_held=(0, 8), dtype=jnp.bfloat16, remat=True, attn_impl="fast")
# ... and the sixth: Laguna-S-2.1's dense full layer and a sparse
# sliding-window layer, a share of the experts held
LAGUNA = LagunaConfig(
    vocab_size=256, hidden_size=64, intermediate_size=96,
    layer_types=laguna_s_2_1_config(2).layer_types,
    mlp_layer_types=("dense", "sparse"),
    num_attention_heads_per_layer=(4, 6), num_key_value_heads=2,
    head_dim=16, sliding_window=32, num_experts=32, num_experts_per_tok=4,
    moe_intermediate_size=24, shared_expert_intermediate_size=24,
    experts_held=(0, 8), dtype=jnp.bfloat16, remat=True, attn_impl="fast")
#: the blocks only the LFM2 step enters (the Nemotron-H step enters the
#: last three of them too)
LFM2_ONLY = ("apex.conv", "apex.moe", "apex.router", "apex.experts")
#: the blocks only the Nemotron-H step enters (the Qwen3-Next step enters
#: the last of them too)
NEMOTRON_H_ONLY = ("apex.ssm", "apex.ssm_scan", "apex.latent",
                   "apex.shared_expert")
#: the blocks only the Qwen3-Next step enters
QWEN3_NEXT_ONLY = ("apex.gdn", "apex.gdn_rule")
#: the blocks only the GLM-4.7-Flash step enters
GLM4_MOE_LITE_ONLY = ("apex.mla", "apex.mtp")
#: the blocks only the Laguna-S-2.1 step enters
LAGUNA_ONLY = ("apex.swa",)
# "%name = <type, maybe a tuple> opcode(operands), ..., metadata={op_name=..."
_INSTRUCTION = re.compile(
    r' = .*? ([a-z][\w-]*)\(.*metadata=\{[^}]*op_name="([^"]*)"')


def _state_and_batch(batch, init=transformer_init, cfg=CFG):
    params = init(jax.random.PRNGKey(0), cfg)
    opt = FusedLAMB(lr=1e-3, weight_decay=0.01, max_grad_norm=1.0,
                    impl="fused")
    state = amp.initialize(params, opt, opt_level="O5", verbosity=0)
    tokens = jnp.zeros((batch, 128), jnp.int32)
    return state, {"tokens": tokens, "targets": tokens,
                   "weights": jnp.ones((batch, 128), jnp.float32)}


def _step(state, batch, ddp=None, loss_impl=transformer_loss, cfg=CFG):
    def loss_fn(p):
        loss = loss_impl(p, batch, cfg)
        return amp.scale_loss(loss, state), loss
    g, loss = jax.grad(loss_fn, has_aux=True)(state.model_params)
    if ddp is not None:
        g = ddp.allreduce_grads(g)
    return amp.amp_step(state, g), loss


def _op_names(fn, *args):
    """``[(opcode, op_name)]`` of the compiled program's instructions."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    return [m.groups() for m in map(_INSTRUCTION.search, text.splitlines())
            if m]


@pytest.fixture(scope="module")
def step_ops():
    return _op_names(_step, *_state_and_batch(2))


@pytest.fixture(scope="module")
def ddp_step_ops():
    mesh = Mesh(jax.devices()[:2], ("data",))
    step = jax.shard_map(
        functools.partial(_step, ddp=DistributedDataParallel("data")),
        mesh=mesh, in_specs=(P(), P("data")), out_specs=(P(), P()),
        check_vma=False)
    return _op_names(step, *_state_and_batch(4))


@pytest.fixture(scope="module")
def lfm2_step_ops():
    return _op_names(
        functools.partial(_step, loss_impl=lfm2_loss, cfg=LFM2),
        *_state_and_batch(2, lfm2_init, LFM2))


@pytest.fixture(scope="module")
def nemotron_h_step_ops():
    return _op_names(
        functools.partial(_step, loss_impl=nemotron_h_loss, cfg=NEMOTRON_H),
        *_state_and_batch(2, nemotron_h_init, NEMOTRON_H))


@pytest.fixture(scope="module")
def qwen3_next_step_ops():
    return _op_names(
        functools.partial(_step, loss_impl=qwen3_next_loss, cfg=QWEN3_NEXT),
        *_state_and_batch(2, qwen3_next_init, QWEN3_NEXT))


@pytest.fixture(scope="module")
def glm4_moe_lite_step_ops():
    return _op_names(
        functools.partial(_step, loss_impl=glm4_moe_lite_loss,
                          cfg=GLM4_MOE_LITE),
        *_state_and_batch(2, glm4_moe_lite_init, GLM4_MOE_LITE))


@pytest.fixture(scope="module")
def laguna_step_ops():
    return _op_names(
        functools.partial(_step, loss_impl=laguna_loss, cfg=LAGUNA),
        *_state_and_batch(2, laguna_init, LAGUNA))


def test_scopes_are_a_fixed_vocabulary():
    assert len(set(pyprof.SCOPES)) == len(pyprof.SCOPES)
    for name in pyprof.SCOPES:
        assert re.fullmatch(r"apex\.[a-z_]+", name), name


@pytest.mark.parametrize("ops", ["step_ops", "lfm2_step_ops",
                                 "nemotron_h_step_ops",
                                 "qwen3_next_step_ops",
                                 "glm4_moe_lite_step_ops",
                                 "laguna_step_ops"])
def test_every_matmul_belongs_to_a_block(ops, request):
    matmuls = [path for opcode, path in request.getfixturevalue(ops)
               if opcode in ("dot", "convolution", "ragged-dot")]
    assert len(matmuls) >= 10
    for path in matmuls:
        assert any(name in path for name in pyprof.SCOPES), path


@pytest.mark.parametrize("name", pyprof.SCOPES)
def test_scope_occurs_in_the_step(name, step_ops, ddp_step_ops,
                                  lfm2_step_ops, nemotron_h_step_ops,
                                  qwen3_next_step_ops,
                                  glm4_moe_lite_step_ops, laguna_step_ops):
    one_chip = {path for _, path in step_ops if name in path}
    if name in LAGUNA_ONLY:
        # no other step has such a block; the Laguna step enters it forward,
        # backward and in remat's second forward
        assert not one_chip
        for ops in (lfm2_step_ops, nemotron_h_step_ops, qwen3_next_step_ops,
                    glm4_moe_lite_step_ops):
            assert not [path for _, path in ops if name in path]
        paths = [path for _, path in laguna_step_ops if name in path]
        for mark in ("transpose(", "rematted_computation"):
            assert any(mark in path for path in paths), (name, mark)
        assert any("transpose(" not in path for path in paths), name
    elif name in GLM4_MOE_LITE_ONLY:
        # no other step has such a block; the GLM-4.7-Flash step enters it
        # forward, backward and in remat's second forward
        assert not one_chip
        for ops in (lfm2_step_ops, nemotron_h_step_ops, qwen3_next_step_ops):
            assert not [path for _, path in ops if name in path]
        paths = [path for _, path in glm4_moe_lite_step_ops if name in path]
        for mark in ("transpose(", "rematted_computation"):
            assert any(mark in path for path in paths), (name, mark)
        assert any("transpose(" not in path for path in paths), name
    elif name in QWEN3_NEXT_ONLY:
        # no other step has such a block; the Qwen3-Next step enters it
        # forward, backward and in remat's second forward, the rule inside
        # the mixer
        assert not one_chip
        for ops in (lfm2_step_ops, nemotron_h_step_ops):
            assert not [path for _, path in ops if name in path]
        paths = [path for _, path in qwen3_next_step_ops if name in path]
        for mark in ("transpose(", "rematted_computation"):
            assert any(mark in path for path in paths), (name, mark)
        assert any("transpose(" not in path for path in paths), name
        if name == "apex.gdn_rule":
            # also inside the checkpointed loop over sequences, whose body
            # starts a name stack of its own
            assert all("apex.gdn" in path.replace("apex.gdn_rule", "")
                       for path in paths), name
    elif name in NEMOTRON_H_ONLY:
        # neither other step has such a block; the Nemotron-H step enters it
        # forward, backward and in remat's second forward
        assert not one_chip
        assert not [path for _, path in lfm2_step_ops if name in path]
        paths = [path for _, path in nemotron_h_step_ops if name in path]
        for mark in ("transpose(", "rematted_computation"):
            assert any(mark in path for path in paths), (name, mark)
        assert any("transpose(" not in path for path in paths), name
        outer = "apex.ssm" if name == "apex.ssm_scan" else "apex.moe"
        if name != "apex.ssm":
            assert all(outer in path for path in paths), name
    elif name in LFM2_ONLY:
        # the BERT step has no such block; the LFM2 step enters it forward,
        # backward and in remat's second forward — but for the experts: the
        # reverse rule of ``parallel.expert._held_experts`` recomputes a
        # walk itself (backward), so remat's own second forward of them, the
        # block's last operation, is dead code and must stay gone
        assert not one_chip
        paths = [path for _, path in lfm2_step_ops if name in path]
        assert any("transpose(" in path for path in paths), name
        assert any("rematted_computation" in path for path in paths) \
            == (name != "apex.experts"), name
        assert any("transpose(" not in path for path in paths), name
    elif name == "apex.ddp_allreduce":
        # only a step that reduces has it, and the collective lies under it
        assert not one_chip
        reduced = [(op, path) for op, path in ddp_step_ops if name in path]
        assert any(op.startswith("all-reduce") for op, _ in reduced)
    else:
        assert one_chip, name
        assert any(name in path for _, path in ddp_step_ops)


@pytest.mark.parametrize("block", ["apex.attn", "apex.mlp"])
@pytest.mark.parametrize("mark", ["rematted_computation", "transpose("])
def test_backward_and_recompute_are_written_into_the_path(mark, block,
                                                          step_ops):
    """jax names them itself: no scope of ours for a phase."""
    paths = [path for _, path in step_ops if block in path]
    assert any(mark in path for path in paths)
    assert any("transpose(" not in path for path in paths)     # the forward


def test_flash_nests_inside_attention(step_ops):
    inside = [path for _, path in step_ops if "apex.flash" in path]
    assert inside
    for path in inside:
        assert path.index("apex.attn") < path.index("apex.flash"), path


@pytest.mark.parametrize("inner", ["apex.router", "apex.experts"])
def test_router_and_experts_nest_inside_moe(inner, lfm2_step_ops):
    inside = [path for _, path in lfm2_step_ops if inner in path]
    assert inside
    for path in inside:
        assert path.index("apex.moe") < path.index(inner), path


@pytest.mark.parametrize("name", ["apex.embed", "apex.attn", "apex.flash",
                                  "apex.mlp", "apex.head", "apex.loss",
                                  "apex.amp_step"])
def test_lfm2_step_reuses_the_shared_blocks(name, lfm2_step_ops):
    """The dense gated FFN is ``apex.mlp``; attention, embedding, head, loss
    and the update are the blocks the BERT step has."""
    assert any(name in path for _, path in lfm2_step_ops), name


@pytest.mark.parametrize("name", ["apex.embed", "apex.attn", "apex.flash",
                                  "apex.moe", "apex.router", "apex.experts",
                                  "apex.shared_expert", "apex.head",
                                  "apex.loss", "apex.amp_step"])
def test_qwen3_next_step_reuses_the_shared_blocks(name, qwen3_next_step_ops):
    """Gated attention is ``apex.attn`` with ``apex.flash`` inside, the
    sparse FFN ``apex.moe`` with router, experts and the gated shared expert
    inside; embedding, head, loss and the update are the BERT step's."""
    paths = [path for _, path in qwen3_next_step_ops if name in path]
    assert paths, name
    if name in ("apex.router", "apex.experts", "apex.shared_expert"):
        assert all(path.index("apex.moe") < path.index(name)
                   for path in paths), name


@pytest.mark.parametrize("name", ["apex.embed", "apex.mla", "apex.flash",
                                  "apex.mlp", "apex.moe", "apex.router",
                                  "apex.experts", "apex.shared_expert",
                                  "apex.head", "apex.loss", "apex.mtp",
                                  "apex.amp_step"])
def test_glm4_moe_lite_step_nests_its_blocks(name, glm4_moe_lite_step_ops):
    """Latent attention is ``apex.mla`` with ``apex.flash`` inside, the
    dense FFN ``apex.mlp``, the sparse FFN ``apex.moe`` with router, experts
    and the shared expert inside; the MTP module's block, head and loss nest
    inside ``apex.mtp`` as they lie in the trunk."""
    paths = [path for _, path in glm4_moe_lite_step_ops if name in path]
    assert paths, name
    inner = {"apex.flash": "apex.mla", "apex.router": "apex.moe",
             "apex.experts": "apex.moe", "apex.shared_expert": "apex.moe"}
    if name in inner:
        assert all(path.index(inner[name]) < path.index(name)
                   for path in paths), name
    if name in ("apex.mla", "apex.moe", "apex.head", "apex.loss"):
        # the trunk's, and the MTP module's inside its scope
        assert any("apex.mtp" not in path for path in paths), name
        assert any("apex.mtp" in path and path.index("apex.mtp")
                   < path.index(name) for path in paths), name


@pytest.mark.parametrize("name", ["apex.embed", "apex.attn", "apex.swa",
                                  "apex.flash", "apex.mlp", "apex.moe",
                                  "apex.router", "apex.experts",
                                  "apex.shared_expert", "apex.head",
                                  "apex.loss", "apex.amp_step"])
def test_laguna_step_nests_its_blocks(name, laguna_step_ops):
    """A window layer's attention is ``apex.swa`` and a full layer's
    ``apex.attn``, each with ``apex.flash`` inside; the dense FFN is
    ``apex.mlp``, the sparse FFN ``apex.moe`` with router, experts and the
    gated shared expert inside."""
    paths = [path for _, path in laguna_step_ops if name in path]
    assert paths, name
    if name == "apex.flash":
        assert all(("apex.swa" in path and path.index("apex.swa")
                    < path.index(name)) or path.index("apex.attn")
                   < path.index(name) for path in paths), name
        assert any("apex.swa" in path for path in paths)
        assert any("apex.swa" not in path for path in paths)
    if name in ("apex.router", "apex.experts", "apex.shared_expert"):
        assert all(path.index("apex.moe") < path.index(name)
                   for path in paths), name


def test_update_blocks_nest_inside_amp_step(step_ops):
    for inner in ("apex.unscale", "apex.opt_update", "apex.model_copy"):
        for path in (p for _, p in step_ops if inner in p):
            assert "apex.amp_step/" + inner in path, path


@pytest.mark.parametrize("name", ["apex.nonesuch", "apex.", "apex.attention",
                                  "apex.attn.core"])
def test_annotate_refuses_an_apex_name_outside_the_vocabulary(name):
    with pytest.raises(ValueError, match="SCOPES"):
        with pyprof.annotate(name):
            pass


@pytest.mark.parametrize("name", ["fwd", "apexish", "my.apex.attn"])
def test_annotate_passes_other_names(name):
    ops = _op_names(lambda x: _annotated(name, x), jnp.ones((4, 4)))
    assert any(name + "|layer=3/" in path for _, path in ops)


def _annotated(name, x):
    with pyprof.annotate(name, layer=3):
        return jnp.dot(x, x)


# ---------------------------------------------------------------------------
# the compile cache: a scope must be a new entry, a checkout path must not
# ---------------------------------------------------------------------------

_PROGRAM = '''
import contextlib, json, os, re, sys
import jax, jax.numpy as jnp
from apex_tpu import pyprof

cache_dir, scope = sys.argv[1], sys.argv[2] == "1"
jax.config.update("jax_compilation_cache_dir", cache_dir)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def f(x, w):
    with pyprof.annotate("apex.attn") if scope else contextlib.nullcontext():
        return jnp.dot(x, w)


x = jnp.ones((8, 8))
text = jax.jit(f).lower(x, x).compile().as_text()
print(json.dumps({
    "package": os.path.dirname(os.path.dirname(pyprof.__file__)),
    "entries": [e for e in os.listdir(cache_dir) if e.startswith("jit_f-")],
    "op_names": re.findall('op_name="([^"]*)"', text)}))
'''


def _checkout(tmp_path, name):
    """A stand-in for a checkout in another directory: the package (a link)
    and a user's file beside it."""
    root = tmp_path / name
    root.mkdir()
    (root / "apex_tpu").symlink_to(os.path.join(ROOT, "apex_tpu"))
    (root / "program.py").write_text(_PROGRAM)
    return root


def _run(root, cache_dir, scope, **env):
    env = {**{k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
           "JAX_PLATFORMS": "cpu", **env}
    proc = subprocess.run(
        [sys.executable, "program.py", str(cache_dir), str(int(scope))],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.splitlines()[-1])
    assert out["package"] == str(root / "apex_tpu")     # the link, not ROOT
    return out


@pytest.fixture(scope="module")
def cache_runs(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("scopes_cache")
    a, b = _checkout(tmp, "a"), _checkout(tmp, "b")
    cache = tmp / "cache"
    cache.mkdir()
    return {"bare": _run(a, cache, False), "scoped": _run(a, cache, True),
            "moved": _run(b, cache, True)}


def test_a_scope_is_a_new_cache_entry_and_shows_in_the_text(cache_runs):
    assert cache_runs["bare"]["op_names"][-1] == "jit(f)/dot_general"
    assert len(cache_runs["bare"]["entries"]) == 1
    assert len(cache_runs["scoped"]["entries"]) == 2
    assert cache_runs["scoped"]["op_names"][-1] == \
        "jit(f)/apex.attn/dot_general"


def test_another_checkout_path_is_the_same_cache_entry(cache_runs):
    assert sorted(cache_runs["moved"]["entries"]) == \
        sorted(cache_runs["scoped"]["entries"])
    assert cache_runs["moved"]["op_names"] == cache_runs["scoped"]["op_names"]


def test_without_metadata_in_the_key_the_cache_hands_out_stale_names(
        tmp_path):
    """The trap itself, and that a user's own setting stays: with the key
    as jax builds it by default, the scoped function is a HIT on the bare
    one's entry and its text has no scope."""
    root = _checkout(tmp_path, "c")
    cache = tmp_path / "cache"
    cache.mkdir()
    off = {"JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY": "0"}
    _run(root, cache, False, **off)
    stale = _run(root, cache, True, **off)
    assert len(stale["entries"]) == 1
    assert stale["op_names"][-1] == "jit(f)/dot_general"
