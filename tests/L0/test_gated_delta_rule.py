"""The gated delta rule's Pallas kernel pair (``apex_tpu.ops.
gated_delta_rule``, interpret mode here) at the published head shape — d_k =
d_v = 128, chunk 64 — against the reference's sequential recurrence
(``benchmarks/reference/qwen3_next_80b_a3b.py``) and against its ``jax.numpy``
twin (``models.qwen3_next._chunked_rule``): output and all five gradients;
which of the two a shape takes; and where the kernels sit in the compiled
step's paths."""
import collections
import dataclasses
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import Qwen3NextConfig, qwen3_next, qwen3_next_init, \
    qwen3_next_loss
from apex_tpu.ops import gated_delta_rule as rule_kernel
from apex_tpu.telemetry import MemorySink, Registry, events

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_spec = importlib.util.spec_from_file_location(
    "qwen3_next_reference",
    os.path.join(ROOT, "benchmarks/reference/qwen3_next_80b_a3b.py"))
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

WIDTH, CHUNK = 128, 64
NAMES = "q k v g beta".split()


@pytest.fixture(autouse=True)
def _float32_products():
    with jax.default_matmul_precision("highest"):
        yield


def _inputs(seq, groups, heads, decay=1.0, seed=0, bsz=2, width=WIDTH):
    """q, k normalised as the mixer hands them; ``g = -decay · softplus``."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = qwen3_next._l2_norm(jax.random.normal(
        ks[0], (bsz, seq, groups, width))) * width ** -0.5
    k = qwen3_next._l2_norm(jax.random.normal(
        ks[1], (bsz, seq, groups, width)))
    v = jax.random.normal(ks[2], (bsz, seq, heads, width))
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3], (bsz, seq, heads)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (bsz, seq, heads)))
    probe = jax.random.normal(ks[5], (bsz, seq, heads, width))
    return (q, k, v, g, beta), probe


def _sequential(q, k, v, g, beta, chunk=None):
    per = v.shape[2] // q.shape[2]
    f32 = jnp.float32
    return reference._delta_recurrence(
        jnp.repeat(q.astype(f32), per, axis=2),
        jnp.repeat(k.astype(f32), per, axis=2), v.astype(f32), g, beta)


def _out_and_grads(rule, args, probe):
    def loss(*a):
        out = rule(*a, CHUNK)
        return jnp.sum(out.astype(jnp.float32) * probe), out
    grads, out = jax.grad(loss, argnums=range(5), has_aux=True)(*args)
    return (out, *grads)


def _close(got, want, what, tol):
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    assert np.all(np.isfinite(got)), what
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=tol * float(np.max(np.abs(want))),
                               err_msg=what)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 5e-5),
                                       (jnp.bfloat16, 4e-2)])
@pytest.mark.parametrize("groups,heads,seq", [
    (1, 2, 64),         # one chunk, the published two value heads a key head
    (2, 4, 100),        # padding: two chunks, the second short
    (1, 4, 192),        # three chunks in one grid step, four heads a key head
    (2, 2, 100),        # a key head with one value head
    (2, 2, 64),         # ... and ONE triangular system a grid step: a pack of
    (1, 3, 192),        # the inverse half empty; nine systems, four and a half
    (1, 1, 100),        # one head in all
])
def test_kernel_is_the_sequential_recurrence(groups, heads, seq, dtype, tol):
    (q, k, v, g, beta), probe = _inputs(seq, groups, heads, seed=seq)
    args = (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta)
    got = _out_and_grads(rule_kernel.gated_delta_rule, args, probe)
    want = _out_and_grads(_sequential, args, probe)
    for name, a, b, like in zip(["out"] + NAMES, got, want,
                                 (args[2],) + args):
        assert a.shape == b.shape == like.shape and a.dtype == like.dtype, \
            name
        _close(a, b, name, tol)


def test_strongly_negative_g_stays_finite():
    """``e^γ`` underflows inside a chunk; every decay is a difference."""
    args, probe = _inputs(100, 1, 2, decay=40.0, seed=3)
    got = _out_and_grads(rule_kernel.gated_delta_rule, args, probe)
    want = _out_and_grads(_sequential, args, probe)
    for name, a, b in zip(["out"] + NAMES, got, want):
        _close(a, b, name, 5e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 2e-2)])
def test_kernel_equals_its_jax_numpy_twin_to_rounding(dtype, tol):
    """One arithmetic in two places: float32 operands agree to float32's
    rounding (the forward bit for bit but for the order of sums), bfloat16
    operands to bfloat16's — the twin rounds every cotangent to the
    operand's dtype, the kernel only a product's operands."""
    (q, k, v, g, beta), probe = _inputs(192, 2, 4, seed=7)
    args = (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta)
    got = _out_and_grads(rule_kernel.gated_delta_rule, args, probe)
    want = _out_and_grads(qwen3_next._chunked_rule, args, probe)
    _close(got[0], want[0], "out", 2e-6 if dtype == jnp.float32 else 8e-3)
    for name, a, b in zip(NAMES, got[1:], want[1:]):
        assert a.dtype == b.dtype, name
        _close(a, b, name, tol)


def _paths_taken(fn, *args):
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    prev = events.set_default(reg)
    try:
        jax.make_jaxpr(fn)(*args)
        return [r["fields"]["path"] for r in reg.flush()
                if r.get("name") == "gdn.rule"]
    finally:
        events.set_default(prev)


@pytest.mark.parametrize("width,chunk,path", [
    (8, 16, "jnp"),         # the CPU tests' heads
    (128, 64, "kernel"),    # the published shape
    (128, 8, "jnp"),        # a chunk under a bfloat16 sublane tile
    (128, 32, "jnp"),       # chunks the kernels could be made to take, but
    (128, 128, "jnp"),      # that no chip has run: the rule is what was run
    (64, 64, "jnp"),        # half a lane tile
    (256, 64, "jnp"),       # two lane tiles
])
def test_the_shape_decides_the_path(width, chunk, path):
    args, _ = _inputs(70, 1, 2, width=width)
    assert rule_kernel.takes(width, width, chunk) == (path == "kernel")
    assert _paths_taken(
        lambda *a: qwen3_next.gated_delta_rule(*a, chunk), *args) == [path]
    # ... and either path is the same function of its operands
    np.testing.assert_allclose(
        qwen3_next.gated_delta_rule(*args, chunk),
        qwen3_next._chunked_rule(*args, chunk), rtol=0, atol=1e-5)


def test_an_unknown_path_is_refused():
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    prev = events.set_default(reg)
    try:
        with pytest.raises(ValueError, match="kernel"):
            events.record_gdn_rule("pallas")
    finally:
        events.set_default(prev)


# ---------------------------------------------------------------------------
# where the kernels sit in the compiled step
# ---------------------------------------------------------------------------

#: one Gated DeltaNet layer at the published head shape, all else tiny
WIDE = Qwen3NextConfig(
    vocab_size=256, hidden_size=64, num_hidden_layers=1,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    linear_num_key_heads=1, linear_key_head_dim=WIDTH,
    linear_num_value_heads=2, linear_value_head_dim=WIDTH, chunk_size=CHUNK,
    num_experts=8, num_experts_per_tok=2, moe_intermediate_size=24,
    shared_expert_intermediate_size=24, experts_held=(0, 8),
    dtype=jnp.bfloat16, remat=True, xent_impl="xla")
_KERNEL = re.compile(r"/(apex_gdn_rule_[a-z]+)/")


@pytest.fixture(scope="module")
def step_paths():
    """Every ``op_name`` path of the compiled gradient of the loss."""
    tokens = jnp.zeros((2, 2 * CHUNK), jnp.int32)
    batch = {"tokens": tokens, "targets": tokens,
             "weights": jnp.ones(tokens.shape, jnp.float32)}
    text = jax.jit(jax.grad(
        lambda p: qwen3_next_loss(p, batch, WIDE))).lower(
            qwen3_next_init(jax.random.PRNGKey(0), WIDE)).compile().as_text()
    return set(re.findall(r'op_name="(jit\([^"]*)"', text))


@pytest.mark.parametrize("kernel,phase", [
    ("apex_gdn_rule_fwd", "forward"), ("apex_gdn_rule_sweep", "backward"),
    ("apex_gdn_rule_bwd", "backward")])
def test_the_kernels_lie_under_the_mixers_scopes(kernel, phase, step_paths):
    """``benchmarks/scopes.py`` reads block and phase off these paths (in
    interpret mode a kernel's body is instructions under the call's own
    path): every call of the rule's kernels is under ``apex.gdn`` /
    ``apex.gdn_rule``; the forward kernel runs in the forward pass ALONE —
    its output is kept by name, so neither remat's second forward nor the
    loop's own checkpoint runs it again —, the backward's sweep and reverse
    kernel in the reverse pass."""
    found = collections.defaultdict(set)
    for path in step_paths:
        match = _KERNEL.search(path)
        if match:
            found[match.group(1)].add(path[:match.start()])
    assert set(found) == {"apex_gdn_rule_fwd", "apex_gdn_rule_sweep",
                          "apex_gdn_rule_bwd"}
    for path in found[kernel]:
        assert path.index("apex.gdn/") < path.index("apex.gdn_rule"), path
        assert ("transpose(" in path) == (phase == "backward"), path
        assert "rematted_computation" not in path, path


def test_remat_still_recomputes_the_rest_of_the_rule_scope(step_paths):
    """The decays and the normalised q, k under ``apex.gdn_rule`` are remat's
    to recompute: the scope is read in all three phases."""
    paths = [p for p in step_paths if "apex.gdn_rule" in p]
    assert any("rematted_computation" in p for p in paths)
    assert any("transpose(" in p and "rematted_computation" not in p
               for p in paths)
    assert any("transpose(" not in p for p in paths)


def test_a_step_on_the_jax_numpy_path_keeps_nothing_by_name(monkeypatch):
    """Only the kernel pair's output is named: at the tests' widths the
    checkpoints' policy finds nothing to keep, and the step lowers to the
    program it is with no policy at all — what it was before the kernels."""
    tiny = dataclasses.replace(WIDE, linear_key_head_dim=8,
                               linear_value_head_dim=8, chunk_size=16)
    tokens = jnp.zeros((2, 32), jnp.int32)
    batch = {"tokens": tokens, "targets": tokens,
             "weights": jnp.ones(tokens.shape, jnp.float32)}

    def step(cfg):
        return jax.jit(jax.grad(lambda p: qwen3_next_loss(p, batch, cfg))), \
            qwen3_next_init(jax.random.PRNGKey(0), cfg)

    def named(cfg):
        fn, params = step(cfg)
        return "gdn_rule_out" in str(jax.make_jaxpr(fn)(params))
    assert named(WIDE) and not named(tiny)
    fn, params = step(tiny)
    with_policy = fn.lower(params).as_text()
    monkeypatch.setattr(qwen3_next, "_KEEP_RULE_OUT", None)
    fn, params = step(tiny)
    assert fn.lower(params).as_text() == with_policy


# ---------------------------------------------------------------------------
# Mosaic takes the kernels at the published shape (compiled for a described
# v5e: no chip; the only test file that loads the TPU's compiler)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("dtype,groups,heads,chunks", [
    (jnp.bfloat16, 1, 2, 8), (jnp.float32, 1, 2, 8), (jnp.bfloat16, 2, 2, 1),
    (jnp.bfloat16, 1, 1, 1)])
def test_mosaic_compiles_the_pair_at_the_published_shape(
        dtype, groups, heads, chunks, one_chip, monkeypatch):
    """Interpret mode knows no VMEM and no tiling: the forward kernel, the
    sweep and the reverse kernel at d 128, chunk 64, two value heads a key
    head and a full grid step of chunks — in float32 too, where a block of
    rows is twice the bytes (a float32 twin on the chip takes this path) —
    and at one value head a key head and one chunk, a half-empty pack of the
    inverse, of two heads and of one."""
    monkeypatch.setattr(rule_kernel, "_interpret", lambda: False)
    seq = chunks * CHUNK

    def shaped(*shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    args = (shaped(1, seq, groups, WIDTH), shaped(1, seq, groups, WIDTH),
            shaped(1, seq, heads, WIDTH),
            shaped(1, seq, heads, dt=jnp.float32),
            shaped(1, seq, heads, dt=jnp.float32))
    for fn in (rule_kernel._forward, rule_kernel._backward):
        fn.clear_cache()                # no trace made for the interpreter
    try:
        text = jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(rule_kernel.gated_delta_rule(
                *a, CHUNK).astype(jnp.float32)), argnums=range(5))).lower(
                    *args).compile().as_text()
    finally:
        for fn in (rule_kernel._forward, rule_kernel._backward):
            fn.clear_cache()
    for kernel in ("apex_gdn_rule_fwd", "apex_gdn_rule_sweep",
                   "apex_gdn_rule_bwd"):
        assert f"{kernel}/pallas_call" in text, kernel


# ---------------------------------------------------------------------------
# flash attention in the projection's layout at the BERT cells' shapes (here
# because this is the one file that may load the TPU's compiler)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("batch,seq", [(112, 512), (544, 128)])
def test_mosaic_compiles_the_projection_flash_at_the_bert_cells(
        batch, seq, one_chip, monkeypatch):
    """``flash_attention_qkv`` and its gradient at ``bert_large.s512`` (b112 x
    S 512) and ``s128_b544`` (b544 x S 128), 16 heads of 64: both packed
    kernels compile under Mosaic for a described v5e — two heads a block
    doubles every stream of a step, which the packed VMEM model counts and
    keeps within the budget — and the compiled program holds no
    (B, S, H, 64) transpose: nothing around the kernels but the seed."""
    from apex_tpu.contrib.multihead_attn import flash as F
    monkeypatch.setattr(F, "_interpret", lambda: False)
    assert F._packed_tile(seq, 16, 64, 2, False) == (seq, seq)
    assert F.vmem_estimate(seq, seq, 128, 2, False, "packed") \
        <= F._vmem_budget()
    qkv = jax.ShapeDtypeStruct((batch, seq, 3 * 1024), jnp.bfloat16,
                               sharding=one_chip)
    bias = jnp.zeros((1, 1, seq), jnp.float32)
    with jax.default_matmul_precision("default"):
        text = jax.jit(jax.value_and_grad(
            lambda x: jnp.sum(F.flash_attention_qkv(
                x, bias, 0, False, 0.0, 16).astype(jnp.float32)))).lower(
                    qkv).compile().as_text()
    for kernel in ("apex_flash_fwd", "apex_flash_bwd_fused"):
        assert f"{kernel}/pallas_call" in text, kernel
    assert not re.search(r"= \S+\[\d+,\d+,16,64\]", text)
    assert f"f32[{batch},8,2,{seq}]" in text          # lse: rows, not columns


#: every (S, dtype, bias over queries too) at which a pair of heads of 64
#: reads the projection in place: the packed backward's VMEM model within
#: the budget
PACKED_SHAPES = [(s, dt, per_q) for s in (128, 256, 384)
                 for dt in (jnp.bfloat16, jnp.float32)
                 for per_q in (False, True)] + [(512, jnp.bfloat16, False)]


def test_the_projection_layout_admits_only_the_compiled_shapes():
    """The layout engages at the shapes below and at no other length, width
    or bias up to S 1152 — each of them is compiled next."""
    from apex_tpu.contrib.multihead_attn import flash as F
    admitted = [(s, dt, per_q) for s in range(128, 1153, 128)
                for dt in (jnp.bfloat16, jnp.float32)
                for per_q in (False, True)
                if F._packed_tile(s, 2, 64, jnp.dtype(dt).itemsize,
                                  per_q) is not None]
    assert admitted == PACKED_SHAPES


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("seq,dtype,per_q", PACKED_SHAPES)
def test_mosaic_fits_the_projection_flash_in_its_vmem_model(
        seq, dtype, per_q, causal, one_chip, monkeypatch):
    """Each admitted shape, with dropout (its hash's tiles are the most the
    kernel holds): the packed forward and backward compile for a described
    v5e, the backward told it may use no more VMEM than the ``"packed"``
    model of ``vmem_estimate`` says it needs — the model is an upper
    bound, and what admits a shape is what Mosaic takes."""
    from apex_tpu.contrib.multihead_attn import flash as F
    monkeypatch.setattr(F, "_interpret", lambda: False)
    esz = jnp.dtype(dtype).itemsize
    limit = F.vmem_estimate(seq, seq, 128, esz, per_q, "packed")
    real = F._compiler_params

    def capped(semantics, vmem_limit_bytes=None):
        if tuple(semantics) == ("arbitrary",):      # the packed backward
            vmem_limit_bytes = limit
        return real(semantics, vmem_limit_bytes)
    monkeypatch.setattr(F, "_compiler_params", capped)
    qkv = jax.ShapeDtypeStruct((1, seq, 3 * 128), dtype, sharding=one_chip)
    bias = jnp.zeros((1, seq if per_q else 1, seq), jnp.float32)
    with jax.default_matmul_precision("default"):
        text = jax.jit(jax.value_and_grad(
            lambda x: jnp.sum(F.flash_attention_qkv(
                x, bias, 0, causal, 0.1, 2).astype(jnp.float32)))).lower(
                    qkv).compile().as_text()
    for kernel in ("apex_flash_fwd", "apex_flash_bwd_fused"):
        assert f"{kernel}/pallas_call" in text, kernel
    assert f"f32[1,1,2,{seq}]" in text                 # the packed lse


# ---------------------------------------------------------------------------
# the replicated LAMB update by the chip's own compiler (here because this is
# the one file that may load the TPU's compiler: a second file can go to
# another worker, whose fixture would then skip in silence)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layers,most", [(24, 55.0), (4, 66.0)])
def test_the_replicated_update_moves_a_leaf_twice_and_keeps_no_scratch(
        layers, most, one_chip):
    """``amp.amp_step`` for amp O5 + per-leaf ``FusedLAMB`` — what
    ``run_standard`` builds — lowered for a described v5e on BERT-large's
    tree (334 M parameters at 24 stacked layers, the benchmark's; 82 M at
    four), bfloat16 gradients: the compiler's ``bytes accessed`` a parameter
    and its scratch.  The fusions move 46 B: the finite check and the clip's
    norm read g (2 + 2), a leaf's two norms come from ONE read of g, p, m, v
    (14), the apply reads them again and writes p, m, v and the bfloat16
    copy (14 + 14), with amp's skip select inside it.  The rest is the
    compiler's own prefetch of an operand into fast memory, counted as a
    read and a write: 3 B where a leaf is 24 layers, 17 B at four, where
    every leaf fits.  The flat engine read 100 / 102 B here and kept 8.7 /
    8.9 B a parameter of scratch (PERF.md section 6, PR 37): packing a tiled
    leaf into a flat buffer is a relayout each way."""
    from apex_tpu import amp
    from apex_tpu.models import bert_large_config, transformer_init
    from apex_tpu.optimizers import FusedLAMB
    cfg = dataclasses.replace(bert_large_config(), num_layers=layers)
    opt = FusedLAMB(lr=1e-3, weight_decay=0.01, max_grad_norm=1.0, impl="xla")
    state = jax.eval_shape(
        lambda key: amp.initialize(transformer_init(key, cfg), opt,
                                   opt_level="O5", verbosity=0),
        jax.random.PRNGKey(0))
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    state, grads = on_chip(state), on_chip(state.model_params)
    n = sum(x.size for x in jax.tree_util.tree_leaves(grads))
    compiled = jax.jit(amp.amp_step, donate_argnums=0).lower(
        state, grads).compile()
    accessed = compiled.cost_analysis()["bytes accessed"] / n
    scratch = compiled.memory_analysis().temp_size_in_bytes / n
    assert 44.0 <= accessed <= most, accessed
    assert scratch < 1.0, scratch
