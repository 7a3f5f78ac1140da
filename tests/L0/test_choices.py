"""One place each choice is made: the table of built-in choices.

Every kernel / scheme / mode choice in the library is ``argument > env pin
> built-in``, and the built-in is a constant or a rule of the shape or the
platform.  This file pins that table:

  * ``test_cell_choice``: for the attention shapes of the benchmark's
    transformer cells (read from ``benchmarks/``, read-only) the flash
    forward tile, the backward path and tile as TRACED (nothing executed),
    the backward route and the xentropy ``auto`` choice on either platform;
  * ``test_cell_buffer``: for the cells whose model routes, the rows of the
    expert dispatch buffer the rule gives (``parallel.expert.buffer_rows``);
  * ``test_cell_token_sum``: for the same cells, the form of the sum back
    to the tokens (``parallel.expert.sums_in_row_space``: the one that moves
    fewer rows) and the rows its gathers write, as TRACED;
  * ``test_builtin_choice``: the cell-independent choosers, once each;
  * ``test_stale_profile_is_ignored``: a ``tuned_defaults.json`` left over
    from the retired measured-tuning loop, naming the OTHER value for every
    chooser, changes no answer — nothing reads it;
  * ``test_env_pin_beats_builtin`` / ``test_argument_beats_env``: the two
    rungs left, for every selecting ``APEX_TPU_*`` name;
  * ``test_env_name_is_documented``: every ``APEX_TPU_*`` name the library
    reads is a row of the one options table in ``docs/performance.md``, and
    the set equals the list below — it can only change knowingly.
"""
import functools
import importlib
import json
import math
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu import amp
from apex_tpu.contrib.multihead_attn import flash as F
from apex_tpu.contrib.multihead_attn.flash import flash_attention
from apex_tpu.contrib.optimizers import (DistributedFusedAdam,
                                         DistributedFusedLAMB)
from apex_tpu.contrib.xentropy import softmax_xentropy as sx
from apex_tpu.mlp import MLP
from apex_tpu.models import (Glm4MoeLiteConfig, LagunaConfig, Lfm2Config,
                             NemotronHConfig, Qwen3NextConfig,
                             TransformerConfig, bert_large_config,
                             laguna_s_2_1_config, lfm2_cut_layer_types)
from apex_tpu.optimizers import FusedAdam
from apex_tpu.parallel import collectives, expert, overlap
from apex_tpu.parallel.mesh import create_mesh, use_mesh
from apex_tpu.parallel import plan as planmod
from apex_tpu.parallel import weight_update as wu
from apex_tpu.telemetry import MemorySink, Registry, events

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: every ``APEX_TPU_*`` literal under ``apex_tpu/`` ...
LITERAL_ENV_NAMES = (
    "APEX_TPU_CEILINGS", "APEX_TPU_COLLECTIVES", "APEX_TPU_CONTROL",
    "APEX_TPU_COORDINATOR_ADDRESS", "APEX_TPU_FAULTS",
    "APEX_TPU_FLASH_BLOCK_K", "APEX_TPU_FLASH_BLOCK_Q",
    "APEX_TPU_FLASH_BWD_BLOCK_K", "APEX_TPU_FLASH_BWD_BLOCK_Q",
    "APEX_TPU_FLASH_BWD_FUSE", "APEX_TPU_FLASH_BWD_FUSE_MB",
    "APEX_TPU_FLASH_BWD_IMPL", "APEX_TPU_FLASH_VMEM_MB", "APEX_TPU_GUARD",
    "APEX_TPU_METRICS_PORT", "APEX_TPU_NUM_PROCESSES", "APEX_TPU_OVERLAP",
    "APEX_TPU_OVERLAP_FRACTION", "APEX_TPU_PROCESS_ID",
    "APEX_TPU_TELEMETRY", "APEX_TPU_TELEMETRY_MEM", "APEX_TPU_TRACE",
    "APEX_TPU_UPDATE_SHARDING", "APEX_TPU_XENT_IMPL",
)
#: ... and the four ``flash._chosen_blocks`` builds with an f-string
BUILT_ENV_NAMES = (
    "APEX_TPU_FLASH_BWD_DQ_BLOCK_Q", "APEX_TPU_FLASH_BWD_DQ_BLOCK_K",
    "APEX_TPU_FLASH_BWD_DKV_BLOCK_Q", "APEX_TPU_FLASH_BWD_DKV_BLOCK_K",
)
ENV_NAMES = LITERAL_ENV_NAMES + BUILT_ENV_NAMES


@pytest.fixture(autouse=True)
def no_ambient_choice(monkeypatch):
    """The built-in end of every chain: no ambient pin, live override,
    amp-level default or leftover profile path stands in front of it."""
    for name in ENV_NAMES + ("APEX_TPU_TUNING_FILE",):
        monkeypatch.delenv(name, raising=False)
    prev_live = collectives.set_live_spec(None)
    prev_bwd = F._DEFAULT_BACKWARD
    F.set_default_backward("auto")
    yield
    collectives.set_live_spec(prev_live)
    F.set_default_backward(prev_bwd)


@pytest.fixture
def flash_events():
    reg = Registry(sink=MemorySink(), flush_interval=0, rank0_only=False)
    prev = events.set_default(reg)
    yield reg
    events.set_default(prev)


# ---------------------------------------------------------------------------
# the cells
# ---------------------------------------------------------------------------

def _read(*rel):
    with open(os.path.join(ROOT, *rel)) as f:
        return json.load(f)


def _attention_cells():
    """{cell: (BH, S, D, dtype, causal)} for the benchmark's transformer
    cells, as ``benchmarks/jobs/*`` derive them: the per-chip batch times
    the query heads, the traffic's sequence, the config's head width.
    Both cells' models hand flash a (1, 1, S) zero bias (no padding mask,
    no dropout)."""
    cells = {}
    for w in _read("BENCHMARK.json")["workloads"]:
        cfg = _read("benchmarks", "configs", w["config"] + ".json")
        traffic = _read("benchmarks", "workloads", w["name"] + ".json")
        model = cfg["model"]
        if cfg["job"] == "bert_pretrain":
            heads, width, causal = (model["num_heads"], model["d_model"],
                                    bert_large_config().causal)
        elif cfg["job"] == "lfm2_pretrain":
            heads, width, causal = (model["num_attention_heads"],
                                    model["hidden_size"], True)
        elif cfg["job"] == "nemotron_h_pretrain":
            heads = model["attention_heads_held"][1]
            width, causal = heads * model["head_dim"], True
        elif cfg["job"] == "qwen3_next_pretrain":
            heads = model["num_attention_heads"]
            width, causal = heads * model["head_dim"], True
        elif cfg["job"] == "glm4_moe_lite_pretrain":
            # latent attention: the QK head (nope + rope) is as wide as V's
            heads = model["num_attention_heads"]
            width, causal = heads * model["v_head_dim"], True
        elif cfg["job"] == "laguna_pretrain":
            # the full layers' causal calls (the window layers' band:
            # ``test_window_choice``)
            heads = model["num_attention_heads_per_layer"][
                model["layer_types"].index("full_attention")]
            width, causal = heads * model["head_dim"], True
        else:
            continue                    # resnet50: no attention
        cells[w["name"]] = (traffic["batch"] // w["chips"] * heads,
                            traffic["seq"], width // heads, cfg["dtype"],
                            causal)
    return cells


CELLS = _attention_cells()

#: cell -> (forward tile, (backward path, bq, bk, nk)): the parent's, which
#: PRs 26 and 28 chose from the shape (PERF.md section 6)
CELL_TILES = {
    "bert_large.s512": ((512, 512), ("whole_key", 512, 512, 1)),
    "bert_large.s128": ((128, 128), ("whole_key", 128, 128, 1)),
    "bert_large.s512_b8": ((512, 512), ("whole_key", 512, 512, 1)),
    "bert_large.dp4_s512": ((512, 512), ("whole_key", 512, 512, 1)),
    "lfm2_24b_a2b.ep8_s4096": ((512, 1024), ("resident", 512, 512, 8)),
    "bert_large.s512_b136": ((512, 512), ("whole_key", 512, 512, 1)),
    "nemotron3_super_120b_a12b.tp8_ep64_s8192": (
        (512, 1024), ("resident", 512, 512, 16)),     # BH 8, D 128
    "bert_large.s128_b544": ((128, 128), ("whole_key", 128, 128, 1)),
    "qwen3_next_80b_a3b.ep16_s4096": (
        (512, 1024), ("resident", 512, 512, 8)),      # BH 128, D 256
    "glm47_flash_30b_a3b.ep8_s4096": (
        (512, 1024), ("resident", 512, 512, 8)),      # BH 80, D 256
    "laguna_s_2_1.ep32_s8192": (
        (512, 1024), ("resident", 512, 512, 16)),     # BH 48, D 128
}


def _expert_cells():
    """{cell: (tokens a chip, top_k, experts, held)} for the cells whose
    model routes, from the same files."""
    cells = {}
    for w in _read("BENCHMARK.json")["workloads"]:
        model = _read("benchmarks", "configs", w["config"] + ".json")["model"]
        experts = model.get("num_experts", model.get("n_routed_experts"))
        if experts is None:
            continue
        traffic = _read("benchmarks", "workloads", w["name"] + ".json")
        cells[w["name"]] = (
            traffic["batch"] // w["chips"] * traffic["seq"],
            model["num_experts_per_tok"], experts, model["experts_held"][1])
    return cells


EXPERT_CELLS = _expert_cells()

#: cell -> (rows of the dispatch buffer, of the T·k assignments): the rule
#: of ``parallel.expert.buffer_rows`` — twice the held experts' even share
CELL_BUFFERS = {
    "lfm2_24b_a2b.ep8_s4096": (32768, 131072),
    "nemotron3_super_120b_a12b.tp8_ep64_s8192": (11264, 360448),
    "qwen3_next_80b_a3b.ep16_s4096": (40960, 327680),
    "glm47_flash_30b_a3b.ep8_s4096": (16384, 65536),
    "laguna_s_2_1.ep32_s8192": (5120, 81920),
}


#: cell -> (the sum back to the tokens is taken in row space, rows the
#: gathers of ONE sum write): ``parallel.expert.sums_in_row_space`` — in row
#: space the buffer's rows sorted by token and a row a token, where that
#: moves fewer rows than k gathers of T; a gather of T rows a slot where the
#: buffer is as long as the tokens
CELL_SUM_ROWS = {
    "lfm2_24b_a2b.ep8_s4096": (False, 131072),
    "nemotron3_super_120b_a12b.tp8_ep64_s8192": (True, 27648),
    # 10 · 40 960 + 32 768 > 327 680: ten gathers of T rows a sum
    "qwen3_next_80b_a3b.ep16_s4096": (False, 327680),
    # LFM2's experts at half its tokens: 4 · 16 384 + 16 384 > 65 536
    "glm47_flash_30b_a3b.ep8_s4096": (False, 65536),
    # 5120 + 8192 rows in row space against ten gathers of 8192
    "laguna_s_2_1.ep32_s8192": (True, 13312),
}


def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk_eqns(sub)


def _traced_kernels(BH, S, D, dtype, causal, grad):
    """{kernel name: grid} of flash_attention (or its gradient) traced at
    the cell's shape with every choice left to the library."""
    q = jax.ShapeDtypeStruct((BH, S, D), jnp.dtype(dtype))
    bias = jnp.zeros((1, 1, S), jnp.float32)

    def fwd(q, k, v):
        # the bias is (1, 1, S): ``heads`` only has to divide BH
        return flash_attention(q, k, v, bias, 0, causal, 0.0,
                               math.gcd(BH, 16), "auto")

    fn = fwd if not grad else jax.grad(
        lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))
    jaxpr = jax.make_jaxpr(fn)(q, q, q)
    return {e.params["name"]: tuple(e.params["grid_mapping"].grid)
            for e in _walk_eqns(jaxpr.jaxpr)
            if e.primitive.name == "pallas_call"}


def _xent_choice(monkeypatch, impl="auto"):
    """Which implementation xentropy hands the logits to: the two are
    replaced by markers, nothing is computed."""
    monkeypatch.setattr(sx, "_xent_fwd_pallas", lambda *a: ("pallas", None))
    monkeypatch.setattr(sx, "_xent_fwd_xla", lambda *a: ("xla", None))
    return sx._fwd(None, None, 0.0, impl)[0]


def _xent_auto_choice(monkeypatch, backend):
    """... under ``impl="auto"`` with the backend reading ``backend``."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    return _xent_choice(monkeypatch)


CELL_CHOOSERS = ("fwd_tile", "bwd_tile", "bwd_kernels", "bwd_impl",
                 "xent_on_tpu", "xent_on_cpu")


def test_the_cell_table_covers_the_benchmark():
    assert set(CELLS) == set(CELL_TILES)


def test_the_widest_head_leaves_the_resident_backward_at_8k():
    """D 256 (no other configuration's head is wider than 128): at S 4096 a
    head's K, V and float32 dk / dv fit the ``resident`` backward's budget on
    512 x 512 tiles; from S 8192 they do not, and the backward falls to the
    128 x 128 ``split`` pair PR 28 measured ten times slower — why the
    Qwen3-Next cell runs at S 4096 (ROADMAP W5)."""
    assert F._resident_blocks(4096, 4096, 256, 2, False) == (512, 512)
    assert F._resident_blocks(8192, 8192, 256, 2, False) is None
    assert F._resident_blocks(8192, 8192, 128, 2, False) == (512, 512)


def test_the_buffer_table_covers_the_routed_cells():
    assert set(EXPERT_CELLS) == set(CELL_BUFFERS) == set(CELL_SUM_ROWS)


@pytest.mark.parametrize("cell", sorted(CELL_BUFFERS))
def test_cell_buffer(cell):
    tokens, top_k, experts, held = EXPERT_CELLS[cell]
    assert (expert.buffer_rows(tokens, top_k, experts, held),
            tokens * top_k) == CELL_BUFFERS[cell]
    # every expert held: the buffer is every assignment, one walk always
    assert expert.buffer_rows(tokens, top_k, experts, experts) \
        == tokens * top_k


@pytest.mark.parametrize("cell", sorted(CELL_SUM_ROWS))
def test_cell_token_sum(cell, flash_events):
    """Traced at the cell's tokens, top-k, experts and share (abstract, the
    widths small: they choose nothing): the form the rule of shapes gives,
    the layout event's ``sum_rows``, and the row gathers of a forward walk
    themselves — the buffer's rows of the input, then either the same rows
    sorted by token and one row a token, or T rows a slot."""
    tokens, top_k, experts, held = EXPERT_CELLS[cell]
    in_rows, sum_rows = CELL_SUM_ROWS[cell]
    buffer = CELL_BUFFERS[cell][0]
    assert expert.sums_in_row_space(tokens, top_k, held, buffer) == in_rows
    # every expert held: the buffer is every assignment, a slot at a time
    assert not expert.sums_in_row_space(tokens, top_k, experts,
                                        tokens * top_k)
    d, r, f = 16, 24, 8
    sds = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(lambda *a: expert.routed_experts(
        a[0], a[1], jnp.zeros((experts,)), a[3], a[4], top_k=top_k,
        form="relu2", rows=a[2], axis_name=None)[0])(
        sds((tokens, d), jnp.bfloat16), sds((d, experts), jnp.float32),
        sds((tokens, r), jnp.bfloat16), sds((held, r, f), jnp.bfloat16),
        sds((held, f, r), jnp.bfloat16))
    layout, = [rec["fields"] for rec in flash_events.flush()
               if rec.get("name") == "moe.layout"]
    assert (layout["buffer_rows"], layout["sum_rows"]) == (buffer, sum_rows)
    assert sum_rows == (buffer + tokens if in_rows else tokens * top_k)
    written = sorted(e.outvars[0].aval.shape[0]
                     for e in _walk_eqns(jaxpr.jaxpr)
                     if e.primitive.name == "gather"
                     and e.outvars[0].aval.shape[1:] == (r,))
    assert written == sorted([buffer] + (
        [buffer, tokens] if in_rows else [tokens] * top_k))


@pytest.mark.parametrize("chooser", CELL_CHOOSERS)
@pytest.mark.parametrize("cell", sorted(CELL_TILES))
def test_cell_choice(cell, chooser, monkeypatch, flash_events):
    BH, S, D, dtype, causal = CELLS[cell]
    (fbq, fbk), (path, bq, bk, nk) = CELL_TILES[cell]
    if chooser == "fwd_tile":
        grid = _traced_kernels(BH, S, D, dtype, causal, grad=False)
        assert grid == {"apex_flash_fwd": (BH, S // fbq, S // fbk)}
    elif chooser == "bwd_tile":
        sds = jax.ShapeDtypeStruct
        x = sds((BH, S, D), jnp.dtype(dtype))
        jax.eval_shape(
            lambda q, k, v, bias, out, lse, do: F._flash_bwd(
                q, k, v, bias, causal, 0.0, 0, math.gcd(BH, 16), out, lse,
                do),
            x, x, x, sds((1, 1, S), jnp.float32), x,
            sds((BH, S, 1), jnp.float32), x)
        ev = [r["fields"] for r in flash_events.flush()
              if r.get("name") == "flash.bwd"]
        assert ev == [{"path": path, "bq": bq, "bk": bk, "nk": nk}]
    elif chooser == "bwd_kernels":
        # ONE fused kernel writes dq, dk and dv; no dq / dkv pair
        grids = _traced_kernels(BH, S, D, dtype, causal, grad=True)
        assert set(grids) == {"apex_flash_fwd", "apex_flash_bwd_fused"}
        want = (BH, S // bq) if path == "resident" else (BH, nk, S // bq)
        assert grids["apex_flash_bwd_fused"] == want
    elif chooser == "bwd_impl":
        assert F._resolve_backward("auto") == "pallas"
    else:
        backend = chooser[len("xent_on_"):]
        want = "pallas" if backend == "tpu" else "xla"
        assert _xent_auto_choice(monkeypatch, backend) == want


#: (BH, S, D, causal) -> {kernel: grid} of ``flash_attention`` and its
#: gradient with no window: BERT's S 512 on the (B·H, S, D) entry, the
#: hybrids' S 4096 at D 64 (LFM2) and S 8192 at D 128 (Nemotron-H, and the
#: Laguna cell's full layers at BH 48) — what they lowered to before the
#: band existed
NO_WINDOW = {
    (1792, 512, 64, False): {"apex_flash_fwd": (1792, 1, 1),
                             "apex_flash_bwd_fused": (1792, 1, 1)},
    (256, 4096, 64, True): {"apex_flash_fwd": (256, 8, 4),
                            "apex_flash_bwd_fused": (256, 8)},
    (8, 8192, 128, True): {"apex_flash_fwd": (8, 16, 8),
                           "apex_flash_bwd_fused": (8, 16)},
    (48, 8192, 128, True): {"apex_flash_fwd": (48, 16, 8),
                            "apex_flash_bwd_fused": (48, 16)},
}


def _grad_kernels(BH, S, D, causal, dtype=jnp.bfloat16, **kw):
    x = jax.ShapeDtypeStruct((BH, S, D), dtype)
    bias = kw.pop("bias", jnp.zeros((1, 1, S), jnp.float32))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash_attention(q, k, v, bias, 0, causal, heads=BH,
                                        **kw).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))(x, x, x)
    return {e.params["name"]: tuple(e.params["grid_mapping"].grid)
            for e in _walk_eqns(jaxpr.jaxpr)
            if e.primitive.name == "pallas_call"}


@pytest.mark.parametrize("shape", sorted(NO_WINDOW))
def test_no_window_lowers_to_the_causal_kernels_as_before(shape,
                                                          flash_events):
    """``window=None`` (the default, or passed) leaves every grid, tile and
    name as it was: the same kernels, the same grids, the same backward
    path."""
    assert _grad_kernels(*shape) == _grad_kernels(*shape, window=None) \
        == NO_WINDOW[shape]
    paths = {r["fields"]["path"] for r in flash_events.flush()
             if r.get("name") == "flash.bwd"}
    assert paths == {"whole_key" if shape[1] == 512 else "resident"}


def test_window_choice(flash_events):
    """The Laguna cell's window layers: BH 72, S 8192, D 128, bfloat16, W
    512 — the band's grids, two key blocks a q block and two q blocks a key
    block, on 512 x 512 tiles, path ``window``; no causal kernel."""
    assert _grad_kernels(72, 8192, 128, True, window=512) == {
        "apex_flash_window_fwd": (72, 16, 2),
        "apex_flash_window_bwd_dq": (72, 16, 2),
        "apex_flash_window_bwd_dkv": (72, 16, 2)}
    ev = [r["fields"] for r in flash_events.flush()
          if r.get("name") == "flash.bwd"]
    assert ev == [{"path": "window", "bq": 512, "bk": 512, "nk": 16}]


@pytest.mark.parametrize("what,kw", [
    ("window 256", dict(window=256)),
    ("window 1024", dict(window=1024)),
    ("D 64", dict(D=64)),
    ("float32", dict(dtype=jnp.float32)),
    ("S 8320", dict(S=8320)),
    ("dropout", dict(dropout_rate=0.1)),
    ("a bias a query", dict(bias=jnp.zeros((1, 8192, 8192), jnp.float32))),
])
def test_the_band_kernels_refuse_what_they_were_not_measured_at(
        what, kw, flash_events):
    """Only the shape the band kernels compiled and ran at on the chip
    (``flash._BAND_SHAPES``: W 512, D 128, bfloat16, whole 512-blocks, a
    bias over keys, no dropout) takes them; any other window takes the XLA
    band path — no Pallas kernel at all, backward ``xla``."""
    shape = dict(BH=8, S=8192, D=128, causal=True, window=512)
    shape.update(kw)
    assert _grad_kernels(**shape) == {}, what
    ev = [r["fields"] for r in flash_events.flush()
          if r.get("name") == "flash.bwd"]
    assert ev == [{"path": "xla"}], what


#: the cells whose model reads q, k and v from the projection
#: (``transformer._attention`` -> ``flash_attention_qkv``): BERT's 16 heads
#: of 64.  The hybrids call ``flash_attention`` on (B·H, S, D) themselves.
BERT_HEADS = 16


@pytest.mark.parametrize("cell", sorted(CELL_TILES))
def test_cell_layout(cell, flash_events):
    """Every BERT cell's shape takes the projection layout — the packed
    forward on a (B·H/2, 1, 1) grid, the packed backward on (B·H/2,), its
    path recorded as ``projection`` at the whole-key tile — and no
    hybrid cell's shape would, so their (B·H, S, D) entry is untouched."""
    BH, S, D, dtype, causal = CELLS[cell]
    esz = jnp.dtype(dtype).itemsize
    if not cell.startswith("bert_large."):
        assert F._packed_tile(S, 2, D, esz, False) is None
        return
    B = BH // BERT_HEADS
    qkv = jax.ShapeDtypeStruct((B, S, 3 * BERT_HEADS * D), jnp.dtype(dtype))
    bias = jnp.zeros((1, 1, S), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(lambda x: F.flash_attention_qkv(
        x, bias, 0, causal, 0.0, BERT_HEADS).astype(jnp.float32).sum()))(qkv)
    grids = {e.params["name"]: tuple(e.params["grid_mapping"].grid)
             for e in _walk_eqns(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"}
    assert grids == {"apex_flash_fwd": (BH // 2, 1, 1),
                     "apex_flash_bwd_fused": (BH // 2,)}
    ev = [r["fields"] for r in flash_events.flush()
          if r.get("name") == "flash.bwd"]
    assert ev == [{"path": "projection", "bq": S, "bk": S, "nk": 1}]


# ---------------------------------------------------------------------------
# the update's path: leaf by leaf where it is replicated, flat where sharded
# ---------------------------------------------------------------------------

#: the smallest model of each configuration's type, through the builder its
#: cells' job calls: ``run_standard`` for the five ``*_pretrain`` jobs
_TINY_MODELS = {
    "bert_pretrain": lambda: TransformerConfig(
        vocab_size=128, max_len=16, num_layers=2, d_model=32, num_heads=2,
        d_ff=64),
    "lfm2_pretrain": lambda: Lfm2Config(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=16, num_experts=8, num_experts_per_tok=2,
        num_dense_layers=1, layer_types=lfm2_cut_layer_types(1, 1),
        num_attention_heads=4, num_key_value_heads=2, experts_held=(0, 4),
        xent_impl="xla"),
    "nemotron_h_pretrain": lambda: NemotronHConfig(
        vocab_size=128, hidden_size=32, hybrid_override_pattern="ME*E",
        mamba_num_heads=4, mamba_head_dim=8, n_groups=2, ssm_state_size=8,
        conv_kernel=4, chunk_size=8, num_attention_heads=4,
        num_key_value_heads=2, head_dim=8, n_routed_experts=8,
        num_experts_per_tok=2, moe_latent_size=16, moe_intermediate_size=16,
        moe_shared_expert_intermediate_size=32, mamba_heads_held=(0, 4),
        attention_heads_held=(0, 4), experts_held=(0, 4), xent_impl="xla"),
    "qwen3_next_pretrain": lambda: Qwen3NextConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=4,
        num_attention_heads=2, num_key_value_heads=1, head_dim=16,
        linear_num_key_heads=2, linear_key_head_dim=8,
        linear_num_value_heads=2, linear_value_head_dim=8, chunk_size=8,
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=16,
        shared_expert_intermediate_size=16, experts_held=(0, 4),
        xent_impl="xla"),
    "glm4_moe_lite_pretrain": lambda: Glm4MoeLiteConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=2,
        intermediate_size=64, num_attention_heads=2, q_lora_rank=16,
        kv_lora_rank=8, qk_nope_head_dim=12, qk_rope_head_dim=4,
        v_head_dim=16, num_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=16, experts_held=(0, 4), xent_impl="xla"),
    "laguna_pretrain": lambda: LagunaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        layer_types=laguna_s_2_1_config(5).layer_types,
        mlp_layer_types=("dense",) + ("sparse",) * 4,
        num_attention_heads_per_layer=(2, 4, 4, 4, 2), num_key_value_heads=2,
        head_dim=16, sliding_window=8, num_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=16, shared_expert_intermediate_size=16,
        experts_held=(0, 4), xent_impl="xla"),
}


@functools.lru_cache(maxsize=None)
def _job_state(job):
    """The amp state a cell's job steps, at the smallest size: what
    ``examples/bert/pretrain.run_standard`` builds for the model's type,
    and for ResNet what ``examples/imagenet/main_amp.py`` and its adapter
    both build — per-leaf ``FusedAdam`` under the configured opt level."""
    if job == "resnet_train":
        return amp.initialize(
            {"w": jnp.ones((4, 8)), "b": jnp.zeros((8,))}, FusedAdam(lr=0.1),
            opt_level=_read("benchmarks", "configs",
                            "resnet50.json")["amp_opt_level"], verbosity=0)
    spec = importlib.util.spec_from_file_location(
        "pretrain_for_choices", os.path.join(ROOT, "examples", "bert",
                                             "pretrain.py"))
    pretrain = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pretrain)
    mesh = create_mesh({"data": 1}, devices=jax.devices()[:1])
    with use_mesh(mesh):
        state, _ = pretrain.run_standard(pretrain.parse_args([]),
                                         _TINY_MODELS[job](), mesh)
    return state


def _update_paths(reg, fn, *args):
    """``{path: times taken}`` by the ``optimizer.update_path.*`` counters
    of tracing ``fn`` — nothing runs; a fresh function each time, or jax
    answers from its cache of traces."""
    jax.eval_shape(lambda *a: fn(*a), *args)
    prefix = "optimizer.update_path."
    return {k[len(prefix):]: v for k, v in reg.read().items()
            if k.startswith(prefix)}


@pytest.mark.parametrize("cell", sorted(
    w["name"] for w in _read("BENCHMARK.json")["workloads"]))
def test_cell_update_path(cell, flash_events):
    """Every cell's update is replicated, so every cell takes it leaf by
    leaf: masters and moments are trees shaped like the parameters, no flat
    buffer exists in the state, and the traced ``amp_step`` says so."""
    config, = [w["config"] for w in _read("BENCHMARK.json")["workloads"]
               if w["name"] == cell]
    state = _job_state(_read("benchmarks", "configs",
                             config + ".json")["job"])
    assert getattr(state.opt_state, "master", None) is None
    shapes = lambda t: [x.shape for x in jax.tree_util.tree_leaves(t)]
    assert shapes(state.master_params) == shapes(state.model_params) \
        == shapes(state.opt_state.m) == shapes(state.opt_state.v)
    assert _update_paths(flash_events, amp.amp_step, state,
                         state.model_params) == {"leafwise": 1.0}


@pytest.mark.parametrize("sharded", [False, True],
                         ids=["amp_flat_state", "ShardedUpdate"])
def test_flat_states_take_the_flat_path(sharded, flash_events):
    """What slices one buffer keeps it: an amp state whose masters live flat
    in a ``impl="fused"`` optimizer's state, and ``ShardedUpdate``, whose
    replica holds a slice of every flat field."""
    params = {"w": jnp.ones((4, 8)), "b": jnp.zeros((8,))}
    if not sharded:
        state = amp.initialize(params, FusedAdam(lr=0.1, impl="fused"),
                               opt_level="O5", verbosity=0)
        assert state.master_params is None
        step, args = amp.amp_step, (state, state.model_params)
    else:
        su = wu.ShardedUpdate(FusedAdam(lr=0.1, impl="fused"),
                              axis_name="data")
        mesh = create_mesh({"data": 2}, devices=jax.devices()[:2])
        rep = jax.tree_util.tree_map(lambda _: P(), params)
        sspec = su.state_pspecs(params, 2)
        init = shard_map(su.init, mesh=mesh, in_specs=(rep,),
                         out_specs=sspec)
        step = shard_map(su.step, mesh=mesh, in_specs=(sspec, rep, rep),
                         out_specs=(rep, sspec))
        args = (jax.eval_shape(init, params), params, params)
    assert _update_paths(flash_events, step, *args) == {"flat": 1.0}


def test_an_unknown_update_path_is_refused(flash_events):
    with pytest.raises(ValueError, match="leafwise"):
        events.record_update_path("per_leaf")


# ---------------------------------------------------------------------------
# the cell-independent choosers
# ---------------------------------------------------------------------------

def _layer_norm_default(monkeypatch):
    from apex_tpu.ops import layer_norm as ln_ops
    # the module, not the function the package re-exports under its name
    fln = importlib.import_module("apex_tpu.normalization.fused_layer_norm")
    monkeypatch.setattr(ln_ops, "layer_norm_pallas", lambda *a: "pallas")
    monkeypatch.setattr(fln, "_fused_layer_norm_affine_xla",
                        lambda *a: "xla")
    return fln.fused_layer_norm_affine(None, None, None, 8,
                                       use_pallas=None)


def _plan_overlap_factor(monkeypatch):
    prof = planmod.ModelProfile(
        name="oracle", flops=1e12, bytes_accessed=1e11,
        params_bytes=400 << 20, optimizer_bytes=800 << 20,
        activations_bytes=1 << 30, batch_bytes=64 << 20,
        temps_bytes=1 << 28, output_bytes=4096)
    for scheme in planmod.PLAN_SCHEMES:
        p = planmod.predict(prof, planmod.Plan(dp=8,
                                               collective_scheme=scheme),
                            platform="tpu_v5e")
        assert p.breakdown["overlap_fraction"] == 1.0, scheme
    return planmod.resolve_overlap_fraction()


#: chooser -> (ask(monkeypatch) -> answer, the built-in answer)
BUILTINS = {
    "flash_bwd_impl": (lambda mp: F._resolve_backward("auto"), "pallas"),
    "flash_fwd_blocks": (lambda mp: F._clamp_blocks(
        None, None, 64, 2, False), (512, 1024)),
    "flash_bwd_blocks": (lambda mp: F._clamp_blocks(
        None, None, 64, 2, False, bwd="fused"), (128, 128)),
    "flash_bwd_dq_blocks": (lambda mp: F._clamp_blocks(
        None, None, 64, 2, False, bwd="dq", sq=4096, sk=4096), (128, 128)),
    "flash_bwd_dkv_blocks": (lambda mp: F._clamp_blocks(
        None, None, 64, 2, False, bwd="dkv", sq=4096, sk=4096), (128, 128)),
    "flash_bwd_whole_key": (lambda mp: F._clamp_blocks(
        None, None, 64, 2, False, bwd="fused", sq=512, sk=512), (512, 512)),
    "flash_bwd_fuse": (lambda mp: F._forced_fuse(None), None),
    "xent_auto_impl": (lambda mp: _xent_auto_choice(mp, "tpu"), "pallas"),
    "bert_attn_impl": (lambda mp: bert_large_config().attn_impl,
                       TransformerConfig().attn_impl),
    "layer_norm_use_pallas": (_layer_norm_default, "xla"),
    "mlp_use_pallas": (lambda mp: MLP([8, 8], use_pallas=None).use_pallas,
                       False),
    "zero_adam_impl": (lambda mp: DistributedFusedAdam(lr=1e-3).impl, "xla"),
    "zero_lamb_impl": (lambda mp: DistributedFusedLAMB(lr=1e-3).impl, "xla"),
    "ddp_collective_scheme": (lambda mp: collectives.resolve(None), None),
    "ddp_update_sharding": (lambda mp: wu.resolve_mode(None), "off"),
    "ddp_update_allgather_scheme": (lambda mp: wu.ShardedUpdate(
        FusedAdam(lr=1e-3, impl="fused"))._resolve_ag(), None),
    "ddp_overlap": (lambda mp: overlap.resolve_mode(None), "off"),
    "plan_overlap_fraction": (_plan_overlap_factor, 1.0),
}


@pytest.mark.parametrize("chooser", sorted(BUILTINS))
def test_builtin_choice(chooser, monkeypatch):
    ask, builtin = BUILTINS[chooser]
    assert ask(monkeypatch) == builtin


#: what ``tools/apply_perf_results.py`` could have written: for every key
#: the retired ``utils/tuning.SCHEMA`` had a consumer for, the value the
#: built-in is NOT
STALE_PROFILE = {
    "flash_block_q": 128, "flash_block_k": 128,
    "flash_bwd_block_q": 256, "flash_bwd_block_k": 256,
    "flash_bwd_dq_block_q": 64, "flash_bwd_dq_block_k": 256,
    "flash_bwd_dkv_block_q": 64, "flash_bwd_dkv_block_k": 256,
    "flash_bwd_impl": "xla", "flash_bwd_fuse": False,
    "xent_auto_impl": "xla", "bert_attn_impl": "fast",
    "layer_norm_use_pallas": True, "mlp_use_pallas": True,
    "zero_impl": "fused", "ddp_collective_scheme": "int8_blockscale",
    "collective_min_compress_bytes": 1,
    "ddp_update_sharding": "zero1",
    "ddp_update_allgather_scheme": "bf16", "ddp_overlap": "bucketed",
    "overlap_measured_fraction": 0.25, "overlap_fraction_fp32": 0.25,
    "overlap_fraction_bf16": 0.25, "overlap_fraction_int8_blockscale": 0.25,
}


@pytest.fixture
def stale_profile(tmp_path, monkeypatch):
    """The leftover profile at both places the retired loader looked, on
    what reads as a TPU (the only platform it applied a profile on)."""
    old_default = os.path.join(ROOT, "apex_tpu", "tuned_defaults.json")
    assert not os.path.exists(old_default)
    pinned = tmp_path / "tuned_defaults.json"
    pinned.write_text(json.dumps(STALE_PROFILE))
    monkeypatch.setenv("APEX_TPU_TUNING_FILE", str(pinned))
    jax.devices()                       # a backend is up, as at trace time
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with open(old_default, "w") as f:
        json.dump(STALE_PROFILE, f)
    try:
        yield
    finally:
        os.remove(old_default)


@pytest.mark.parametrize("chooser", sorted(BUILTINS))
def test_stale_profile_is_ignored(chooser, stale_profile, monkeypatch):
    ask, builtin = BUILTINS[chooser]
    assert ask(monkeypatch) == builtin


# ---------------------------------------------------------------------------
# the two rungs left: argument > env pin > built-in
# ---------------------------------------------------------------------------

def _blocks(bwd, **shape):
    def ask(bq=None, bk=None):
        return F._clamp_blocks(bq, bk, 64, 2, False, bwd=bwd, **shape)
    return ask


_S4096 = dict(sq=4096, sk=4096)
#: env name -> (value pinned, ask(monkeypatch) -> fn(*argument), answer
#: with nothing chosen, answer under the pin, an argument, answer with both)
PINS = {
    "APEX_TPU_FLASH_BWD_IMPL": (
        "xla", lambda mp: lambda b="auto": F._resolve_backward(b),
        "pallas", "xla", ("pallas",), "pallas"),
    "APEX_TPU_FLASH_BWD_FUSE": (
        "0", lambda mp: lambda f=None: F._forced_fuse(f),
        None, False, (True,), True),
    "APEX_TPU_FLASH_BLOCK_Q": (
        "64", lambda mp: _blocks(False), (512, 1024), (64, 1024),
        (256, None), (256, 1024)),
    "APEX_TPU_FLASH_BLOCK_K": (
        "256", lambda mp: _blocks(False), (512, 1024), (512, 256),
        (None, 512), (512, 512)),
    "APEX_TPU_FLASH_BWD_BLOCK_Q": (
        "64", lambda mp: _blocks("fused", **_S4096), (128, 128), (64, 128),
        (256, None), (256, 128)),
    "APEX_TPU_FLASH_BWD_BLOCK_K": (
        "256", lambda mp: _blocks("fused", **_S4096), (128, 128),
        (128, 256), (None, 512), (128, 512)),
    "APEX_TPU_FLASH_BWD_DQ_BLOCK_Q": (
        "64", lambda mp: _blocks("dq", **_S4096), (128, 128), (64, 128),
        (256, None), (256, 128)),
    "APEX_TPU_FLASH_BWD_DQ_BLOCK_K": (
        "256", lambda mp: _blocks("dq", **_S4096), (128, 128), (128, 256),
        (None, 512), (128, 512)),
    "APEX_TPU_FLASH_BWD_DKV_BLOCK_Q": (
        "64", lambda mp: _blocks("dkv", **_S4096), (128, 128), (64, 128),
        (256, None), (256, 128)),
    "APEX_TPU_FLASH_BWD_DKV_BLOCK_K": (
        "256", lambda mp: _blocks("dkv", **_S4096), (128, 128), (128, 256),
        (None, 512), (128, 512)),
    "APEX_TPU_XENT_IMPL": (
        "pallas", lambda mp: functools.partial(_xent_choice, mp),
        "xla", "pallas", ("xla",), "xla"),
    "APEX_TPU_COLLECTIVES": (
        "bf16", lambda mp: lambda s=None: getattr(
            collectives.resolve(s), "scheme", None),
        None, "bf16", ("int8_blockscale",), "int8_blockscale"),
    "APEX_TPU_UPDATE_SHARDING": (
        "zero1", lambda mp: wu.resolve_mode, "off", "zero1", ("off",),
        "off"),
    "APEX_TPU_OVERLAP": (
        "bucketed", lambda mp: overlap.resolve_mode, "off", "bucketed",
        ("off",), "off"),
    "APEX_TPU_OVERLAP_FRACTION": (
        "0.25", lambda mp: planmod.resolve_overlap_fraction, 1.0, 0.25,
        (0.5,), 0.5),
}


def test_every_selecting_name_has_its_rungs_tested():
    """The names that SELECT a path, tile or scheme (the rest of ENV_NAMES
    size a budget, switch a subsystem on or place a process)."""
    selecting = {n for n in ENV_NAMES if re.search(
        r"FLASH_(BWD_)?((DQ|DKV)_)?BLOCK|FLASH_BWD_(IMPL|FUSE)$|XENT_IMPL"
        r"|COLLECTIVES|UPDATE_SHARDING|OVERLAP", n)}
    assert selecting == set(PINS)


@pytest.mark.parametrize("name", sorted(PINS))
def test_env_pin_beats_builtin(name, monkeypatch):
    value, make, builtin, pinned, _, _ = PINS[name]
    ask = make(monkeypatch)
    assert ask() == builtin
    monkeypatch.setenv(name, value)
    assert ask() == pinned != builtin


@pytest.mark.parametrize("name", sorted(PINS))
def test_argument_beats_env(name, monkeypatch):
    value, make, _, pinned, argument, both = PINS[name]
    ask = make(monkeypatch)
    monkeypatch.setenv(name, value)
    assert ask(*argument) == both != pinned


# ---------------------------------------------------------------------------
# the names themselves
# ---------------------------------------------------------------------------

def _env_names_in_the_library():
    found = set()
    for dirpath, _, files in os.walk(os.path.join(ROOT, "apex_tpu")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(dirpath, name)) as f:
                    found.update(re.findall(r"APEX_TPU_[A-Z0-9_]*[A-Z0-9]",
                                            f.read()))
    return found


@functools.lru_cache(maxsize=None)
def _documented_env_names():
    """The first column of the one ``APEX_TPU_*`` table in
    docs/performance.md."""
    with open(os.path.join(ROOT, "docs", "performance.md")) as f:
        rows = [ln for ln in f if ln.startswith("| `APEX_TPU_")]
    return [re.match(r"\| `(APEX_TPU_[A-Z0-9_]+)`", ln).group(1)
            for ln in rows]


def test_the_env_name_list_is_the_library_s():
    """A name added to (or dropped from) the library must be added to (or
    dropped from) LITERAL_ENV_NAMES and the docs table with it.  The
    f-string stems ``flash._chosen_blocks`` builds from count as built."""
    found = {n for n in _env_names_in_the_library()
             if n != "APEX_TPU_FLASH_BWD"}      # the f-string's stem
    assert found == set(LITERAL_ENV_NAMES)
    documented = _documented_env_names()
    assert sorted(documented) == sorted(ENV_NAMES)      # once each


@pytest.mark.parametrize("name", ENV_NAMES)
def test_env_name_is_documented(name):
    assert name in _documented_env_names()
