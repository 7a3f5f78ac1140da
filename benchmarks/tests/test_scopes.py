"""Blocks, phases and shares read from the scopes of a small synthetic trace
with hand-computed answers, in ``test_reduce.py``'s style: one chip, the
instruction texts a "TPU v5 lite" trace has, the ``op_name`` paths jax 0.9.0
writes for the program's scopes (copied from the compiled BERT step).

One step of 1000 ns, traced twice (at 1000 and 2000) after a lead-in step:

    while.6                      0..600   self 10: the layer scan
      convolution_add_fusion.11    0..200   apex.mlp, forward, matmul
      apex_flash_fwd.13          200..300   apex.attn/apex.flash, recompute
      apex_flash_bwd_fused.10    300..450   apex.attn/apex.flash, backward
      reduce.5 (partial sums)    450..500   apex.attn/apex.flash, backward
      fusion.7 (a transpose)     500..590   apex.attn, backward
    convolution.2                600..700   apex.head, backward
    fusion.8 (xentropy)          700..740   apex.loss, forward
    all-reduce.1                 740..800   apex.ddp_allreduce
    fusion.9 (LAMB)              800..950   apex.amp_step/apex.opt_update
    copy.3                       950..1000  no scope

Busy 1000 of 1000 a step.

The paths reach the readers as they do on the chip: from the ``Hlo Proto``
that the ``.xplane.pb`` carries in its plane ``/host:metadata``, written here
byte by byte.  Two instructions are bare there, as XLA leaves its own: the
LAMB fusion (read by what it fuses) and the copy (outside every block,
whatever it copies).  The xentropy fusion carries a name XLA gave it
(``.../shard_map/convert.34``, as the four-chip step's update does) and is
read by what it fuses too; the forward matmul fuses instructions of the
update as well and keeps the path jax gave it.
"""
import os
import re

import pytest

from benchmarks import reduce, run, scopes
from benchmarks.job import load_module

import test_reduce as shapes

REAL = os.path.join(run.ROOT, "BENCHMARK.json")
HEAD = ('%convolution.2 = bf16[1024,30592]{1,0:T(8,128)(2,1)} convolution('
        'bf16[96,512,1024]{2,1,0} %fusion.3, bf16[96,512,30592]{2,1,0} '
        '%fusion.4), window={size=96}, dim_labels=0fb_0io->bf0')
PARTIALS = ('%reduce.5 = f32[1536,512,64]{2,1,0:T(8,128)} reduce(f32[1536,4,'
            '512,64]{3,2,1,0:T(8,128)} %get-tuple-element.7, f32[]{:T(128)} '
            '%constant.1), dimensions={1}, to_apply=%add.2')
XENT = shapes.LOOP.replace("%fusion.7", "%fusion.8")
LAMB = shapes.LOOP.replace("%fusion.7", "%fusion.9")

_LAYER = "jit(train_step)/jit(main)/shard_map/{}/while/body/closed_call/"
FWD, BWD = _LAYER.format("jvp()"), _LAYER.format("transpose(jvp())")
PATHS = {
    shapes.WHILE: "jit(train_step)/jit(main)/shard_map/jvp()/while",
    shapes.CONV: FWD + "apex.mlp/bsd,df->bsf/dot_general",
    shapes.FLASH_FWD: BWD + "checkpoint/rematted_computation/apex.attn/"
                            "apex.flash/apex_flash_fwd/pallas_call",
    shapes.FLASH_BWD: BWD + "checkpoint/apex.attn/apex.flash/"
                            "apex_flash_bwd_fused/pallas_call",
    PARTIALS: BWD + "checkpoint/apex.attn/apex.flash/reduce_sum",
    shapes.LOOP: BWD + "checkpoint/apex.attn/transpose",
    HEAD: "jit(train_step)/jit(main)/shard_map/transpose(jvp(apex.head))/"
          "bsd,dv->bsv/dot_general",
    XENT: "jit(train_step)/jit(main)/shard_map/jvp(apex.loss)/"
          "apex_xentropy_fwd/pallas_call",
    shapes.ALL_REDUCE: "jit(train_step)/jit(main)/shard_map/"
                       "apex.ddp_allreduce/psum",
    LAMB: "jit(train_step)/jit(main)/shard_map/apex.amp_step/"
          "apex.opt_update/mul",
    shapes.COPY: "jit(train_step)/jit(main)/shard_map/transpose(jvp())/"
                 "while/body/dynamic_update_slice",
}
STEP_OPS = [(shapes.WHILE, 0, 600), (shapes.CONV, 0, 200),
            (shapes.FLASH_FWD, 200, 100), (shapes.FLASH_BWD, 300, 150),
            (PARTIALS, 450, 50), (shapes.LOOP, 500, 90), (HEAD, 600, 100),
            (XENT, 700, 40), (shapes.ALL_REDUCE, 740, 60), (LAMB, 800, 150),
            (shapes.COPY, 950, 50)]


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n >> 7 else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _message(*fields):
    """``(number, value)`` pairs as protobuf wire format: an int is a
    varint, a list of ints a packed run, text or bytes carry their length."""
    out = bytearray()
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
            continue
        if isinstance(value, list):
            value = b"".join(map(_varint, value))
        elif isinstance(value, str):
            value = value.encode()
        out += _varint(number << 3 | 2) + _varint(len(value)) + value
    return bytes(out)


BARE = (LAMB, shapes.COPY)
IDS = {name: 10 + at for at, (name, _, _) in enumerate(STEP_OPS)}
OPERANDS = {PARTIALS: [IDS[shapes.FLASH_BWD]],
            shapes.COPY: [IDS[shapes.FLASH_BWD]],
            LAMB: [IDS[shapes.ALL_REDUCE]]}
CALLS = {LAMB: [2], shapes.CONV: [2], XENT: [3]}
RENAMED = "jit(train_step)/jit(main)/shard_map/convert.34"


def _hlo_proto(scoped: bool):
    def path(name):
        found = PATHS[name]
        return found if scoped else re.sub(r"apex\.[a-z_]+/?", "", found)

    def instruction(name, ident, op_name="", operands=(), calls=()):
        short, _, opcode, _ = reduce.instruction(name)
        return _message((1, short), (2, opcode), (7, _message((2, op_name))),
                        (35, ident), (36, list(operands)),
                        *((38, c) for c in calls))
    def fused(ident, paths):
        return _message((1, f"fused_computation.{ident}"), (5, ident), *(
            (2, _message((1, f"multiply.{ident}.{at}"), (2, "multiply"),
                         (35, at), (7, _message((2, op_name)))))
            for at, op_name in enumerate(paths)))
    lamb, xent = path(LAMB), path(XENT)
    own = {**{name: "" for name in BARE}, XENT: RENAMED}
    entry = _message((1, "main.1"), (5, 1), (2, _message(
        (1, "get-tuple-element.9"), (2, "get-tuple-element"), (35, 99))), *(
        (2, instruction(name, IDS[name], own.get(name, path(name)),
                        OPERANDS.get(name, ()), CALLS.get(name, ())))
        for name, _, _ in STEP_OPS))
    return _message((1, _message(
        (1, "jit_train_step"),
        (3, fused(2, [lamb, lamb.replace("/mul", "/add"), lamb, ""])),
        (3, fused(3, [xent, RENAMED, xent])), (3, entry))))


def _xspace(scoped: bool, first_ns=1000):
    """What the readers need of the file: the program in
    ``/host:metadata``, beside a device plane whose ``XLA Ops`` line (XPlane
    .lines=3; XLine.name=2 .timestamp_ns=3 .events=4; XEvent.metadata_id=1
    .offset_ps=2 .duration_ps=3) holds the window's first operation."""
    stat = _message((1, 1), (6, _hlo_proto(scoped)))
    metadata = _message((1, 7), (2, shapes.STEP), (5, stat))
    ops = _message((1, 1), (2, reduce.OPS_LINE), (3, first_ns),
                   (4, _message((1, 1), (2, 0), (3, 600000))))
    return _message(
        (1, _message((1, 0), (2, "/device:TPU:0"), (3, ops),
                     (4, _message((1, 1), (2, _message(
                         (1, 1), (2, shapes.WHILE))))))),
        (1, _message((1, 1), (2, "/host:metadata"),
                     (4, _message((1, 7), (2, metadata))),
                     (5, _message((1, 1), (2, _message(
                         (1, 1), (2, "Hlo Proto"))))))))


def _trace():
    starts = (0, 1000, 2000)
    lines = [
        reduce.Line("/device:TPU:0", "XLA Modules", [
            reduce.Event(shapes.STEP, float(t), 1000.0, {}) for t in starts]),
        reduce.Line("/device:TPU:0", "XLA Ops", [
            reduce.Event(name, float(t + at), float(dur), {})
            for t in starts for name, at, dur in STEP_OPS])]
    return reduce.Trace(lines, n_steps=2)


@pytest.fixture(scope="module")
def trace():
    return _trace()


@pytest.fixture(scope="module")
def names():
    return scopes.module_paths(_hlo_proto(scoped=True))


class FakeRun:
    """What a reader asks of ``run.Run``: the trace, the chips, the other
    readers' values, read once, and the checkout the trace was written
    under."""
    metric = run.Run.metric

    def __init__(self, trace, root, chips=4, scoped=True):
        self.trace, self.chips, self._values = trace, chips, {}
        self.manifest = run.Manifest(REAL)
        self.manifest.root = str(root)
        if scoped is not None:
            write_trace(root, "cell", _xspace(scoped))


def write_trace(root, cell, xspace):
    at = root / scopes.TRACE_DIR / cell / "plugins" / "profile"
    at.mkdir(parents=True)
    (at / "host.xplane.pb").write_bytes(xspace)
    return str(at / "host.xplane.pb")


NEW = ("update_time_share", "recompute_time_share", "attention_glue_share",
       "head_loss_time_share", "ddp_time_share", "scope_coverage_share")


def test_names_are_the_programs_vocabulary():
    from apex_tpu import pyprof
    assert scopes.NAMES == pyprof.SCOPES


@pytest.mark.parametrize("path,inside,phase", [
    (PATHS[shapes.CONV], ("apex.mlp",), "forward"),
    (PATHS[shapes.FLASH_FWD], ("apex.attn", "apex.flash"), "recompute"),
    (PATHS[shapes.FLASH_BWD], ("apex.attn", "apex.flash"), "backward"),
    (PATHS[HEAD], ("apex.head",), "backward"),
    (PATHS[XENT], ("apex.loss",), "forward"),
    (PATHS[shapes.ALL_REDUCE], ("apex.ddp_allreduce",), "reduce"),
    (PATHS[LAMB], ("apex.amp_step", "apex.opt_update"), "update"),
    (PATHS[shapes.COPY], (), "backward"),
    (PATHS[shapes.WHILE], (), "forward"),
    ("jit(f)/apex.nonesuch/apex.attn|layer=3/mul", ("apex.attn",), "forward"),
    ("", (), "forward"),
])
def test_blocks_and_phase_of_a_path(path, inside, phase):
    assert scopes.blocks(path) == inside
    assert scopes.phase(path) == phase


def test_paths_are_read_from_the_traces_own_program(trace, names, tmp_path):
    paths = names.paths
    assert paths["apex_flash_fwd.13"] == PATHS[shapes.FLASH_FWD]
    assert paths["while.6"] == PATHS[shapes.WHILE]
    assert paths["reduce.5"] == PATHS[PARTIALS]
    # bare in the program: a fusion is read by most of what it fuses, and so
    # is one that carries a name XLA gave it; a fusion that jax named keeps
    # its path whatever it fuses
    assert paths["fusion.9"] == PATHS[LAMB]
    assert paths["fusion.8"] == PATHS[XENT]
    assert names.renamed == {"fusion.9", "fusion.8"}
    assert paths["convolution_add_fusion.11"] == PATHS[shapes.CONV]
    # a bare copy is outside every block, whatever it copies
    assert paths["copy.3"] == paths["get-tuple-element.9"] == ""
    assert scopes.path_of(reduce.Event(HEAD, 0.0, 1.0, {}), names) \
        == PATHS[HEAD]
    assert scopes.path_of(reduce.Event(shapes.ALL_REDUCE_START, 0.0, 1.0, {}),
                          names) == ""
    # the same through the file, found where run.py writes a trace
    assert scopes.names_of(FakeRun(trace, tmp_path)) == names
    assert scopes.hlo_protos(_xspace(True)) == [_hlo_proto(True)]


def _one(own, inside, opcode="fusion"):
    """A program of one instruction ``fusion.1`` with the path ``own`` that
    calls a computation of instructions with the paths ``inside``."""
    def ins(name, opcode, ident, path, *more):
        return _message((1, name), (2, opcode), (35, ident),
                        (7, _message((2, path))), *more)
    return _message((1, _message(
        (1, "jit_step"),
        (3, _message((1, "fused_computation"), (5, 2), *(
            (2, ins(f"multiply.{at}", "multiply", at, path))
            for at, path in enumerate(inside)))),
        (3, _message((1, "main"), (5, 1),
                     (2, ins("fusion.1", opcode, 7, own, (38, 2))))))))


_UPDATE = "jit(step)/apex.amp_step/apex.opt_update/"
_UNSCALE = "jit(step)/apex.amp_step/apex.unscale/mul"
_WGRAD = "jit(step)/transpose(jvp())/conv_general_dilated"


@pytest.mark.parametrize("own,inside,found", [
    # XLA's own fusion, bare or under a name of XLA's: what it fuses
    ("", [_UPDATE + "mul", _UPDATE + "add", ""], _UPDATE + "mul"),
    ("jit(step)/jit(main)/shard_map/convert.34",
     [_UPDATE + "mul", "jit(step)/jit(main)/shard_map/convert.34"],
     _UPDATE + "mul"),
    # counted by block and phase, not by the exact text: three instructions
    # of the update outvote two equal ones of the backward pass
    ("", [_WGRAD, _WGRAD, _UPDATE + "mul", _UPDATE + "add", _UPDATE + "sub"],
     _UPDATE + "mul"),
    # most of it outside every block: it stays there
    ("", [_WGRAD, _WGRAD, _UNSCALE], None),
    ("", ["", ""], None),
    # ResNet's weight gradients: jax named the fusion (backward, no block),
    # XLA fused the update's finite check into it — it stays backward,
    # whether the update's instructions are the fewer or the more
    (_WGRAD, [_WGRAD, _WGRAD, _WGRAD, _UNSCALE, _UNSCALE], None),
    (_WGRAD, [_WGRAD, _UNSCALE, _UNSCALE], None),
    # a block of its own: kept
    (_UPDATE + "mul", [_UNSCALE, _UNSCALE], None),
])
def test_a_fusion_is_read_by_what_it_fuses_only_where_xla_named_it(
        own, inside, found):
    names = scopes.module_paths(_one(own, inside))
    assert names.paths["fusion.1"] == (found or own)
    assert names.renamed == ({"fusion.1"} if found else set())


def test_only_a_fusion_is_read_by_what_it_calls():
    names = scopes.module_paths(_one("", [_UPDATE + "mul"], opcode="call"))
    assert names.paths["fusion.1"] == "" and not names.renamed


@pytest.mark.parametrize("path,xla", [
    ("", True), ("jit(step)/jit(main)/shard_map/convert.34", True),
    ("broadcast.29", True), (_WGRAD, False), (_UPDATE + "mul", False),
    ("jit(step)/bsd,df->bsf/dot_general", False)])
def test_a_name_of_xlas_ends_in_an_instructions_name(path, xla):
    assert scopes.xla_named(path) is xla


def test_a_checkout_without_the_runs_trace_has_no_names(trace, tmp_path):
    assert scopes.names_of(FakeRun(trace, tmp_path, scoped=None)) is None


def test_the_trace_file_is_told_by_what_it_holds(trace, names, tmp_path):
    """Two cells have left a trace in the checkout, the other cell's the
    newer and with the same instruction names: the run's own file is the
    one that holds its first traced operation at the same time."""
    fake = FakeRun(trace, tmp_path)
    other = write_trace(tmp_path, "other", _xspace(False, first_ns=5000))
    os.utime(other, (os.path.getmtime(other) + 60,) * 2)
    assert scopes.names_of(fake) == names
    # and neither file is the run's: nothing, not the newest
    elsewhere = FakeRun(trace, tmp_path / "b", scoped=None)
    write_trace(tmp_path / "b", "one", _xspace(True, first_ns=5000))
    write_trace(tmp_path / "b", "two", _xspace(True, first_ns=7000))
    assert scopes.names_of(elsewhere) is None


def test_shares_are_self_time_over_busy(trace, names):
    assert trace.busy_s == pytest.approx(2000e-9)
    # the scan's own 10 ns and the copy's 50 carry no block
    assert scopes.share(trace, lambda ev, path: bool(scopes.blocks(path)),
                        names) == pytest.approx(100.0 * 940 / 1000)
    assert scopes.share(trace, scopes.under("apex.attn"), names) \
        == pytest.approx(100.0 * (100 + 150 + 50 + 90) / 1000)
    assert scopes.share(trace, scopes.under("apex.head", "apex.loss"),
                        names) == pytest.approx(14.0)
    assert scopes.unscoped_rows(trace, names) == [
        ["copy copy", pytest.approx(5.0)], ["while while", pytest.approx(1.0)]]


def test_table_is_innermost_block_by_phase(trace, names):
    cells = scopes.table(trace, names)
    assert cells == {
        ("apex.mlp", "forward"): pytest.approx(20.0),
        ("apex.flash", "recompute"): pytest.approx(10.0),
        ("apex.flash", "backward"): pytest.approx(20.0),
        ("apex.attn", "backward"): pytest.approx(9.0),
        ("apex.head", "backward"): pytest.approx(10.0),
        ("apex.loss", "forward"): pytest.approx(4.0),
        ("apex.ddp_allreduce", "reduce"): pytest.approx(6.0),
        ("apex.opt_update", "update"): pytest.approx(15.0),
        (scopes.UNSCOPED, "forward"): pytest.approx(6.0)}
    assert sum(cells.values()) == pytest.approx(100.0)
    text = scopes.format_table(cells).splitlines()
    assert text[0].split() == ["%", "of", "busy", *scopes.PHASES, "all"]
    assert text[1].split() == ["apex.attn", "-", "9.00", "-", "-", "-",
                               "9.00"]
    assert text[-1].split() == ["all", "30.00", "39.00", "10.00", "15.00",
                                "6.00", "100.00"]


@pytest.mark.parametrize("name,value", [
    ("scope_coverage_share", 94.0),
    ("update_time_share", 15.0),
    ("recompute_time_share", 10.0),
    # under apex.attn: the partial sums (5) and the transpose (9); not the
    # two kernels
    ("attention_glue_share", 14.0),
    ("head_loss_time_share", 14.0),
    ("ddp_time_share", 6.0),
])
def test_reader(name, value, trace, tmp_path, capsys):
    assert FakeRun(trace, tmp_path).metric(name) == pytest.approx(value)
    out = capsys.readouterr().out
    assert out.count("block x phase") == 1        # the table, logged once
    assert out.count("under a block: 94.00, of it 19.00 in fusions XLA "
                     "named") == 1
    assert out.count("outside every block: copy copy 5.00; while while "
                     "1.00") == 1
    if name == "attention_glue_share":
        assert "5.00 under apex.flash and 9.00 directly under apex.attn" in out


def test_one_chip_has_no_ddp_share(trace, tmp_path):
    assert FakeRun(trace, tmp_path, chips=1).metric("ddp_time_share") is None


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("scoped", [False, None])
def test_a_step_compiled_before_the_scopes_reads_none(name, scoped, trace,
                                                      tmp_path, capsys):
    """The parent's program, or a cache entry keyed without its metadata
    (and a trace file that is not there): nothing to read is None — never
    0 — and one line says why."""
    fake = FakeRun(trace, tmp_path, scoped=scoped)
    assert fake.metric(name) is None
    for other in NEW:
        assert fake.metric(other) is None
    assert capsys.readouterr().out.count(
        "no instruction of the trace carries an apex.* scope") == 1


@pytest.mark.parametrize("name", NEW)
def test_off_the_chip_there_is_no_trace_and_no_value(name, tmp_path):
    assert FakeRun(None, tmp_path, scoped=None).metric(name) is None


def test_readers_are_the_manifests_new_entries():
    manifest = run.Manifest(REAL)
    tail = manifest.doc["per_layer"][-len(NEW):]
    assert [m["name"] for m in tail] == list(NEW)
    bert = [w["name"] for w in manifest.doc["workloads"]
            if w["config"] == "bert_large"]
    for m in tail:
        assert (m["unit"], m["source"], m["moves"]) == (
            "%", "device_trace", "samples_per_s")
        load_module(manifest.find("layer_metrics", m["name"] + ".py"),
                    "reader_" + m["name"]).read
    cells = {m["name"]: m.get("workloads") for m in tail}
    assert cells["update_time_share"] is None                 # every cell
    assert cells["ddp_time_share"] == ["bert_large.dp4_s512"]
    for name in NEW[1:4] + NEW[5:]:
        assert sorted(cells[name]) == sorted(bert)
