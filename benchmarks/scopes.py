"""From an instruction of the device trace to the block of the step it
belongs to, read from the names the program gives its blocks from inside.

The program enters ``jax.named_scope``s from a fixed vocabulary
(``apex_tpu.pyprof.SCOPES``; a program from before the scopes has no such
tuple, no block is then found and every reader returns None).  jax writes
the scopes into every instruction's ``op_name``, with what the
transformations add::

    jit(train_step)/jvp()/while/body/closed_call/apex.attn/apex.flash/
        apex_flash_fwd/pallas_call                      forward
    .../transpose(jvp())/while/body/closed_call/checkpoint/apex.mlp/
        bsd,df->bsf/dot_general                         backward
    .../transpose(jvp())/while/body/closed_call/checkpoint/
        rematted_computation/apex.attn/...              remat's second forward
    jit(train_step)/apex.amp_step/apex.opt_update/mul   update

Outside ``jax.checkpoint`` a backward instruction reads
``transpose(jvp(apex.head))/...``: the scope moves inside the brackets, so a
block is found anywhere in the path, not only between slashes.

Where the path comes from (looked at on the chip, PR 24; jax 0.9.0, "TPU v5
lite").  An ``XLA Ops`` event is named by the instruction's text WITHOUT its
``metadata={...}``, and its own stats are ``device_offset_ps``,
``device_duration_ps`` and ``Time Scale Multiplier``: neither holds the path.
The ``.xplane.pb`` does: its plane ``/host:metadata`` carries the ``Hlo
Proto`` of every program that ran (the step's: 0.8 MB), with each
instruction's ``metadata.op_name``.  ``jax.profiler.ProfileData`` shows
neither that plane's bytes nor the stats of an event's metadata (where
``tf_op`` repeats the same ``op_name`` for about two instructions in three),
so :func:`op_names` reads the file's wire format itself — six message types,
of which it needs ten fields.  It is the trace's own copy of what ran, so a
program whose step the harness cannot reach (ResNet's) is read the same way.

XLA names some instructions itself, and a scope cannot reach those: the
LAMB update is one ``fusion`` of 51 instructions with no ``op_name`` on one
chip and ``.../shard_map/convert.34`` — an instruction's name where jax puts
a primitive's — on four (8.3 % of that cell's busy time).  One rule, for
fusions only: a fusion whose own path is empty or ends in an instruction's
name (:func:`xla_named`) belongs where most of what it fuses belongs, counted
by block and phase and not by exact path.  :func:`module_paths` says which
fusions it named so, and ``scope_coverage_share`` logs their share of every
run.  A fusion that jax named keeps its path whatever it fuses: ResNet's
weight-gradient convolutions fuse the update's finite check (3 instructions
of the backward to 2 of ``apex.unscale``) and stay backward, outside every
block, so ``update_time_share`` there is a lower bound.  Every other
instruction without a path — layout copies, ``copy-done``, ``slice-done``
— stays outside every block.
"""
from __future__ import annotations

import collections
import functools
import os
import re

from apex_tpu import pyprof

from benchmarks import reduce

#: the program's vocabulary, outermost first where the blocks nest
NAMES = tuple(getattr(pyprof, "SCOPES", ()))
PHASES = ("forward", "backward", "recompute", "update", "reduce")
UNSCOPED = "(no scope)"
TRACE_DIR = ".bench_trace"          # under the manifest's root: run.py's

_BLOCK = re.compile(r"apex\.[a-z_]+")
_XLA_NAME = re.compile(r"(^|/)[a-z][a-z_-]*\.\d+$")

#: ``paths``: {instruction name: op_name path}; ``renamed``: the fusions
#: whose path is not their own but that of what they fuse
Names = collections.namedtuple("Names", "paths renamed")


# ---------------------------------------------------------------------------
# the trace file's own copy of the program
# ---------------------------------------------------------------------------

def _varint(buf, at):
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a slice of ``buf`` for anything with a length."""
    at = 0
    while at < len(buf):
        key, at = _varint(buf, at)
        wire = key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, at = buf[at:at + size], at + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, value


def _ints(value) -> list:
    """A repeated int64: one varint, or a packed run of them."""
    if isinstance(value, int):
        return [value]
    out, at = [], 0
    while at < len(value):
        one, at = _varint(value, at)
        out.append(one)
    return out


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def hlo_protos(xspace) -> list:
    """The ``Hlo Proto`` bytes an ``XSpace`` carries in ``/host:metadata``:
    XSpace.planes=1; XPlane.name=2 .event_metadata=4 (a map: value=2);
    XEventMetadata.stats=5; XStat.bytes_value=6."""
    out = []
    for field, plane in _fields(xspace):
        if field != 1:
            continue
        parts = list(_fields(plane))
        if not any(f == 2 and _text(v) == "/host:metadata" for f, v in parts):
            continue
        for entry in (v for f, v in parts if f == 4):
            for _, metadata in (p for p in _fields(entry) if p[0] == 2):
                for _, stat in (p for p in _fields(metadata) if p[0] == 5):
                    out += [v for f, v in _fields(stat) if f == 6]
    return out


def _instruction(buf) -> dict:
    """What :func:`module_paths` needs of one ``HloInstructionProto``: name=1
    opcode=2 metadata=7 (OpMetadata.op_name=2) id=35 operand_ids=36
    called_computation_ids=38."""
    ins = {"name": "", "opcode": "", "path": "", "id": None, "operands": [],
           "calls": []}
    for field, value in _fields(buf):
        if field == 1:
            ins["name"] = _text(value)
        elif field == 2:
            ins["opcode"] = _text(value)
        elif field == 7:
            ins["path"] = "".join(
                _text(v) for f, v in _fields(value) if f == 2)
        elif field == 35:
            ins["id"] = value
        elif field == 36:
            ins["operands"] += _ints(value)
        elif field == 38:
            ins["calls"] += _ints(value)
    return ins


def xla_named(path: str) -> bool:
    """XLA made this name, not jax: the path is empty or ends in an
    instruction's name (``convert.34``), where jax ends it in a primitive's
    (``dot_general``) — and no scope can reach it."""
    return not path or bool(_XLA_NAME.search(path))


def module_paths(hlo_proto) -> Names:
    """The :class:`Names` of one ``HloProto``; a fusion that XLA named is
    renamed as the module docstring says.  HloProto.hlo_module=1;
    HloModuleProto.computations=3; HloComputationProto.instructions=2
    .id=5."""
    computations = {}                        # id -> its instructions
    for _, module in (p for p in _fields(hlo_proto) if p[0] == 1):
        for _, comp in (p for p in _fields(module) if p[0] == 3):
            parts = list(_fields(comp))
            computations[next(v for f, v in parts if f == 5)] = [
                _instruction(v) for f, v in parts if f == 2]

    paths, renamed = {}, set()
    for members in computations.values():
        for ins in members:
            paths[ins["name"]] = ins["path"]
            if ins["opcode"] != "fusion" or not xla_named(ins["path"]):
                continue
            inside = [i["path"] for c in ins["calls"]
                      for i in computations.get(c, ()) if i["path"]]
            where = collections.Counter(
                (blocks(path), phase(path)) for path in inside)
            most = where.most_common(1)[0][0] if where else ((), "")
            if most[0]:
                paths[ins["name"]] = next(
                    path for path in inside
                    if (blocks(path), phase(path)) == most)
                renamed.add(ins["name"])
    return Names(paths, frozenset(renamed))


@functools.lru_cache(maxsize=4)
def op_names(xplane_path: str) -> Names:
    """The :class:`Names` over the programs the trace at ``xplane_path``
    carries; where two programs use one name the larger program — the step —
    has it."""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    paths, renamed = {}, set()
    for module in sorted(map(module_paths, hlo_protos(space)),
                         key=lambda m: len(m.paths)):
        paths.update(module.paths)
        renamed = renamed - set(module.paths) | module.renamed
    return Names(paths, frozenset(renamed))


@functools.lru_cache(maxsize=4)
def _file_with(trace_dir: str, name: str, start_ns: float):
    """The ``.xplane.pb`` under ``trace_dir`` that holds the ``XLA Ops``
    event ``name`` at ``start_ns``, newest first; a lone file is it."""
    found = sorted(reduce.find_xplanes(trace_dir), key=os.path.getmtime,
                   reverse=True)
    for path in found:
        if len(found) == 1 or any(
                (ev.name, ev.start_ns) == (name, start_ns)
                for line in reduce.read_xplane(path)
                if line.name == reduce.OPS_LINE for ev in line.events):
            return path
    return None


def names_of(run):
    """:func:`op_names` of the file ``run.trace`` was read from, or None.
    ``run.py`` writes a cell's trace under ``.bench_trace/<cell>`` and a
    ``Run`` does not know its cell's name, so where other cells have left
    traces in the checkout the file is told by what it holds: the run's
    first traced operation, at the same time."""
    ops = run.trace.devices[0].ops
    path = ops and _file_with(os.path.join(run.manifest.root, TRACE_DIR),
                              ops[0].name, ops[0].start_ns)
    return op_names(path) if path else None


# ---------------------------------------------------------------------------
# from a path to a block and a phase
# ---------------------------------------------------------------------------

def path_of(event, names) -> str:
    """The event's ``op_name`` path, or "" where the program has none for
    its instruction."""
    return names.paths.get(reduce.instruction(event.name)[0], "")


@functools.lru_cache(maxsize=65536)
def blocks(path: str) -> tuple:
    """The ``apex.*`` names in the path, outermost first."""
    return tuple(n for n in _BLOCK.findall(path) if n in NAMES)


@functools.lru_cache(maxsize=65536)
def phase(path: str) -> str:
    """Which part of the step the instruction at ``path`` belongs to."""
    if "rematted_computation" in path:
        return "recompute"
    if "transpose(" in path:
        return "backward"
    inside = blocks(path)
    if "apex.amp_step" in inside:
        return "update"
    if "apex.ddp_allreduce" in inside:
        return "reduce"
    return "forward"


def share(trace, predicate, names) -> float:
    """``Trace.share_of_busy`` over ``predicate(event, path)``: self time of
    the matching instructions over busy time, in %, mean over chips."""
    return trace.share_of_busy(
        lambda ev: predicate(ev, path_of(ev, names)))


def table(trace, names) -> dict:
    """``{(innermost block, phase): % of busy}``, mean over chips; time
    outside every block is under :data:`UNSCOPED`."""
    out = collections.Counter()
    for dev in trace.devices:
        if not dev.busy_ns:
            continue
        for ev, ns in dev.selfs:
            path = path_of(ev, names)
            inside = blocks(path)
            out[inside[-1] if inside else UNSCOPED, phase(path)] += (
                100.0 * ns / dev.busy_ns / len(trace.devices))
    return dict(out)


def format_table(cells: dict) -> str:
    """The table as lines of text: a row per block, a column per phase."""
    rows = [n for n in (*NAMES, UNSCOPED) if any(b == n for b, _ in cells)]
    lines = [f"{'% of busy':<20}" + "".join(f"{p:>10}" for p in PHASES)
             + f"{'all':>10}"]
    for name in rows:
        values = [cells.get((name, p), 0.0) for p in PHASES]
        lines.append(f"{name:<20}" + "".join(
            f"{v:10.2f}" if v else f"{'-':>10}" for v in values)
            + f"{sum(values):10.2f}")
    totals = [sum(v for (_, q), v in cells.items() if q == p) for p in PHASES]
    lines.append(f"{'all':<20}" + "".join(f"{v:10.2f}" for v in totals)
                 + f"{sum(totals):10.2f}")
    return "\n".join(lines)


def unscoped_rows(trace, names, n=6) -> list:
    """``[[label, % of busy]]``: the instructions outside every block that
    cost most, grouped as ``Trace.top_ops`` groups them."""
    dev = max(trace.devices, key=lambda d: d.busy_ns)
    acc = collections.Counter()
    for ev, ns in dev.selfs:
        if not blocks(path_of(ev, names)):
            acc[reduce.op_label(ev)] += 100.0 * ns / dev.busy_ns
    return [[label, value] for label, value in acc.most_common(n)]


# ---------------------------------------------------------------------------
# what the readers share
# ---------------------------------------------------------------------------

def seen(run):
    """The run's ``names`` where the traced step carries the program's
    scopes, else None.  Every reader asks this first:
    ``scope_coverage_share`` is read once a run (``Run.metric``), logs the
    table, and says why where there is nothing — a reader then returns None,
    never 0."""
    if run.trace and run.metric("scope_coverage_share"):
        return names_of(run)
    return None


def under(*names):
    """A predicate for :func:`share`: the path lies under any of ``names``."""
    return lambda ev, path: any(n in blocks(path) for n in names)
