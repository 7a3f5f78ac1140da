"""From a profiler trace (``.xplane.pb``) to intervals, and from intervals to
the numbers the per-layer readers report.

Reading: ``jax.profiler.ProfileData`` gives planes, lines and events with a
start and a duration in nanoseconds; :func:`read_xplane` flattens that into
:class:`Line` records of plain tuples, which is also the shape the test
fixture has, so the arithmetic below never touches the profiler.

What a TPU trace looks like (jax 0.9.0, "TPU v5 lite"; looked at by hand, PR
22): one plane per chip named ``/device:TPU:<n>``.  Its line ``XLA Modules``
has one event per executed program (``jit_train_step(<fingerprint>)``), its
line ``XLA Ops`` one event per HLO instruction, NAMED BY THE INSTRUCTION'S
WHOLE TEXT (``%apex_flash_fwd.13 = (bf16[1536,512,64]{...}, ...)
custom-call(...), custom_call_target="tpu_custom_call"``; a fusion ends in
``kind=kOutput, calls=%fused_computation.84``), with the instructions of a
``while`` body (the layer scan) nested inside the ``while`` event on the same
line.  Events carry no category.  ``Async XLA Ops`` holds what runs beside the
instruction stream (``copy-start``, ``slice-start``, asynchronous collectives)
for as long as it is in flight.  Host threads are lines of the plane
``/host:CPU``; ``jax.profiler.TraceAnnotation`` spans land on the line of the
thread that made them, under their own names, on the same clock as the device
lines.

Which instructions are matrix work: ``convolution`` / ``dot`` themselves, and
fusions of ``kind=kOutput`` — on the TPU a fusion built around a convolution
(every dot is one there) with its epilogue.  Checked against the compiled HLO
of the BERT-large step (PR 22): of 201 fusions the 18 ``kOutput`` ones are
exactly those whose computation holds a convolution.

Time is attributed by SELF time: an event's duration minus what its nested
children cover, so a scan and its body are not counted twice.  The interval
arithmetic (:func:`merge`, :func:`subtract`, :func:`clip`) is a copy of
``apex_tpu.telemetry.timeline``'s, which is oracle-tested there.
"""
from __future__ import annotations

import collections
import functools
import glob
import os
import re

Event = collections.namedtuple("Event", "name start_ns dur_ns stats")
Line = collections.namedtuple("Line", "plane name events")

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "bench."

#: HLO opcodes that move data between chips (``all-reduce-start`` and
#: ``all-reduce-done`` begin with them too).
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute", "collective-broadcast")


# ---------------------------------------------------------------------------
# reading
# ---------------------------------------------------------------------------

def find_xplanes(trace_dir: str) -> list:
    return sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))


def lines_of(profile) -> list:
    """``ProfileData`` -> ``[Line]``; stats become a dict."""
    out = []
    for plane in profile.planes:
        for line in plane.lines:
            events = [Event(e.name, float(e.start_ns), float(e.duration_ns),
                            dict(e.stats)) for e in line.events]
            out.append(Line(plane.name, line.name, events))
    return out


def read_xplane(path: str) -> list:
    from jax.profiler import ProfileData
    return lines_of(ProfileData.from_file(path))


# ---------------------------------------------------------------------------
# exact interval arithmetic (half-open intervals, any one unit)
# ---------------------------------------------------------------------------

def merge(intervals):
    """Sorted union; empty and negative spans drop."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def subtract(a, b):
    """``a - b`` for MERGED lists: the parts of ``a`` nothing in ``b``
    covers."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            bs, be = b[k]
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(intervals, t0, t1):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def span(event):
    return (event.start_ns, event.start_ns + event.dur_ns)


# ---------------------------------------------------------------------------
# one device's operations
# ---------------------------------------------------------------------------

def self_times(events) -> list:
    """``[(event, self_ns)]``: each event's duration minus the part its
    nested children (events that start inside it, on the same line) cover."""
    ordered = sorted(events, key=lambda e: (e.start_ns, -e.dur_ns))
    out, stack = [], []           # stack of [event, end, covered_by_children]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            ev, _, covered = stack.pop()
            out.append((ev, max(ev.dur_ns - covered, 0.0)))

    for ev in ordered:
        close(ev.start_ns)
        end = ev.start_ns + ev.dur_ns
        if stack:
            # a child reaching past its parent's end is clipped to it
            stack[-1][2] += min(end, stack[-1][1]) - ev.start_ns
        stack.append([ev, end, 0.0])
    close(float("inf"))
    return out


_SUFFIX = re.compile(r"(\.\d+)+$")


@functools.lru_cache(maxsize=65536)
def instruction(name: str) -> tuple:
    """``(name, result type, opcode, kind)`` of an event named by its HLO
    text: ``%fusion.311 = (bf16[1024]{0}, f32[96,512]{1,0}) fusion(...),
    kind=kOutput, calls=...`` -> ``("fusion.311", "(bf16[1024]{0}, ...)",
    "fusion", "kOutput")``.  A name that is no HLO text (a program on the
    modules line) comes back without its arguments and with empty parts.
    Cached: a scan body's instructions recur once per layer and step, and
    their texts run to kilobytes."""
    text = name.strip()
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text.lstrip("%").split("(", 1)[0], "", "", ""
    if rest.startswith("("):              # a tuple type: find its closing ")"
        depth = 0
        for at, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        result, after = rest[:at + 1], rest[at + 1:].lstrip()
    else:
        result, _, after = rest.partition(" ")
    kind = re.search(r"\bkind=(k\w+)", after)
    return (head.lstrip("%"), result, after.split("(", 1)[0],
            kind.group(1) if kind else "")


def base_name(name: str) -> str:
    """The instruction's (or program's) name without its numeric suffix:
    ``%apex_flash_fwd.13 = ...`` -> ``apex_flash_fwd``."""
    return _SUFFIX.sub("", instruction(name)[0])


def is_collective(name: str) -> bool:
    return instruction(name)[2].startswith(_COLLECTIVES)


#: bytes of one element, by the HLO's name for the type
_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
             "s16": 2, "u16": 2, "f16": 2, "bf16": 2, "s32": 4, "u32": 4,
             "f32": 4, "s64": 8, "u64": 8, "f64": 8}
_ARRAY_TYPE = re.compile(r"\b([a-z]\w*)\[([\d,]*)\]")


def type_bytes(text: str) -> int:
    """Bytes of every array type written in ``text``
    (``bf16[24,1024,4096]{2,1,0:T(8,128)(2,1)}`` -> 201326592; a scalar
    ``f32[]`` -> 4).  A type this table does not know is an error, not 0."""
    nbytes = 0
    for dtype, dims in _ARRAY_TYPE.findall(text):
        size = _ITEMSIZE[dtype]
        for dim in filter(None, dims.split(",")):
            size *= int(dim)
        nbytes += size
    return nbytes


@functools.lru_cache(maxsize=4096)
def operand_bytes(name: str) -> int:
    """Bytes of the operands in an instruction's text: what stands between
    the opcode's parenthesis and its partner (tiled layouts carry
    parentheses of their own)."""
    after = name.partition(" = ")[2][len(instruction(name)[1]):]
    start = after.index("(")
    depth = 0
    for at in range(start, len(after)):
        depth += (after[at] == "(") - (after[at] == ")")
        if depth == 0:
            break
    return type_bytes(after[start:at + 1])


def op_class(event) -> str:
    """``matmul`` (a convolution or dot, alone or as the heart of a
    ``kOutput`` fusion), ``collective``, ``kernel`` (a custom call — the
    Pallas kernels, named by the program) or ``other``."""
    _, _, opcode, kind = instruction(event.name)
    if opcode.startswith(_COLLECTIVES):
        return "collective"
    if opcode in ("convolution", "dot") or kind == "kOutput":
        return "matmul"
    if opcode == "custom-call":
        return "kernel"
    return "other"


def comm_intervals(ops, async_ops=()) -> list:
    """Intervals during which a collective holds one chip's instruction
    stream (a synchronous all-reduce, the wait in a ``-done``) or is in
    flight beside it (an event of the asynchronous line lasts as long as
    the transfer)."""
    return merge(span(ev) for ev in (*ops, *async_ops)
                 if is_collective(ev.name))


class DeviceWindow:
    """One chip's operations inside the traced steps' window."""

    def __init__(self, index, ops, async_ops, t0, t1):
        self.index, self.t0, self.t1 = index, t0, t1

        def inside(events):
            return [e for e in events
                    if e.start_ns < t1 and e.start_ns + e.dur_ns > t0]
        self.ops, self.async_ops = inside(ops), inside(async_ops)
        self.busy = clip(merge(span(e) for e in self.ops), t0, t1)
        self.selfs = self_times(self.ops)

    @property
    def window_ns(self):
        return self.t1 - self.t0

    @property
    def busy_ns(self):
        return total(self.busy)

    def self_ns(self, predicate) -> float:
        return sum(ns for ev, ns in self.selfs if predicate(ev))

    def count(self, predicate) -> int:
        return sum(1 for ev in self.ops if predicate(ev))

    def exposed_comm_ns(self) -> float:
        """Collective time no compute on this chip covers."""
        comm = clip(comm_intervals(self.ops, self.async_ops), self.t0,
                    self.t1)
        compute = clip(merge(
            span(ev) for ev, ns in self.selfs
            if ns > 0 and op_class(ev) != "collective"
            and not _is_container(ev)), self.t0, self.t1)
        return total(subtract(comm, compute))

    def comm_bytes(self) -> int:
        """Bytes this chip handed to collectives in the window: the operands
        of every collective instruction that starts a transfer (a ``-done``
        only waits for one)."""
        return sum(operand_bytes(ev.name)
                   for ev in (*self.ops, *self.async_ops)
                   if is_collective(ev.name)
                   and not instruction(ev.name)[2].endswith("-done"))

    def gaps(self):
        return subtract([(self.t0, self.t1)], self.busy)


def _is_container(event) -> bool:
    """Control flow whose children do the work (its own interval covers
    them, so it must not count as compute covering a collective)."""
    return instruction(event.name)[2] in ("while", "conditional", "call")


# ---------------------------------------------------------------------------
# the whole trace
# ---------------------------------------------------------------------------

class Trace:
    """The traced window of every chip, plus the host's spans."""

    def __init__(self, lines, n_steps: int):
        self.n_steps = n_steps
        self.devices = []
        by_plane = collections.defaultdict(dict)
        for line in lines:
            m = DEVICE_PLANE.match(line.plane)
            if m:
                by_plane[int(m.group(1))][line.name] = line.events
        for index in sorted(by_plane):
            ops = by_plane[index].get(OPS_LINE, [])
            modules = by_plane[index].get(MODULES_LINE, [])
            window = step_window(modules, n_steps)
            if ops and window:
                self.devices.append(DeviceWindow(
                    index, ops, by_plane[index].get(ASYNC_LINE, []), *window))
        self.host_spans = sorted(
            (e for line in lines if line.plane == HOST_PLANE
             for e in line.events if e.name.startswith(HOST_SPAN_PREFIX)),
            key=lambda e: e.start_ns)

    def __bool__(self):
        return bool(self.devices)

    def mean(self, fn) -> float:
        return sum(fn(d) for d in self.devices) / len(self.devices)

    @property
    def busy_s(self):
        return self.mean(lambda d: d.busy_ns) / 1e9

    @property
    def window_s(self):
        return self.mean(lambda d: d.window_ns) / 1e9

    def share_of_busy(self, predicate) -> float:
        """Self time of the matching operations over busy time, in %, mean
        over chips."""
        return 100.0 * self.mean(
            lambda d: d.self_ns(predicate) / d.busy_ns if d.busy_ns else 0.0)

    def top_ops(self, n=10) -> list:
        """``[[label, seconds]]``: self time on the busiest chip, summed over
        the window, grouped by :func:`op_label` — instructions that differ
        only in their numeric suffix are one row, so the two call sites of a
        kernel or the hundred layout copies of a step show as what they cost
        together."""
        dev = max(self.devices, key=lambda d: d.busy_ns)
        acc, calls = collections.Counter(), collections.Counter()
        for ev, ns in dev.selfs:
            label = op_label(ev)
            acc[label] += ns
            calls[label] += 1
        return [[f"{label} x{calls[label]}", ns / 1e9]
                for label, ns in acc.most_common(n)]

    def idle_gaps(self, n=10) -> list:
        """``[[what the host was doing, seconds]]`` for the idle time of the
        chip that idled most: each gap goes to the host span that covers its
        start (the innermost one), or to ``(no span)``."""
        dev = max(self.devices, key=lambda d: d.window_ns - d.busy_ns)
        acc = collections.Counter()
        for s, e in dev.gaps():
            covering = [h for h in self.host_spans
                        if h.start_ns <= s < h.start_ns + h.dur_ns]
            label = (min(covering, key=lambda h: h.dur_ns).name
                     if covering else "(no span)")
            acc[label] += e - s
        return [[name, ns / 1e9] for name, ns in acc.most_common(n)]


def step_window(modules, n_steps: int):
    """``(t0, t1)`` from the start of the first to the end of the last of
    the ``n_steps`` LAST runs of the program that took most device time —
    the training step; lead-in steps before them and everything else the
    trace caught fall outside."""
    by_name = collections.defaultdict(list)
    for ev in modules:
        by_name[base_name(ev.name)].append(ev)
    if not by_name:
        return None
    runs = max(by_name.values(), key=lambda evs: sum(e.dur_ns for e in evs))
    runs = sorted(runs, key=lambda e: e.start_ns)[-n_steps:]
    return runs[0].start_ns, runs[-1].start_ns + runs[-1].dur_ns


def op_label(event) -> str:
    """A name a reader of the ledger can act on: the instruction's name
    without its numeric suffix, and its opcode with the fusion kind —
    ``apex_flash_bwd_fused custom-call``, ``fusion fusion/kOutput`` (an
    unnamed convolution fusion), ``copy copy``."""
    name, _, opcode, kind = instruction(event.name)
    what = "/".join(x for x in (opcode, kind) if x)
    return " ".join(x for x in (_SUFFIX.sub("", name), what) if x)
