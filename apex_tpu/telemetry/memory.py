"""Memory observability — the third telemetry pillar ("where do the
BYTES go", next to ``registry``'s "what are the rates" and ``trace``'s
"what ran just before").

HBM fit is the binding constraint for every ROADMAP scaling lever (bf16
O4/O5, ZeRO state sharding, remat trades), and the auto-parallel
planner cannot rank dp×tp/ZeRO/SP plans without a per-strategy memory
cost model.  Three pieces:

  * **static attribution** — :func:`memory_table` compiles a train step
    AOT (never executed), reads the executable's ``memory_analysis()``
    (argument/output/temp/alias bytes) and runs an **HLO liveness
    sweep** over the scheduled entry computation: every buffer gets a
    [def, last-use] interval, the peak of the live-byte curve is found,
    and the buffers live at the peak are attributed per op and per
    class — ``params`` / ``optimizer`` / ``batch`` / ``activations`` /
    ``temps`` / ``output`` / ``constants`` — joining
    :func:`attrib.parse_hlo`'s FLOPs rows.  The sweep is pure text over
    the optimized HLO, so it is CPU-deterministic and tier-1 testable.
    :func:`memory_model` exports the compact per-class dict the ROADMAP
    planner consumes (and registers it as the process attribution the
    OOM post-mortem embeds).
  * **live gauges** — :class:`MemoryMonitor` polls
    ``device.memory_stats()`` (bytes_in_use, peak_bytes_in_use, largest
    allocation) from inside ``Registry.flush()``'s one batched host
    read, emitting ``mem.*`` gauges plus a Chrome **counter track**
    (``ph: "C"``) through the default tracer, so Perfetto timelines
    show the memory curve under the span rows.  Disabled
    (``APEX_TPU_TELEMETRY_MEM=0``) or unsupported (CPU allocators
    report nothing) the monitor is a true zero-sync/zero-alloc no-op —
    the registry's asserted standard.
  * **OOM post-mortem** — :func:`is_oom_error` recognizes
    ``RESOURCE_EXHAUSTED`` failures, :func:`parse_allocator_report`
    extracts the allocator's top allocations from the error text, and
    :func:`dump_oom` writes a schema-validated
    ``flight-oom-<ts>.json`` (flight-recorder ring + live-memory
    history + the registered static attribution + the faulting step).
    ``resilience.TrainGuard`` calls it on any OOM — including the
    deterministic ``oom@N`` fault kind (:func:`synthetic_oom`), so the
    whole path is CPU-chaos-testable — then RE-RAISES: an OOM is
    deterministic, retry/rollback would only burn the budget.

``python -m apex_tpu.telemetry mem`` renders the attribution table
from the flagship transformer step or a flight-oom dump.  Like the
registry, no jax at module scope; ``memory_stats()`` calls live ONLY
here (the host-sync lint enforces it).
"""
from __future__ import annotations

import collections
import json
import re
from typing import Any, Dict, List, Optional

from . import attrib as _attrib
from . import trace as _trace

__all__ = [
    "MEM_CLASSES", "classify_arg", "hlo_liveness", "memory_table",
    "memory_model", "format_memory_table", "MemoryMonitor",
    "device_memory_stats", "device_memory_json", "compiled_memory_stats",
    "is_oom_error", "parse_allocator_report", "InjectedOomError",
    "synthetic_oom", "dump_oom", "oom_violations", "set_attribution",
    "get_attribution", "cli",
]

# ---------------------------------------------------------------------------
# static attribution: HLO liveness sweep
# ---------------------------------------------------------------------------

#: Peak-HBM attribution classes.  ``params``/``optimizer``/``batch``/
#: ``args`` come from the entry parameters' jax keypath metadata;
#: ``activations`` are intermediates HELD across the peak instruction
#: (live before and after it — the fwd tensors a backward is keeping),
#: ``temps`` die at the peak, ``output`` buffers flow to the root.
MEM_CLASSES = ("params", "optimizer", "batch", "args", "constants",
               "activations", "temps", "output")

_OPT_KEYS = ("master", "opt_state", "scaler", "moment", "exp_avg",
             "'m'", "'v'", ".m[", ".v[", "adam", "lamb", "mu'", "nu'")
_PARAM_KEYS = ("model_params", "param", "weight", "kernel", "embed")
_BATCH_KEYS = ("token", "image", "label", "target", "batch", "input",
               "boost")
#: a bare terminal ``.m`` / ``.v`` / ``['m']`` / ``['v']`` field — the
#: fused/sharded optimizer-state moment buffers (``FusedAdamState.m``
#: and the weight-update-sharding 1/N slices keypath exactly so);
#: terminal-only, so ``vectors``/``m_tokens`` never false-positive
_MOMENT_FIELD_RE = re.compile(r"(?:\.|\[')([mv])(?:'\])?$")


def classify_arg(path: str) -> str:
    """Bin one entry-parameter keypath (the jax ``op_name`` metadata,
    e.g. ``state.master_params['w']`` or ``tokens``) into its memory
    class.  Optimizer keys win over param keys: ``master_params`` is
    optimizer STATE (the fp32 shadow), not the serving weights."""
    # HLO metadata escapes quotes (op_name="state[\'opt\'][\'m\']") —
    # strip the backslashes so the quoted-key patterns match
    p = (path or "").replace("\\", "").lower()
    if any(k in p for k in _OPT_KEYS):
        return "optimizer"
    if any(k in p for k in _PARAM_KEYS):
        return "params"
    # the bare terminal-field heuristic ranks BELOW the explicit param
    # names: a genuine model parameter literally keyed 'm'
    # (model_params['m']) must stay params, not flip to optimizer
    if _MOMENT_FIELD_RE.search(p):
        return "optimizer"
    if any(k in p for k in _BATCH_KEYS) or p in ("x", "y"):
        return "batch"
    return "args"


# view opcodes: no storage of their own — they alias an operand's buffer
_VIEW_OPS = frozenset(("get-tuple-element", "tuple", "bitcast"))
_OPERAND_NAME_RE = re.compile(r"%([\w.\-]+)")
_ALIAS_PARAM_RE = re.compile(r":\s*\(\s*(\d+)\s*,")


def _donated_params(text: str) -> frozenset:
    """Parameter numbers the module header marks as input/output
    aliased (jit donation) — their buffers can die at last use instead
    of living to program end.  The header value nests braces
    (``{ {0}: (0, {}, may-alias) }``), so scan to the balanced close
    instead of regexing it."""
    head = text.split("\n", 1)[0]
    start = head.find("input_output_alias={")
    if start < 0:
        return frozenset()
    i = start + len("input_output_alias={")
    depth = 1
    j = i
    while j < len(head) and depth:
        if head[j] == "{":
            depth += 1
        elif head[j] == "}":
            depth -= 1
        j += 1
    return frozenset(int(p) for p in
                     _ALIAS_PARAM_RE.findall(head[i:j]))


def _operand_region(rest: str) -> str:
    """The operand text of ``opcode(...)`` — cut at the balanced close
    paren, before the attribute section (``calls=%...`` etc.)."""
    depth = 1
    for i, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return rest[:i]
    return rest


def _parse_entry(text: str):
    """Entry-computation instructions in schedule order: one record per
    instruction with ``op``, ``opcode``, ``out_bytes``, ``operands``
    (referenced var names), ``jax_op``, ``param_no``, ``is_root``."""
    entry_name: Optional[str] = None
    current: Optional[str] = None
    comp_order: List[str] = []
    by_comp: Dict[str, List[dict]] = {}
    for line in text.splitlines():
        if not line.strip():
            continue
        cm = _attrib._COMP_RE.match(line)
        if cm and line.rstrip().endswith("{"):
            current = cm.group("name")
            by_comp[current] = []
            comp_order.append(current)
            if line.lstrip().startswith("ENTRY"):
                entry_name = current
            continue
        if line.strip() == "}" or current is None:
            continue
        im = _attrib._INSTR_RE.match(line)
        if im is None:
            continue
        opcode = im.group("opcode")
        rest = im.group("rest")
        _, out_bytes = _attrib._type_info(im.group("type"))
        param_no = None
        if opcode == "parameter":
            pm = re.match(r"\s*(\d+)", rest)
            param_no = int(pm.group(1)) if pm else None
        nm = _attrib._OPNAME_RE.search(rest)
        by_comp[current].append({
            "op": im.group("var"), "opcode": opcode,
            "out_bytes": int(out_bytes),
            "operands": _OPERAND_NAME_RE.findall(_operand_region(rest)),
            "jax_op": nm.group(1) if nm else "",
            "param_no": param_no,
            "is_root": line.lstrip().startswith("ROOT"),
        })
    if entry_name is None and comp_order:
        entry_name = comp_order[-1]   # HLO text ends with ENTRY
    instrs = by_comp.get(entry_name, [])
    for i, ins in enumerate(instrs):
        ins["idx"] = i
    return instrs, _donated_params(text)


def hlo_liveness(text: str) -> dict:
    """Liveness sweep over the scheduled entry computation.

    Every buffer-producing instruction gets a [def, last-use] interval
    (parameters live from 0 — to program end unless donated; root/
    output buffers live to the end; view ops alias their operand's
    buffer, extending its lifetime).  Fusion-internal intermediates
    stay on-chip by construction and loop-body internals are not
    modeled — this is the HBM residency model, not a VMEM one.

    Returns ``{peak_bytes, peak_index, peak_op, n_instructions,
    n_buffers, live_at_peak: [rows], by_class: {cls: bytes},
    timeline: [{i, bytes}]}`` where ``by_class`` partitions
    ``peak_bytes`` exactly (asserted by the tier-1 tests).
    """
    instrs, donated = _parse_entry(text)
    n = len(instrs)
    if n == 0:
        return {"peak_bytes": 0, "peak_index": 0, "peak_op": "",
                "n_instructions": 0, "n_buffers": 0, "live_at_peak": [],
                "by_class": {}, "timeline": []}

    # view ops alias underlying buffers; resolve chains (gte of a tuple
    # of a bitcast ...) down to the producing ops.  A ``tuple`` fans out
    # to ALL of its operands: a consumer of the tuple (a while loop's
    # carry, a conditional) keeps every element alive, not just the
    # first — collapsing to one element would understate the peak the
    # planner and the OOM dump rely on.  (gte carries an index we don't
    # parse, so it conservatively keeps the whole tuple alive — an
    # overstatement, the safe direction for a fit model.)
    alias: Dict[str, List[str]] = {}
    producer = {ins["op"]: ins for ins in instrs}

    def roots_of(name: str) -> List[str]:
        out: List[str] = []
        stack = [name]
        seen = set()
        while stack:
            n = stack.pop()
            if n in seen:
                continue
            seen.add(n)
            al = alias.get(n)
            if al is None:
                out.append(n)
            else:
                stack.extend(al)
        return out

    for ins in instrs:
        if ins["opcode"] in _VIEW_OPS and ins["operands"]:
            alias[ins["op"]] = (list(ins["operands"])
                                if ins["opcode"] == "tuple"
                                else [ins["operands"][0]])

    last_use: Dict[str, int] = {}
    for ins in instrs:
        for opn in ins["operands"]:
            for r in roots_of(opn):
                if r in producer:
                    last_use[r] = max(last_use.get(r, -1), ins["idx"])

    root = next((i for i in reversed(instrs) if i["is_root"]), instrs[-1])
    output_ops = set()
    if root["opcode"] == "tuple":
        for o in root["operands"]:
            output_ops.update(roots_of(o))
    else:
        output_ops.update(roots_of(root["op"]))

    buffers: List[dict] = []
    for ins in instrs:
        if ins["out_bytes"] <= 0 or ins["opcode"] in _VIEW_OPS:
            continue
        op = ins["op"]
        if ins["opcode"] == "parameter":
            start = 0
            if ins["param_no"] in donated:
                end = last_use.get(op, ins["idx"])
            else:
                end = n - 1          # the caller owns it for the call
        else:
            start = ins["idx"]
            end = (n - 1 if (op in output_ops or ins["is_root"])
                   else last_use.get(op, ins["idx"]))
        buffers.append({"op": op, "opcode": ins["opcode"],
                        "jax_op": ins["jax_op"], "bytes": ins["out_bytes"],
                        "start": start, "end": end,
                        "param_no": ins["param_no"],
                        "is_output": op in output_ops})

    delta = [0] * (n + 1)
    for b in buffers:
        delta[b["start"]] += b["bytes"]
        delta[b["end"] + 1] -= b["bytes"]
    series: List[int] = []
    acc = 0
    for i in range(n):
        acc += delta[i]
        series.append(acc)
    peak_idx = max(range(n), key=lambda i: series[i])
    peak_bytes = series[peak_idx]

    rows: List[dict] = []
    by_class: Dict[str, int] = {}
    for b in buffers:
        if not (b["start"] <= peak_idx <= b["end"]):
            continue
        if b["opcode"] == "parameter":
            cls = classify_arg(b["jax_op"] or b["op"])
        elif b["opcode"] == "constant":
            cls = "constants"
        elif b["is_output"]:
            cls = "output"
        elif b["end"] > peak_idx:
            cls = "activations"      # held ACROSS the peak instruction
        else:
            cls = "temps"            # consumed at the peak
        rows.append({"op": b["op"], "opcode": b["opcode"], "class": cls,
                     "jax_op": b["jax_op"], "bytes": b["bytes"],
                     "def_index": b["start"], "last_use": b["end"]})
        by_class[cls] = by_class.get(cls, 0) + b["bytes"]
    rows.sort(key=lambda r: -r["bytes"])

    stride = max(1, n // 256)        # dumps carry a bounded curve
    timeline = [{"i": i, "bytes": series[i]} for i in range(0, n, stride)]
    return {"peak_bytes": peak_bytes, "peak_index": peak_idx,
            "peak_op": instrs[peak_idx]["op"], "n_instructions": n,
            "n_buffers": len(buffers), "live_at_peak": rows,
            "by_class": by_class, "timeline": timeline}


# ---------------------------------------------------------------------------
# compiled stats + the joined table
# ---------------------------------------------------------------------------

def _stats_dict(ma) -> Optional[dict]:
    if ma is None:
        return None
    d = {"argument_bytes": int(ma.argument_size_in_bytes),
         "output_bytes": int(ma.output_size_in_bytes),
         "temp_bytes": int(ma.temp_size_in_bytes),
         "alias_bytes": int(ma.alias_size_in_bytes),
         "generated_code_bytes": int(ma.generated_code_size_in_bytes)}
    # the executable's whole-footprint model: everything resident at
    # once, minus the donated buffers counted on both sides
    d["peak_bytes"] = (d["argument_bytes"] + d["output_bytes"]
                       + d["temp_bytes"] - d["alias_bytes"])
    return d


def compiled_memory_stats(fn_or_jitted, *args, **kwargs) -> Optional[dict]:
    """``memory_analysis()`` of the AOT-compiled function as a plain
    dict (argument/output/temp/alias bytes + the summed ``peak_bytes``
    footprint model), or None when the backend has no analysis.
    Accepts a plain callable or an already-``jax.jit``-ed one.  NOTE:
    ``lower().compile()`` bypasses the in-memory jit executable cache
    (it may hit the persistent XLA cache when one is configured) — on
    a TPU this can re-pay a full compile."""
    import jax
    jitted = (fn_or_jitted if hasattr(fn_or_jitted, "lower")
              else jax.jit(fn_or_jitted))
    try:
        ma = jitted.lower(*args, **kwargs).compile().memory_analysis()
    except Exception:
        return None
    return _stats_dict(ma)


def memory_table(fn, *args, static_argnums=(), donate_argnums=(),
                 **kwargs) -> dict:
    """Compile ``fn(*args, **kwargs)`` AOT (never executed) and return
    the peak-HBM attribution: the liveness sweep joined with
    ``memory_analysis()`` totals and :func:`attrib.parse_hlo` FLOPs per
    live-at-peak row — the memory analog of :func:`attrib.op_table`.
    """
    import jax
    jitted = jax.jit(fn, static_argnums=static_argnums,
                     donate_argnums=donate_argnums)
    compiled = jitted.lower(*args, **kwargs).compile()
    text = compiled.as_text()
    table = hlo_liveness(text)
    try:
        table["stats"] = _stats_dict(compiled.memory_analysis())
    except Exception:   # pragma: no cover - backend without the API
        table["stats"] = None
    flops = {r["op"]: r["flops"] for r in _attrib.parse_hlo(text)}
    for row in table["live_at_peak"]:
        row["flops"] = flops.get(row["op"], 0.0)
    table["platform"] = jax.devices()[0].platform
    return table


def memory_model(fn=None, *args, table: Optional[dict] = None,
                 register: bool = True, update_sharding_world: int = 1,
                 **kwargs) -> dict:
    """The compact per-class memory cost model the ROADMAP auto-parallel
    planner consumes (and the shape the OOM post-mortem embeds).  Pass a
    precomputed ``table`` or let it compile ``fn(*args)`` itself.
    ``register=True`` installs the result as the process attribution
    (:func:`set_attribution`), so a later OOM dump names where the
    bytes were expected to go.

    ``update_sharding_world``: shard count of a weight-update-sharded
    run (``parallel.weight_update``).  The liveness sweep attributes
    GLOBAL shapes, so under sharding the optimizer class sums all
    replicas' slices; ``optimizer_bytes_per_replica`` divides it back
    to what one replica actually holds — the number the planner's HBM
    fit check needs.  Default 1 = replicated (per-replica == total,
    the classic DDP meaning)."""
    if table is None:
        table = memory_table(fn, *args, **kwargs)
    cls = table["by_class"]
    world = max(1, int(update_sharding_world))
    model = {
        "peak_hbm_bytes": int(table["peak_bytes"]),
        "platform": table.get("platform", "?"),
        "peak_op": table["peak_op"],
        "by_class": {k: int(v) for k, v in cls.items()},
        "params_bytes": int(cls.get("params", 0)),
        "optimizer_bytes": int(cls.get("optimizer", 0)),
        "optimizer_bytes_per_replica": int(cls.get("optimizer", 0)) // world,
        "update_sharding_world": world,
        "batch_bytes": int(cls.get("batch", 0)),
        "activations_bytes": int(cls.get("activations", 0)),
        "temps_bytes": int(cls.get("temps", 0)),
        "output_bytes": int(cls.get("output", 0)),
        # the remaining classes, surfaced so a planner consuming this
        # dict scales EVERY byte at the peak — a by_class partition
        # summed from the named keys must equal peak_hbm_bytes
        "args_bytes": int(cls.get("args", 0)),
        "constants_bytes": int(cls.get("constants", 0)),
        "compiled": table.get("stats"),
        "top": [{"op": r["op"], "class": r["class"],
                 "bytes": int(r["bytes"]), "opcode": r["opcode"]}
                for r in table["live_at_peak"][:12]],
    }
    if register:
        set_attribution(model)
    return model


def _human(n, unit: str = "") -> str:
    """Local bytes humanizer (pyprof's ``_human`` rides a module that
    imports jax at module scope; rendering artifacts must not)."""
    if n is None:
        return "n/a"
    n = float(n)
    for mag, suffix in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "K")):
        if abs(n) >= mag:
            return f"{n / mag:.2f} {suffix}{unit}"
    return f"{n:.0f} {unit}".rstrip()


def format_memory_table(table: dict, top: int = 16) -> str:
    """Render the per-class peak-HBM table + the largest live buffers —
    the ``python -m apex_tpu.telemetry mem`` output."""
    peak = table["peak_bytes"]
    lines = [
        f"peak-HBM attribution ({table.get('platform', '?')}; "
        f"{table['n_buffers']} buffers over {table['n_instructions']} "
        f"instructions; peak at #{table['peak_index']} "
        f"({table['peak_op']}))",
        "per-class residency at peak",
    ]
    by_class = table["by_class"]
    for cls in MEM_CLASSES:
        b = by_class.get(cls)
        if b is None:
            continue
        pct = 100.0 * b / peak if peak else 0.0
        lines.append(f"  {cls:<12} {_human(b, 'B'):>12} {pct:>6.1f}%")
    lines.append(f"  {'total':<12} {_human(peak, 'B'):>12} "
                 f"(= liveness-sweep peak)")
    rows = table["live_at_peak"][:top]
    if rows:
        lines.append(f"largest live buffers at peak (top {len(rows)})")
        lines.append(f"  {'op':<28} {'opcode':<12} {'class':<12} "
                     f"{'bytes':>12} {'flops':>10}")
        for r in rows:
            name = r["op"] if len(r["op"]) <= 28 else r["op"][:25] + "..."
            lines.append(
                f"  {name:<28} {r['opcode']:<12} {r['class']:<12} "
                f"{_human(r['bytes'], 'B'):>12} "
                f"{_human(r.get('flops', 0.0)):>10}")
    stats = table.get("stats")
    if stats:
        lines.append(
            f"compiled memory_analysis: args {_human(stats['argument_bytes'], 'B')}"
            f"  output {_human(stats['output_bytes'], 'B')}"
            f"  temps {_human(stats['temp_bytes'], 'B')}"
            f"  aliased {_human(stats['alias_bytes'], 'B')}"
            f"  (footprint {_human(stats['peak_bytes'], 'B')})")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# live gauges
# ---------------------------------------------------------------------------

def device_memory_stats(device=None) -> Optional[dict]:
    """ONE host-side read of the device allocator's counters
    (``device.memory_stats()`` — a local PJRT call, not a device sync);
    None when the backend exposes nothing (CPU)."""
    try:
        if device is None:
            import jax
            device = jax.devices()[0]
        stats = device.memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    return {k: int(v) for k, v in stats.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def device_memory_json() -> str:
    """The counter-track args for a streaming timeline: a one-line JSON
    object of the allocator counters, or the empty string when
    unsupported (the writer then appends nothing)."""
    stats = device_memory_stats()
    if not stats:
        return ""
    keys = ("bytes_in_use", "peak_bytes_in_use", "largest_alloc_size",
            "bytes_limit", "num_allocs")
    picked = {k: stats[k] for k in keys if k in stats}
    return json.dumps(picked or stats)


class MemoryMonitor:
    """Polls the device allocator at registry-flush cadence.

    ``Registry.flush()`` calls :meth:`observe_flush` as part of its one
    batched host read: the poll sets ``mem.bytes_in_use`` /
    ``mem.peak_bytes_in_use`` / ``mem.largest_alloc_bytes`` gauges,
    appends to a bounded history ring (the OOM post-mortem embeds it),
    and emits a ``device_mem`` Chrome counter track through the default
    tracer.  Disabled (``enabled=False`` / ``APEX_TPU_TELEMETRY_MEM=0``)
    or unsupported (first poll found no stats — cached), every call is
    a single attribute check: no device access, no allocation."""

    def __init__(self, *, enabled: Optional[bool] = None,
                 history: int = 512, device=None):
        self.enabled = (_trace.env_flag("APEX_TPU_TELEMETRY_MEM")
                        if enabled is None else bool(enabled))
        self.history: "collections.deque" = collections.deque(
            maxlen=int(history))
        self._device = device
        self._unsupported = False

    @property
    def supported(self) -> Optional[bool]:
        """False once a poll found no allocator stats; None before the
        first poll resolves it."""
        return False if self._unsupported else None

    def poll(self) -> Optional[dict]:
        if not self.enabled or self._unsupported:
            return None
        stats = device_memory_stats(self._device)
        if stats is None:
            self._unsupported = True     # never probe again: the
            return None                  # no-op contract after one miss
        out = {"bytes_in_use": float(stats.get("bytes_in_use", 0)),
               "peak_bytes_in_use": float(
                   stats.get("peak_bytes_in_use", 0))}
        if "largest_alloc_size" in stats:
            out["largest_alloc_bytes"] = float(stats["largest_alloc_size"])
        if stats.get("bytes_limit"):
            out["bytes_limit"] = float(stats["bytes_limit"])
        return out

    def observe_flush(self, reg) -> Optional[dict]:
        """The registry-flush hook: poll once, gauge + ring + counter
        track.  Returns the polled stats (None when disabled or
        unsupported — and then does nothing else)."""
        stats = self.poll()
        if stats is None:
            return None
        step = int(getattr(reg, "_step", 0))
        for key in ("bytes_in_use", "peak_bytes_in_use",
                    "largest_alloc_bytes"):
            if key in stats:
                reg.gauge("mem." + key).set(stats[key])
        self.history.append({"step": step,
                             "bytes_in_use": stats["bytes_in_use"],
                             "peak_bytes_in_use":
                                 stats["peak_bytes_in_use"]})
        _trace.note_counter(
            "device_mem", step=step,
            values={"bytes_in_use": stats["bytes_in_use"],
                    "peak_bytes_in_use": stats["peak_bytes_in_use"]})
        return stats

    def snapshot(self) -> List[dict]:
        return list(self.history)


# ---------------------------------------------------------------------------
# OOM post-mortem
# ---------------------------------------------------------------------------

class InjectedOomError(RuntimeError):
    """The deterministic ``oom@N`` fault: message shaped like a real
    XLA ``RESOURCE_EXHAUSTED`` allocator report so the post-mortem
    parser is chaos-tested against the format it must survive."""


def synthetic_oom(step: int, nbytes: int = 2 ** 31) -> InjectedOomError:
    return InjectedOomError(
        f"RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
        f"{int(nbytes)} bytes. [injected oom fault at step {int(step)}]\n"
        "Largest program allocations in hbm:\n"
        f"  1. Size: {_human(nbytes, 'B').replace(' ', '')}\n"
        "     Operator: op_name=\"injected/oom/fault\"\n"
        "     Shape: f32[536870912]\n"
        "     Allocation type: HLO temp\n"
        "  2. Size: 128.00MB\n"
        "     Operator: op_name=\"injected/oom/activations\"\n"
        "     Shape: bf16[8,512,64,256]\n"
        "     Allocation type: HLO temp\n")


def is_oom_error(err: BaseException) -> bool:
    """True for allocator exhaustion — the injected fault or a real
    backend failure (``RESOURCE_EXHAUSTED`` / out-of-memory text)."""
    if isinstance(err, InjectedOomError):
        return True
    s = f"{type(err).__name__}: {err}"
    return ("RESOURCE_EXHAUSTED" in s or "Out of memory" in s
            or "out of memory" in s)


_REQ_RE = re.compile(r"allocat\w*\s+(\d+)\s+bytes", re.I)
_SIZE_RE = re.compile(
    r"^\s*\d+\.\s+Size:\s*([0-9.]+)\s*([KMGTP]?i?B?)\s*$", re.M)
_SHAPE_LINE_RE = re.compile(r"Shape:\s*(\S+)")
_ALLOC_TYPE_RE = re.compile(r"Allocation type:\s*([^\n]+)")

_SIZE_MULT = {"": 1, "B": 1,
              "K": 1e3, "KB": 1e3, "KIB": 2 ** 10,
              "M": 1e6, "MB": 1e6, "MIB": 2 ** 20,
              "G": 1e9, "GB": 1e9, "GIB": 2 ** 30,
              "T": 1e12, "TB": 1e12, "TIB": 2 ** 40}


def _size_bytes(num: str, suffix: str) -> int:
    return int(float(num) * _SIZE_MULT.get(suffix.upper(), 1))


def parse_allocator_report(text: str) -> dict:
    """Tolerant parse of an XLA allocator failure message: the
    requested byte count plus the "Largest program allocations" stanzas
    (size / operator / shape / allocation type).  Anything it cannot
    read is simply absent — the dump must still land on a format
    drift."""
    text = str(text)
    req = _REQ_RE.search(text)
    allocations: List[dict] = []
    headers = list(_SIZE_RE.finditer(text))
    for i, m in enumerate(headers):
        stanza_end = (headers[i + 1].start() if i + 1 < len(headers)
                      else len(text))
        stanza = text[m.end():stanza_end]
        alloc = {"size_bytes": _size_bytes(m.group(1), m.group(2))}
        nm = _attrib._OPNAME_RE.search(stanza)
        if nm:
            alloc["operator"] = nm.group(1)[:200]
        sm = _SHAPE_LINE_RE.search(stanza)
        if sm:
            alloc["shape"] = sm.group(1)[:80]
        tm = _ALLOC_TYPE_RE.search(stanza)
        if tm:
            alloc["alloc_type"] = tm.group(1).strip()[:40]
        allocations.append(alloc)
    return {"requested_bytes": int(req.group(1)) if req else None,
            "allocations": allocations}


# -- the process attribution (what the OOM dump embeds) ----------------------

_attribution: Optional[dict] = None


def set_attribution(model: Optional[dict]) -> Optional[dict]:
    """Install the static attribution (a :func:`memory_model` dict) the
    OOM post-mortem embeds; None uninstalls.  Returns the previous one
    so tests can restore it."""
    global _attribution
    prev = _attribution
    _attribution = model
    return prev


def get_attribution() -> Optional[dict]:
    return _attribution


_is_int = lambda v: isinstance(v, int) and not isinstance(v, bool)


def _oom_section_violations(sec: Any) -> List[str]:
    if not isinstance(sec, dict):
        return ["oom section is not an object"]
    out = []
    if not _is_int(sec.get("bad_step")):
        out.append(f"oom: bad_step must be an int, got "
                   f"{sec.get('bad_step')!r}")
    if not isinstance(sec.get("error"), str):
        out.append("oom: missing error text")
    if not isinstance(sec.get("error_type"), str):
        out.append("oom: missing error_type")
    req = sec.get("requested_bytes")
    if req is not None and not _is_int(req):
        out.append(f"oom: requested_bytes must be int/null, got {req!r}")
    allocs = sec.get("allocations")
    if not isinstance(allocs, list):
        out.append("oom: allocations must be a list")
    else:
        for i, a in enumerate(allocs):
            if not isinstance(a, dict) or not _is_int(a.get("size_bytes")):
                out.append(f"oom: allocations[{i}] needs int size_bytes")
    hist = sec.get("live_memory")
    if not isinstance(hist, list):
        out.append("oom: live_memory must be a list")
    attr = sec.get("attribution")
    if attr is not None and not (isinstance(attr, dict)
                                 and _is_int(attr.get("peak_hbm_bytes"))):
        out.append("oom: attribution must be null or a memory_model dict "
                   "(peak_hbm_bytes int)")
    return out


def oom_violations(doc: Any) -> List[str]:
    """Schema complaints for a ``flight-oom-*.json`` post-mortem dump
    (the flight-recorder schema plus the ``oom`` section)."""
    out = _trace.dump_violations(doc)
    sec = doc.get("oom") if isinstance(doc, dict) else None
    if sec is None:
        out.append("missing 'oom' section")
    else:
        out.extend(_oom_section_violations(sec))
    return out


def dump_oom(recorder=None, *, step: int, error: BaseException,
             directory: Optional[str] = None, path: Optional[str] = None,
             registry=None, attribution: Optional[dict] = None
             ) -> Optional[str]:
    """Write the OOM post-mortem ``flight-oom-<ts>.json``: the flight
    ring (``recorder``; a fresh empty one when the run was untraced —
    the crash artifact must land regardless), the parsed allocator
    report, the registry monitor's live-memory history, and the
    registered static attribution.  Writer-validated against
    :func:`oom_violations` before it touches disk."""
    if recorder is None:
        recorder = _trace.FlightRecorder(capacity=8)
    report = parse_allocator_report(str(error))
    monitor = getattr(registry, "_memory", None) if registry is not None \
        else None
    section = {
        "bad_step": int(step),
        "error_type": type(error).__name__,
        "error": str(error)[:4000],
        "requested_bytes": report["requested_bytes"],
        "allocations": report["allocations"][:16],
        "live_memory": monitor.snapshot() if monitor is not None else [],
        "attribution": (attribution if attribution is not None
                        else get_attribution()),
    }
    bad = _oom_section_violations(section)
    if bad:   # writer-validates, the JsonlSink posture
        raise ValueError("oom post-mortem fails its schema: "
                         + "; ".join(bad[:4]))
    return recorder.dump(
        "oom", step=step, directory=directory, path=path,
        fields={"bad_step": int(step),
                "error_type": type(error).__name__},
        sections={"oom": section})


# ---------------------------------------------------------------------------
# CLI: python -m apex_tpu.telemetry mem
# ---------------------------------------------------------------------------

def _render_oom_dump(doc: dict, top: int) -> int:
    sec = doc.get("oom") or {}
    lines = [f"OOM post-mortem ({doc.get('ts')}; "
             f"bad_step={sec.get('bad_step')}; "
             f"{sec.get('error_type')})"]
    if sec.get("requested_bytes") is not None:
        lines.append(f"  requested        "
                     f"{_human(sec['requested_bytes'], 'B')}")
    allocs = sec.get("allocations") or []
    if allocs:
        lines.append(f"  top allocations  ({len(allocs)})")
        for a in allocs[:top]:
            lines.append(f"    {_human(a.get('size_bytes'), 'B'):>12}  "
                         f"{a.get('alloc_type', '?'):<12} "
                         f"{a.get('operator', a.get('shape', ''))[:60]}")
    hist = sec.get("live_memory") or []
    if hist:
        last = hist[-1]
        lines.append(f"  live memory      {len(hist)} samples; last: "
                     f"in-use {_human(last.get('bytes_in_use'), 'B')} "
                     f"peak {_human(last.get('peak_bytes_in_use'), 'B')} "
                     f"@ step {last.get('step')}")
    attr = sec.get("attribution")
    if attr:
        lines.append(f"  expected peak    "
                     f"{_human(attr.get('peak_hbm_bytes'), 'B')} "
                     f"(static attribution)")
        for cls, b in sorted((attr.get("by_class") or {}).items(),
                             key=lambda kv: -kv[1]):
            lines.append(f"    {cls:<12} {_human(b, 'B'):>12}")
    lines.append(f"  ring entries     {doc.get('n_entries', 0)}")
    print("\n".join(lines))
    return 0


def _render_artifact(path: str, top: int) -> int:
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict) and doc.get("kind") == "flight_recorder":
        return _render_oom_dump(doc, top)
    print(f"{path} is not a flight-oom dump")
    return 1


def cli(argv=None) -> int:
    """``python -m apex_tpu.telemetry mem [artifact] [--top N]``."""
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m apex_tpu.telemetry mem",
        description="Peak-HBM attribution: with no argument, compile the "
                    "flagship transformer train step on the ambient "
                    "backend and render the per-class liveness table; "
                    "with a path, render a flight-oom-*.json "
                    "post-mortem.")
    ap.add_argument("artifact", nargs="?", default=None,
                    help="flight-oom dump")
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--top", type=int, default=12)
    args = ap.parse_args(argv)
    if args.artifact is not None:
        return _render_artifact(args.artifact, top=args.top)

    import jax.numpy as jnp
    from .report import demo_step_fn
    train_step, state, make_batch = demo_step_fn(
        layers=args.layers, batch=args.batch, seq=args.seq)
    tokens, targets = make_batch(0)
    table = memory_table(train_step, state, tokens, targets,
                         jnp.asarray(1.0, jnp.float32))
    print(format_memory_table(table, top=args.top))
    model = memory_model(table=table)    # registers the attribution
    print(f"memory_model: peak {_human(model['peak_hbm_bytes'], 'B')}  "
          f"params {_human(model['params_bytes'], 'B')}  "
          f"optimizer {_human(model['optimizer_bytes'], 'B')}  "
          f"activations {_human(model['activations_bytes'], 'B')}  "
          f"temps {_human(model['temps_bytes'], 'B')}")
    return 0
