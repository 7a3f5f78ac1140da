"""Incremental bench-leg persistence.

A bench run that dies halfway used to lose every completed measurement.
Each bench leg flushes its JSON to a legs directory the moment it
completes (atomic tmp+rename, so a SIGKILL mid-write never leaves a
corrupt file), and :func:`assemble` rebuilds a driver-shaped payload
from whatever legs landed.

Leg file format (one JSON object per file, ``<name>.json``)::

    {"leg": name, "ts": "2026-07-30T22:41:07Z", "backend": "tpu",
     "data": {...}}

No reference counterpart; the posture is the reference's per-epoch
checkpoint one (examples/imagenet/main_amp.py:252-261): never lose
completed work to a crash.

CLI::

    python -m apex_tpu.utils.bench_legs <legs_dir> [--kind bench|kernels]

prints the assembled one-line JSON on stdout.
"""
from __future__ import annotations

import json
import os
import time
from typing import Any, Callable, Dict, Optional


def _deep_merge(old: dict, new: dict) -> dict:
    """New values win; dict-vs-dict merges recursively (keeps a previous
    window's sweep rows when the re-run re-measured only some of them)."""
    out = dict(old)
    for k, v in new.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _deep_merge(out[k], v)
        else:
            out[k] = v
    return out


def _scrub_keys(data: Any, keys) -> Any:
    """Recursively drop ``keys`` from nested dicts (returns a copy)."""
    if not isinstance(data, dict):
        return data
    return {k: _scrub_keys(v, keys) for k, v in data.items()
            if k not in keys}


def flush_leg(legs_dir: Optional[str], name: str, data: Any,
              backend: Optional[str] = None, merge: bool = False,
              drop: tuple = ()) -> None:
    """Atomically write ``<legs_dir>/<name>.json``.  No-op when
    ``legs_dir`` is falsy.  Re-flushing the same name overwrites — legs
    that accrete results (the headline A/B) flush after every
    sub-measurement so a run that dies mid-leg keeps the finished parts.

    ``merge=True``: dict data is DEEP-merged over the leg file's
    existing dict data (new keys win leaf-wise; nested dicts — sweep
    rows like ``by_seq`` — merge recursively) instead of replacing it,
    so a re-run that dies EARLIER than a previous run did cannot
    destroy the previous run's already-captured measurements.
    Merging only applies when both old and new data are dicts and the
    old record's backend matches (a CPU leg must never leak values into
    a TPU leg).

    ``drop``: key names scrubbed (recursively) from the final record —
    how renamed/retired fields leave merged artifacts: a deep-merge
    alone would leave e.g. the pre-r5 ``pallaserror`` key standing next
    to the new ``pallas_error`` forever (ADVICE r5 #4)."""
    if not legs_dir:
        return
    os.makedirs(legs_dir, exist_ok=True)
    if backend is None:
        import jax
        backend = jax.default_backend()
    old = read_legs(legs_dir).get(name)
    if (old is not None and old.get("backend") == "tpu"
            and backend != "tpu"):
        # never downgrade: a CPU re-run into the same legs dir (jax
        # fell back after the probe succeeded) must not destroy a
        # previously captured TPU measurement — the TPU leg IS the
        # perf story; the CPU record is noise here
        return
    if merge and isinstance(data, dict):
        if (old is not None and old.get("backend") == backend
                and isinstance(old.get("data"), dict)):
            data = _deep_merge(old["data"], data)
    if drop:
        data = _scrub_keys(data, frozenset(drop))
    rec = {"leg": name,
           "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
           "backend": backend,
           "data": data}
    tmp = os.path.join(legs_dir, f".{name}.tmp")
    with open(tmp, "w") as f:
        json.dump(rec, f)
    os.replace(tmp, os.path.join(legs_dir, f"{name}.json"))


def make_flusher(legs_dir: Optional[str],
                 drop: tuple = ()) -> Callable[..., None]:
    """Bind ``legs_dir`` (and retired key names to scrub) once; benches
    call ``flush(name, data)``."""
    def flush(name: str, data: Any, merge: bool = False) -> None:
        flush_leg(legs_dir, name, data, merge=merge, drop=drop)
    return flush


def argval(argv, flag):
    """Value of ``--flag VALUE`` in argv, else None (shared by the two
    bench scripts' hand-rolled CLIs)."""
    if flag in argv:
        i = argv.index(flag)
        if i + 1 < len(argv):
            return argv[i + 1]
    return None


def errored(results: dict) -> list:
    """Names of the legs / sections in ``results`` that recorded an
    ``error`` instead of a measurement."""
    return sorted(k for k, v in results.items()
                  if isinstance(v, dict) and "error" in v)


def read_tpu_legs(legs_dir: Optional[str]) -> Dict[str, dict]:
    """TPU-backend legs only — what ``bench_kernels.run`` resumes from (a
    leg recorded on another backend is not a measurement of the chip)."""
    if not legs_dir:
        return {}
    return {n: r for n, r in read_legs(legs_dir).items()
            if r.get("backend") == "tpu"}


def read_legs(legs_dir: str) -> Dict[str, dict]:
    """All parseable leg records in ``legs_dir``, keyed by leg name.
    Unparseable files (shouldn't exist, given atomic writes) are
    skipped, not fatal."""
    out: Dict[str, dict] = {}
    if not legs_dir or not os.path.isdir(legs_dir):
        return out
    for fn in sorted(os.listdir(legs_dir)):
        if not fn.endswith(".json") or fn.startswith("."):
            continue
        try:
            with open(os.path.join(legs_dir, fn)) as f:
                rec = json.load(f)
            out[rec.get("leg", fn[:-5])] = rec
        except (OSError, ValueError):
            continue
    return out


def assemble(legs_dir: str, kind: str = "bench") -> dict:
    """Rebuild a driver-shaped payload from the legs that landed.

    ``kind="bench"`` mirrors ``bench.py``'s output (headline metric +
    detail legs); ``kind="kernels"`` mirrors ``bench_kernels.py``'s.
    The result always carries ``"partial": true`` and the per-leg
    timestamps — an assembled payload documents an interrupted run, it
    never impersonates a complete one.
    """
    legs = read_legs(legs_dir)
    ts = {name: rec.get("ts") for name, rec in legs.items()}
    backends = {rec.get("backend") for rec in legs.values()}
    # "none" (not "mixed") for an empty dir: nothing was measured on ANY
    # backend, and downstream tooling treats "mixed" as partially
    # TPU-backed (apply_perf_results' tpu_sourced gate)
    backend = (backends.pop() if len(backends) == 1
               else "mixed" if backends else "none")

    def tag(rec, data):
        """With mixed backends, every merged value must say which
        backend produced it — a CPU ms next to a TPU ms with no label is
        the honesty failure the per-round bench hardening guards
        against."""
        if backend != "mixed":
            return data
        if isinstance(data, dict):
            return {"_backend": rec.get("backend"), **data}
        return {"_backend": rec.get("backend"), "value": data}

    if kind == "kernels":
        kernels: Dict[str, Any] = {}
        for name, rec in legs.items():
            data = rec.get("data")
            if isinstance(data, dict):
                for k, v in data.items():
                    kernels[k] = tag(rec, v)
            else:
                kernels[name] = tag(rec, data)
        return {"metric": "pallas_kernel_microbench", "backend": backend,
                "compiled": backend == "tpu", "kernels": kernels,
                "partial": True, "leg_timestamps": ts}

    detail: Dict[str, Any] = {}
    value = None
    vs_baseline = None
    head_rec = legs.get("headline", {})
    head = head_rec.get("data")
    if isinstance(head, dict):
        detail.update(tag(head_rec, head))
        # the headline metric only surfaces from a TPU-backend headline
        # leg (or a uniform non-mixed run, where top-level `backend`
        # already labels it)
        if backend != "mixed" or head_rec.get("backend") == "tpu":
            # best-vs-best across dtype-matched pairs, mirroring
            # bench.py's headline logic (fp32 impls vs optax-fp32;
            # flat-bf16 vs optax-bf16).  A pair missing its baseline
            # (a run that died between the impl and its optax twin) must not win
            # `value` and silently drop vs_baseline when a FULL pair
            # exists — prefer the best full pair; fall back to the best
            # baseline-less impl only when no pair completed.
            base = head.get("optax_baseline_ms")
            pairs = [(head.get("xla_impl_ms"), base),
                     (head.get("fused_flat_impl_ms"), base),
                     (head.get("fused_flat_bf16grads_ms"),
                      head.get("optax_bf16grads_ms")),
                     (head.get("fused_flat_bf16state_ms"),
                      head.get("optax_bf16grads_ms"))]
            done = [(m, b) for m, b in pairs
                    if isinstance(m, (int, float))]
            full = [(m, b) for m, b in done
                    if isinstance(b, (int, float))]
            if full:
                value, vbase = min(full, key=lambda p: p[0])
                if head_rec.get("backend") == "tpu":
                    vs_baseline = round(vbase / value, 3)
            elif done:
                value = min(m for m, _ in done)
    for name, rec in legs.items():
        if name != "headline":
            detail[name] = tag(rec, rec.get("data"))
    return {"metric": "fused_lamb_step_ms_bert_large", "value": value,
            "unit": "ms", "vs_baseline": vs_baseline, "backend": backend,
            "partial": True, "leg_timestamps": ts, "detail": detail}


def main(argv=None):  # pragma: no cover - thin CLI over assemble()
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("legs_dir")
    ap.add_argument("--kind", choices=("bench", "kernels"), default="bench")
    args = ap.parse_args(argv)
    print(json.dumps(assemble(args.legs_dir, args.kind)))


if __name__ == "__main__":  # pragma: no cover
    main()
