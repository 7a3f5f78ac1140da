#!/usr/bin/env python
"""Regression watchdog over the committed bench trajectory + goodput
artifacts (ISSUE 15 satellite).

Ingests the per-round bench artifacts (``BENCH_r*.json`` driver
wrappers and ``BENCH_TPU_r*.json`` raw captures), any
``GOODPUT*.json`` run ledgers, and any ``FLEET*.json`` multi-host
merges (``telemetry.fleet``: fleet goodput fraction + max straggler z
become series keyed by host count, so fleet-level drift fails stage 4b
the same way per-leg drift does), assembles per-leg metric series —
step time, throughput, MFU, goodput fraction — keyed by the leg's
config signature (model/batch/seq/layers: a config change starts a NEW
series, it is not a regression), and flags the newest point in each
series when it drifts beyond the tolerance band from the best prior
point.

Backend posture (the repo rule — ``bench.py`` nulls ``vs_baseline``
on CPU for the same reason): **TPU-backed drift fails the run**
(exit 1); CPU/unknown-backend drift is reported as a warning only —
the committed CPU trajectory carries environment noise that says
nothing about the product thesis.  ``--strict-cpu`` promotes CPU
drift to failing.  Schema-invalid goodput ledgers fail regardless of
backend: a ledger whose classes don't partition the wall is broken
accounting, not noise.

One JSON document on stdout with ``--json``; the human table otherwise.
Exit 0 = no drift, 1 = drift / invalid ledger, 2 = nothing to ingest.

No jax import, ever — this tool runs in CI; the goodput schema is file-loaded exactly like
``apply_perf_results`` loads the telemetry schema.
"""
from __future__ import annotations

import argparse
import glob as _glob
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: metric name -> True when LOWER is better
_LOWER_BETTER = {"step_ms": True, "value_ms": True,
                 "images_per_sec": False, "sequences_per_sec": False,
                 "mfu_pct": False, "mfu_analytic_pct": False,
                 "goodput_fraction": False,
                 "fleet_goodput_fraction": False,
                 "fleet_max_straggler_z": True}

_LEG_METRICS = ("step_ms", "images_per_sec", "sequences_per_sec",
                "mfu_pct", "mfu_analytic_pct")

#: leg-config fields that define a series identity: a round that
#: changed the model/shape starts a fresh series
_SIG_FIELDS = ("model", "batch", "seq", "layers", "arch", "chips",
               "global_batch")


def _schema_module(name):
    """File-load a telemetry module for its schema functions (no
    package import, no jax — the apply_perf_results posture)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        f"_apex_tpu_telemetry_{name}",
        os.path.join(REPO, "apex_tpu", "telemetry", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _goodput_schema():
    return _schema_module("goodput")


def _load(path):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        print(f"[bench_trend] cannot read {path}: {e}", file=sys.stderr)
        return None


def _artifact(doc):
    """Unwrap a driver round file (``{"parsed": {...}}``) to the bench
    artifact; raw artifacts pass through."""
    if isinstance(doc, dict) and isinstance(doc.get("parsed"), dict):
        return doc["parsed"]
    return doc if isinstance(doc, dict) else None


def _num(v):
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def _sig(leg: dict) -> str:
    parts = [f"{k}={leg[k]}" for k in _SIG_FIELDS if k in leg]
    return ",".join(parts) or "-"


def extract_points(artifact: dict, round_name: str):
    """``(series_key, backend, metric, value)`` rows for one artifact.
    The series key folds in the leg name, metric, backend, and the
    leg's config signature, so only like-for-like points compare."""
    rows = []
    backend = artifact.get("backend") or "unknown"
    val = artifact.get("value")
    if _num(val) and val > 0 and artifact.get("unit") == "ms":
        key = f"headline:{artifact.get('metric', 'value')}"
        rows.append((f"{key}|{backend}", backend, "value_ms", float(val)))
    detail = artifact.get("detail")
    if not isinstance(detail, dict):
        return rows

    def leg_rows(name, leg):
        lb = leg.get("_backend") or backend
        sig = _sig(leg)
        for m in _LEG_METRICS:
            if _num(leg.get(m)):
                rows.append((f"{name}:{m}|{lb}|{sig}", lb, m,
                             float(leg[m])))
        gp = leg.get("goodput") if name == "goodput" else None
        if isinstance(gp, dict) and _num(gp.get("goodput_fraction")):
            rows.append((f"goodput:goodput_fraction|{lb}", lb,
                         "goodput_fraction",
                         float(gp["goodput_fraction"])))

    for name, leg in detail.items():
        if isinstance(leg, dict):
            leg_rows(name, leg)
    return rows


def check_series(series: dict, tolerance: float):
    """Drift rows: the NEWEST point in each >=2-point series vs the
    best prior point, beyond the tolerance band."""
    drifts = []
    for key, points in sorted(series.items()):
        if len(points) < 2:
            continue
        metric = points[-1]["metric"]
        lower = _LOWER_BETTER.get(metric, metric.endswith("_ms"))
        prior = [p["value"] for p in points[:-1]]
        best = min(prior) if lower else max(prior)
        last = points[-1]["value"]
        if best <= 0:
            continue
        ratio = last / best
        bad = ratio > 1.0 + tolerance if lower else ratio < 1.0 - tolerance
        if bad:
            drifts.append({
                "series": key, "metric": metric,
                "backend": points[-1]["backend"],
                "best_prior": best, "last": last,
                "last_round": points[-1]["round"],
                "ratio": round(ratio, 4),
                "direction": "lower_better" if lower else "higher_better",
            })
    return drifts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dir", default=REPO,
                    help="directory holding the round artifacts")
    ap.add_argument("--glob", action="append", default=None,
                    help="round-artifact glob(s); default "
                         "BENCH_r*.json + BENCH_TPU_r*.json")
    ap.add_argument("--goodput-glob", default="GOODPUT*.json",
                    help="goodput run-artifact glob")
    ap.add_argument("--fleet-glob", default="FLEET*.json",
                    help="fleet merge-artifact glob (telemetry.fleet)")
    ap.add_argument("--tolerance", type=float, default=0.25,
                    help="allowed fractional drift before flagging")
    ap.add_argument("--strict-cpu", action="store_true",
                    help="CPU/unknown-backend drift fails too (default: "
                         "warning only — CPU stand-ins are noise)")
    ap.add_argument("--json", action="store_true",
                    help="print the machine-readable trend document")
    args = ap.parse_args(argv)

    globs = args.glob or ["BENCH_r*.json", "BENCH_TPU_r*.json"]
    paths = sorted(p for g in globs
                   for p in _glob.glob(os.path.join(args.dir, g)))
    series: dict = {}
    rounds = []
    for path in paths:
        art = _artifact(_load(path))
        if art is None:
            continue
        rnd = os.path.basename(path)
        rounds.append(rnd)
        for key, backend, metric, value in extract_points(art, rnd):
            series.setdefault(key, []).append(
                {"round": rnd, "backend": backend, "metric": metric,
                 "value": value})

    # standalone goodput run artifacts: schema-check every ledger and
    # fold the fractions into one series (ordered by ts, then name)
    schema = None
    ledger_violations = []
    gp_paths = sorted(_glob.glob(os.path.join(args.dir,
                                              args.goodput_glob)))
    gp_docs = []
    for path in gp_paths:
        doc = _load(path)
        if not isinstance(doc, dict):
            continue
        if schema is None:
            schema = _goodput_schema()
        bad = schema.goodput_violations(doc)
        name = os.path.basename(path)
        ledger_violations.extend(f"{name}: {v}" for v in bad)
        if not bad and _num(doc.get("goodput_fraction")):
            gp_docs.append((doc.get("ts") or "", name,
                            float(doc["goodput_fraction"])))
    for ts, name, frac in sorted(gp_docs):
        rounds.append(name)
        series.setdefault("goodput:artifact_fraction", []).append(
            {"round": name, "backend": "run", "metric":
             "goodput_fraction", "value": frac})

    # fleet merge artifacts (telemetry.fleet): the host count is the
    # series signature — a 2-host fleet and a 4-host fleet are
    # different configurations, not a regression — and the points are
    # the fleet goodput fraction + the worst straggler z, so a fleet
    # that starts wasting wall-clock or growing a straggler fails the
    # gate like any TPU-backed leg ("run"-backend, the goodput posture)
    fl_paths = [p for p in sorted(_glob.glob(os.path.join(
        args.dir, args.fleet_glob)))
        if not os.path.basename(p).startswith("FLEET_TRACE")]
    fl_docs = []
    fl_schema = _schema_module("fleet") if fl_paths else None
    for path in fl_paths:
        doc = _load(path)
        if not isinstance(doc, dict):
            continue
        name = os.path.basename(path)
        bad = fl_schema.fleet_violations(doc)
        ledger_violations.extend(f"{name}: {v}" for v in bad)
        if bad:
            continue
        fl_docs.append((doc.get("ts") or "", name, doc))
    for ts, name, doc in sorted(fl_docs, key=lambda t: (t[0], t[1])):
        rounds.append(name)
        sig = f"hosts={doc.get('n_hosts')}"
        frac = (doc.get("goodput") or {}).get("goodput_fraction")
        if _num(frac):
            series.setdefault(f"fleet:goodput_fraction|run|{sig}",
                              []).append(
                {"round": name, "backend": "run",
                 "metric": "fleet_goodput_fraction", "value": float(frac)})
        z = (doc.get("stragglers") or {}).get("max_z")
        if _num(z) and z > 0:
            series.setdefault(f"fleet:max_straggler_z|run|{sig}",
                              []).append(
                {"round": name, "backend": "run",
                 "metric": "fleet_max_straggler_z", "value": float(z)})

    drifts = check_series(series, args.tolerance)
    gate = ("tpu", "run") if not args.strict_cpu else None
    regressions = [d for d in drifts
                   if gate is None or d["backend"] in gate]
    warnings = [d for d in drifts if d not in regressions]

    doc = {
        "kind": "bench_trend",
        "version": 1,
        "rounds": rounds,
        "n_series": len(series),
        "tolerance": args.tolerance,
        "series": series,
        "regressions": regressions,
        "warnings": warnings,
        "ledger_violations": ledger_violations,
        "ok": not regressions and not ledger_violations,
    }
    if args.json:
        print(json.dumps(doc))
    else:
        print(f"bench trend: {len(rounds)} round(s), {len(series)} "
              f"series, tolerance {args.tolerance:.0%}")
        for key, points in sorted(series.items()):
            tail = " -> ".join(f"{p['value']:g}" for p in points[-4:])
            print(f"  {key:<56} {tail}")
        for d in regressions:
            print(f"  REGRESSION {d['series']}: best prior "
                  f"{d['best_prior']:g} -> {d['last']:g} "
                  f"({d['ratio']}x, {d['last_round']})")
        for d in warnings:
            print(f"  warning (non-TPU) {d['series']}: "
                  f"{d['best_prior']:g} -> {d['last']:g} ({d['ratio']}x)")
        for v in ledger_violations:
            print(f"  LEDGER SCHEMA: {v}")
    if not rounds:
        print("[bench_trend] nothing to ingest", file=sys.stderr)
        return 2
    return 0 if doc["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
