"""``pyprof.prof`` analog — FLOPs/bytes attribution for a compiled step.

The reference's ``apex/pyprof/prof`` (25 modules, ~2.5k LoC — ``prof.py``,
``blas.py:340``, ``conv.py:236``, ``pointwise.py`` ...) maps captured GPU
kernels back to torch ops and hand-computes FLOPs/bytes per op class so the
user can see arithmetic intensity and utilisation.  On TPU that bookkeeping
is owned by the compiler: XLA's cost analysis knows the FLOPs and the bytes
touched of the *whole optimized module* (post-fusion — i.e. what actually
runs), so the analog is a report over a compiled function rather than a
SQLite kernel dump.

    from apex_tpu.pyprof import prof
    rep = prof.cost_report(train_step, state, batch)
    print(prof.format_report(rep))

``cost_report`` compiles (AOT, via ``jax.jit(fn).lower(...).compile()``) and
reads ``cost_analysis()`` + ``memory_analysis()``; it never executes the
function.  Derived metrics mirror the reference's tables:

    flops              total floating-point ops of the optimized HLO
    bytes_accessed     HBM traffic the cost model attributes to the module
    arithmetic_intensity   flops / bytes_accessed (roofline x-coordinate)
    projected_ms       max(flops/peak_flops, bytes/peak_bw) — the roofline
                       lower bound for the given hardware ceilings
    *_bytes            temp/argument/output/generated-code allocation sizes

CLI (profiles the flagship transformer train step, the analog of running
``python -m apex.pyprof.prof net.sql``):

    python -m apex_tpu.pyprof.prof [--layers N] [--batch B] [--seq S]
"""
from __future__ import annotations

import os
from typing import Any, Callable

import jax

# Per-chip ceilings used for the roofline projection when the caller does
# not pass their own.  Public figures (jax-ml.github.io/scaling-book):
#   v4  275 bf16 TFLOP/s, 1228 GB/s HBM, 32 GB, ~45 GB/s/link ICI
#   v5e 197 bf16 TFLOP/s,  819 GB/s HBM, 16 GB, ~45 GB/s/link ICI
#   v5p 459 bf16 TFLOP/s, 2765 GB/s HBM, 95 GB, ~90 GB/s/link ICI
# ``ici_bw`` is the one-way per-neighbor link bandwidth the planner's
# alpha-beta collective model divides wire bytes by; ``ici_alpha_s`` the
# per-hop launch latency; ``hbm_bytes`` the capacity its feasibility
# check prunes against.  A TPU's row is chosen by its ``device_kind``
# (``DEVICE_KIND_ROWS``), never by the bare platform: one generation's
# peaks under another's name would make every roofline share wrong.  CPU
# gets token entries so reports/tests stay meaningful (its "ici" is the
# host-memory shuffle an emulated mesh pays).
#: ``dcn_bw``/``dcn_alpha_s`` are the inter-slice data-center-network
#: tier the planner's multi-slice terms charge when a collective axis
#: spans slices (``num_slices`` — detected from device.slice_index or
#: pinned via the env): ~25 GB/s per host and tens-of-microseconds
#: launch latency on current pods (public multislice figures); the CPU
#: row keeps DCN == ICI so single-host emulation is unchanged.
HW_CEILINGS = {
    "tpu_v4": {"peak_flops": 275e12, "peak_bw": 1228e9,
               "ici_bw": 45e9, "ici_alpha_s": 1e-6, "hbm_bytes": 32e9,
               "dcn_bw": 25e9, "dcn_alpha_s": 1e-5},
    "tpu_v5e": {"peak_flops": 197e12, "peak_bw": 819e9,
                "ici_bw": 45e9, "ici_alpha_s": 1e-6, "hbm_bytes": 16e9,
                "dcn_bw": 25e9, "dcn_alpha_s": 1e-5},
    "tpu_v5p": {"peak_flops": 459e12, "peak_bw": 2765e9,
                "ici_bw": 90e9, "ici_alpha_s": 1e-6, "hbm_bytes": 95e9,
                "dcn_bw": 25e9, "dcn_alpha_s": 1e-5},
    # CPU models the 8-device EMULATED mesh tier-1 runs on, not the
    # host's datasheet: effective bandwidth and per-collective launch
    # cost are dominated by XLA's threaded emulation (calibrated
    # against the measured flagship dp-family A/B in test_plan.py —
    # the planner's relative predictions there land within ~15%)
    "cpu": {"peak_flops": 1e11, "peak_bw": 2e10,
            "ici_bw": 1e10, "ici_alpha_s": 5e-5, "hbm_bytes": 64e9,
            "dcn_bw": 1e10, "dcn_alpha_s": 5e-5},
    "gpu": {"peak_flops": 1e14, "peak_bw": 1e12,
            "ici_bw": 300e9, "ici_alpha_s": 1e-6, "hbm_bytes": 80e9,
            "dcn_bw": 50e9, "dcn_alpha_s": 1e-5},
}

#: ``jax.Device.device_kind`` -> ``HW_CEILINGS`` row, for the TPU
#: generations the table carries.  A kind missing here is an error in
#: :func:`ceilings_row`, not a default.
DEVICE_KIND_ROWS = {
    "TPU v4": "tpu_v4",
    "TPU v5 lite": "tpu_v5e",
    "TPU v5e": "tpu_v5e",
    "TPU v5": "tpu_v5p",
    "TPU v5p": "tpu_v5p",
}

#: every key a ceilings row may carry (the APEX_TPU_CEILINGS grammar
#: rejects anything else — a typo'd override must fail loudly, not
#: silently leave the device's row in place).  ``num_slices`` is
#: topology, not silicon, but rides the same override surface so a
#: session can pin the multislice fact a CPU-side planner can't detect.
CEILING_KEYS = ("peak_flops", "peak_bw", "ici_bw", "ici_alpha_s",
                "hbm_bytes", "dcn_bw", "dcn_alpha_s", "num_slices")

ENV_CEILINGS = "APEX_TPU_CEILINGS"


def ceilings_row(device=None) -> str:
    """The ``HW_CEILINGS`` row name for ``device``: a ``jax.Device``
    (default ``jax.devices()[0]``), a ``device_kind`` string, or a row
    name.  A TPU resolves through its ``device_kind``; other backends
    through their platform.  A device the table does not carry raises
    ``ValueError`` — ceilings borrowed from another chip are worse than
    none."""
    if device is None:
        device = jax.devices()[0]
    if isinstance(device, str):
        kind = device
    else:
        kind = (device.device_kind if device.platform == "tpu"
                else device.platform)
    row = kind if kind in HW_CEILINGS else DEVICE_KIND_ROWS.get(kind)
    if row is None:
        raise ValueError(
            f"no hardware ceilings for device {kind!r} (rows: "
            f"{tuple(sorted(HW_CEILINGS))}; TPU device kinds: "
            f"{tuple(sorted(DEVICE_KIND_ROWS))}) — add its published "
            "peaks to pyprof.prof.HW_CEILINGS / DEVICE_KIND_ROWS")
    return row


def resolve_ceilings(device="cpu") -> dict:
    """The ceilings row for ``device`` (anything :func:`ceilings_row`
    accepts), with the documented ``APEX_TPU_CEILINGS`` override
    applied.  Grammar (comma-separated tokens, applied left to right)::

        APEX_TPU_CEILINGS="v5p"                      # named generation row
        APEX_TPU_CEILINGS="peak_flops=2.75e14"       # key override
        APEX_TPU_CEILINGS="v4,ici_bw=5e10"           # row, then override

    A bare token names an ``HW_CEILINGS`` row (``v4``/``v5e``/``v5p``
    shorthands resolve to their ``tpu_*`` rows); ``key=value`` tokens
    override individual ceilings."""
    base = dict(HW_CEILINGS[ceilings_row(device)])
    spec = os.environ.get(ENV_CEILINGS, "").strip()
    for tok in filter(None, (t.strip() for t in spec.split(","))):
        if "=" in tok:
            key, _, val = tok.partition("=")
            key = key.strip()
            if key not in CEILING_KEYS:
                raise ValueError(
                    f"{ENV_CEILINGS}: unknown ceiling {key!r} "
                    f"(known: {CEILING_KEYS})")
            base[key] = float(val)
        else:
            name = tok if tok in HW_CEILINGS else f"tpu_{tok}"
            if name not in HW_CEILINGS:
                raise ValueError(
                    f"{ENV_CEILINGS}: unknown ceilings row {tok!r} "
                    f"(known: {tuple(sorted(HW_CEILINGS))})")
            base.update(HW_CEILINGS[name])
    return base


def _first(d: Any, *keys, default=0.0):
    """cost_analysis() key names drift across jax versions; try aliases."""
    if not d:
        return default
    for k in keys:
        v = d.get(k)
        if v is not None:
            return float(v)
    return default


def cost_report(fn: Callable, *args,
                static_argnums=(), donate_argnums=(),
                peak_flops: float | None = None,
                peak_bw: float | None = None,
                **kwargs) -> dict:
    """Compile ``fn(*args, **kwargs)`` and return its cost/memory analysis.

    Purely ahead-of-time: the function is lowered and compiled but NOT run
    (the reference's prof likewise post-processes, it never re-executes).
    """
    jitted = jax.jit(fn, static_argnums=static_argnums,
                     donate_argnums=donate_argnums)
    compiled = jitted.lower(*args, **kwargs).compile()

    try:
        cost = compiled.cost_analysis()
    except Exception:   # pragma: no cover - backend without cost model
        cost = None
    try:
        mem = compiled.memory_analysis()
    except Exception:   # pragma: no cover
        mem = None

    platform = jax.devices()[0].platform
    ceil = resolve_ceilings(jax.devices()[0])
    pf = peak_flops or ceil["peak_flops"]
    pb = peak_bw or ceil["peak_bw"]

    flops = _first(cost, "flops")
    byts = _first(cost, "bytes accessed", "bytes_accessed")
    rep = {
        "platform": platform,
        # a backend's compiled cost_analysis can come back empty/keyless
        # — flag it so a 0-FLOPs report reads as "no cost data from this
        # backend", not "this program does nothing"
        "cost_data_available": bool(flops or byts),
        "flops": flops,
        "bytes_accessed": byts,
        "transcendentals": _first(cost, "transcendentals"),
        "arithmetic_intensity": (flops / byts) if byts else 0.0,
        "projected_ms": 1e3 * max(flops / pf, byts / pb) if (flops or byts)
                        else 0.0,
        "peak_flops": pf,
        "peak_bw": pb,
    }
    for name, attr in (("temp_bytes", "temp_size_in_bytes"),
                       ("argument_bytes", "argument_size_in_bytes"),
                       ("output_bytes", "output_size_in_bytes"),
                       ("code_bytes", "generated_code_size_in_bytes")):
        rep[name] = float(getattr(mem, attr, 0) or 0) if mem else 0.0
    return rep


def _human(n: float, unit: str = "") -> str:
    for scale, suffix in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "K")):
        if abs(n) >= scale:
            return f"{n / scale:.2f} {suffix}{unit}"
    return f"{n:.0f} {unit}"


def format_report(rep: dict) -> str:
    """The reference's summary table (`prof/output.py`) shape, one module."""
    lines = [
        f"platform            {rep['platform']}",
        f"flops               {_human(rep['flops'], 'FLOP')}",
        f"bytes accessed      {_human(rep['bytes_accessed'], 'B')}",
        f"arith intensity     {rep['arithmetic_intensity']:.1f} FLOP/B",
        f"roofline projection {rep['projected_ms']:.3f} ms  "
        f"(ceilings: {_human(rep['peak_flops'], 'FLOP/s')}, "
        f"{_human(rep['peak_bw'], 'B/s')})",
        f"temp / args / out   {_human(rep['temp_bytes'], 'B')} / "
        f"{_human(rep['argument_bytes'], 'B')} / "
        f"{_human(rep['output_bytes'], 'B')}",
    ]
    return "\n".join(lines)


def measured_vs_projected(fn: Callable, *args, iters: int = 10,
                          static_argnums=(), donate_argnums=(),
                          peak_flops: float | None = None,
                          peak_bw: float | None = None,
                          **kwargs) -> dict:
    """Run the compiled fn and report measured ms next to the roofline
    projection (utilisation = projected/measured) — the reference's
    'TC utilisation' column analog.  Only ``kwargs`` not named here are
    forwarded to ``fn``."""
    import time
    rep = cost_report(fn, *args, static_argnums=static_argnums,
                      peak_flops=peak_flops, peak_bw=peak_bw, **kwargs)
    # donation is excluded from the timed executable: a donated arg could
    # only be passed once, and re-lowering without it keeps `args` reusable
    # across the `iters` calls below
    jitted = jax.jit(fn, static_argnums=static_argnums)
    out = jitted(*args, **kwargs)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jitted(*args, **kwargs)
    jax.block_until_ready(out)
    ms = (time.perf_counter() - t0) / iters * 1e3
    rep["measured_ms"] = ms
    rep["utilisation"] = (rep["projected_ms"] / ms) if ms else 0.0
    return rep


def _main():   # pragma: no cover - exercised via CLI
    import argparse

    import jax.numpy as jnp

    from apex_tpu import amp
    from apex_tpu.models import (TransformerConfig, transformer_init,
                                 transformer_loss)
    from apex_tpu.optimizers import FusedAdam

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--d-model", type=int, default=256)
    p.add_argument("--run", action="store_true",
                   help="also execute and report measured ms + utilisation")
    args = p.parse_args()

    cfg = TransformerConfig(vocab_size=1024, max_len=args.seq,
                            num_layers=args.layers, d_model=args.d_model,
                            num_heads=4, d_ff=4 * args.d_model,
                            dtype=jnp.bfloat16)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    state = amp.initialize(params, FusedAdam(lr=1e-4), opt_level="O5",
                           verbosity=0)
    batch = {"tokens": jnp.zeros((args.batch, args.seq), jnp.int32),
             "targets": jnp.zeros((args.batch, args.seq), jnp.int32)}

    def train_step(state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: amp.scale_loss(
                transformer_loss(p, batch, cfg), state))(state.model_params)
        return amp.amp_step(state, grads), loss

    fn = measured_vs_projected if args.run else cost_report
    rep = fn(train_step, state, batch)
    print(format_report(rep))
    if args.run:
        print(f"measured            {rep['measured_ms']:.3f} ms"
              f"  ({100 * rep['utilisation']:.1f}% of roofline)")


if __name__ == "__main__":   # pragma: no cover
    from ..utils.platform import enable_compile_cache
    enable_compile_cache()
    _main()
