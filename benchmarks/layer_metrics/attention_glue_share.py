"""Layer: ``models``.  Self time under ``apex.attn`` that is neither a flash
kernel (``apex_flash_*``) nor matrix work (``reduce.op_class``): the
transposes between (B, S, H, hd) and (B, H, S, hd), layout copies, pads, the
sum of the fused backward's partials, layer norm and residual — over busy
time.  The log line says how much of it lies under ``apex.flash`` (the
kernel's own wrapping) and how much directly under ``apex.attn`` (the
model's)."""
from benchmarks import flash, reduce, scopes


def _glue(ev, path):
    return ("apex.attn" in scopes.blocks(path) and not flash.is_flash(ev)
            and reduce.op_class(ev) != "matmul")


def read(run):
    names = scopes.seen(run)
    if not names:
        return None
    glue = scopes.share(run.trace, _glue, names)
    kernels = scopes.share(run.trace, lambda ev, path: _glue(ev, path)
                           and "apex.flash" in scopes.blocks(path), names)
    print(f"[bench] attention glue: {glue:.2f} % of busy, of it "
          f"{kernels:.2f} under apex.flash and {glue - kernels:.2f} directly "
          "under apex.attn", flush=True)
    return glue
