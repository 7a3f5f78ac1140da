"""Layer: ``models`` (``glm4_moe_lite``).  The part of ``mla_time_share``
that is neither a flash kernel (``apex_flash_*``) nor matrix work
(``reduce.op_class``): the latent norms, RoPE on 64 of a head's 256 dims,
the key assembled from each head's own part and the ONE rotated part every
head shares, the (B, S, H, D) <-> (B·H, S, D) layout copies, the input norm
and residual — ``attention_glue_share``'s rule read on ``apex.mla``, over
busy time.  What an MLA-specific optimisation would move."""
from benchmarks import flash, reduce, scopes


def read(run):
    names = scopes.seen(run)
    if not names:
        return None
    return scopes.share(
        run.trace, lambda ev, path: "apex.mla" in scopes.blocks(path)
        and not flash.is_flash(ev) and reduce.op_class(ev) != "matmul",
        names) or None
