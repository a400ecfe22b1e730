from perfbench.harness import flops_pangu
from perfbench.metrics._pangu import here_share
from perfbench.metrics._util import peaks, serve_work


def read(ctx):
    """Operations of the served work for this cut (expert terms by the
    share of picks that landed here; attention pairs expanded in
    prefill, absorbed in decode) over the window, against the chip's
    bf16 peak."""
    pk, share = peaks(ctx), here_share(ctx)
    if pk is None or share is None:
        return None
    w = serve_work(ctx)
    f = flops_pangu.serve_flops(
        ctx["cfg"], w["prefill_tokens"], w["prefill_rows"],
        w["decode_tokens"], w["prefill_ctx"], w["decode_ctx"], share)
    return 100.0 * f / ctx["seconds"] / pk["flops_per_s"] if f else None
