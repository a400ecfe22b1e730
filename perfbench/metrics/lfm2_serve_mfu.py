from perfbench.harness import flops_lfm2
from perfbench.metrics._lfm2 import is_lfm2
from perfbench.metrics._util import peaks, serve_work


def read(ctx):
    """Operations of the served work for this cut (four experts a
    token) over the window, against the chip's bf16 peak."""
    pk = peaks(ctx)
    if pk is None or not is_lfm2(ctx):
        return None
    w = serve_work(ctx)
    f = flops_lfm2.serve_flops(
        ctx["cfg"], w["prefill_tokens"], w["prefill_rows"],
        w["decode_tokens"], w["prefill_ctx"], w["decode_ctx"])
    return 100.0 * f / ctx["seconds"] / pk["flops_per_s"] if f else None
