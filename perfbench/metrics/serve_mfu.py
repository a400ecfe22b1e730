from perfbench.harness import flops
from perfbench.metrics._util import peaks, serve_work


def read(ctx):
    pk = peaks(ctx)
    if pk is None:
        return None
    w = serve_work(ctx)
    f = flops.decoder_serve_flops(
        ctx["cfg"], w["prefill_tokens"], w["prefill_rows"],
        w["decode_tokens"], w["prefill_ctx"], w["decode_ctx"])
    return 100.0 * f / ctx["seconds"] / pk["flops_per_s"] if f else None
