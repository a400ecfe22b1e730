from perfbench.harness import flops_kimi
from perfbench.metrics._util import peaks, program_runs, serve_work, trace


def read(ctx):
    """Latent bytes the traced decode steps have to read (each live
    token's latent row once a latent layer, whatever the kernel reads
    beyond that: padded lanes, the pool a second time as V) over the
    device time of the ``paged_attention`` kernel, against the HBM
    peak.  Where it is the trace's only Mosaic kernel (this cell since
    the grouped expert product is plain XLA) the time is the trace's
    ``kernel_s``, as ``paged_attn_roofline`` reads it; beside other
    kernels it is read from the trace's ten largest ops, and a kernel
    that is not among them leaves the metric out (PERF.md, Open
    question D)."""
    pk, t = peaks(ctx), trace(ctx)
    runs = program_runs(ctx, "decode_fn")
    steps = ctx["window"]["decode_steps"]
    if pk is None or not runs or not steps \
            or "linear_attn_config" not in ctx["cfg"]:
        return None
    names = t.get("mosaic_kernels") or []
    if names and all("paged_attention" in n for n in names):
        kernel_s = t["kernel_s"]
    else:
        kernel_s = sum(s for name, s in t["device_ops"]
                       if "paged_attention" in name)
    if kernel_s <= 0:
        return None
    live = serve_work(ctx)["decode_ctx"] / steps     # positions a step
    nbytes = flops_kimi.latent_read_bytes(ctx["cfg"], live) * len(runs)
    return 100.0 * nbytes / kernel_s / pk["hbm_bytes_per_s"]
