from perfbench.harness import flops_lfm2
from perfbench.metrics._lfm2 import is_lfm2
from perfbench.metrics._util import peaks, program_runs, serve_work, trace


def read(ctx):
    """Live K and V bytes of the traced decode steps, attention layers
    only, 64 lanes a head (what the algorithm has to read, whatever the
    kernel reads beyond it) over the device time of the
    ``paged_attention`` kernel, against the HBM peak.  Where it is the
    trace's only Mosaic kernel (this cell: prefill buckets under 1,024
    attend in plain XLA) the time is the trace's ``kernel_s``; beside
    other kernels it is read from the trace's ten largest ops, and a
    kernel that is not among them leaves the metric out (PERF.md, Open
    question D)."""
    pk, t = peaks(ctx), trace(ctx)
    runs = program_runs(ctx, "decode_fn")
    steps = ctx["window"]["decode_steps"]
    if pk is None or not runs or not steps or not is_lfm2(ctx):
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
    nbytes = flops_lfm2.kv_read_bytes(ctx["cfg"], live) * len(runs)
    return 100.0 * nbytes / kernel_s / pk["hbm_bytes_per_s"]
