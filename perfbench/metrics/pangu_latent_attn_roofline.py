from perfbench.harness import flops_pangu
from perfbench.metrics._pangu import is_pangu, kernel_seconds
from perfbench.metrics._util import peaks, program_runs, serve_work, trace


def read(ctx):
    """The least time the chip could take for the traced decode steps'
    latent attention (for every live position and layer the LARGER of
    the absorbed products, 278,528 FLOP at the MXU's peak, and the
    latent row, 1,152 B at the HBM's: at 128 heads the two meet) over
    the device time of the ``paged_attention`` kernel.  What the kernel
    does beyond that (640 lanes for 576, the pool read a second time as
    V, the weights' bf16 halves) is in the time and not in the count,
    so a kernel at either peak reads 100."""
    pk, t = peaks(ctx), trace(ctx)
    runs = program_runs(ctx, "decode_fn")
    steps = ctx["window"]["decode_steps"]
    if pk is None or not runs or not steps or not is_pangu(ctx):
        return None
    kernel_s = kernel_seconds(t)
    if kernel_s <= 0:
        return None
    live = serve_work(ctx)["decode_ctx"] / steps     # positions a step
    least, _ = flops_pangu.latent_attn_seconds(
        ctx["cfg"], live, pk["flops_per_s"], pk["hbm_bytes_per_s"])
    return 100.0 * least * len(runs) / kernel_s
