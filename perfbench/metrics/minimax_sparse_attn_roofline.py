from perfbench.harness import flops_minimax
from perfbench.metrics._minimax import (is_minimax, kernel_seconds,
                                        tiles_per_step)
from perfbench.metrics._util import peaks, program_runs, trace


def read(ctx):
    """The least time the chip could take for the traced decode steps'
    block-sparse attention (for every selected (page, kv head) tile the
    LARGER of its 64 KB of K and V at the HBM's peak and its 1 MFLOP of
    products at the MXU's: the bytes bound) over the device time of the
    ``paged_sparse_attention`` kernel, found by its name.  The tiles a
    step are the decode program's counter over the run; a row's own
    partial page counts whole (the kernel's unit of selection), and the
    padding of a short list with the trash page does not count."""
    pk, t = peaks(ctx), trace(ctx)
    runs = program_runs(ctx, "decode_fn")
    tiles = tiles_per_step(ctx)
    if pk is None or not runs or tiles is None or not is_minimax(ctx):
        return None
    kernel_s = kernel_seconds(t)
    if kernel_s <= 0:
        return None
    least, _ = flops_minimax.sparse_attn_seconds(
        ctx["cfg"], tiles[0], pk["flops_per_s"], pk["hbm_bytes_per_s"])
    return 100.0 * least * len(runs) / kernel_s
