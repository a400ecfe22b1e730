import statistics

from perfbench.harness import flops
from perfbench.metrics._util import peaks, program_runs, serve_work


def read(ctx):
    """Bytes a decode step must read (weights once + live K and V)
    over the decode program's device time, against the HBM peak."""
    pk = peaks(ctx)
    runs = program_runs(ctx, "decode_fn")
    steps = ctx["window"]["decode_steps"]
    if pk is None or not runs or not steps:
        return None
    live = serve_work(ctx)["decode_ctx"] / steps
    nbytes = flops.decoder_decode_step_bytes(ctx["cfg"], live)
    return 100.0 * nbytes / statistics.median(runs) / pk["hbm_bytes_per_s"]
