import statistics

from perfbench.harness import flops_lfm2
from perfbench.metrics._lfm2 import is_lfm2
from perfbench.metrics._util import peaks, program_runs, serve_work


def read(ctx):
    """Bytes a decode step must move (held weights once, the live K
    and V of the attention layers, the live rows' convolution tails
    read and written) over the decode program's device time, against
    the HBM peak."""
    pk = peaks(ctx)
    runs = program_runs(ctx, "decode_fn")
    w = ctx["window"]
    if pk is None or not runs or not w["decode_steps"] or not is_lfm2(ctx):
        return None
    rows = (w["tokens_generated"] - w["prefill_rows"]) / w["decode_steps"]
    live = serve_work(ctx)["decode_ctx"] / w["decode_steps"]
    nbytes = flops_lfm2.decode_step_bytes(ctx["cfg"], rows, live)
    return 100.0 * nbytes / statistics.median(runs) / pk["hbm_bytes_per_s"]
