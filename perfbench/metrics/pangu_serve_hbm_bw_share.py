import statistics

from perfbench.harness import flops_pangu
from perfbench.metrics._pangu import is_pangu
from perfbench.metrics._util import peaks, program_runs, serve_work


def read(ctx):
    """Bytes a decode step must move (held weights once but the
    embedding, the live latent rows once a layer) over the decode
    program's device time, against the HBM peak."""
    pk = peaks(ctx)
    runs = program_runs(ctx, "decode_fn")
    w = ctx["window"]
    if pk is None or not runs or not w["decode_steps"] or not is_pangu(ctx):
        return None
    rows = (w["tokens_generated"] - w["prefill_rows"]) / w["decode_steps"]
    live = serve_work(ctx)["decode_ctx"] / w["decode_steps"]
    nbytes = flops_pangu.decode_step_bytes(ctx["cfg"], rows, live)
    return 100.0 * nbytes / statistics.median(runs) / pk["hbm_bytes_per_s"]
