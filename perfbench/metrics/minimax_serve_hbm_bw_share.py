import statistics

from perfbench.harness import flops_minimax
from perfbench.metrics._minimax import tiles_per_step
from perfbench.metrics._util import peaks, program_runs


def read(ctx):
    """Bytes a decode step must move (held weights once but the
    embedding, the selected K/V tiles, the live pages' pooled keys)
    over the decode program's device time, against the HBM peak: the
    whole step's share."""
    pk = peaks(ctx)
    runs = program_runs(ctx, "decode_fn")
    w = ctx["window"]
    tiles = tiles_per_step(ctx)
    if pk is None or not runs or not w["decode_steps"] or tiles is None:
        return None
    c = ctx["cfg"]
    rows = (w["tokens_generated"] - w["prefill_rows"]) / w["decode_steps"]
    # the dense count is per kv head: a live page's pooled keys are one
    # vector a kv head
    nbytes = flops_minimax.decode_step_bytes(
        c, rows, tiles[0], tiles[1] / flops_minimax.n_layers(c))
    return 100.0 * nbytes / statistics.median(runs) / pk["hbm_bytes_per_s"]
