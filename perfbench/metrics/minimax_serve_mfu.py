import statistics

from perfbench.harness import flops_minimax
from perfbench.metrics._minimax import here_share, tiles_per_step
from perfbench.metrics._util import peaks, program_runs


def read(ctx):
    """Operations a decode step must do for this cut (the live rows
    through the layers with the share of picks that landed here, the
    head, the selected tiles' products, the indexer's page scores) over
    the decode program's device time, against the chip's bf16 peak: the
    whole decode step's share, the one a decode kernel of this cell is
    bounded by."""
    pk, share = peaks(ctx), here_share(ctx)
    runs = program_runs(ctx, "decode_fn")
    w = ctx["window"]
    tiles = tiles_per_step(ctx)
    if pk is None or share is None or not runs or tiles is None \
            or not w["decode_steps"]:
        return None
    rows = (w["tokens_generated"] - w["prefill_rows"]) / w["decode_steps"]
    f = flops_minimax.decode_step_flops(ctx["cfg"], rows, tiles[0],
                                        tiles[1], share)
    return 100.0 * f / statistics.median(runs) / pk["flops_per_s"]
