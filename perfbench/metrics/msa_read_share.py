from perfbench.metrics._minimax import tiles_per_step


def read(ctx):
    """The (page, kv head) tiles the sparse decode read over those a
    dense read of every live page would have, in percent, from the
    decode program's two counters over the run (19 of ~270 pages at
    the cell's contexts: ~7)."""
    tiles = tiles_per_step(ctx)
    return None if tiles is None else 100.0 * tiles[0] / tiles[1]
