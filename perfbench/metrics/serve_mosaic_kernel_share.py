from perfbench.metrics._util import trace


def read(ctx):
    """Device time in Mosaic (Pallas) custom calls over busy time; 0
    where no such kernel ran -- a fact worth a line."""
    t = trace(ctx)
    return 100.0 * t["kernel_s"] / t["busy_s"] if t else None
