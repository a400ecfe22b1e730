from perfbench.metrics._util import trace


def read(ctx):
    t = trace(ctx)
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"]) if t else None
