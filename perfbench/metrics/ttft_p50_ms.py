from perfbench.harness.stats import percentile


def read(ctx):
    v = percentile(ctx["ttft"], 50)
    return None if v is None else v * 1e3
