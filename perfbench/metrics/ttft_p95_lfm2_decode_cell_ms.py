from perfbench.harness.stats import percentile


def read(ctx):
    """The LFM2 decode cell's TTFT tail: a request waits for the decode
    step in flight and then its prefill batch, each of which streams
    the experts' 8.5 GB, and which prompts share a batch moves the p95
    from seed to seed; no two sets of runs hold its spread against half
    the bound yet, so it carries no bound and stands here, beside the
    rate it should move."""
    v = percentile(ctx["ttft"], 95)
    return None if v is None else v * 1e3
