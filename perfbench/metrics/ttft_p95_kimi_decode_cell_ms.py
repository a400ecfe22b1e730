from perfbench.harness.stats import percentile


def read(ctx):
    """The Kimi decode cell's TTFT tail: a request waits one decode
    step and then its prefill batch, and which prompts share a batch
    moves the p95 by a tenth from seed to seed (spreads 0.041 and 0.117
    over two sets; half its bound admits 0.04), so it carries no bound
    and stands here, beside the rate it should move."""
    v = percentile(ctx["ttft"], 95)
    return None if v is None else v * 1e3
