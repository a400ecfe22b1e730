from perfbench.harness.stats import percentile


def read(ctx):
    """The Pangu decode cell's TTFT tail: a request waits for the
    decode step in flight and then its prefill call of up to two
    prompts of a few thousand tokens, and which prompts share a call
    moves the p95 from seed to seed (spread 0.16 and 0.24 over two
    sets of six); it carries no bound and stands here, beside the tail
    between tokens, which waits out the same prefill calls and carries
    the cell's."""
    v = percentile(ctx["ttft"], 95)
    return None if v is None else v * 1e3
