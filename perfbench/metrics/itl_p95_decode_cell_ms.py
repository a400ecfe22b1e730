from perfbench.harness.stats import percentile


def read(ctx):
    """The decode cell's inter-token tail: it sits on the edge between
    plain decode steps and steps that waited for a prefill batch, and
    swings 6-8% from run to run -- too unsteady to carry a bound, so it
    stands here, beside the rate it should move."""
    v = percentile(ctx["itl"], 95)
    return None if v is None else v * 1e3
