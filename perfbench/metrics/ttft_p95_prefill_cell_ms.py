from perfbench.harness.stats import percentile


def read(ctx):
    """The prefill cell's TTFT tail: too unsteady from seed to seed to
    carry a bound (which prompts share a prefill batch moves it by a
    fifth), so it stands here, beside the rate it should move."""
    v = percentile(ctx["ttft"], 95)
    return None if v is None else v * 1e3
