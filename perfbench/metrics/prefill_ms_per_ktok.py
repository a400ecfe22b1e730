from perfbench.metrics._util import program_runs


def read(ctx):
    """Device time of the prefill programs in the traced sub-window
    over the prompt tokens prefilled in it (the server's counter)."""
    runs = program_runs(ctx, "prefill_fn")
    tc = ctx.get("trace_counters")
    if not runs or not tc or not tc["prefill_tokens"]:
        return None
    return sum(runs) * 1e3 / (tc["prefill_tokens"] / 1e3)
