import statistics


def read(ctx):
    return statistics.median(ctx["step_s"]) * 1e3 if ctx["step_s"] else None
