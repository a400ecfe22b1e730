from perfbench.metrics._util import median_ms, program_runs


def read(ctx):
    return median_ms(program_runs(ctx, "decode_fn"))
