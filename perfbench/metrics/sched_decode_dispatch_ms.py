from perfbench.metrics._spans import decode_phase_p50_ms


def read(ctx):
    return decode_phase_p50_ms(ctx, ("serve.decode.dispatch",))
