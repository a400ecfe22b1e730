from perfbench.metrics._scopes import decode_step_ms


def read(ctx):
    """Device time under the ``mixer`` scope (a token mixer that is not
    softmax attention) per decode step."""
    return decode_step_ms(ctx, ("mixer",))
