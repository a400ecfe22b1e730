from perfbench.metrics._scopes import decode_step_ms


def read(ctx):
    """Device time under the ``attn`` scope per decode step."""
    return decode_step_ms(ctx, ("attn",))
