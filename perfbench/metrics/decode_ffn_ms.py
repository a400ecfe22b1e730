from perfbench.metrics._scopes import decode_step_ms


def read(ctx):
    """Device time under the ``moe`` and ``ffn`` scopes per decode
    step."""
    return decode_step_ms(ctx, ("moe", "ffn"))
