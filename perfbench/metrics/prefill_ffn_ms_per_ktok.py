from perfbench.metrics._scopes import prefill_ms_per_ktok


def read(ctx):
    """Device time under the ``moe`` and ``ffn`` scopes of the prefill
    programs per thousand prompt tokens."""
    return prefill_ms_per_ktok(ctx, ("moe", "ffn"))
