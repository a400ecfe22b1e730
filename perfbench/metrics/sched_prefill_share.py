def read(ctx):
    w = ctx["window"]
    tot = w["prefill_ms"] + w["decode_ms"]
    return 100.0 * w["prefill_ms"] / tot if tot > 0 else None
