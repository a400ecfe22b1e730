def read(ctx):
    w = ctx["window"]
    ms = ctx["seconds"] * 1e3
    return 100.0 * max(ms - w["prefill_ms"] - w["decode_ms"], 0.0) / ms
