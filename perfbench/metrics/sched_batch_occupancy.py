def read(ctx):
    w = ctx["window"]
    if not w["decode_steps"]:
        return None
    decoded = w["tokens_generated"] - w["prefill_rows"]
    return 100.0 * decoded / (w["decode_steps"] * ctx["server"]["num_slots"])
