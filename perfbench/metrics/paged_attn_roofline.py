from perfbench.metrics._util import peaks, program_runs, serve_work, trace


def read(ctx):
    """Live K and V bytes of the traced decode steps (what the
    algorithm has to read, whatever implements it: the term that
    ``flops.decoder_decode_step_bytes`` adds to the weights) over the
    trace's Mosaic kernel time, against the HBM peak.  In its cell the
    decode kernel is the only Mosaic kernel that runs; a program
    without it has no kernel time there and the metric is left out."""
    pk, t = peaks(ctx), trace(ctx)
    runs = program_runs(ctx, "decode_fn")
    steps = ctx["window"]["decode_steps"]
    if pk is None or not runs or not steps or t["kernel_s"] <= 0:
        return None
    c = ctx["cfg"]
    hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    per_position = 2 * c["num_hidden_layers"] * c["num_key_value_heads"] \
        * hd * 2                                     # K and V, bf16
    live = serve_work(ctx)["decode_ctx"] / steps     # positions a step
    return (100.0 * live * per_position * len(runs) / t["kernel_s"]
            / pk["hbm_bytes_per_s"])
