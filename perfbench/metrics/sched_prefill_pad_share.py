from perfbench.metrics._spans import serve_spans


def read(ctx):
    """The share of prefilled positions that are padding: the chip
    multiplies ``batch x bucket`` positions a batch, ``tokens`` of them
    belong to a prompt."""
    rows = [r.args for r in serve_spans(ctx)
            if r.name == "serve.prefill.stage"]
    padded = sum(a["batch"] * a["bucket"] for a in rows)
    if not padded:
        return None
    return 100.0 * (1.0 - sum(a["tokens"] for a in rows) / padded)
