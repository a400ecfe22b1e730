from perfbench.metrics._spans import DECODE_STEP_MARK, serve_spans


def read(ctx):
    """100 x the share of the window's decode steps that were
    dispatched while the step before them was still unread: the
    ``overlapped`` count on the step's ``serve.decode.dispatch`` row
    (ISSUE 32).  Decode steps as ``_spans.decode_phase_p50_ms`` knows
    them: one cut by an edge of the window is left out.  A program from
    before the pipeline writes no such count and reads 0."""
    whole, overlapped = set(), {}
    for r in serve_spans(ctx):
        step = (getattr(r, "tl", 0), r.step)
        if r.name == "serve":
            whole.add(step)
        elif r.name == DECODE_STEP_MARK:
            overlapped[step] = bool(r.args.get("overlapped"))
    steps = [overlapped[s] for s in whole & set(overlapped)]
    return 100.0 * sum(steps) / len(steps) if steps else None
