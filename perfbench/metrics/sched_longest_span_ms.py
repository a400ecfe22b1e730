from perfbench.harness.common import log
from perfbench.metrics._spans import ms, serve_spans


def read(ctx):
    """The longest single phase that is work: no step rows, and not
    ``serve.idle`` (the wait for a request when none is in flight)."""
    rows = [r for r in serve_spans(ctx)
            if "." in r.name and r.name != "serve.idle"]
    if not rows:
        return None
    r = max(rows, key=ms)
    log(f"longest scheduler phase: {r.name} {ms(r):.1f} ms in step "
        f"{r.step}, {r.t_start - ctx['t0']:.3f} s into the window, "
        f"args {str(r.args)[:200]}")
    return ms(r)
