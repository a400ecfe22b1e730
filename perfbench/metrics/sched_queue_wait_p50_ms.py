from perfbench.harness.stats import percentile
from perfbench.metrics._spans import serve_spans


def read(ctx):
    return percentile([w for r in serve_spans(ctx)
                       if r.name == "serve.admit"
                       for w in r.args.get("queue_wait_ms", ())], 50)
