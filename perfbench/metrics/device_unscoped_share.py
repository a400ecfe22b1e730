import json

from perfbench.harness.common import log
from perfbench.harness.op_scopes import UNSCOPED
from perfbench.metrics._scopes import tables
from perfbench.metrics._util import trace


def read(ctx):
    """100 x the self time under no scope over all self time of the
    ``decode_fn`` and ``prefill_fn`` runs in the trace: how much of the
    step programs the block readers cannot see.  Logs the whole table
    (every program, every scope), the largest ops under no scope and
    the device's idle time by scheduler phase."""
    tabs = tables(ctx, "decode_fn") + tables(ctx, "prefill_fn")
    if not tabs:
        return None
    t = trace(ctx)
    log("device seconds by scope " + json.dumps(
        {k: t.get(k) for k in ("scopes", "unscoped_ops", "idle_by_phase")}))
    total = sum(sum(v.values()) for v in tabs)
    return 100.0 * sum(v.get(UNSCOPED, 0.0) for v in tabs) / total \
        if total else None
