"""What the MiniMax-M3 readers share (not a metric)."""
from __future__ import annotations

KERNEL = "paged_sparse_attention"


def is_minimax(ctx) -> bool:
    """Whether the cell's configuration is the block-sparse family
    these readers count."""
    return "msa_block" in ctx["cfg"].get("assumed", {})


def here_share(ctx):
    """Share of the routed picks that landed on the held experts, from
    the decode program's counter (the whole run's).  None where the
    program has no such counter."""
    st, c = ctx["stats_end"], ctx["cfg"]
    picks, steps = st.get("moe_picks_here"), st.get("decode_steps")
    if not picks or not steps or not is_minimax(ctx):
        return None
    from perfbench.harness.flops_minimax import n_moe_layers
    return picks / (steps * ctx["server"]["num_slots"] * n_moe_layers(c)
                    * c["num_experts_per_tok"])


def tiles_per_step(ctx):
    """(page, kv head) tiles the sparse decode read a step, summed over
    the layers, and the pages a dense read would have: the decode
    program's two counters over the whole run, or None where the
    program has none."""
    st = ctx["stats_end"]
    read, live = st.get("msa_pages_read"), st.get("msa_pages_live")
    steps = st.get("decode_steps")
    if not read or not live or not steps or not is_minimax(ctx):
        return None
    return read / steps, live / steps


def kernel_seconds(t):
    """Device time of the ``paged_sparse_attention`` kernel in the
    trace, found by its name: the trace's ``kernel_s`` while it is the
    only Mosaic kernel there, else the op's time among the trace's ten
    largest ops (0 where it is not among them: PERF.md, Open question
    D)."""
    names = t.get("mosaic_kernels") or []
    if names and all(KERNEL in n for n in names):
        return t["kernel_s"]
    return sum(s for name, s in t["device_ops"] if KERNEL in name)
