"""What the three Kimi-Linear readers share (not a metric)."""
from __future__ import annotations


def here_share(ctx):
    """Share of the routed picks that landed on the held experts, from
    the decode program's counter (the whole run's: the window's delta
    is not among the runner's counters).  None where the program has
    no such counter."""
    st, c = ctx["stats_end"], ctx["cfg"]
    picks, steps = st.get("moe_picks_here"), st.get("decode_steps")
    if not picks or not steps or "linear_attn_config" not in c:
        return None
    from perfbench.harness.flops_kimi import layer_kinds
    n_moe = sum(1 for _, moe in layer_kinds(c) if moe)
    return picks / (steps * ctx["server"]["num_slots"] * n_moe
                    * c["num_experts_per_token"])
