"""What the openPangu-Ultra-MoE readers share (not a metric)."""
from __future__ import annotations


def is_pangu(ctx) -> bool:
    """Whether the cell's configuration is the sandwich-norm latent
    family these readers count."""
    return "sandwich_norm" in ctx["cfg"]


def here_share(ctx):
    """Share of the routed picks that landed on the held experts, from
    the decode program's counter (the whole run's: the window's delta
    is not among the runner's counters).  None where the program has
    no such counter."""
    st, c = ctx["stats_end"], ctx["cfg"]
    picks, steps = st.get("moe_picks_here"), st.get("decode_steps")
    if not picks or not steps or not is_pangu(ctx):
        return None
    from perfbench.harness.flops_pangu import n_moe_layers
    return picks / (steps * ctx["server"]["num_slots"] * n_moe_layers(c)
                    * c["num_experts_per_tok"])


def kernel_seconds(t):
    """Device time of the ``paged_attention`` kernel in the trace: the
    trace's ``kernel_s`` while it is the only Mosaic kernel there, else
    the op's time among the trace's ten largest ops (0 where it is not
    among them: PERF.md, Open question D)."""
    names = t.get("mosaic_kernels") or []
    if names and all("paged_attention" in n for n in names):
        return t["kernel_s"]
    return sum(s for name, s in t["device_ops"] if "paged_attention" in name)
