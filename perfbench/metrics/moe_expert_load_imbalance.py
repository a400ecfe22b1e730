def read(ctx):
    """Largest load over mean load of the held experts in a decode
    step's expert layers (1 = even), from the decode program's two
    counters over the whole run."""
    st = ctx["stats_end"]
    picks, top = st.get("moe_picks_here"), st.get("moe_max_expert_load")
    if not picks or not top:
        return None
    return top * ctx["cfg"]["num_experts"] / picks
