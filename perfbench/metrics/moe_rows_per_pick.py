def read(ctx):
    """Row-products the expert layers did for every pick that landed
    on a held expert (rows x the experts each was multiplied by, over
    the picks), from the decode program's counters over the whole run:
    ``num_experts / num_experts_per_tok`` (8 here) means the masked
    dense pass, 1 is the least any form can do."""
    st = ctx["stats_end"]
    rows, picks = st.get("moe_rows_multiplied"), st.get("moe_picks_here")
    if not rows or not picks:
        return None
    return rows / picks
