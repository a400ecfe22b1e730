"""What the LFM2-MoE readers share (not a metric)."""
from __future__ import annotations


def is_lfm2(ctx) -> bool:
    """Whether the cell's configuration is the gated short-convolution
    family these readers count."""
    return "conv_L_cache" in ctx["cfg"]
