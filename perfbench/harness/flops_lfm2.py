"""Operations and bytes the LFM2-MoE cut needs, from shapes alone
(``configs/lfm2-8b-a1b.json``: gated short-convolution layers, GQA
layers with 64-wide heads, a dense FFN in the leading layers, sparse
experts all held here).  A matmul of [m,k]x[k,n] is 2*m*k*n
operations.  What an implementation does beyond the algorithm (padded
prompt rows, experts multiplied for tokens that did not choose them, a
pool read a second time) never counts.
"""
from __future__ import annotations


def layer_kinds(c: dict):
    """[(is_attention, is_moe)] of the layers held here."""
    return [(t == "full_attention", l >= c["num_dense_layers"])
            for l, t in enumerate(
                c["layer_types"][:c["num_hidden_layers"]])]


def head_dim(c: dict) -> int:
    return c["hidden_size"] // c["num_attention_heads"]


def conv_matmul_params(c: dict) -> int:
    h = c["hidden_size"]
    return h * 3 * h + h * h


def conv_params(c: dict) -> int:
    return conv_matmul_params(c) + c["conv_L_cache"] * c["hidden_size"]


def conv_flops_per_token(c: dict) -> int:
    """The operator besides its two projections: the depthwise taps and
    the two gates, two operations an element each."""
    return 2 * (c["conv_L_cache"] + 2) * c["hidden_size"]


def conv_tail_bytes(c: dict, itemsize: int = 2) -> int:
    """One slot's convolution tail, a layer."""
    return itemsize * (c["conv_L_cache"] - 1) * c["hidden_size"]


def attn_matmul_params(c: dict) -> int:
    h, d = c["hidden_size"], head_dim(c)
    return (2 * h * c["num_attention_heads"] * d
            + 2 * h * c["num_key_value_heads"] * d)


def attn_params(c: dict) -> int:
    return attn_matmul_params(c) + 2 * head_dim(c)


def attn_pair_flops(c: dict) -> int:
    """QK^T and PV per (query, key) pair and attention layer."""
    return 4 * c["num_attention_heads"] * head_dim(c)


def kv_bytes_per_token(c: dict, itemsize: int = 2) -> int:
    """K and V of one token over the attention layers held here."""
    n_attn = sum(1 for a, _ in layer_kinds(c) if a)
    return n_attn * 2 * c["num_key_value_heads"] * head_dim(c) * itemsize


def dense_ffn_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c: dict) -> int:
    return c["hidden_size"] * c["num_experts"] + c["num_experts"]


def embed_params(c: dict) -> int:
    """The embedding, which is the head too (tied)."""
    return c["hidden_size"] * c["vocab_size"]


def held_weight_params(c: dict) -> int:
    """Every parameter held here, norms included."""
    h = c["hidden_size"]
    n = embed_params(c) + h
    for is_attn, is_moe in layer_kinds(c):
        n += 2 * h + (attn_params(c) if is_attn else conv_params(c))
        n += (router_params(c) + c["num_experts"] * expert_params(c)
              if is_moe else dense_ffn_params(c))
    return n


def token_flops(c: dict) -> int:
    """Forward operations of one token through the layers held here,
    attention over the context left out: each token passes
    ``num_experts_per_tok`` experts."""
    f = 0
    for is_attn, is_moe in layer_kinds(c):
        f += 2 * (attn_matmul_params(c) if is_attn
                  else conv_matmul_params(c))
        if not is_attn:
            f += conv_flops_per_token(c)
        f += 2 * (c["hidden_size"] * c["num_experts"]
                  + c["num_experts_per_tok"] * expert_params(c)
                  if is_moe else dense_ffn_params(c))
    return f


def serve_flops(c: dict, prefill_tokens: int, prefill_rows: int,
                decode_tokens: int, prefill_ctx_sum: int,
                decode_ctx_sum: int) -> int:
    """Forward operations of served work: every processed token goes
    through the layers; the head runs once per prefilled prompt and
    once per decoded token; attention per (query, key) pair."""
    n_attn = sum(1 for a, _ in layer_kinds(c) if a)
    return ((prefill_tokens + decode_tokens) * token_flops(c)
            + 2 * (prefill_rows + decode_tokens) * embed_params(c)
            + n_attn * attn_pair_flops(c)
            * int(prefill_ctx_sum + decode_ctx_sum))


def kv_read_bytes(c: dict, live_context_tokens: float) -> float:
    """Bytes the attention layers of one decode step must read: every
    live token's K and V once, 64 lanes a head."""
    return live_context_tokens * kv_bytes_per_token(c)


def decode_step_bytes(c: dict, live_rows: float, live_context_tokens: float,
                      weight_bytes: int = 2) -> float:
    """Bytes one decode step must move: every held weight once, the
    live K and V of the attention layers read, and the convolution
    tail of every live row read and written."""
    n_conv = sum(1 for a, _ in layer_kinds(c) if not a)
    return (weight_bytes * held_weight_params(c)
            + kv_read_bytes(c, live_context_tokens)
            + 2 * live_rows * n_conv * conv_tail_bytes(c))
