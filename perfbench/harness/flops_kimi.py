"""Operations and bytes the Kimi-Linear cut needs, from shapes alone
(``configs/kimi-linear-48b-a3b.json``: KDA and MLA layers, a dense
first FFN, sparse experts of which this chip holds a share).  A matmul
of [m,k]x[k,n] is 2*m*k*n operations.  What an implementation does
beyond the algorithm (padded pages, absorbed attention's wider
products, experts multiplied for tokens that did not choose them)
never counts.
"""
from __future__ import annotations


def _la(c):
    return c["linear_attn_config"]


def layer_kinds(c: dict):
    """[(is_mla, is_moe)] of the layers held here (1-indexed pattern
    of the published config, cut to ``num_hidden_layers``)."""
    full = set(_la(c)["full_attn_layers"])
    return [((l + 1) in full, l >= c["first_k_dense_replace"])
            for l in range(c["num_hidden_layers"])]


def kda_matmul_params(c: dict) -> int:
    h = c["hidden_size"]
    kd = _la(c)["num_heads"] * _la(c)["head_dim"]
    r = c.get("assumed", {}).get("gate_low_rank", _la(c)["head_dim"])
    return 4 * h * kd + 2 * (h * r + r * kd) + h * _la(c)["num_heads"]


def kda_state_flops_per_token(c: dict) -> int:
    """The recurrence itself: S^T k, the rank-one update and S^T q,
    two operations an element each, per head."""
    la = _la(c)
    return 6 * la["num_heads"] * la["head_dim"] * la["head_dim"]


def kda_state_bytes(c: dict) -> int:
    """One slot's float32 state and bf16 convolution tails, a layer."""
    la = _la(c)
    kd = la["num_heads"] * la["head_dim"]
    return (4 * kd * la["head_dim"]
            + 2 * 3 * (la["short_conv_kernel_size"] - 1) * kd)


def mla_matmul_params(c: dict) -> int:
    h, nh = c["hidden_size"], c["num_attention_heads"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    kvr = c["kv_lora_rank"]
    return (h * nh * (dn + dr) + h * (kvr + dr) + kvr * nh * (dn + dv)
            + nh * dv * h)


def mla_pair_flops(c: dict) -> int:
    """QK^T and PV per (query, key) pair and layer, expanded form."""
    return 2 * c["num_attention_heads"] * (
        c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"])


def mla_latent_bytes_per_token(c: dict, itemsize: int = 2) -> int:
    return itemsize * (c["kv_lora_rank"] + c["qk_rope_head_dim"])


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_width(c: dict) -> int:
    return int(c.get("published", {}).get("num_experts", c["num_experts"]))


def moe_fixed_params(c: dict) -> int:
    """Router and shared expert: what every token passes."""
    return (c["hidden_size"] * router_width(c)
            + c["num_shared_experts"] * expert_params(c))


def dense_ffn_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def head_params(c: dict) -> int:
    return c["hidden_size"] * c["vocab_size"]


def token_flops(c: dict, here_share: float) -> float:
    """Forward operations of one token through the layers held here,
    attention over the context left out; ``here_share`` is the share
    of a token's ``num_experts_per_token`` picks that land on the held
    experts (measured: ``moe_picks_here``)."""
    f = 0.0
    for is_mla, is_moe in layer_kinds(c):
        f += 2 * (mla_matmul_params(c) if is_mla
                  else kda_matmul_params(c))
        if not is_mla:
            f += kda_state_flops_per_token(c)
        if is_moe:
            f += 2 * (moe_fixed_params(c) + here_share * expert_params(c)
                      * c["num_experts_per_token"])
        else:
            f += 2 * dense_ffn_params(c)
    return f


def serve_flops(c: dict, prefill_tokens: int, prefill_rows: int,
                decode_tokens: int, prefill_ctx_sum: int,
                decode_ctx_sum: int, here_share: float) -> float:
    """Forward operations of served work: every processed token goes
    through the layers; the head runs once per prefilled prompt and
    once per decoded token; latent attention per (query, key) pair."""
    n_mla = sum(1 for m, _ in layer_kinds(c) if m)
    return ((prefill_tokens + decode_tokens) * token_flops(c, here_share)
            + 2 * (prefill_rows + decode_tokens) * head_params(c)
            + n_mla * mla_pair_flops(c)
            * (prefill_ctx_sum + decode_ctx_sum))


def held_weight_params(c: dict) -> int:
    n = head_params(c)
    for is_mla, is_moe in layer_kinds(c):
        n += mla_matmul_params(c) if is_mla else kda_matmul_params(c)
        n += (moe_fixed_params(c) + c["num_experts"] * expert_params(c)
              if is_moe else dense_ffn_params(c))
    return n


def latent_read_bytes(c: dict, live_context_tokens: float) -> float:
    """Bytes the latent layers of one decode step must read: every
    live token's latent row once a layer."""
    n_mla = sum(1 for m, _ in layer_kinds(c) if m)
    return live_context_tokens * n_mla * mla_latent_bytes_per_token(c)


def decode_step_bytes(c: dict, live_rows: float, live_context_tokens: float,
                      weight_bytes: int = 2) -> float:
    """Bytes one decode step must move: every held weight once, the
    recurrent state of every live row read and written, and the live
    latent rows read."""
    kinds = layer_kinds(c)
    n_kda = sum(1 for m, _ in kinds if not m)
    return (weight_bytes * held_weight_params(c)
            + 2 * live_rows * n_kda * kda_state_bytes(c)
            + latent_read_bytes(c, live_context_tokens))

