"""Operations and bytes the openPangu-Ultra-MoE cut needs, from shapes
alone (``configs/openpangu-ultra-moe-718b.json``: latent attention with
a low-rank query in every layer, a dense FFN in the leading layer,
sparse experts of which this chip holds a share).  A matmul of
[m,k]x[k,n] is 2*m*k*n operations.  What an implementation does beyond
the algorithm (the page's lanes padded from 576 to 640, the pool read a
second time as V, experts multiplied for tokens that did not choose
them, padded prompt rows) never counts.
"""
from __future__ import annotations


def n_layers(c: dict) -> int:
    return c["num_hidden_layers"]


def n_moe_layers(c: dict) -> int:
    return c["num_hidden_layers"] - c["first_k_dense_replace"]


def attn_params(c: dict) -> int:
    """The five projections of one latent-attention layer."""
    h, nh = c["hidden_size"], c["num_attention_heads"]
    qr, kvr = c["q_lora_rank"], c["kv_lora_rank"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    return (h * qr + qr * nh * (dn + dr) + h * (kvr + dr)
            + kvr * nh * (dn + dv) + nh * dv * h)


def norm_params(c: dict) -> int:
    """A layer's norm vectors: the four sandwich norms and the two
    inside the attention."""
    return 4 * c["hidden_size"] + c["q_lora_rank"] + c["kv_lora_rank"]


def dense_ffn_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_width(c: dict) -> int:
    return int(c.get("published", {}).get("n_routed_experts",
                                          c["n_routed_experts"]))


def moe_fixed_params(c: dict) -> int:
    """Router and shared expert: what every token passes."""
    return (c["hidden_size"] * router_width(c)
            + c["n_shared_experts"] * expert_params(c))


def embed_params(c: dict) -> int:
    """The embedding; the untied head is as large."""
    return c["hidden_size"] * c["vocab_size"]


def held_weight_params(c: dict) -> int:
    """Every parameter held here (``n_routed_experts`` counts the
    experts held)."""
    dense = c["first_k_dense_replace"]
    return (2 * embed_params(c) + c["hidden_size"]
            + n_layers(c) * (attn_params(c) + norm_params(c))
            + dense * dense_ffn_params(c)
            + n_moe_layers(c) * (moe_fixed_params(c) + c["n_routed_experts"]
                                 * expert_params(c)))


def latent_bytes_per_token(c: dict, itemsize: int = 2) -> int:
    """What a token leaves in one layer's cache: ``[c | k_pe]``."""
    return itemsize * (c["kv_lora_rank"] + c["qk_rope_head_dim"])


def expanded_pair_flops(c: dict) -> int:
    """QK^T and PV per (query, key) pair and layer with K and V
    expanded from the latent (a prefill block)."""
    return 2 * c["num_attention_heads"] * (
        c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"])


def absorbed_pair_flops(c: dict) -> int:
    """The same pair over the latent row itself (a decode step: the
    query absorbs the expansion, so a cached position is read as its
    576 numbers and multiplied by every head: 576 wide for the logit,
    512 wide for the weighted sum)."""
    kvr = c["kv_lora_rank"]
    return 2 * c["num_attention_heads"] * (
        kvr + c["qk_rope_head_dim"] + kvr)


def token_flops(c: dict, here_share: float) -> float:
    """Forward operations of one token through the layers held here,
    attention over the context left out; ``here_share`` is the share
    of a token's ``num_experts_per_tok`` picks that land on the held
    experts (measured: ``moe_picks_here``)."""
    return 2.0 * (
        n_layers(c) * attn_params(c)
        + c["first_k_dense_replace"] * dense_ffn_params(c)
        + n_moe_layers(c) * (moe_fixed_params(c) + here_share
                             * c["num_experts_per_tok"] * expert_params(c)))


def serve_flops(c: dict, prefill_tokens: int, prefill_rows: int,
                decode_tokens: int, prefill_ctx_sum: int,
                decode_ctx_sum: int, here_share: float) -> float:
    """Forward operations of served work: every processed token goes
    through the layers; the head runs once per prefilled prompt and
    once per decoded token; attention per (query, key) pair, expanded
    in prefill and absorbed in decode."""
    return ((prefill_tokens + decode_tokens) * token_flops(c, here_share)
            + 2 * (prefill_rows + decode_tokens) * embed_params(c)
            + n_layers(c) * (expanded_pair_flops(c) * prefill_ctx_sum
                             + absorbed_pair_flops(c) * decode_ctx_sum))


def latent_read_bytes(c: dict, live_context_tokens: float) -> float:
    """Bytes the latent layers of one decode step must read: every
    live token's latent row once a layer."""
    return live_context_tokens * n_layers(c) * latent_bytes_per_token(c)


def latent_attn_seconds(c: dict, live_context_tokens: float,
                        flops_per_s: float, bytes_per_s: float):
    """The least time the chip could take for one decode step's latent
    attention: the LARGER of the absorbed products at the MXU's peak
    and the latent rows at the HBM's (at 128 heads the two meet).
    Returns (seconds, "mxu" | "hbm")."""
    mxu = (live_context_tokens * n_layers(c) * absorbed_pair_flops(c)
           / flops_per_s)
    hbm = latent_read_bytes(c, live_context_tokens) / bytes_per_s
    return (mxu, "mxu") if mxu >= hbm else (hbm, "hbm")


def decode_step_bytes(c: dict, live_rows: float, live_context_tokens: float,
                      weight_bytes: int = 2) -> float:
    """Bytes one decode step must move: every held weight once but the
    embedding, of which each live row reads its one row, and the live
    latent rows."""
    return (weight_bytes * (held_weight_params(c) - embed_params(c)
                            + live_rows * c["hidden_size"])
            + latent_read_bytes(c, live_context_tokens))
