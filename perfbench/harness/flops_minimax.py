"""Operations and bytes the MiniMax-M3 cut needs, from shapes alone
(``configs/minimax-m3.json``: GQA made block-sparse by a learned
indexer in every layer, a dense FFN in the leading layer, sparse
experts of which this chip holds a share).  A matmul of [m,k]x[k,n] is
2*m*k*n operations.  What an implementation does beyond the algorithm
(the masked square of a prefill call, padded prompt rows, experts
multiplied for tokens that did not choose them) never counts; decode
attention counts the selected (page, kv head) tiles.
"""
from __future__ import annotations


def n_layers(c: dict) -> int:
    return c["num_hidden_layers"]


def n_moe_layers(c: dict) -> int:
    return sum(int(f) for f in c["moe_layer_freq"][:n_layers(c)])


def n_dense_layers(c: dict) -> int:
    return n_layers(c) - n_moe_layers(c)


def msa(c: dict):
    """(block, top_k, local, index heads)."""
    a = c["assumed"]
    return (a["msa_block"], a["msa_top_k"], a["msa_local"],
            a["msa_index_heads"])


def attn_params(c: dict) -> int:
    """The four projections of one attention layer and the indexer's."""
    h, d = c["hidden_size"], c["head_dim"]
    nh, kh = c["num_attention_heads"], c["num_key_value_heads"]
    return (2 * h * nh * d + 2 * h * kh * d
            + h * kh * msa(c)[3] * d)


def norm_params(c: dict) -> int:
    """A layer's two Gemma norms and its three head norms."""
    return 2 * c["hidden_size"] + 3 * c["head_dim"]


def dense_ffn_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["dense_intermediate_size"]


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def router_width(c: dict) -> int:
    return int(c.get("published", {}).get("num_local_experts",
                                          c["num_local_experts"]))


def moe_fixed_params(c: dict) -> int:
    """Router, its bias and the shared expert: what every token
    passes."""
    return ((c["hidden_size"] + 1) * router_width(c)
            + 3 * c["hidden_size"] * c["shared_intermediate_size"])


def embed_params(c: dict) -> int:
    """The embedding; the untied head is as large."""
    return c["hidden_size"] * c["vocab_size"]


def held_weight_params(c: dict) -> int:
    """Every parameter held here (``num_local_experts`` counts the
    experts held)."""
    return (2 * embed_params(c) + c["hidden_size"]
            + n_layers(c) * (attn_params(c) + norm_params(c))
            + n_dense_layers(c) * dense_ffn_params(c)
            + n_moe_layers(c) * (moe_fixed_params(c)
                                 + c["num_local_experts"]
                                 * expert_params(c)))


def kv_bytes_per_token(c: dict, itemsize: int = 2) -> int:
    """What a token leaves in one layer's pages: K and V of every kv
    head."""
    return itemsize * 2 * c["num_key_value_heads"] * c["head_dim"]


def tile_bytes(c: dict, itemsize: int = 2) -> int:
    """One (page, kv head) of K and of V: what the sparse kernel copies
    for a selected page."""
    return itemsize * 2 * msa(c)[0] * c["head_dim"]


def tile_flops(c: dict) -> int:
    """QK^T and PV of one (page, kv head) for the query heads that read
    that kv head."""
    bs = msa(c)[0]
    r = c["num_attention_heads"] // c["num_key_value_heads"]
    return 4 * r * bs * c["head_dim"]


def token_flops(c: dict, here_share: float) -> float:
    """Forward operations of one token through the layers held here,
    attention over the context left out; ``here_share`` is the share
    of a token's ``num_experts_per_tok`` picks that land on the held
    experts (measured: ``moe_picks_here``)."""
    return 2.0 * (
        n_layers(c) * attn_params(c)
        + n_dense_layers(c) * dense_ffn_params(c)
        + n_moe_layers(c) * (moe_fixed_params(c) + here_share
                             * c["num_experts_per_tok"] * expert_params(c)))


def decode_step_flops(c: dict, live_rows: float, tiles: float,
                      live_pages: float, here_share: float) -> float:
    """Forward operations of one decode step: ``live_rows`` tokens
    through the layers and the head, the keys of ``tiles`` selected
    (page, kv head) tiles (``msa_block`` keys each, every query head of
    the kv head; a row's own partial page counted whole) and the
    indexer's scores of ``live_pages`` (page, kv head, layer) pooled
    keys."""
    return (live_rows * (token_flops(c, here_share)
                         + 2 * embed_params(c))
            + tiles * tile_flops(c)
            + 2 * c["head_dim"] * live_pages)


def sparse_attn_seconds(c: dict, tiles: float, flops_per_s: float,
                        bytes_per_s: float):
    """The least time the chip could take to read and multiply
    ``tiles`` selected (page, kv head) tiles: the LARGER of their bytes
    at the HBM's peak and their products at the MXU's (16 query heads
    over a 128-wide tile: 16 FLOP a byte, the bytes bound).  Returns
    (seconds, "hbm" | "mxu")."""
    hbm = tiles * tile_bytes(c) / bytes_per_s
    mxu = tiles * tile_flops(c) / flops_per_s
    return (hbm, "hbm") if hbm >= mxu else (mxu, "mxu")


def decode_step_bytes(c: dict, live_rows: float, tiles: float,
                      live_pages: float, weight_bytes: int = 2,
                      pooled_bytes: int = 4) -> float:
    """Bytes one decode step must move: every held weight once but the
    embedding, of which each live row reads its one row; the selected
    tiles; and the pooled keys (float32) of every live page, which the
    indexer scores."""
    kh, d = c["num_key_value_heads"], c["head_dim"]
    return (weight_bytes * (held_weight_params(c) - embed_params(c)
                            + live_rows * c["hidden_size"])
            + tiles * tile_bytes(c)
            + n_layers(c) * live_pages * pooled_bytes * d)
