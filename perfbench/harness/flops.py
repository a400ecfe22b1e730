"""Operations and bytes the algorithm needs, from shapes alone.

Recomputed (remat) operations never count.  A matmul of [m,k]x[k,n]
is 2*m*k*n operations.  ``kernel_flops_bytes`` is copied from
``bench._kernel_flops_bytes`` (flash attention entry, generalised to a
non-causal call); the original is listed in PERF.md for deletion.
"""
from __future__ import annotations


# ---- decoder (Llama/Mistral-shaped) ---------------------------------
def decoder_layer_matmul_params(c: dict) -> int:
    h = c["hidden_size"]
    hd = c.get("head_dim") or h // c["num_attention_heads"]
    q = h * c["num_attention_heads"] * hd
    kv = 2 * h * c["num_key_value_heads"] * hd
    o = c["num_attention_heads"] * hd * h
    return q + kv + o + 3 * h * c["intermediate_size"]


def decoder_head_params(c: dict) -> int:
    return c["hidden_size"] * c["vocab_size"]


def decoder_attn_flops(c: dict, n_queries_context_sum: int) -> int:
    """QK^T and PV for queries whose contexts sum to the argument, over
    all layers: 4 * heads * head_dim per (query, key) pair."""
    hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    return (4 * c["num_attention_heads"] * hd * c["num_hidden_layers"]
            * int(n_queries_context_sum))


def decoder_train_flops_per_seq(c: dict, seq: int) -> int:
    """Forward + backward (3x forward) of one causal sequence; the loss
    reads seq-1 positions but the head runs over all of them."""
    L = c["num_hidden_layers"]
    fwd = 2 * seq * (L * decoder_layer_matmul_params(c)
                     + decoder_head_params(c))
    fwd += decoder_attn_flops(c, seq * (seq + 1) // 2)
    return 3 * fwd


def decoder_serve_flops(c: dict, prefill_tokens: int, prefill_rows: int,
                        decode_tokens: int, prefill_ctx_sum: int,
                        decode_ctx_sum: int) -> int:
    """Forward operations of served work: every processed token goes
    through the layers; the head runs once per prefilled prompt (its
    last position) and once per decoded token."""
    L = c["num_hidden_layers"]
    body = 2 * (prefill_tokens + decode_tokens) * L \
        * decoder_layer_matmul_params(c)
    head = 2 * (prefill_rows + decode_tokens) * decoder_head_params(c)
    return body + head + decoder_attn_flops(
        c, prefill_ctx_sum + decode_ctx_sum)


def decoder_decode_step_bytes(c: dict, live_context_tokens: int,
                              weight_bytes: int = 2,
                              kv_bytes: int = 2) -> int:
    """Bytes one decode step must read: every weight once, and the
    live K and V of every active slot."""
    hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    L = c["num_hidden_layers"]
    w = weight_bytes * (L * decoder_layer_matmul_params(c)
                        + decoder_head_params(c))
    kv = (kv_bytes * 2 * L * c["num_key_value_heads"] * hd
          * int(live_context_tokens))
    return w + kv


# ---- BERT -----------------------------------------------------------
def bert_layer_matmul_params(c: dict) -> int:
    h = c["hidden_size"]
    return 4 * h * h + 2 * h * c["intermediate_size"]


def bert_train_flops_per_seq(c: dict, seq: int, masked: int) -> int:
    """Forward + backward of one sequence: the encoder over every
    position, bidirectional attention (4*h per query-key pair and
    layer), and the MLM head over the masked positions only."""
    h, L = c["hidden_size"], c["num_hidden_layers"]
    fwd = 2 * seq * L * bert_layer_matmul_params(c)
    fwd += 4 * h * L * seq * seq
    fwd += 2 * masked * (h * h + h * c["vocab_size"])
    fwd += 2 * (h * h + 2 * h)          # pooler + NSP on [CLS]
    return 3 * fwd


# ---- kernels --------------------------------------------------------
def kernel_flops_bytes(name: str, **p):
    """(operations, bytes) of one call of a kernel, forward pass."""
    if name == "flash_attention":
        b, h, s, d = p["b"], p["h"], p["s"], p["d"]
        pairs = s * s // 2 if p.get("causal", True) else s * s
        item = p.get("itemsize", 2)
        return (4 * b * h * pairs * d, 4 * b * h * s * d * item)
    raise KeyError(name)


def roofline_seconds(flops: int, nbytes: int, peaks: dict):
    """The least time the chip could take, and which peak bounds it."""
    tf = flops / peaks["flops_per_s"]
    tb = nbytes / peaks["hbm_bytes_per_s"]
    return (tf, "compute") if tf >= tb else (tb, "memory")
