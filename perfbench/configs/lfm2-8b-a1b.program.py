"""Binding of the lfm2-8b-a1b configuration to the program under test:
``paddle_tpu.text.models.Lfm2MoeForCausalLM`` (serving only)."""
from __future__ import annotations

CONV = {"in_proj": "win", "conv": "cw", "out_proj": "wout"}
ATTN = {"q_proj": "wq", "k_proj": "wk", "v_proj": "wv", "out_proj": "wo",
        "q_layernorm": "qn", "k_layernorm": "kn"}
DENSE = {"w1": "w1", "w3": "w3", "w2": "w2"}
MOE = {"router": "router", "router_bias": "rbias"}
EXPERTS = {"gate_w": "eg", "up_w": "eu", "down_w": "ed"}


def model_config(cfg, max_len):
    from paddle_tpu.text.models.lfm2_moe import Lfm2MoeConfig
    a = cfg["assumed"]
    if not a["tie_word_embeddings"]:
        raise ValueError("the program ties the head to the embedding")
    return Lfm2MoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        layer_types=tuple(cfg["layer_types"]),
        num_dense_layers=cfg["num_dense_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        conv_L_cache=cfg["conv_L_cache"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        held_experts=tuple(a["held_experts"]),
        norm_eps=cfg["norm_eps"], rope_theta=float(cfg["rope_theta"]),
        initializer_range=a["initializer_range"],
        max_position_embeddings=int(max_len),
        compute_dtype="bfloat16")


def build_serving(cfg, max_model_len):
    from paddle_tpu.framework.core import abstract_init
    from paddle_tpu.text.models import Lfm2MoeForCausalLM
    with abstract_init():
        model = Lfm2MoeForCausalLM(model_config(cfg, max_model_len))
    model.eval()
    return model


def name_map(cfg, model) -> dict:
    """program parameter name -> reference leaf."""
    out = {"model.embed_tokens": "embed", "model.embedding_norm": "norm"}
    for l, lyr in enumerate(model.model.layers):
        p, r = f"model.layers.{l}.", f"layers.{l}."
        out[p + "operator_norm"] = r + "ln1"
        out[p + "ffn_norm"] = r + "ln2"
        if lyr.is_attention:
            for pn, rn in ATTN.items():
                out[p + "self_attn." + pn] = r + rn
        else:
            for pn, rn in CONV.items():
                out[p + "conv." + pn] = r + rn
        for pn, rn in (MOE if lyr.is_moe else DENSE).items():
            out[p + "feed_forward." + pn] = r + rn
        if lyr.is_moe:
            # each stacked expert leaf is a group of its own
            for pn, rn in EXPERTS.items():
                out[p + "feed_forward." + pn] = f"layers.{l}{rn}.{rn}"
    return out
