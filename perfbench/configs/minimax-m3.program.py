"""Binding of the minimax-m3 configuration to the program under test:
``paddle_tpu.text.models.MiniMaxM3ForCausalLM`` (serving only)."""
from __future__ import annotations

ATTN = {"input_layernorm": "ln1", "post_attention_layernorm": "ln2",
        "self_attn.q_proj": "wq", "self_attn.k_proj": "wk",
        "self_attn.v_proj": "wv", "self_attn.o_proj": "wo",
        "self_attn.q_norm": "qn", "self_attn.k_norm": "kn",
        "self_attn.index_proj": "wi", "self_attn.index_norm": "inn"}
DENSE = {"gate_proj": "wg", "up_proj": "wu", "down_proj": "wd"}
MOE = {"router": "router", "router_bias": "rbias", "shared_gate": "sg",
       "shared_up": "su", "shared_down": "sd", "gate_w": "eg",
       "up_w": "eu", "down_w": "ed"}


def model_config(cfg, max_len):
    from paddle_tpu.nn.layer.moe import SWIGLU_OAI_ALPHA, SWIGLU_OAI_LIMIT
    from paddle_tpu.text.models.minimax_m3 import MiniMaxM3Config
    if (cfg["hidden_act"], cfg["swiglu_alpha"], cfg["swiglu_limit"]) != (
            "swigluoai", SWIGLU_OAI_ALPHA, SWIGLU_OAI_LIMIT):
        raise ValueError("the program's FFNs are swigluoai, alpha 1.702, "
                         "limit 7")
    a = cfg["assumed"]
    return MiniMaxM3Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        dense_intermediate_size=cfg["dense_intermediate_size"],
        shared_intermediate_size=cfg["shared_intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        moe_layer_freq=tuple(cfg["moe_layer_freq"]),
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], rotary_dim=cfg["rotary_dim"],
        rope_theta=float(cfg["rope_theta"]),
        num_local_experts=cfg.get("published", cfg)["num_local_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        held_experts=tuple(a["held_experts"]),
        msa_block=a["msa_block"],
        msa_top_k=a["msa_top_k"], msa_local=a["msa_local"],
        msa_index_heads=a["msa_index_heads"],
        rms_norm_eps=cfg["rms_norm_eps"],
        initializer_range=a["initializer_range"],
        max_position_embeddings=int(max_len), compute_dtype="bfloat16")


def build_serving(cfg, max_model_len):
    from paddle_tpu.framework.core import abstract_init
    from paddle_tpu.text.models import MiniMaxM3ForCausalLM
    with abstract_init():
        model = MiniMaxM3ForCausalLM(model_config(cfg, max_model_len))
    model.eval()
    return model


def name_map(cfg, model) -> dict:
    """program parameter name -> reference leaf; the large leaves are
    each a group of their own (``layers.<l><leaf>.<leaf>``: the
    reference's OWN_GROUP)."""
    own = ("wq", "wo", "wg", "wu", "wd", "eg", "eu", "ed")
    out = {"model.embed_tokens": "embed", "model.norm": "norm",
           "lm_head": "head"}
    for l, lyr in enumerate(model.model.layers):
        leaf = lambda rn: (f"layers.{l}{rn}.{rn}" if rn in own
                           else f"layers.{l}.{rn}")
        for pn, rn in ATTN.items():
            out[f"model.layers.{l}.{pn}"] = leaf(rn)
        for pn, rn in (MOE if lyr.is_moe else DENSE).items():
            out[f"model.layers.{l}.mlp.{pn}"] = leaf(rn)
    return out
