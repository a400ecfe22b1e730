"""Binding of the kimi-linear-48b-a3b configuration to the program
under test: ``paddle_tpu.text.models.KimiLinearForCausalLM`` (serving
only)."""
from __future__ import annotations

KDA = {"q_proj": "kq", "k_proj": "kk", "v_proj": "kv", "q_conv": "cq",
       "k_conv": "ck", "v_conv": "cv", "f_a": "fa", "f_b": "fb",
       "A_log": "alog", "dt_bias": "dtb", "b_proj": "wb", "g_a": "ga",
       "g_b": "gb", "o_norm": "onorm", "o_proj": "ko"}
MLA = {"q_proj": "mq", "kv_a_proj": "mkva", "kv_a_norm": "mkvn",
       "kv_b_proj": "mkvb", "o_proj": "mo"}
DENSE = {"gate_proj": "wg", "up_proj": "wu", "down_proj": "wd"}
MOE = {"router": "router", "router_bias": "rbias", "shared_gate": "sg",
       "shared_up": "su", "shared_down": "sd"}
EXPERTS = {"gate_w": "eg", "up_w": "eu", "down_w": "ed"}


def model_config(cfg, max_len):
    from paddle_tpu.text.models.kimi_linear import KimiLinearConfig
    la, a = cfg["linear_attn_config"], cfg["assumed"]
    return KimiLinearConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        full_attn_layers=tuple(la["full_attn_layers"]),
        first_k_dense_replace=cfg["first_k_dense_replace"],
        kda_num_heads=la["num_heads"], kda_head_dim=la["head_dim"],
        short_conv_kernel_size=la["short_conv_kernel_size"],
        gate_low_rank=a["gate_low_rank"],
        num_attention_heads=cfg["num_attention_heads"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], kv_lora_rank=cfg["kv_lora_rank"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg.get("published", cfg)["num_experts"],
        num_experts_per_token=cfg["num_experts_per_token"],
        num_shared_experts=cfg["num_shared_experts"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        held_experts=tuple(a["held_experts"]),
        rms_norm_eps=cfg["rms_norm_eps"],
        initializer_range=a["initializer_range"],
        max_position_embeddings=int(max_len),
        compute_dtype="bfloat16")


def build_serving(cfg, max_model_len):
    from paddle_tpu.framework.core import abstract_init
    from paddle_tpu.text.models import KimiLinearForCausalLM
    with abstract_init():
        model = KimiLinearForCausalLM(model_config(cfg, max_model_len))
    model.eval()
    return model


def name_map(cfg, model) -> dict:
    """program parameter name -> reference leaf."""
    out = {"model.embed_tokens": "embed", "model.norm": "norm",
           "lm_head": "head"}
    for l, lyr in enumerate(model.model.layers):
        p, r = f"model.layers.{l}.", f"layers.{l}."
        out[p + "input_layernorm"] = r + "ln1"
        out[p + "post_attention_layernorm"] = r + "ln2"
        for pn, rn in (MLA if lyr.is_mla else KDA).items():
            out[p + "self_attn." + pn] = r + rn
        for pn, rn in (MOE if lyr.is_moe else DENSE).items():
            out[p + "mlp." + pn] = r + rn
        if lyr.is_moe:
            # each stacked expert leaf is a group of its own
            for pn, rn in EXPERTS.items():
                out[p + "mlp." + pn] = f"layers.{l}{rn}.{rn}"
    return out
