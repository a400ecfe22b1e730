"""Binding of the openpangu-ultra-moe-718b configuration to the program
under test: ``paddle_tpu.text.models.PanguUltraMoEForCausalLM`` (serving
only)."""
from __future__ import annotations

NORMS = {"input_layernorm": "ln1", "post_attention_layernorm": "ln2",
         "pre_mlp_layernorm": "ln3", "post_mlp_layernorm": "ln4"}
MLA = {"q_a_proj": "qa", "q_a_norm": "qan", "q_b_proj": "qb",
       "kv_a_proj": "kva", "kv_a_norm": "kvn", "kv_b_proj": "kvb",
       "o_proj": "wo"}
DENSE = {"gate_proj": "wg", "up_proj": "wu", "down_proj": "wd"}
MOE = {"router": "router", "router_bias": "rbias", "shared_gate": "sg",
       "shared_up": "su", "shared_down": "sd"}
EXPERTS = {"gate_w": "eg", "up_w": "eu", "down_w": "ed"}
MTP = {"enorm": "enorm", "hnorm": "hnorm", "eh_proj": "eh", "norm": "mnorm"}


def model_config(cfg, max_len):
    from paddle_tpu.text.models.pangu_ultra_moe import PanguUltraMoEConfig
    a = cfg["assumed"]
    return PanguUltraMoEConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        num_attention_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_theta=float(cfg["rope_theta"]),
        sandwich_norm=cfg["sandwich_norm"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_routed_experts=cfg.get("published", cfg)["n_routed_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        held_experts=tuple(a["held_experts"]),
        num_nextn_predict_layers=cfg["num_nextn_predict_layers"],
        rms_norm_eps=cfg["rms_norm_eps"],
        initializer_range=a["initializer_range"],
        max_position_embeddings=int(max_len),
        compute_dtype="bfloat16")


def build_serving(cfg, max_model_len):
    from paddle_tpu.framework.core import abstract_init
    from paddle_tpu.text.models import PanguUltraMoEForCausalLM
    with abstract_init():
        model = PanguUltraMoEForCausalLM(model_config(cfg, max_model_len))
    model.eval()
    return model


def _block(out, p, r, lyr):
    """One decoder layer's parameters under program prefix ``p`` to the
    reference's leaves under ``r``; the large leaves are each a group
    of their own (``<r><leaf>.<leaf>``: the reference's OWN_GROUP)."""
    for pn, rn in NORMS.items():
        out[p + pn] = f"{r}.{rn}"
    for pn, rn in MLA.items():
        out[p + "self_attn." + pn] = f"{r}wo.wo" if rn == "wo" \
            else f"{r}.{rn}"
    if lyr.is_moe:
        for pn, rn in MOE.items():
            out[p + "mlp." + pn] = f"{r}.{rn}"
    for pn, rn in (EXPERTS if lyr.is_moe else DENSE).items():
        out[p + "mlp." + pn] = f"{r}{rn}.{rn}"


def name_map(cfg, model) -> dict:
    """program parameter name -> reference leaf."""
    out = {"model.embed_tokens": "embed", "model.norm": "norm",
           "lm_head": "head"}
    for l, lyr in enumerate(model.model.layers):
        _block(out, f"model.layers.{l}.", f"layers.{l}", lyr)
    for k in range(model.config.num_nextn_predict_layers):
        for pn, rn in MTP.items():
            out[f"mtp.{k}.{pn}"] = f"layers.mtp{k}.{rn}"
        _block(out, f"mtp.{k}.layer.", f"layers.mtp{k}", model.mtp[k].layer)
    return out
