"""Binding of the mistral-7b-v0.3 configuration to the program under
test: ``paddle_tpu.text.models.LlamaForCausalLM`` (the repo's decoder
is Llama-shaped; Mistral-7B-v0.3 without a sliding window is the same
block at other sizes)."""
from __future__ import annotations

LEAF = {"input_layernorm.weight": "ln1", "self_attn.q_proj.weight": "wq",
        "self_attn.k_proj.weight": "wk", "self_attn.v_proj.weight": "wv",
        "self_attn.o_proj.weight": "wo",
        "post_attention_layernorm.weight": "ln2",
        "mlp.gate_proj.weight": "wg", "mlp.up_proj.weight": "wu",
        "mlp.down_proj.weight": "wd"}


def _config(cfg, max_len, **kw):
    from paddle_tpu.text.models.llama import LlamaConfig
    if cfg["hidden_size"] // cfg["num_attention_heads"] != cfg["head_dim"]:
        raise ValueError("the decoder derives head_dim from hidden/heads")
    return LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=int(max_len),
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        initializer_range=cfg["initializer_range"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        compute_dtype="bfloat16", **kw)


def build_serving(cfg, max_model_len):
    from paddle_tpu.framework.core import abstract_init
    from paddle_tpu.text.models import LlamaForCausalLM
    with abstract_init():
        model = LlamaForCausalLM(_config(cfg, max_model_len, remat=False))
    model.eval()
    return model


def build_training(cfg, seq):
    from paddle_tpu.framework.core import abstract_init
    from paddle_tpu.text.models import LlamaForCausalLM
    with abstract_init():
        model = LlamaForCausalLM(_config(cfg, seq, remat=True,
                                         scan_layers=True))
    model.train()
    return model


def name_map(cfg, model) -> dict:
    """program parameter name -> reference leaf (or the list of
    per-layer leaves that the scanned decoder keeps stacked)."""
    L = cfg["num_hidden_layers"]
    out = {"model.embed_tokens.weight": "embed",
           "model.norm.weight": "norm", "lm_head.weight": "head"}
    for pn, rn in LEAF.items():
        out["model.decoder." + pn.replace(".", "__")] = [
            f"layers.{l}.{rn}" for l in range(L)]
        for l in range(L):
            out[f"model.layers.{l}.{pn}"] = f"layers.{l}.{rn}"
    have = {n for n, _ in model.named_parameters()}
    return {k: v for k, v in out.items() if k in have}


def loss_fn(model):
    def f(ids):
        loss, _ = model(ids, labels=ids)
        return loss
    return f


def batch_args(batch):
    return [batch["ids"]]
