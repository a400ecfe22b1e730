"""Binding of the bert-base configuration to the program under test:
``paddle_tpu.text.models.bert.BertForPretraining`` with
``BertPretrainingCriterion``."""
from __future__ import annotations

LAYER = {"attention.qkv_proj.weight": "attn.wqkv",
         "attention.qkv_proj.bias": "attn.bqkv",
         "attention.out_proj.weight": "attn.wo",
         "attention.out_proj.bias": "attn.bo",
         "intermediate.weight": "ffn.w1", "intermediate.bias": "ffn.b1",
         "output.weight": "ffn.w2", "output.bias": "ffn.b2",
         "norm1.weight": "ln1.w", "norm1.bias": "ln1.b",
         "norm2.weight": "ln2.w", "norm2.bias": "ln2.b"}
GLOBAL = {"mlm_bias": "mlm.bias",
          "bert.embeddings.word_embeddings.weight": "emb.word",
          "bert.embeddings.position_embeddings.weight": "emb.pos",
          "bert.embeddings.token_type_embeddings.weight": "emb.type",
          "bert.embeddings.layer_norm.weight": "emb.ln.w",
          "bert.embeddings.layer_norm.bias": "emb.ln.b",
          "bert.pooler.weight": "pooler.w", "bert.pooler.bias": "pooler.b",
          "mlm_transform.weight": "mlm.w", "mlm_transform.bias": "mlm.b",
          "mlm_norm.weight": "mlm.ln.w", "mlm_norm.bias": "mlm.ln.b",
          "nsp.weight": "nsp.w", "nsp.bias": "nsp.b"}


def build_training(cfg, seq):
    from paddle_tpu.framework.core import abstract_init
    from paddle_tpu.text.models.bert import BertConfig, BertForPretraining
    if seq > cfg["max_position_embeddings"]:
        raise ValueError("sequence longer than the position table")
    with abstract_init():
        model = BertForPretraining(BertConfig(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            num_hidden_layers=cfg["num_hidden_layers"],
            num_attention_heads=cfg["num_attention_heads"],
            intermediate_size=cfg["intermediate_size"],
            hidden_act=cfg["hidden_act"],
            hidden_dropout_prob=cfg["hidden_dropout_prob"],
            attention_probs_dropout_prob=cfg[
                "attention_probs_dropout_prob"],
            max_position_embeddings=cfg["max_position_embeddings"],
            type_vocab_size=cfg["type_vocab_size"],
            initializer_range=cfg["initializer_range"],
            layer_norm_eps=cfg["layer_norm_eps"]))
    model.train()
    return model


def name_map(cfg, model) -> dict:
    out = dict(GLOBAL)
    for l in range(cfg["num_hidden_layers"]):
        for pn, rn in LAYER.items():
            out[f"bert.layers.{l}.{pn}"] = f"layers.{l}.{rn}"
    return out


def loss_fn(model):
    from paddle_tpu.text.models.bert import BertPretrainingCriterion
    crit = BertPretrainingCriterion(model.config.vocab_size)

    def f(input_ids, token_type_ids, masked_positions, mlm_labels,
          nsp_labels):
        mlm, nsp = model(input_ids, token_type_ids,
                         masked_positions=masked_positions)
        return crit(mlm, nsp, mlm_labels, nsp_labels)
    return f


def batch_args(batch):
    return [batch[k] for k in ("input_ids", "token_type_ids",
                               "masked_positions", "mlm_labels",
                               "nsp_labels")]
