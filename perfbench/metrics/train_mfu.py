from perfbench.harness import flops
from perfbench.metrics._util import peaks


def read(ctx):
    pk = peaks(ctx)
    if pk is None or not ctx["rate_per_chip"]:
        return None
    tr, cfg = ctx["traffic"], ctx["cfg"]
    seq = tr["seq"]
    if tr["task"] == "bert_pretrain":
        import math
        per_seq = flops.bert_train_flops_per_seq(
            cfg, seq, math.ceil(seq * tr["mask_share"]))
    elif tr["task"] == "causal_lm":
        per_seq = flops.decoder_train_flops_per_seq(cfg, seq)
    else:
        return None
    return 100.0 * per_seq / seq * ctx["rate_per_chip"] / pk["flops_per_s"]
