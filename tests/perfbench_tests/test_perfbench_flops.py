import json
import os

import pytest

from perfbench.harness import flops as F
from perfbench.harness.manifest import BENCH_DIR


def _cfg(name):
    with open(os.path.join(BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


def test_mistral_hand_counts():
    c = _cfg("mistral-7b-v0.3")
    # q 4096x4096, k and v 4096x1024, o 4096x4096, three 4096x14336
    layer = 4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert F.decoder_layer_matmul_params(c) == layer == 218_103_808
    assert F.decoder_head_params(c) == 4096 * 32768
    seq = 4096
    fwd = 2 * seq * (8 * layer + 4096 * 32768) \
        + 4 * 32 * 128 * 8 * (seq * (seq + 1) // 2)
    assert F.decoder_train_flops_per_seq(c, seq) == 3 * fwd
    # about 12 GFLOP a token at 8 layers, as ISSUE 24 reckons
    assert 3 * fwd / seq == pytest.approx(12.08e9, rel=0.01)


def test_mistral_decode_bytes_and_serve_flops():
    c = _cfg("mistral-7b-v0.3")
    w = 2 * (8 * 218_103_808 + 4096 * 32768)
    assert F.decoder_decode_step_bytes(c, 0) == w
    # one live token: K and V, 8 layers, 8 kv heads x 128, bf16
    assert F.decoder_decode_step_bytes(c, 1) - w == 2 * 2 * 8 * 8 * 128
    f = F.decoder_serve_flops(c, prefill_tokens=10, prefill_rows=1,
                              decode_tokens=5, prefill_ctx_sum=55,
                              decode_ctx_sum=60)
    assert f == (2 * 15 * 8 * 218_103_808 + 2 * 6 * 4096 * 32768
                 + 4 * 32 * 128 * 8 * 115)


def test_bert_hand_counts():
    c = _cfg("bert-base")
    assert F.bert_layer_matmul_params(c) == 4 * 768 * 768 + 2 * 768 * 3072
    seq, masked = 512, 77
    fwd = (2 * seq * 12 * (4 * 768 * 768 + 2 * 768 * 3072)
           + 4 * 768 * 12 * seq * seq
           + 2 * masked * (768 * 768 + 768 * 30522)
           + 2 * (768 * 768 + 2 * 768))
    assert F.bert_train_flops_per_seq(c, seq, masked) == 3 * fwd


def test_flash_kernel_and_roofline():
    fl, by = F.kernel_flops_bytes("flash_attention", b=2, h=4, s=1024, d=128)
    assert fl == 4 * 2 * 4 * (1024 * 1024 // 2) * 128
    assert by == 4 * 2 * 4 * 1024 * 128 * 2
    fl2, _ = F.kernel_flops_bytes("flash_attention", b=2, h=4, s=1024,
                                  d=128, causal=False)
    assert fl2 == 2 * fl
    with pytest.raises(KeyError):
        F.kernel_flops_bytes("nope")
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert F.roofline_seconds(1000, 10, peaks) == (10.0, "compute")
    assert F.roofline_seconds(10, 1000, peaks) == (100.0, "memory")


def test_peaks_table_has_no_default():
    from perfbench.harness.peaks import peaks_for
    assert peaks_for("TPU v5 lite")["flops_per_s"] == 197e12
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("cpu")
