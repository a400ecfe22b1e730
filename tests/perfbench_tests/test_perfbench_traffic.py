import json
import os

import numpy as np
import pytest

from perfbench.harness import traffic as T
from perfbench.harness.manifest import BENCH_DIR


def _mix(name):
    with open(os.path.join(BENCH_DIR, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,pmean,omean", [
    ("closed-chat-decode", 173.0, 215.0),
    ("closed-doc-prefill", 1430.0, 40.0)])
def test_lengths_match_the_stated_distribution(name, pmean, omean):
    t = T.ServeTraffic(_mix(name), 32768, 1)
    p, o = t.mean_lengths()
    assert p == pytest.approx(pmean, rel=0.02)
    assert o == pytest.approx(omean, rel=0.02)
    mix = _mix(name)
    for k in (1, 2):
        lens = [len(t.request(c, k)["prompt"]) for c in range(t.clients)]
        assert min(lens) >= mix["prompt_len"]["lo"]
        assert max(lens) <= mix["prompt_len"]["hi"]


def test_loguniform_quantiles():
    v = T.quantile_lengths({"dist": "loguniform", "lo": 32, "hi": 512}, 128)
    # the median of a log-uniform is the geometric mean of its ends
    assert np.median(v) == pytest.approx(128, rel=0.03)
    assert (np.diff(v) >= 0).all()


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 12345, 2 ** 32 + 1])
def test_same_seed_same_requests_any_seed_same_sizes(seed):
    mix = _mix("closed-chat-decode")
    a, b = (T.ServeTraffic(mix, 32768, seed) for _ in range(2))
    other = T.ServeTraffic(mix, 32768, seed + 1)
    for k in (0, 1, 3):
        ra = [a.request(c, k) for c in range(a.clients)]
        rb = [b.request(c, k) for c in range(b.clients)]
        ro = [other.request(c, k) for c in range(other.clients)]
        for x, y in zip(ra, rb):
            assert (x["prompt"] == y["prompt"]).all()
            assert x["max_new_tokens"] == y["max_new_tokens"]
        # another seed: the same set of sizes in another order
        assert sorted(len(x["prompt"]) for x in ra) == \
            sorted(len(x["prompt"]) for x in ro)
        if k > 0:
            assert sorted(x["max_new_tokens"] for x in ra) == \
                sorted(x["max_new_tokens"] for x in ro)
        assert [len(x["prompt"]) for x in ra] != \
            [len(x["prompt"]) for x in ro]
        assert all((x["prompt"] >= 1).all() and
                   (x["prompt"] < 32768).all() for x in ra)


def test_warmup_round_is_cut_to_spread_the_clients():
    t = T.ServeTraffic(_mix("closed-chat-decode"), 32768, 3)
    o0 = sum(t.request(c, 0)["max_new_tokens"] for c in range(t.clients))
    o1 = sum(t.request(c, 1)["max_new_tokens"] for c in range(t.clients))
    assert 0.4 * o1 < o0 < 0.6 * o1


def test_bert_batches_rows_all_differ_and_repeat_from_seed():
    mix = _mix("pretrain-seq512")
    cfg = {"vocab_size": 30522}
    a = T.train_batches(mix, cfg, 1, 2 ** 31 + 5)
    b = T.train_batches(mix, cfg, 1, 2 ** 31 + 5)
    assert len(a) == mix["distinct_batches"]
    for x, y in zip(a, b):
        for k in x:
            assert (x[k] == y[k]).all()
    first = a[0]
    assert first["input_ids"].shape == (32, 512)
    assert first["masked_positions"].shape == (32, 77)   # ceil(15% of 512)
    rows = {r.tobytes() for r in first["input_ids"]}
    assert len(rows) == 32
    pos = first["masked_positions"]
    assert (np.diff(pos, axis=1) > 0).all()              # sorted, distinct
    assert (a[0]["input_ids"] != a[1]["input_ids"]).any()
    assert set(np.unique(first["token_type_ids"])) == {0, 1}


def test_causal_batches(toy_manifest):
    mix = {"kind": "train_batches", "task": "causal_lm",
           "batch_per_chip": 1, "seq": 64, "distinct_batches": 2}
    out = T.train_batches(mix, {"vocab_size": 100}, 4, 9)
    assert out[0]["ids"].shape == (4, 64)
    assert len({r.tobytes() for r in out[0]["ids"]}) == 4


def test_unknown_kind_is_an_error():
    with pytest.raises(ValueError):
        T.ServeTraffic({"kind": "train_batches"}, 10, 0)
    with pytest.raises(ValueError):
        T.train_batches({"kind": "serve_closed_loop"}, {}, 1, 0)


def test_open_loop_schedule_offers_the_stated_rate_on_every_seed():
    mix = {"kind": "serve_open_loop", "round": 64, "rate_per_s": 8.0,
           "arrivals": "poisson",
           "prompt_len": {"dist": "loguniform", "lo": 64, "hi": 3072},
           "output_len": {"dist": "loguniform", "lo": 16, "hi": 512}}
    ends = []
    for seed in (1, 2 ** 31 + 9):
        t = T.ServeTraffic(mix, 1000, seed)
        it = t.schedule()
        rows = [next(it) for _ in range(128)]
        dues = [d for d, _ in rows]
        assert dues == sorted(dues) and dues[0] > 0
        gaps = np.diff([0.0] + dues)
        # exponential gaps: mean 1/rate, and about as wide as their mean
        assert gaps.mean() == pytest.approx(1 / 8.0, rel=1e-6)
        assert 0.8 < gaps.std() / gaps.mean() < 1.2
        ends.append(dues[63])
        again = T.ServeTraffic(mix, 1000, seed).schedule()
        d2, r2 = next(again)
        assert d2 == rows[0][0]
        assert (r2["prompt"] == rows[0][1]["prompt"]).all()
    # every seed's round lasts the same: the same load in another order
    assert ends[0] == pytest.approx(ends[1])
    assert ends[0] == pytest.approx(64 / 8.0)
