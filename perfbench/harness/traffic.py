"""The one general traffic generator.  A traffic mix is a data file
under ``traffic/``; this module turns (file, seed) into requests or
batches.  The same seed gives the same inputs.

Every seed gets the SAME set of sizes: lengths are the stratified
quantiles of the stated distribution, and the seed only permutes their
order (and draws the token ids), so the seed does not change the work.
"""
from __future__ import annotations

import numpy as np


def _rng(seed: int, *stream: int):
    return np.random.default_rng([int(seed) & 0xFFFFFFFFFFFFFFFF,
                                  *[int(s) for s in stream]])


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """The n stratified quantiles ((i + 0.5) / n) of a length
    distribution: ``loguniform`` or ``uniform`` over [lo, hi]."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = float(dist["lo"]), float(dist["hi"])
    if dist["dist"] == "loguniform":
        v = lo * (hi / lo) ** u
    elif dist["dist"] == "uniform":
        v = lo + (hi - lo) * u
    elif dist["dist"] == "fixed":
        v = np.full(n, lo)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(v), lo, hi).astype(np.int64)


class ServeTraffic:
    """Request source of a serving mix.  Requests come in rounds of
    ``clients`` (closed loop) or ``round`` (open loop) requests; every
    round holds the same stratified set of prompt and output lengths,
    permuted by the seed.

    ``serve_closed_loop``: ``clients`` callers, each sending its next
    request when the last completes.  Round 0 is the warm-up round: its
    outputs are cut to a stratified share of their length, so the
    clients enter the window spread over their requests' lives as in
    steady state, not 128 first requests at once.

    ``serve_open_loop``: requests are due on a schedule fixed in the
    file (``rate_per_s``; ``arrivals``: "poisson" or "uniform"),
    whether or not earlier ones have finished.  A round's gaps are the
    stratified quantiles of the exponential distribution, permuted by
    the seed, so every seed offers the same load.  A request is timed
    from when it was due."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        if traffic["kind"] not in ("serve_closed_loop", "serve_open_loop"):
            raise ValueError(f"not a serving mix: {traffic['kind']!r}")
        self.t = traffic
        self.open = traffic["kind"] == "serve_open_loop"
        self.clients = int(traffic["round" if self.open else "clients"])
        self.vocab = int(vocab)
        self.seed = int(seed)
        n = self.clients
        self._plens = quantile_lengths(traffic["prompt_len"], n)
        self._olens = quantile_lengths(traffic["output_len"], n)
        self._rounds = {}

    def _round(self, k: int):
        if k not in self._rounds:
            r = _rng(self.seed, 1, k)
            p = self._plens[r.permutation(self.clients)]
            o = self._olens[r.permutation(self.clients)]
            if k == 0 and not self.open:
                frac = (r.permutation(self.clients) + 0.5) / self.clients
                o = np.maximum(1, np.rint(o * frac)).astype(np.int64)
            self._rounds[k] = (p, o)
        return self._rounds[k]

    def request(self, client: int, k: int) -> dict:
        """The k-th request of a client: prompt ids and output length.
        Ids avoid 0 (the program's pad id)."""
        p, o = self._round(k)
        ids = _rng(self.seed, 2, k, client).integers(
            1, self.vocab, size=int(p[client]), dtype=np.int64)
        return {"client": client, "k": k,
                "prompt": ids.astype(np.int32),
                "max_new_tokens": int(o[client])}

    def schedule(self):
        """Open loop: yields (seconds after the start of traffic at
        which the request is due, request), for ever."""
        n, rate = self.clients, float(self.t["rate_per_s"])
        u = (np.arange(n) + 0.5) / n
        gaps = (-np.log1p(-u) if self.t.get("arrivals", "poisson")
                == "poisson" else np.ones(n))
        gaps = gaps / gaps.mean() / rate
        due, k = 0.0, 0
        while True:
            order = _rng(self.seed, 4, k).permutation(n)
            for i in range(n):
                due += float(gaps[order[i]])
                yield due, self.request(i, k)
            k += 1

    def mean_lengths(self):
        return float(self._plens.mean()), float(self._olens.mean())


def train_batches(traffic: dict, cfg: dict, chips: int, seed: int):
    """``distinct_batches`` host batches (dict of numpy arrays) for a
    training mix; rows all differ.  ``task``:

    - ``causal_lm``: ``ids`` [B, S]; labels are the ids themselves.
    - ``bert_pretrain``: ``input_ids``, ``token_type_ids`` (sentence A
      then B, split drawn per row), ``masked_positions`` (sorted,
      distinct, ``mask_share`` of S, rounded up), ``mlm_labels`` and
      ``nsp_labels``.
    """
    if traffic["kind"] != "train_batches":
        raise ValueError(f"not a training mix: {traffic['kind']!r}")
    B = int(traffic["batch_per_chip"]) * int(chips)
    S = int(traffic["seq"])
    V = int(cfg["vocab_size"])
    out = []
    for i in range(int(traffic["distinct_batches"])):
        r = _rng(seed, 3, i)
        if traffic["task"] == "causal_lm":
            out.append({"ids": r.integers(1, V, (B, S)).astype(np.int32)})
        elif traffic["task"] == "bert_pretrain":
            M = int(np.ceil(S * float(traffic["mask_share"])))
            split = r.integers(S // 4, 3 * S // 4, (B, 1))
            pos = np.sort(np.stack(
                [r.permutation(S)[:M] for _ in range(B)]), axis=1)
            out.append({
                "input_ids": r.integers(1, V, (B, S)).astype(np.int32),
                "token_type_ids": (np.arange(S)[None, :] >= split
                                   ).astype(np.int32),
                "masked_positions": pos.astype(np.int32),
                "mlm_labels": r.integers(1, V, (B, M)).astype(np.int32),
                "nsp_labels": r.integers(0, 2, (B,)).astype(np.int32)})
        else:
            raise ValueError(f"unknown task {traffic['task']!r}")
    return out
