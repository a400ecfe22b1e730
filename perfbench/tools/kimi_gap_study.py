#!/usr/bin/env python3
"""Not the benchmark's command: a study of WHY the kimi-linear cell's
``served_logit_gap`` reads several times a dense decoder's (PERF.md,
PR 27).  At the configuration's published widths, with the benchmark's
weights for ``--seed``, it

1. lets the program generate greedily (prefill in the server's bucket,
   then one decode step a token, through the model's own paged caches)
   in bfloat16 and, with ``--dtype float32``, in float32 arithmetic, or
   replays the tokens a chip run served (``--sample``, prompts and
   tokens as JSON), and records which experts the PROGRAM's router
   chose for every token and expert layer;
2. runs the plain reference over the same tokens (float32) and records
   the experts ITS router chose;
3. reads, at every generated position, the gap that ``correct``
   compares (how far the served token's reference logit lies below the
   reference's best, in units of that position's logit deviation), and
   splits the tokens by whether the two routers agreed.

Runs wherever JAX runs (a CPU: ``JAX_PLATFORMS=cpu``; minutes a
request) and prints one JSON object.  A float32 program that reads a
gap of nought shows the mathematics of the two to be the same at this
size; a bfloat16 gap that collapses on the tokens whose routing the
reference confirms shows the rest to be flipped experts.

    python3 perfbench/tools/kimi_gap_study.py --seed 2147487001 \
        [--dtype bfloat16] [--requests 4] [--prompt 300] [--new 96] \
        [--sample chiprun_out/fx_sample_<seed>.json] [--out FILE]
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CELL = "kimi-linear-serve-decode"
HISTORY = 16     # a flip this many tokens back still counts as near


def log(msg):
    print(f"study: {msg}", file=sys.stderr, flush=True)


def program_routes(cfg, cell, seed, dtype, requests):
    """Greedy generation (or the replay of given tokens) through the
    program's paged caches, one sequence in one slot.  Returns per
    request (tokens, routes [positions, expert layers, k]) with the
    router's choices at every position the program processed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.framework.core import abstract_init, no_grad
    from paddle_tpu.nn.layer import moe as MOE
    from paddle_tpu.text.models import KimiLinearForCausalLM
    from perfbench.harness.program import install_weights
    binding, ref = cell.binding(), cell.reference()
    srv = cell.spec["server"]
    max_len, buckets = srv["max_model_len"], srv["prompt_buckets"]
    block = srv["block_size"]
    with abstract_init():
        model = KimiLinearForCausalLM(dataclasses.replace(
            binding.model_config(cfg, max_len), compute_dtype=dtype))
    model.eval()
    # the benchmark's weights: bfloat16 values, held in ``dtype``
    install_weights(model, binding.name_map(cfg, model),
                    ref.param_specs(cfg), seed, jnp.bfloat16)
    params = dict(model.state_dict())
    pvals = {k: t._value.astype(dtype) for k, t in params.items()}
    M = max_len // block
    table = jnp.arange(1, M + 1, dtype=jnp.int32)[None]
    seen = []

    def recording(route):
        @functools.wraps(route)
        def f(x, router_w, router_b, top_k, scale, held):
            local, w, here = route(x, router_w, router_b, top_k, scale,
                                   held)
            seen.append(local + held[0])
            return local, w, here
        return f

    def call(pvals, pools, ids, pos, wm, gather_at, slots):
        old = {k: t._value for k, t in params.items()}
        del seen[:]
        try:
            for k, t in params.items():
                t._value = pvals[k]
            with no_grad():
                lg, pools, _ = model.forward_paged(
                    ids, pos, pools, table, wm, gather_at=gather_at,
                    slots=slots)
        finally:
            for k, t in params.items():
                t._value = old[k]
        return (jnp.argmax(lg._value[0, -1]), pools,
                jnp.stack(seen, 1))                  # [S, layers, k]

    def one(n, prompt, given, new):
        t = time.perf_counter()
        L = len(prompt)
        Lb = min(b for b in buckets if b >= L)
        pools = model.init_paged_cache(M + 1, block, 1)
        ids = np.zeros((1, Lb), np.int32)
        ids[0, :L] = prompt
        first, pools, r = prefill(
            pvals, pools, jnp.asarray(ids),
            jnp.arange(Lb, dtype=jnp.int32)[None],
            jnp.arange(Lb)[None] < L, jnp.asarray([L - 1]))
        toks, routes = [int(first)], [np.asarray(r)[:L]]
        for j in range(1, new):
            fed = given[j - 1] if given else toks[-1]
            nxt, pools, r = decode(
                pvals, pools, jnp.full((1, 1), fed, jnp.int32),
                jnp.full((1, 1), L + j - 1, jnp.int32),
                jnp.ones((1, 1), bool))
            toks.append(int(nxt))
            routes.append(np.asarray(r))
        log(f"program ({dtype}) request {n}: {L} + {new} tokens in "
            f"{time.perf_counter() - t:.0f} s")
        return given or toks, toks, np.concatenate(routes)

    plain_route = MOE._route
    MOE._route = recording(plain_route)
    prefill = jax.jit(functools.partial(call, slots=jnp.zeros(
        (1,), jnp.int32)))
    decode = jax.jit(functools.partial(call, gather_at=None, slots=None))
    try:
        return [one(n, *rq) for n, rq in enumerate(requests)]
    finally:
        MOE._route = plain_route


def reference_numbers(cfg, cell, seed, seqs):
    """Per request: the reference's routing at every position and, at
    the generated positions, the gap ``correct`` compares and whether
    the reference's best is another token."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench.harness import weights as W
    ref = cell.reference()
    specs = ref.param_specs(cfg)
    key = W.seed_key(seed)

    def make(names):
        t = jax.jit(functools.partial(
            W.make_tree, specs, dtype=jnp.bfloat16, names=tuple(names)))(key)
        return {k.split(".")[-1]: v.astype(jnp.float32)
                for k, v in t.items()}

    @jax.jit
    def layer(lp, h):
        """``ref.layer_apply`` with the router's choice handed out."""
        eps = cfg["rms_norm_eps"]
        x = ref.rms_norm(h, lp["ln1"], eps)
        h = h + (ref.kda(cfg, lp, x) if "kq" in lp else ref.mla(cfg, lp, x))
        x = ref.rms_norm(h, lp["ln2"], eps)
        if "eg" not in lp:
            return h + ref.swiglu(x, lp["wg"], lp["wu"], lp["wd"]), None
        s = jax.nn.sigmoid(x @ lp["router"]) + lp["rbias"]
        return h + ref.moe(cfg, lp, x), jax.lax.top_k(
            s, cfg["num_experts_per_token"])[1]
    gp = make(ref.GLOBAL_LEAVES)
    ids = [jnp.asarray(np.concatenate([p, np.asarray(t[:-1], np.int32)]))
           for p, t in seqs]
    hs = [ref.embed(cfg, gp, i) for i in ids]
    routes = [[] for _ in seqs]
    with jax.default_matmul_precision("highest"):
        for l in range(cfg["num_hidden_layers"]):
            lp = make(ref.layer_names(l))
            for i, h in enumerate(hs):
                hs[i], r = layer(lp, h)
                if r is not None:
                    routes[i].append(np.asarray(r))
            del lp
        out = []
        for (p, t), h, r in zip(seqs, hs, routes):
            L, n = len(p), len(t)
            lr = np.asarray(ref.logits(cfg, gp, h))[L - 1:L - 1 + n]
            served = lr[np.arange(n), np.asarray(t)]
            out.append({"gap": (lr.max(-1) - served) / lr.std(-1),
                        "mismatch": lr.argmax(-1) != np.asarray(t),
                        "routes": np.stack(r, 1)})
    return out


def summarize(prog, refd, seqs):
    import numpy as np
    gap, mis, own, near = [], [], [], []
    flips, picks = 0, 0
    for (p, _), (_, _, pr), rd in zip(seqs, prog, refd):
        L, n = len(p), len(rd["gap"])
        T = L + n - 1
        # [positions, layers]: the two routers chose other experts
        flip = (np.sort(pr[:T], -1) != np.sort(rd["routes"][:T], -1)).any(-1)
        flips += int(flip.sum())
        picks += flip.size
        anyl = flip.any(-1)
        gap.append(rd["gap"])
        mis.append(rd["mismatch"])
        own.append(anyl[L - 1:])
        near.append(np.asarray([anyl[max(0, t - HISTORY):t + 1].any()
                                for t in range(L - 1, T)]))
    gap, mis, own, near = map(np.concatenate, (gap, mis, own, near))

    def part(mask):
        if not mask.any():
            return {"tokens": 0}
        g = gap[mask]
        return {"tokens": int(mask.sum()), "gap_max": float(g.max()),
                "gap_p99": float(np.quantile(g, 0.99)),
                "gap_mean": float(g.mean()),
                "mismatch_share": float(mis[mask].mean())}
    return {"all": part(np.ones_like(own)),
            "routing_confirmed_at_the_token": part(~own),
            "routing_flipped_at_the_token": part(own),
            f"routing_confirmed_over_{HISTORY}_tokens_back": part(~near),
            "flipped_token_layers_share": flips / max(picks, 1)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--dtype", default="bfloat16",
                    choices=("bfloat16", "float32"))
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt", type=int, default=300)
    ap.add_argument("--new", type=int, default=96)
    ap.add_argument("--sample")
    ap.add_argument("--out")
    a = ap.parse_args(argv)
    import jax
    import numpy as np

    from perfbench.harness import manifest as M
    cell = M.Cell(M.load_manifest(), CELL)
    cfg = cell.config
    if a.sample:
        with open(a.sample) as f:
            rows = sorted(json.load(f), key=lambda r: len(r["prompt"])
                          + len(r["tokens"]))[:a.requests]
        requests = [(np.asarray(r["prompt"], np.int32), r["tokens"],
                     len(r["tokens"])) for r in rows]
    else:
        rng = np.random.default_rng([a.seed & 0xFFFFFFFF, 11])
        requests = [(rng.integers(1, cfg["vocab_size"], size=a.prompt
                                  + 17 * i).astype(np.int32), None, a.new)
                    for i in range(a.requests)]
    prog = program_routes(cfg, cell, a.seed, a.dtype, requests)
    seqs = [(rq[0], fed) for rq, (fed, _, _) in zip(requests, prog)]
    t = time.perf_counter()
    refd = reference_numbers(cfg, cell, a.seed, seqs)
    log(f"reference over {len(seqs)} requests in "
        f"{time.perf_counter() - t:.0f} s")
    res = {"seed": a.seed, "dtype": a.dtype, "sample": a.sample,
           "platform": jax.devices()[0].platform,
           "requests": [[len(p), len(t)] for p, t in seqs],
           **summarize(prog, refd, seqs)}
    if a.sample:
        # a replay feeds the served tokens: how often the replaying
        # arithmetic would itself have served another
        res["replay_disagrees_share"] = float(np.mean(np.concatenate(
            [np.asarray(fed) != np.asarray(own)
             for fed, own, _ in prog])))
    line = json.dumps(res)
    print(line, flush=True)
    if a.out:
        with open(a.out, "a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
