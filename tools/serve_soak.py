#!/usr/bin/env python3
"""Chip soak of a benchmark cell's server at full size (PERF.md section
7, X: the prefill programs of the expert cells are the family that
stalled a v5e): closed-loop decode-only traffic on every slot (if
asked) and prefill-only traffic (answers of one token, prompts
log-uniform over every bucket up to ``max_model_len``'s, so mixed
buckets AND batches), with a watchdog that ends the run (exit 7) when
no token comes for 90 s.

    chiprun --timeout 1800 -- python3 tools/serve_soak.py <cell> <seed> \\
        <decode_steps> <prefill_calls> [clients]

Compiles the serving programs in 12 threads before ``start()`` loads
them.  ``SOAK_TOY=1`` runs the toy manifests' cell of that name on a
CPU (a rehearsal of the script, no soak)."""
import concurrent.futures as cf
import glob
import json
import os
import queue
import sys
import threading
import time

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
T0 = time.perf_counter()
COUNTED = ("decode_steps", "prefill_batches", "prefill_tokens",
           "tokens_generated", "decode_ms", "prefill_ms", "traffic_compiles",
           "prefills_overlapped", "prefill_attn_pairs_multiplied",
           "prefill_attn_pairs_square")


def log(*a):
    print(f"[{time.perf_counter() - T0:7.1f}s]", *a, flush=True)


def load_cell(name: str):
    from perfbench.harness import manifest as M
    if not os.environ.get("SOAK_TOY"):
        return M.Cell(M.load_manifest(), name)
    toy = os.path.join(ROOT, "tests", "perfbench_tests", "toy")
    for path in sorted(glob.glob(os.path.join(toy, "manifest*.json"))):
        with open(path) as f:
            man = json.load(f)
        if any(w["name"] == name for w in man["workloads"]):
            return M.Cell(man, name, bench_dir=toy)
    sys.exit(f"serve_soak: no toy manifest lists {name}")


def compile_in_threads(GenerationServer):
    """``_prewarm`` behind a thread pool: lower what it would call,
    compile twelve at a time, then let it load them from the cache."""
    import numpy as np
    real = GenerationServer._prewarm

    def prewarm(self):
        W = int(np.asarray(self._seq_key_data(0)).shape[-1])
        z, o = np.zeros, np.ones
        jobs = []
        for b in self._buckets:
            for pb in self._pbatches:
                args = (z((pb, b), np.int32), z((pb,), np.int32),
                        z((pb,), np.int32), z((pb, self._M), np.int32),
                        z((pb, W), np.uint32), o((pb,), np.float32),
                        z((pb,), np.int32), o((pb,), np.float32),
                        z((pb,), bool))
                jobs.append(self._prefill_fn.lower(
                    self._pvals, self._pools, *args,
                    **self._row_slots([], pb)))
        B = self._num_slots
        dec = (z((B, 1), np.int32), z((B, 1), np.int32),
               z((B, self._M), np.int32), z((B, 1), bool),
               z((B, W), np.uint32), z((B,), np.int32), o((B,), np.float32),
               z((B,), np.int32), o((B,), np.float32), z((B,), bool))
        jobs.append(self._decode_fn.lower(self._pvals, self._pools,
                                          self._prev, *dec))
        log("lowered", len(jobs), "programs")
        with cf.ThreadPoolExecutor(12) as ex:
            list(ex.map(lambda j: j.compile(), jobs))
        log("compiled")
        real(self)
    GenerationServer._prewarm = prewarm


def drive(server, progress, phase, n_clients, make, done):
    """Closed loop: ``n_clients`` requests in flight until
    ``done(stats, stats at the start)``; the counters' deltas."""
    progress[1] = phase
    active, k, toks, last = {}, 0, 0, 0
    tick = time.perf_counter()
    s0 = server.stats()
    t0 = time.perf_counter()
    for c in range(n_clients):
        active[c] = server.submit(*make(k))
        k += 1
    stop = False
    while active:
        for c, stream in list(active.items()):
            try:
                while True:
                    stream.__next__(timeout=0)
                    toks += 1
            except StopIteration:
                del active[c]
                if not stop:
                    active[c] = server.submit(*make(k))
                    k += 1
            except queue.Empty:                    # nothing to read yet
                pass
        if toks != last:
            last, progress[0] = toks, time.perf_counter()
        s = server.stats()
        if time.perf_counter() - tick > 60:
            tick = time.perf_counter()
            log(phase, "so far", {n: s[n] - s0[n]
                                  for n in ("decode_steps", "prefill_batches")})
        stop = stop or done(s, s0)
        time.sleep(0.002)
    s1 = server.stats()
    dt = time.perf_counter() - t0
    d = {n: s1[n] - s0[n] for n in COUNTED if n in s1}
    d["bucket_rows"] = {b: n - s0["prefill_bucket_hits"].get(b, 0)
                        for b, n in s1["prefill_bucket_hits"].items()}
    log(phase, f"{dt:.1f}s", "tokens", toks, json.dumps(d))
    return d, dt


def main():
    if len(sys.argv) < 5:
        sys.exit(__doc__)
    name, seed, n_dec, n_pre = sys.argv[1], *map(int, sys.argv[2:5])
    clients = int(sys.argv[5]) if len(sys.argv) > 5 else 6
    sys.path.insert(0, ROOT)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.framework import compile_cache
    from paddle_tpu.inference import GenerationServer
    from perfbench.harness.program import install_weights
    toy = bool(os.environ.get("SOAK_TOY"))
    if jax.devices()[0].platform == "cpu" and not toy:
        sys.exit("serve_soak: no accelerator (SOAK_TOY=1 rehearses on a CPU)")
    compile_cache.ensure_compile_cache()
    cell = load_cell(name)
    cfg, srv = cell.config, cell.spec["server"]
    binding, ref = cell.binding(), cell.reference()
    model = binding.build_serving(cfg, srv["max_model_len"])
    install_weights(model, binding.name_map(cfg, model),
                    ref.param_specs(cfg), seed, jnp.bfloat16)
    jax.block_until_ready([p._value for p in model.parameters()])

    def gib(key="bytes_in_use"):
        return (jax.devices()[0].memory_stats() or {}).get(key, 0) / 2 ** 30
    log("weights on device", gib(), "GiB")
    compile_in_threads(GenerationServer)
    server = GenerationServer(
        model, num_slots=srv["num_slots"], block_size=srv["block_size"],
        max_model_len=srv["max_model_len"],
        prompt_buckets=srv["prompt_buckets"],
        max_prefill_batch=srv["max_prefill_batch"], prefix_cache=False,
        max_waiting=1024, request_timeout_s=600.0, seed=seed & 0x7FFFFFFF)
    t = time.perf_counter()
    server.start()
    log("start()", f"{time.perf_counter() - t:.1f}s", "programs",
        server.num_compiles(), "mem", gib(), "GiB")
    progress = [time.perf_counter(), "start"]

    def watchdog():
        while True:
            time.sleep(5)
            if time.perf_counter() - progress[0] > 90:
                log("STALL: no progress for 90 s in phase", progress[1],
                    json.dumps({k: v for k, v in server.stats().items()
                                if isinstance(v, (int, float))})[:1500])
                os._exit(7)
    threading.Thread(target=watchdog, daemon=True).start()
    rng = np.random.default_rng(seed)
    V = cfg["vocab_size"]
    first = min(srv["prompt_buckets"])
    short = 8 if toy else min(800, first)
    # up to the fall-back bucket of max_model_len (an answer of one
    # token has to fit behind the prompt)
    lo, hi = (8, 40) if toy else (first * 3 // 4, srv["max_model_len"] - 2)
    if n_dec:
        # every slot, short prompts, the longest answers that fit
        d, dt = drive(
            server, progress, "decode-only", srv["num_slots"],
            lambda k: (rng.integers(1, V, short).astype(np.int32),
                       srv["max_model_len"] - short - 28),
            lambda s, s0: s["decode_steps"] - s0["decode_steps"] >= n_dec)
        log("decode step ms (scheduler)",
            d["decode_ms"] / max(d["decode_steps"], 1), "tok/s",
            d["tokens_generated"] / dt)
    d, dt = drive(
        server, progress, "prefill-only", clients,
        lambda k: (rng.integers(
            1, V, int(lo * (hi / lo) ** rng.random())).astype(np.int32), 1),
        lambda s, s0: s["prefill_batches"] - s0["prefill_batches"] >= n_pre)
    log("prefill ms a call", d["prefill_ms"] / max(d["prefill_batches"], 1),
        "rows a call", d["tokens_generated"] / max(d["prefill_batches"], 1))
    log("peak GiB", gib("peak_bytes_in_use"))
    server.stop()
    log("SOAK OK")


if __name__ == "__main__":
    main()
