#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py              # on a TPU host; fails anywhere else
    python3 chip_smoke.py --rehearse   # CPU rehearsal at toy size

ONE process (a chip belongs to one process) drives the main path once,
through the entry points a user would call, at the full width of the
repo's decoder proxy (depth cut to 8 layers, random weights from a seed):

- train: the 536M decoder (hidden 2048 x 8 layers, seq 2048, batch 4,
  bf16 AMP, scanned layers + per-layer remat, AdamW) through
  ``fleet.DistributedTrainStep`` on a one-chip mesh, 5 steps on one
  repeated batch — finite falling loss, flash attention dispatched
  ``pallas`` only, ``tpu_custom_call`` in the compiled step;
- four (when there are >= 4 devices): the same step, same global batch
  and seed, over ``{"fsdp": 4}`` ZeRO-2 and ``{"tp": 2, "fsdp": 2}`` —
  loss falls, step-1 loss matches the one-chip run, state lives on four
  devices, ``bytes_in_use`` balanced within 2x;
- serve: ``inference.GenerationServer`` over ``LlamaForCausalLM``
  (hidden 1024 x 8 layers, 16/8 heads, vocab 32000), 16 slots, block
  16: sixteen greedy requests of 8-24 prompt tokens plus one of 1,024
  (the flash prefill branch), 64 new tokens each — full-length streams,
  zero traffic compiles, identical tokens on resubmission, tokens equal
  to ``model.generate()`` (or, where bf16 breaks exact equality, the two
  paths' first-step logits within a stated tolerance);
- kernels: every ``ops.pallas.registry`` kernel once in ``pallas`` mode
  at a real shape against its own ``xla_ref`` inside its registered
  tolerance, dispatch counted ``pallas`` and never ``fallback``.

Timings and byte counts printed along the way are OBSERVATIONS of one
run, not metrics.  The last line of stdout is the result,
``{"ok": true, "device": {...}}``, printed only if every phase passed;
any failure is a non-zero exit with no result line.

``--rehearse`` is the ONLY way this script runs off the chip: the same
phases at toy size on (4 virtual) CPU devices, dispatch sites told the
target is a TPU so the same branches trace, kernels under the Pallas
interpreter.  It says so in its output and in its result line.
``--phases`` runs a subset while debugging.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

PHASES = ("train", "four", "serve", "kernels")

# ---------------------------------------------------------------------
# sizes: the chip run is the bench's decoder proxy / serving proxy at
# full width; the rehearsal is a toy that traces the same branches
# ---------------------------------------------------------------------
FULL = dict(
    train=dict(vocab_size=32000, hidden_size=2048, intermediate_size=5504,
               num_hidden_layers=8, num_attention_heads=16,
               num_key_value_heads=16, seq=2048, batch=4),
    serve=dict(vocab_size=32000, hidden_size=1024, intermediate_size=2816,
               num_hidden_layers=8, num_attention_heads=16,
               num_key_value_heads=8, slots=16, block=16, max_new=64,
               long_prompt=1024, max_model_len=1536,
               buckets=(32, 1024)),
    flash_shape=(2, 16, 2048, 128), flat_n=10_000_000,
    mm=(256, 4096, 4096), seg=(8192, 128, 1024), seg_sorted_nseg=32768,
    deq=(4096, 128),
)
TOY = dict(
    train=dict(vocab_size=512, hidden_size=256, intermediate_size=512,
               num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=4, seq=128, batch=4),
    serve=dict(vocab_size=256, hidden_size=256, intermediate_size=512,
               num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=2, slots=16, block=16, max_new=6,
               long_prompt=128, max_model_len=256, buckets=(32, 128)),
    flash_shape=(1, 2, 256, 64), flat_n=40_000,
    mm=(64, 256, 256), seg=(256, 128, 64), seg_sorted_nseg=8192,
    deq=(300, 128),
)

# four-chip step-1 loss vs the one-chip step-1 loss: same data, same
# seed, bf16 compute — only reduction order differs between layouts
LOSS_MATCH_ATOL = 5e-2
# serve fallback gate (used only when bf16 breaks exact token
# equality): the paged and contiguous paths' first-step logits
LOGIT_RTOL = 2e-2


def log(msg):
    print(msg, flush=True)


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


# ---------------------------------------------------------------------
# train
# ---------------------------------------------------------------------
def _decoder_step(size, degrees, devices, zero_stage):
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.text.models import LlamaForCausalLM, llama_tiny

    t = size["train"]
    mesh_mod.set_mesh(None)
    mesh = mesh_mod.init_mesh(degrees, devices=devices)
    paddle.seed(0)
    cfg = llama_tiny(
        **{k: t[k] for k in ("vocab_size", "hidden_size",
                             "intermediate_size", "num_hidden_layers",
                             "num_attention_heads",
                             "num_key_value_heads")},
        max_position_embeddings=t["seq"], scan_layers=True, remat=True)
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=3e-4,
                                 parameters=model.parameters())
    strategy = fleet.DistributedStrategy()
    strategy.amp = True
    strategy.amp_configs = {"dtype": "bfloat16"}
    if zero_stage:
        strategy.sharding = True
        strategy.sharding_configs = {"stage": zero_stage}

    def loss_fn(ids, labels):
        loss, _ = model(ids, labels=labels)
        return loss

    step = fleet.DistributedTrainStep(model, loss_fn, opt, strategy,
                                      mesh=mesh)
    ids = paddle.to_tensor(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (t["batch"], t["seq"])).astype("int32"))
    return model, opt, step, ids


def _run_steps(step, ids, n):
    import jax
    losses, times = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        loss = step(ids, ids)
        jax.block_until_ready(loss._value)
        times.append(time.perf_counter() - t0)
        losses.append(float(loss))
    return losses, times


def phase_train(size, kernel_path, rehearse):
    import jax

    from paddle_tpu.ops.pallas import registry

    registry.reset_dispatch_counts("flash_attention")
    model, opt, step, ids = _decoder_step(
        size, {"dp": -1}, jax.devices()[:1], zero_stage=0)
    nparams = sum(int(math.prod(p.shape)) for p in model.parameters())
    losses, times = _run_steps(step, ids, 5)
    counts = registry.dispatch_counts("flash_attention")
    kernels = step.audit().hlo_kernels
    log(f"train: params={nparams/1e6:.1f}M losses="
        f"{[round(l, 4) for l in losses]}")
    log(f"train: flash_attention dispatch={json.dumps(counts)} "
        f"hlo_kernels={json.dumps(kernels)}")
    log(f"train: [observation] first step (trace+compile+run) "
        f"{times[0]:.1f} s, steady {1e3 * min(times[1:]):.1f} ms/step, "
        f"peak_bytes_in_use="
        f"{(jax.devices()[0].memory_stats() or {}).get('peak_bytes_in_use')}")
    check(all(math.isfinite(l) for l in losses), f"non-finite loss {losses}")
    check(losses[4] < losses[0], f"loss did not fall: {losses}")
    check(set(counts) == {kernel_path},
          f"flash_attention ran {counts}, wanted {kernel_path} only")
    if not rehearse:
        check(kernels and any("tpu_custom_call" in k for k in kernels),
              f"no tpu_custom_call in the compiled step: {kernels}")
    return losses[0]


# ---------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------
def phase_four(size, kernel_path, loss1_one_chip):
    import jax

    from paddle_tpu.ops.pallas import registry

    devs = jax.devices()[:4]
    for degrees in ({"fsdp": 4}, {"tp": 2, "fsdp": 2}):
        tag = "x".join(f"{k}{v}" for k, v in degrees.items())
        gc.collect()
        registry.reset_dispatch_counts("flash_attention")
        model, opt, step, ids = _decoder_step(size, degrees, devs,
                                              zero_stage=2)
        losses, times = _run_steps(step, ids, 3)
        counts = registry.dispatch_counts("flash_attention")
        log(f"four[{tag}]: losses={[round(l, 4) for l in losses]} "
            f"flash_attention dispatch={json.dumps(counts)}")
        log(f"four[{tag}]: [observation] first step {times[0]:.1f} s, "
            f"steady {1e3 * min(times[1:]):.1f} ms/step")
        check(all(math.isfinite(l) for l in losses),
              f"[{tag}] non-finite loss {losses}")
        check(losses[2] < losses[0], f"[{tag}] loss did not fall: {losses}")
        check(set(counts) == {kernel_path},
              f"[{tag}] flash_attention ran {counts}")
        if loss1_one_chip is not None:
            d = abs(losses[0] - loss1_one_chip)
            log(f"four[{tag}]: step-1 loss {losses[0]:.4f} vs one-chip "
                f"{loss1_one_chip:.4f} (|diff| {d:.2e}, "
                f"atol {LOSS_MATCH_ATOL})")
            check(d <= LOSS_MATCH_ATOL,
                  f"[{tag}] sharded step-1 loss differs from one chip")
        # where the state lives: every parameter and every optimizer
        # slot spans all four devices, and ZeRO-2 moments are SHARDS
        state = [p._value for p in model.parameters()]
        slots = [v for st in opt.opt_state() for v in st.values()
                 if hasattr(v, "sharding") and v.ndim >= 1]
        for arr in state + slots:
            check(len(arr.sharding.device_set) == 4,
                  f"[{tag}] state on {arr.sharding.device_set}")
        big = max(slots, key=lambda a: a.size)
        shard = big.addressable_shards[0].data
        check(shard.size * 2 <= big.size,
              f"[{tag}] optimizer slot not sharded: {shard.shape} of "
              f"{big.shape}")
        del state, slots, big, shard
        gc.collect()
        used = [(d.memory_stats() or {}).get("bytes_in_use") for d in devs]
        log(f"four[{tag}]: [observation] bytes_in_use per device {used}")
        if all(u is not None for u in used):
            check(max(used) <= 2 * min(used),
                  f"[{tag}] memory piled up on one device: {used}")
        del model, opt, step, ids


# ---------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------
def _first_step_logits(model, prompt, block):
    """Logits of the prefill's last position and of the first decode
    step (fed the prefill's argmax), through the paged path (what the
    server runs) and the contiguous-cache path (what generate() runs),
    both at batch 1: [(paged, contiguous) for prefill, for decode]."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.framework.core import Tensor, no_grad

    L = len(prompt)
    M = -(-(L + 1) // block)
    ids = Tensor(jnp.asarray(prompt[None, :]))
    pos = Tensor(jnp.arange(L, dtype=jnp.int32)[None, :])
    tbl = jnp.arange(1, M + 1, dtype=jnp.int32)[None, :]
    one = jnp.ones((1, 1), bool)

    def last(t):
        return np.asarray(t._value, np.float32)[0, -1]
    with no_grad():
        pools = model.init_paged_cache(M + 1, block)
        p0, pools = model.forward_paged(
            ids, pos, pools, tbl, jnp.ones((1, L), bool),
            gather_at=jnp.asarray([L - 1], jnp.int32))
        c0, caches = model.forward_with_cache(
            ids, pos, model.init_cache(1, L + 1), last_logits_only=True)
        nxt = Tensor(jnp.asarray([[int(last(c0).argmax())]], jnp.int32))
        at = Tensor(jnp.asarray([[L]], jnp.int32))
        p1, _ = model.forward_paged(nxt, at, pools, tbl, one)
        c1, _ = model.forward_with_cache(nxt, at, caches)
    return [(last(p0), last(c0)), (last(p1), last(c1))]


def phase_serve(size, kernel_path):
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.inference import GenerationServer
    from paddle_tpu.ops.pallas import registry
    from paddle_tpu.text.models import LlamaForCausalLM, llama_tiny

    s = size["serve"]
    mesh_mod.set_mesh(None)       # the server takes no device: chip 0
    registry.reset_dispatch_counts("flash_attention")
    paddle.seed(0)
    cfg = llama_tiny(
        **{k: s[k] for k in ("vocab_size", "hidden_size",
                             "intermediate_size", "num_hidden_layers",
                             "num_attention_heads",
                             "num_key_value_heads")},
        max_position_embeddings=s["max_model_len"])
    model = LlamaForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(0)
    lens = [(8, 24, 16, 12)[i % 4] for i in range(16)] + [s["long_prompt"]]
    prompts = [rng.randint(1, cfg.vocab_size, (L,)).astype("int32")
               for L in lens]
    new = s["max_new"]

    server = GenerationServer(
        model, num_slots=s["slots"], block_size=s["block"],
        max_model_len=s["max_model_len"], prompt_buckets=s["buckets"],
        request_timeout_s=600.0)
    t0 = time.perf_counter()
    server.start()
    t_start = time.perf_counter() - t0
    n_warm = server.num_compiles()
    try:
        rounds = []
        for _ in range(2):
            t0 = time.perf_counter()
            streams = [server.submit(p, max_new_tokens=new)
                       for p in prompts]
            rounds.append([st.result(timeout=600.0) for st in streams])
            dt = time.perf_counter() - t0
        n_after = server.num_compiles()
        stats = server.stats()
    finally:
        server.stop()
    counts = registry.dispatch_counts("flash_attention")
    log(f"serve: {len(rounds[0])} streams x {new} tokens, prompts "
        f"{sorted(set(lens))}, compiles at start {n_warm}, after "
        f"traffic {n_after}, flash_attention dispatch="
        f"{json.dumps(counts)}")
    log(f"serve: [observation] start() (build + prewarm) {t_start:.1f} s, "
        f"second round of {17 * new} tokens in {dt:.2f} s, "
        f"decode_steps={stats['decode_steps']}")
    check(all(len(o) == new for r in rounds for o in r),
          f"short stream: {[len(o) for o in rounds[0]]}")
    check(n_after == n_warm, f"traffic compiled: {n_warm} -> {n_after}")
    check(rounds[0] == rounds[1], "resubmission changed the tokens")
    check(set(counts) == {kernel_path},
          f"flash prefill ran {counts}, wanted {kernel_path} only")

    # reference: model.generate() on the same prompts (one batched call
    # per prompt length — generate() takes rectangular batches)
    t0 = time.perf_counter()
    ref = [None] * len(prompts)
    for L in sorted(set(lens)):
        idx = [i for i, n in enumerate(lens) if n == L]
        out = model.generate(
            paddle.to_tensor(np.stack([prompts[i] for i in idx])),
            max_new_tokens=new).numpy()
        for row, i in zip(out, idx):
            ref[i] = row[L:].tolist()
    same = [a == b for a, b in zip(rounds[0], ref)]
    log(f"serve: {sum(same)}/{len(same)} streams token-identical to "
        f"model.generate() ([observation] generate() took "
        f"{time.perf_counter() - t0:.1f} s)")
    if not all(same):
        # bf16 on the chip: two correct programs may round differently
        # and flip a near-tie argmax; the gate then is numeric
        for i in (0, len(prompts) - 1):
            for what, (a, b) in zip(("prefill", "first decode step"),
                                    _first_step_logits(
                                        model, prompts[i], s["block"])):
                err = float(np.abs(a - b).max())
                bound = LOGIT_RTOL * max(1.0, float(np.abs(b).max()))
                log(f"serve: prompt len {lens[i]} {what} logits paged vs "
                    f"contiguous max|diff| {err:.3e} (bound {bound:.3e})")
                check(err <= bound,
                      "paged and contiguous logits disagree")
        first_diff = [next(j for j, (x, y) in enumerate(zip(a, b))
                           if x != y)
                      for a, b, ok in zip(rounds[0], ref, same) if not ok]
        log(f"serve: FINDING exact token equality broken at decode "
            f"steps {first_diff} — both paths' logits within tolerance "
            "at batch 1")


# ---------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------
def _kernel_cases(size):
    """name -> (run(mode) -> outputs, reference() -> outputs, rtol,
    atol); an output passes when max|diff| <= atol + rtol * max|ref|,
    so (0, 0) means bit-exact.  Tolerances quote each kernel's
    registration (``registry.kernels()[name].tolerance``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas import registry as kreg
    from paddle_tpu.ops.pallas.flash_attention import (_ref_chunked,
                                                       flash_attention_bhsd)
    from paddle_tpu.ops.pallas.opt_apply import pack_hyper

    rng = np.random.default_rng(0)
    f32 = jnp.float32

    def randn(*shape, dtype=f32):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    cases = {}

    # flash fwd + bwd, bf16 (the hot-path dtype) vs the f32 reference
    q, k, v = (randn(*size["flash_shape"], dtype=jnp.bfloat16)
               for _ in range(3))

    def fa(fn):
        def loss(q_, k_, v_):
            return (fn(q_, k_, v_).astype(f32) ** 2).sum()
        return jax.jit(lambda *a: (fn(*a),) + jax.grad(
            loss, argnums=(0, 1, 2))(*a))
    sc = 1.0 / math.sqrt(q.shape[-1])
    cases["flash_attention"] = (
        lambda mode: fa(lambda *a: flash_attention_bhsd(
            *a, causal=True, interpret=(mode == "interpret")))(q, k, v),
        lambda: fa(lambda *a: _ref_chunked(*a, None, True, sc))(
            q.astype(f32), k.astype(f32), v.astype(f32)),
        2e-2, 0.0)    # bf16 in/out (eps 2^-7): 2% of each output's max

    n = size["flat_n"]
    p, g = randn(n), randn(n)
    m_, v_ = randn(n) * 0.1, jnp.abs(randn(n)) * 0.01
    hy = jnp.asarray(pack_hyper("adam", lr=1e-3, t=3))
    cases["opt_apply"] = (
        lambda mode: kreg.dispatch("opt_apply", "adam", p, g, (m_, v_),
                                   hy, mode=mode),
        lambda: kreg.dispatch("opt_apply", "adam", p, g, (m_, v_), hy,
                              mode="xla_ref"),
        1e-5, 1e-7)

    mm, kk, nn = size["mm"]
    xq = jnp.asarray(rng.integers(-127, 127, (mm, kk)), jnp.int8)
    qw = jnp.asarray(rng.integers(-127, 127, (kk, nn)), jnp.int8)
    wsc = jnp.asarray(rng.random(nn) * 0.01 + 1e-4, f32)
    xf = randn(mm, kk, dtype=jnp.bfloat16)

    def i8(mode):
        return (kreg.dispatch("int8_matmul", xq, qw, wsc,
                              x_scale=np.float32(0.02),
                              compute_dtype=f32, mode=mode),
                kreg.dispatch("int8_matmul", xf, qw, wsc,
                              compute_dtype=jnp.bfloat16, mode=mode))
    # dynamic arm is integer-exact; the weight-only bf16 arm carries
    # the registered rtol 2e-2
    cases["int8_matmul"] = (i8, lambda: i8("xla_ref"), 2e-2, 0.0)

    rows, dim, nseg = size["seg"]
    gi = jnp.asarray(rng.integers(-8, 8, (rows, dim)), f32)
    inv = jnp.asarray(rng.integers(0, nseg, rows), jnp.int32)
    cases["segment_sum"] = (
        lambda mode: kreg.dispatch("segment_sum", gi, inv,
                                   num_segments=nseg, mode=mode),
        lambda: kreg.dispatch("segment_sum", gi, inv, num_segments=nseg,
                              mode="xla_ref"),
        0, 0)     # integer-valued grads: exact under any ordering

    big = size["seg_sorted_nseg"]
    seg = np.sort(rng.integers(0, big, rows)).astype(np.int64)
    cases["segment_sum_sorted"] = (
        lambda mode: kreg.dispatch("segment_sum_sorted", gi, seg,
                                   num_segments=big, mode=mode),
        lambda: kreg.dispatch("segment_sum_sorted", gi, seg,
                              num_segments=big, mode="xla_ref"),
        0, 0)

    dr, dd = size["deq"]
    codes = jnp.asarray(rng.integers(-127, 128, (dr, dd)), jnp.int8)
    dsc = jnp.asarray(rng.random(dr) * 0.01 + 1e-4, f32)
    cases["pull_dequant"] = (
        lambda mode: kreg.dispatch("pull_dequant", codes, dsc, mode=mode),
        lambda: kreg.dispatch("pull_dequant", codes, dsc, mode="xla_ref"),
        0, 0)

    # int8_kv_attention: decode shape over pools laid out [nb,bs,KH,D]
    b, s_, g_, r_, d_, bs, mtab, nb = 4, 1, 8, 2, 128, 16, 16, 257
    kv = [randn(b, s_, g_ * r_, d_),
          jnp.asarray(rng.integers(-127, 127, (nb, bs, g_, d_)), jnp.int8),
          jnp.asarray(rng.integers(-127, 127, (nb, bs, g_, d_)), jnp.int8),
          jnp.asarray(rng.random((nb, bs)) * 0.01 + 1e-4, f32),
          jnp.asarray(rng.random((nb, bs)) * 0.01 + 1e-4, f32),
          jnp.asarray(rng.integers(1, nb, (b, mtab)), jnp.int32),
          jnp.full((b, s_), bs * mtab - 1, jnp.int32)]
    cases["int8_kv_attention"] = (
        lambda mode: kreg.dispatch("int8_kv_attention", *kv, g_,
                                   mode=mode),
        lambda: kreg.dispatch("int8_kv_attention", *kv, g_,
                              mode="xla_ref"),
        1e-4, 2e-5)

    # paged_attention: a decode step over bf16 pools, ragged lengths
    # (1, one block, one past it, the whole table), tables padded with
    # the trash block
    lens = np.array([1, bs, bs + 1, bs * mtab], np.int32)
    ptbl = np.zeros((b, mtab), np.int32)
    for i, n_ in enumerate(lens):
        used = -(-int(n_) // bs)
        ptbl[i, :used] = 1 + rng.permutation(nb - 1)[:used]
    pa = [randn(b, s_, g_ * r_, d_, dtype=jnp.bfloat16),
          randn(nb, bs, g_, d_, dtype=jnp.bfloat16),
          randn(nb, bs, g_, d_, dtype=jnp.bfloat16), None, None,
          jnp.asarray(ptbl), jnp.asarray(lens[:, None] - 1)]
    cases["paged_attention"] = (
        lambda mode: kreg.dispatch("paged_attention", *pa, g_, mode=mode),
        lambda: kreg.dispatch("paged_attention", *pa, g_,
                              mode="xla_ref"),
        2e-2, 0.0)    # bf16 out (eps 2^-7)
    return cases


def phase_kernels(size, kernel_path, rehearse):
    import jax
    import numpy as np

    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.ops.pallas import registry

    mesh_mod.set_mesh(None)
    cases = _kernel_cases(size)
    check(sorted(cases) == sorted(registry.kernels()),
          f"kernel cases {sorted(cases)} != registry "
          f"{sorted(registry.kernels())}")
    failed = []
    for name in sorted(cases):
        run, reference, rtol, atol = cases[name]
        spec = registry.kernels()[name]
        registry.reset_dispatch_counts(name)
        if spec.tpu_default == "xla_ref" and not rehearse:
            # parked behind its reference on TPU (lowering error
            # recorded in PERF.md "Bring-up on v5e"): the DEFAULT
            # route must be the reference, and must run
            out = jax.block_until_ready(run(None))
            counts = registry.dispatch_counts(name)
            log(f"kernels: {name}: defaulted to xla_ref on TPU — "
                f"dispatch={json.dumps(counts)} (not run in pallas mode)")
            check(set(counts) == {"xla_ref"}, f"{name} ran {counts}")
            continue
        with jax.default_matmul_precision("highest"):
            want = jax.block_until_ready(reference())
        registry.reset_dispatch_counts(name)
        got = jax.block_until_ready(run(kernel_path))
        counts = registry.dispatch_counts(name)
        # per output tensor: max|diff| against atol + rtol * max|ref|
        # (a blockwise kernel's rounding error scales with the row, not
        # with the element it lands on)
        ok, detail = set(counts) == {kernel_path}, []
        for w, g in zip(jax.tree_util.tree_leaves(want),
                        jax.tree_util.tree_leaves(got)):
            w, g = np.asarray(w, np.float32), np.asarray(g, np.float32)
            err = float(np.abs(w - g).max())
            bound = atol + rtol * float(np.abs(w).max())
            ok = ok and w.shape == g.shape and err <= bound
            detail.append(f"{err:.3e}/{bound:.3e}")
        log(f"kernels: {name}: max|diff|/bound per output "
            f"[{', '.join(detail)}] (rtol {rtol}, atol {atol}) "
            f"dispatch={json.dumps(counts)} {'ok' if ok else 'FAILED'}")
        if not ok:
            failed.append(name)
    check(not failed, f"kernels outside tolerance or misrouted: {failed}")


# ---------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at toy size (kernels interpreted)")
    ap.add_argument("--phases", default=",".join(PHASES),
                    help=f"comma list out of {PHASES} (debugging)")
    args = ap.parse_args(argv)
    phases = [p for p in args.phases.split(",") if p]
    unknown = [p for p in phases if p not in PHASES]
    if unknown:
        ap.error(f"unknown phases {unknown}")

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4"
            # the CPU backend aborts promoting bf16 collectives
            + " --xla_disable_hlo_passes=all-reduce-promotion").strip()
        os.environ["PADDLE_TPU_FLASH_MIN_SEQ"] = "128"
    import jax
    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")

    devs = jax.devices()      # no chip / chip held: the backend's error
    device = {"platform": devs[0].platform,
              "kind": devs[0].device_kind, "count": len(devs)}
    log(f"device: platform={device['platform']} "
        f"kind={device['kind']!r} count={device['count']}")
    if args.rehearse:
        log("REHEARSAL: toy sizes on CPU, kernels under the Pallas "
            "interpreter — proves the script and the branches, nothing "
            "about the chip")
    elif device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {device}; "
              "--rehearse runs the CPU rehearsal", file=sys.stderr)
        return 1

    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.framework import compile_cache
    from paddle_tpu.ops.pallas import registry

    size = TOY if args.rehearse else FULL
    kernel_path = "interpret" if args.rehearse else "pallas"
    if args.rehearse:
        mesh_mod.target_platform = lambda: "tpu"
        for name in registry.kernels():
            registry.set_mode(name, "interpret")

    cache = compile_cache.ensure_compile_cache()
    n0 = compile_cache.cache_entries()
    log(f"compile cache: {cache} ({n0} entries before)")

    t_all = time.perf_counter()
    loss1 = None
    for ph in phases:
        t0 = time.perf_counter()
        if ph == "train":
            loss1 = phase_train(size, kernel_path, args.rehearse)
        elif ph == "four":
            if len(devs) < 4:
                log(f"four: skipped — {len(devs)} device(s)")
                continue
            phase_four(size, kernel_path, loss1)
        elif ph == "serve":
            phase_serve(size, kernel_path)
        elif ph == "kernels":
            phase_kernels(size, kernel_path, args.rehearse)
        gc.collect()
        log(f"{ph}: passed in {time.perf_counter() - t0:.1f} s")
    n1 = compile_cache.cache_entries()
    log(f"compile cache: {cache} ({n0} entries before, {n1} after, "
        f"{n1 - n0} added); total {time.perf_counter() - t_all:.1f} s")
    result = {"ok": True, "device": device}
    if args.rehearse:
        result["rehearsal"] = True
    if phases != list(PHASES):
        result["phases"] = phases
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
