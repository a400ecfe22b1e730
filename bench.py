"""Headline benchmarks: ResNet-50 images/sec/chip + BERT-base tokens/sec/chip.

Metric definitions follow BASELINE.md (the reference publishes no numbers,
so ``vs_baseline`` is null).  Each training step — forward, backward,
optimizer update — is ONE donated XLA program via ``DistributedTrainStep``
on a single-chip mesh, i.e. the same path a user gets from the fleet API.

Self-validation: every measurement is cross-checked against the XLA
compiler's own cost model (``DistributedTrainStep.cost_analysis()``) and
an analytic model-FLOPs estimate.  When achieved TFLOP/s exceeds the
per-chip peak bound the result is marked ``"plausible": false`` with a
reason.

The measurement path runs on a TPU or not at all: off-TPU it fails
unless ``BENCH_SMOKE=1`` was given (tiny shapes on CPU, kernels under
the interpreter — a plumbing check whose rows say ``platform: cpu``).
Every row names the device it ran on (``platform``, ``device_kind``,
``device_count``).  A metric group that fails makes the run exit
non-zero.

Prints exactly ONE JSON line.  Primary metric fields at top level
(driver contract); the second metric rides in ``"extra_metrics"``.

Env knobs: BENCH_SMOKE=1 (tiny shapes on CPU), BENCH_BATCH, BENCH_STEPS,
BENCH_AMP=0/1, BENCH_PEAK_TFLOPS (plausibility bound override; by
default detected from the chip's device_kind, e.g. 197 for a v5e),
BENCH_METRICS=resnet,bert.
"""
from __future__ import annotations

import json
import os
import sys
import time

# Nominal per-chip bf16 peaks by device kind.  The plausibility bound
# must be the peak of the chip the bench ACTUALLY ran on — a generic
# upper bound (e.g. v5p's 459) would accept numbers 2.3x beyond what a
# v5e can physically do, defeating the anti-fake gate.
CHIP_PEAK_TFLOPS = {
    "v2": 46.0, "v3": 123.0, "v4": 275.0,
    "v5 lite": 197.0, "v5litepod": 197.0, "v5e": 197.0,
    "v5": 459.0, "v5p": 459.0,
    "v6 lite": 918.0, "v6e": 918.0,
}


def _detect_peak_tflops():
    """Per-chip bf16 peak for the device the bench runs on.

    BENCH_PEAK_TFLOPS overrides; otherwise the bound comes from
    ``jax.devices()[0].device_kind`` so the plausibility gate is tight
    for the real hardware (a v5e claiming 300 TFLOP/s must be flagged).
    A device kind that is not in the table is an error, not a default.
    """
    env = os.environ.get("BENCH_PEAK_TFLOPS")
    if env:
        return float(env)
    import jax
    kind = jax.devices()[0].device_kind.lower()
    for key, peak in sorted(CHIP_PEAK_TFLOPS.items(),
                            key=lambda kv: -len(kv[0])):
        if key in kind:
            return peak
    raise SystemExit(
        f"bench: no peak TFLOP/s on record for device_kind {kind!r} "
        f"(known: {sorted(CHIP_PEAK_TFLOPS)}); add it to "
        "CHIP_PEAK_TFLOPS with its source or set BENCH_PEAK_TFLOPS")


def _measure(step, args, steps, items_per_step, metric, unit,
             analytic_flops, peak_tflops, **extra):
    """Shared measure → validate → report block for every benchmark.

    Warmup (compile + steady state), timed loop ending in
    ``block_until_ready`` plus a host fetch of the loss, then
    plausibility-check achieved TFLOP/s against the per-chip peak bound
    (``peak_tflops`` is None under BENCH_SMOKE: a CPU has no peak on
    record, and its rows claim no utilization).
    """
    import jax

    for _ in range(2):
        step(*args)
    loss = step(*args)
    jax.block_until_ready(loss._value)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(*args)
    jax.block_until_ready(loss._value)
    float(loss)
    dt = time.perf_counter() - t0

    cost = step.cost_analysis()
    flops_xla = float(cost.get("flops") or 0.0)
    # cross-check (VERDICT r3 weak #3): XLA's cost analysis and the
    # analytic model must agree within ~5% — EXCEPT that XLA cannot see
    # inside Pallas custom-calls, so a program running flash-attention
    # kernels reports a large undercount.  Prefer XLA when the two
    # agree; fall back to the analytic model (flagging the ratio) when
    # XLA is clearly missing kernel FLOPs.
    agreement = (flops_xla / analytic_flops
                 if analytic_flops and flops_xla > 0 else None)
    if not analytic_flops:
        flops_per_step = flops_xla or None
        src = "xla_cost_analysis" if flops_xla > 0 else "none"
    elif flops_xla <= 0:
        flops_per_step, src = analytic_flops, "analytic"
    elif abs(flops_xla - analytic_flops) <= 0.05 * analytic_flops:
        flops_per_step, src = flops_xla, "xla_cost_analysis"
    elif agreement < 0.8:
        # a LARGE undercount means XLA cannot see the kernels doing the
        # work (Pallas custom-call interiors are invisible to cost
        # analysis); the analytic model is the truthful count
        flops_per_step = analytic_flops
        src = (f"analytic (xla counts {agreement:.2f}x — "
               "custom-call/pallas flops invisible to cost analysis)")
    elif flops_xla < analytic_flops:
        # small disagreement in the undercount direction: stay on the
        # compiler's count (the r1-r3 convention), flagged
        flops_per_step = flops_xla
        src = (f"xla_cost_analysis ({agreement:.2f}x the analytic "
               "model)")
    else:
        # XLA counts MORE than the analytic model: either its conv
        # flop-counting convention (ResNet reports ~2x the textbook
        # 4.1 GF/img figure) or rematerialized recompute ops.  The
        # compiler's own count of the EXECUTED program stays the source
        # (the r1-r3 convention the recorded numbers use) with the
        # disagreement flagged rather than silently passed.
        flops_per_step = flops_xla
        src = (f"xla_cost_analysis ({agreement:.2f}x the analytic "
               "model — conv-counting convention and/or recompute "
               "included)")
    achieved = (flops_per_step * steps / dt / 1e12
                if flops_per_step else None)
    plausible, reason = True, None
    if achieved is not None and peak_tflops is not None \
            and achieved > peak_tflops:
        plausible = False
        reason = (f"achieved {achieved:.0f} TFLOP/s exceeds per-chip peak "
                  f"bound {peak_tflops:.0f} — wall-clock not trustworthy; "
                  "treat value as unproven")
    return {
        "metric": metric,
        "value": round(items_per_step * steps / dt, 2),
        "unit": unit,
        "vs_baseline": None,
        "ms_per_step": round(dt / steps * 1e3, 3),
        "flops_per_step": flops_per_step,
        "flops_source": src,
        "flops_xla": flops_xla or None,
        "flops_analytic": analytic_flops,
        "flops_xla_vs_analytic": (round(agreement, 4)
                                  if agreement else None),
        "achieved_tflops": round(achieved, 2) if achieved else None,
        "peak_tflops_bound": peak_tflops,
        "mfu_nominal": (round(achieved / peak_tflops, 4)
                        if achieved and peak_tflops else None),
        "plausible": plausible,
        "suspect_reason": reason,
        "steps": steps,
        **extra,
    }


def _guard_overhead(plain_fn, guarded_fn, steps):
    """BENCH_GUARD=1 support: median-of-3 A/B of the per-step cost of
    the train_guard fused health check.  ``guarded_fn`` must run the
    SAME work as ``plain_fn`` plus the fused reduction and its single
    host fetch (the guard's entire clean-path footprint).  Target
    (PERF.md): <1% of step time."""
    import time as _time

    def loop(fn):
        fn()                                   # warm (compile)
        ts = []
        for _ in range(3):
            t0 = _time.perf_counter()
            for _ in range(steps):
                fn()
            ts.append((_time.perf_counter() - t0) / steps)
        return sorted(ts)[1]

    a = loop(plain_fn)
    b = loop(guarded_fn)
    return {
        "guard_ms_plain": round(a * 1e3, 3),
        "guard_ms_guarded": round(b * 1e3, 3),
        "guard_overhead_pct": round((b - a) / a * 100.0, 2),
    }


def _guard_ab(model, loss_fn, opt, smoke, step, args, steps):
    """BENCH_GUARD=1: A/B the clean-path cost of TrainGuard on this
    model — a second DistributedTrainStep compiled with
    ``guard_health=True`` (the fused health reduction rides inside the
    step program) vs the plain step, plus the guard's single 12-byte
    host fetch per step."""
    if os.environ.get("BENCH_GUARD", "0") != "1":
        return {}
    import jax

    from paddle_tpu.train_guard import TrainGuard
    guard = TrainGuard(min_history=10 ** 9)   # detection-only A/B
    gstep = _make_step(model, loss_fn, opt, smoke, guard_health=True)

    def plain():
        loss = step(*args)
        jax.block_until_ready(loss._value)

    def guarded():
        gstep(*args)
        guard.check(gstep.last_health)  # the fetch forces the same sync

    out = _guard_overhead(plain, guarded, steps)
    out["guard_skips"] = guard.skips
    return out


def _make_step(model, loss_fn, opt, smoke, guard_health=False):
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.fleet.dist_step import DistributedTrainStep

    strategy = fleet.DistributedStrategy()
    # bf16 compute (f32 master weights): convs/matmuls hit the MXU at
    # native precision.  CPU smoke keeps f32 (hosts emulate bf16, slower).
    if os.environ.get("BENCH_AMP", "0" if smoke else "1") == "1":
        strategy.amp = True
        strategy.amp_configs = {"dtype": "bfloat16"}
    mesh_mod.set_mesh(None)
    mesh = mesh_mod.init_mesh({"dp": -1})
    return DistributedTrainStep(model, loss_fn, opt, strategy, mesh=mesh,
                                guard_health=guard_health)


def _bench_resnet(smoke, peak_tflops):
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.vision.models import resnet50

    batch = int(os.environ.get("BENCH_BATCH", "8" if smoke else "256"))
    steps = int(os.environ.get("BENCH_STEPS", "3" if smoke else "20"))
    hw = 32 if smoke else 224
    nclass = 10 if smoke else 1000

    # layouts measured equal end-to-end on a v5e (2078 NCHW vs 2056
    # NHWC img/s): XLA layout assignment already optimizes the whole
    # program, even though a STANDALONE NCHW conv is ~5x slower
    layout = os.environ.get("BENCH_LAYOUT", "NCHW").upper()
    if layout not in ("NCHW", "NHWC"):
        raise SystemExit(f"invalid BENCH_LAYOUT={layout!r}; use NCHW|NHWC")
    paddle.seed(0)
    model = resnet50(num_classes=nclass, data_format=layout)
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=model.parameters())

    def loss_fn(img, label):
        return F.cross_entropy(model(img), label).mean()

    step = _make_step(model, loss_fn, opt, smoke)
    rng = np.random.RandomState(0)
    shape = ((batch, 3, hw, hw) if layout == "NCHW"
             else (batch, hw, hw, 3))
    img = paddle.to_tensor(
        rng.standard_normal(shape).astype("float32"))
    label = paddle.to_tensor(rng.randint(0, nclass, (batch,)).astype("int64"))

    # analytic fallback: fwd ~4.1 GFLOP/img at 224^2, train ~3x fwd
    analytic = 3 * 4.1e9 * (hw / 224.0) ** 2 * batch
    res = _measure(step, (img, label), steps, batch,
                   "resnet50_train_throughput", "images/sec/chip",
                   analytic, peak_tflops, batch=batch, image_size=hw)
    res.update(_guard_ab(model, loss_fn, opt, smoke, step,
                         (img, label), steps))
    return res


def _bench_bert(smoke, peak_tflops):
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.text.models.bert import (
        BertForPretraining, BertPretrainingCriterion, bert_base, bert_tiny)

    # swept on a v5e chip: 32 -> 83.7k, 64 -> 94.8k, 128 -> 106k,
    # 256 -> 103.8k tokens/sec; 128 is the knee
    batch = int(os.environ.get("BENCH_BATCH", "4" if smoke else "128"))
    steps = int(os.environ.get("BENCH_STEPS", "3" if smoke else "20"))
    seq = 32 if smoke else 128
    # the reference pretrain feeds mask_pos and decodes MLM logits ONLY
    # at masked positions (~15% of tokens, bert_dygraph_model.py
    # PretrainModelLayer) — full-vocab logits over every position would
    # be a [B, S, V] tensor the real workload never materializes
    n_mask = max(1, int(seq * 0.15))

    paddle.seed(0)
    cfg = bert_tiny() if smoke else bert_base()
    model = BertForPretraining(cfg)
    crit = BertPretrainingCriterion(cfg.vocab_size)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())

    def loss_fn(ids, mask_pos, mlm_labels, nsp_labels):
        mlm_logits, nsp_logits = model(ids, masked_positions=mask_pos)
        return crit(mlm_logits, nsp_logits, mlm_labels, nsp_labels)

    step = _make_step(model, loss_fn, opt, smoke)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype("int32"))
    mask_pos = paddle.to_tensor(np.sort(
        rng.randint(0, seq, (batch, n_mask)), axis=1).astype("int32"))
    mlm = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, n_mask)).astype("int64"))
    nsp = paddle.to_tensor(rng.randint(0, 2, (batch,)).astype("int64"))

    nparams = sum(int(np.prod(p.shape)) for p in model.parameters())
    # fwd+bwd ~6*P per token over the trunk; the tied MLM decoder runs
    # only on masked positions, so scale its vocab matmul accordingly
    v_h = cfg.vocab_size * cfg.hidden_size
    analytic = (6.0 * (nparams - v_h) * batch * seq
                + 6.0 * v_h * batch * n_mask)
    return _measure(step, (ids, mask_pos, mlm, nsp), steps, batch * seq,
                    ("ernie_bert_base_pretrain_throughput" if not smoke
                     else "bert_tiny_pretrain_throughput"),
                    "tokens/sec/chip", analytic, peak_tflops,
                    batch=batch, seq_len=seq, masked_per_seq=n_mask)


def _llama_proxy_cfg(seq, smoke, remat):
    """ONE definition of the Llama proxy used by the seq-2048 headline
    and the seq-4096 long-context A/B (they must stay the same model)."""
    from paddle_tpu.text.models import llama_tiny
    if smoke:
        return llama_tiny(scan_layers=True, remat=remat,
                          max_position_embeddings=seq)
    # ~536M-param proxy (incl. 65.5M embeddings): big enough that
    # matmuls dominate, small enough for f32 master params + AdamW
    # moments on one chip
    return llama_tiny(
        vocab_size=32000, hidden_size=2048, intermediate_size=5504,
        num_hidden_layers=8, num_attention_heads=16,
        num_key_value_heads=16, max_position_embeddings=seq,
        scan_layers=True, remat=remat)


def _llama_analytic(cfg, nparams, batch, seq):
    """Model FLOPs: 6*P per token + causal attention (coefficient 6 =
    half of bidirectional 12*L*B*S^2*H; hand-reviewed in r3)."""
    return (6.0 * nparams * batch * seq
            + 6.0 * cfg.num_hidden_layers * batch * seq * seq
            * cfg.hidden_size)


def _bench_llama(smoke, peak_tflops):
    """Llama-proxy decoder pretrain: seq 2048 causal, bf16, scanned
    layers + per-layer remat, Pallas flash attention on the hot path
    (BASELINE north-star family; the 2021 reference has no Llama, so the
    proxy documents absolute tokens/sec/chip)."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.text.models import LlamaForCausalLM, llama_tiny

    batch = int(os.environ.get("BENCH_BATCH", "2" if smoke else "4"))
    steps = int(os.environ.get("BENCH_STEPS", "3" if smoke else "10"))
    seq = 64 if smoke else 2048

    paddle.seed(0)
    # remat default ON (honesty note, PERF.md round 4: r1-r3 passed
    # remat=True but an eager-tape bug made it a silent no-op; with the
    # bug fixed the no-recompute program no longer fits batch 4 HBM —
    # the residual set the outer AD picks runs ~0.7 GB past the r3
    # layout).  BENCH_REMAT=0 reproduces the no-recompute program at a
    # smaller batch for A/B.
    remat = os.environ.get("BENCH_REMAT", "1") == "1"
    cfg = _llama_proxy_cfg(seq, smoke, remat)
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())

    flash_info = {}
    if not smoke:
        # on-chip parity: the exact kernel the model dispatches to at
        # seq 2048 vs the XLA softmax composition
        import jax
        import jax.numpy as jnp
        from paddle_tpu.nn.functional.attention import _sdpa_ref
        from paddle_tpu.ops.flash_attention import (flash_attention_bhsd,
                                                    flash_eligible)
        assert flash_eligible(seq, cfg.head_dim), \
            "flash kernel must be live on the llama bench path"
        rng = np.random.RandomState(0)
        qkv = [jnp.asarray(rng.randn(1, 4, seq, cfg.head_dim),
                           jnp.bfloat16) for _ in range(3)]
        fo = flash_attention_bhsd(*qkv, causal=True)
        ro = _sdpa_ref(jnp.swapaxes(qkv[0], 1, 2),
                       jnp.swapaxes(qkv[1], 1, 2),
                       jnp.swapaxes(qkv[2], 1, 2), None, 0.0, True, None)
        err = float(jnp.max(jnp.abs(fo.astype(jnp.float32)
                                    - jnp.swapaxes(ro, 1, 2)
                                    .astype(jnp.float32))))
        assert err < 3e-2, f"flash-vs-ref parity failed on chip: {err}"
        flash_info = {"flash_parity_max_abs_err": round(err, 6),
                      "flash_kernel": "pallas"}

    def loss_fn(ids, labels):
        loss, _ = model(ids, labels=labels)
        return loss

    step = _make_step(model, loss_fn, opt, smoke)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype("int32"))

    nparams = sum(int(np.prod(p.shape)) for p in model.parameters())
    analytic = _llama_analytic(cfg, nparams, batch, seq)
    res = _measure(step, (ids, ids), steps, batch * seq,
                   "llama_proxy_pretrain_throughput", "tokens/sec/chip",
                   analytic, peak_tflops, batch=batch, seq_len=seq,
                   n_params=nparams, **flash_info)
    res.update(_guard_ab(model, loss_fn, opt, smoke, step,
                         (ids, ids), steps))
    return res


def _bench_llama_long(smoke, peak_tflops, seq=4096, default_batch="2",
                      smoke_seq=128):
    """Long-sequence regime (VERDICT r3 weak #3: 'the regime where
    flash should win big is never measured'): the Llama proxy at seq
    4096 (and seq 8192 via ``_bench_llama_8k``, VERDICT r4 item 5),
    measured twice — with the Pallas flash kernels (the model's own
    dispatch) and with the kernel forcibly disabled (the query-chunked
    XLA fallback) — so the kernel's raison d'être is a recorded A/B,
    not an assertion."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.text.models import LlamaForCausalLM, llama_tiny

    batch = int(os.environ.get("BENCH_BATCH",
                               "1" if smoke else default_batch))
    steps = int(os.environ.get("BENCH_STEPS", "2" if smoke else "8"))
    seq = smoke_seq if smoke else seq

    def run(use_flash):
        import importlib
        fa_mod = importlib.import_module("paddle_tpu.ops.flash_attention")
        orig = fa_mod.flash_eligible
        if not use_flash:
            fa_mod.flash_eligible = lambda *a, **k: False
        try:
            paddle.seed(0)
            cfg = _llama_proxy_cfg(seq, smoke, remat=True)
            if use_flash and not smoke:
                # the A/B must never silently compare fallback against
                # fallback (cf. _bench_llama's on-path assertion)
                assert fa_mod.flash_eligible(seq, cfg.head_dim), \
                    "flash must be live on the llama_long flash arm"
            model = LlamaForCausalLM(cfg)
            opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                         parameters=model.parameters())

            def loss_fn(ids, labels):
                loss, _ = model(ids, labels=labels)
                return loss

            step = _make_step(model, loss_fn, opt, smoke)
            rng = np.random.RandomState(0)
            ids = paddle.to_tensor(rng.randint(
                0, cfg.vocab_size, (batch, seq)).astype("int32"))
            nparams = sum(int(np.prod(p.shape))
                          for p in model.parameters())
            analytic = _llama_analytic(cfg, nparams, batch, seq)
            return _measure(
                step, (ids, ids), steps, batch * seq,
                f"llama_seq{seq}_pretrain_throughput", "tokens/sec/chip",
                analytic, peak_tflops, batch=batch, seq_len=seq,
                attention=("pallas_flash" if use_flash
                           else "xla_chunked"))
        finally:
            fa_mod.flash_eligible = orig

    flash = run(True)
    xla = run(False)
    flash["xla_chunked_tok_s"] = xla["value"]
    flash["xla_chunked_ms_per_step"] = xla["ms_per_step"]
    flash["flash_speedup_vs_xla"] = (
        round(flash["value"] / xla["value"], 3) if xla["value"] else None)
    return flash


def _bench_llama_8k(smoke, peak_tflops):
    """Seq-8192 long-context A/B (VERDICT r4 item 5): batch 1, remat on,
    same flash-vs-XLA-chunked methodology as the 4096 metric."""
    return _bench_llama_long(smoke, peak_tflops, seq=8192,
                             default_batch="1", smoke_seq=256)


def _bench_wide_deep(smoke, peak_tflops):
    """PS-path rec-model bench (BASELINE configs[4]: wide_deep /
    DeepFM through the parameter-server runtime), two sparse backends:

    native (default, r6 tentpole): the host-native ``SparseTable`` IS
    the sparse path — pull is one batched C gather, push is one fused C
    dedup + segment-sum + optimizer call (native/ps_core.cc); the pulled
    rows ride into the jitted dense step as an input (the
    host-offloaded-embedding pattern).  On the 1-core bench host this
    removes the per-step Python directory transaction and device
    dispatch storm the r5 roofline identified.  ``BENCH_PS_NATIVE=0``
    selects the r5 DeviceCachedTable (device-resident rows) path.

    Metric: examples/sec through the full pull -> dense-step -> push
    loop; the loss is fetched every step (the same cannot-be-faked
    discipline as the headline metrics) and must fall."""
    import time as _time

    import numpy as np

    import jax
    import jax.numpy as jnp

    from paddle_tpu.distributed.fleet.heter import (DeviceCachedTable,
                                                    HeterTrainer)
    from paddle_tpu.distributed.fleet.ps import SparseTable

    n_slots = 4 if smoke else 26
    dim = 8 if smoke else 16
    batch = int(os.environ.get("BENCH_BATCH", "64" if smoke else "1024"))
    steps = int(os.environ.get("BENCH_STEPS", "4" if smoke else "20"))
    vocab = 1000 if smoke else 20_000
    n_dense = 13
    hidden = 64 if smoke else 256

    use_native = os.environ.get("BENCH_PS_NATIVE", "1") == "1"
    # BENCH_CHAOS=1: sanity mode — the same training loop, but the
    # sparse path rides the PS RPC service with the "flaky" fault plan
    # injecting delays/dups/lost acks/cuts.  Not a headline number; it
    # proves the fault-tolerant client keeps a wide_deep run training
    # (loss falls, zero double-applies) under transport failure.
    chaos_on = os.environ.get("BENCH_CHAOS", "0") == "1"
    ps_server = ps_client = chaos_plan = None
    cache = None
    if use_native:
        # optimizer applies host-side in the fused native push
        table = SparseTable(dim, optimizer="sgd", lr=0.05)
    if use_native and chaos_on:
        from paddle_tpu.distributed.fleet import chaos as chaos_mod
        from paddle_tpu.distributed.fleet.heter import RemoteTable
        from paddle_tpu.distributed.fleet.ps_service import (PSClient,
                                                             PSServer)
        ps_server = PSServer({"slots": table}, host="127.0.0.1")
        ps_server.start()
        chaos_plan = chaos_mod.install(
            chaos_mod.named_plan("flaky", seed=0))
        ps_client = PSClient([f"127.0.0.1:{ps_server.port}"],
                             mode="sync", rpc_timeout=2.0,
                             connect_timeout=5.0, backoff_base=0.02,
                             rpc_deadline=30.0)
        sparse = RemoteTable(ps_client, "slots", dim)
    elif use_native:
        sparse = table
    else:
        table = SparseTable(dim, optimizer="sgd", lr=1.0)
        cache = DeviceCachedTable(table, capacity=batch * n_slots * 3,
                                  optimizer="sgd", lr=0.05)
        sparse = cache
    rng = np.random.RandomState(0)
    w1 = jnp.asarray(rng.randn(n_slots * dim + n_dense, hidden)
                     * 0.05, jnp.float32)
    b1 = jnp.zeros((hidden,), jnp.float32)
    w2 = jnp.asarray(rng.randn(hidden, 1) * 0.05, jnp.float32)
    wide_w = jnp.asarray(rng.randn(n_dense, 1) * 0.05, jnp.float32)
    params = (w1, b1, w2, wide_w)

    def _dense_core(params, emb, dense, label):
        def loss_of(params, emb):
            w1, b1, w2, wide_w = params
            e = emb.reshape(batch, n_slots * dim)
            deep_in = jnp.concatenate([e, dense], axis=1)
            h = jax.nn.relu(deep_in @ w1 + b1)
            logit = jnp.clip((h @ w2 + dense @ wide_w)[:, 0], -15, 15)
            # binary cross-entropy with logits
            return jnp.mean(jnp.logaddexp(0.0, logit) - logit * label)
        l, (gp, ge) = jax.value_and_grad(
            loss_of, argnums=(0, 1))(params, emb)
        new_params = tuple(p - 0.05 * g for p, g in zip(params, gp))
        return l, new_params, ge

    dense_fwd_bwd = jax.jit(_dense_core)

    state = {"params": params, "losses": []}

    def dense_step(embs, batch_data):
        dense, label = batch_data[1], batch_data[2]
        emb = embs["slots"]
        l, new_params, ge = dense_fwd_bwd(
            state["params"], emb, jnp.asarray(dense), jnp.asarray(label))
        state["params"] = new_params
        # keep the loss ON DEVICE during the run (a per-step scalar
        # fetch would stall the dispatch queue); the end-of-run fetch
        # of every loss still forces the whole in-order chain to have
        # executed
        state["losses"].append(l)
        return l, {"slots": ge.reshape(-1, dim)}

    def ids_fn(batch_data):
        return {"slots": batch_data[0].reshape(-1)}

    batches = []
    # CTR id traffic is Zipf-skewed: heavy reuse of hot ids is what the
    # device cache exists for (uniform draws would make every batch a
    # full miss + python-side eviction storm, which no real feed does)
    zipf = np.clip(rng.zipf(1.3, size=(steps, batch, n_slots)), 1, vocab)
    for i in range(steps):
        ids = ((zipf[i] - 1)
               + np.arange(n_slots) * vocab).astype(np.int64)
        dense = rng.rand(batch, n_dense).astype(np.float32)
        # learnable rule so the loss can fall
        label = (dense[:, 0] > 0.5).astype(np.float32)
        batches.append((ids, dense, label))

    # push_lag=1: push(i) overlaps compute(i) and pull(i+1) (capacity
    # above covers the 3-batch pinned working set)
    tr = HeterTrainer({"slots": sparse}, dense_step, sync_mode=False,
                      push_lag=1)
    if cache is not None:
        # pre-compile every bucketed device program the serving loop can
        # touch (first-seen bucket shapes otherwise cost ~5 s compiles
        # INSIDE the timed window — measured ~90% of a 20-step run)
        cache.prime(batch * n_slots)
    tr.run(batches[:2], ids_fn)            # warmup (compile + cache fill)
    n_warm = len(state["losses"])
    if cache is not None:
        cache.hits = cache.misses = 0      # steady-state hit rate only
    t0 = _time.perf_counter()
    n = tr.run(batches, ids_fn)
    state["losses"] = [float(l) for l in state["losses"]]  # forced fetch
    dt = _time.perf_counter() - t0
    tr.shutdown()
    if cache is not None:
        cache.flush()
    chaos_report = None
    if chaos_plan is not None:
        from paddle_tpu.distributed.fleet import chaos as chaos_mod
        stats = ps_server._stats()
        chaos_report = {"injected": chaos_plan.stats_dict(),
                        "rpc_retries": ps_client.retries,
                        "server_applied": stats["applied"],
                        "server_dup_acks": stats["dup_acks"]}
        chaos_mod.uninstall()
        ps_client.close()
        ps_server.stop()
    ex_s = batch * n / dt
    timed_losses = state["losses"][n_warm:]
    falling = timed_losses[-1] < timed_losses[0]
    if smoke and not falling:
        # a 4-step CPU smoke run may not move the loss; finiteness is
        # the smoke-level check
        falling = bool(np.isfinite(state["losses"][-1]))
    backend = ("device_cache" if cache is not None else
               "native+chaos_rpc" if chaos_report is not None
               else "native")
    guard_report = {}
    if os.environ.get("BENCH_GUARD", "0") == "1":
        # per-step guard cost on the dense hot path: the fused health
        # reduction compiled INTO the dense step (same pattern as
        # DistributedTrainStep guard_health) + its one host fetch — the
        # sync point a real guarded PS loop pays each step
        from paddle_tpu.train_guard import TrainGuard, fused_health
        guard = TrainGuard(min_history=10 ** 9)

        @jax.jit
        def dense_fwd_bwd_guarded(params, emb, dense, label):
            l, new_params, ge = _dense_core(params, emb, dense, label)
            return l, new_params, ge, fused_health([ge], loss=l,
                                                   precise=False)

        emb0 = jnp.zeros((batch * n_slots, dim), jnp.float32)
        dense0 = jnp.asarray(batches[0][1])
        label0 = jnp.asarray(batches[0][2])

        def plain():
            l, _, ge = dense_fwd_bwd(state["params"], emb0, dense0,
                                     label0)
            jax.block_until_ready(ge)

        def guarded():
            l, _, ge, h = dense_fwd_bwd_guarded(state["params"], emb0,
                                                dense0, label0)
            guard.check(h)   # the fetch forces the same sync

        guard_report = _guard_overhead(plain, guarded, max(steps, 10))
        guard_report["guard_skips"] = guard.skips
    return {
        "metric": "wide_deep_ps_throughput",
        "value": round(ex_s, 2),
        "unit": "examples/sec",
        "vs_baseline": None,
        "ms_per_step": round(dt / n * 1e3, 3),
        "steps": n,
        "batch": batch,
        "n_slots": n_slots,
        "emb_dim": dim,
        "ps_backend": backend,
        "chaos": chaos_report,
        "cache_hit_rate": (None if cache is None else round(
            cache.hits / max(cache.hits + cache.misses, 1), 4)),
        "loss_first": round(timed_losses[0], 4),
        "loss_last": round(timed_losses[-1], 4),
        "plausible": bool(falling),
        "suspect_reason": None if falling else
            "loss did not fall over the run — pipeline may be broken",
        **guard_report,
    }


def _ps_scaling_worker(endpoint, steps, batch, n_slots, dim, vocab,
                       worker_id):
    """Subprocess body for _bench_ps_scaling: pull -> fake grad -> push
    against the shared PSServer (numpy only — no device)."""
    import numpy as np

    from paddle_tpu.distributed.fleet.ps_service import PSClient

    import time as _time
    import zlib

    c = PSClient([endpoint], mode="sync", worker_id=worker_id)
    rng = np.random.RandomState(zlib.crc32(worker_id.encode()))
    c.worker_barrier(timeout=60.0)          # simultaneous start
    t0 = _time.time()
    for _ in range(steps):
        ids = ((np.clip(rng.zipf(1.3, size=batch * n_slots), 1, vocab)
                - 1)).astype(np.int64)
        rows = c.pull("emb", ids)
        c.push("emb", ids, rows * 0.01)
    t1 = _time.time()
    c.worker_barrier(timeout=600.0)         # simultaneous finish
    c.close()
    # the parent computes throughput from these (its own clock would
    # include subprocess interpreter + jax import time)
    print(f"PSW {t0:.6f} {t1:.6f}", flush=True)


def _bench_ps_scaling(smoke, peak_tflops):
    """Multi-trainer PS throughput (the PS runtime's reason-for-being —
    reference framework/trainer.h:124 multi-trainer DownpourWorker): N
    worker PROCESSES drive one PSServer over sockets, sync pull/push of
    Zipf-skewed CTR ids; combined examples/sec for 1 and 2 workers.

    CPU-only by design (it measures the PS runtime, not the chip).
    Honesty note: the bench host has ONE core, so server + 2 workers
    timeshare it — the 2-worker number records protocol concurrency
    (socket IO overlap), not ideal linear scaling."""
    import socket
    import subprocess
    import sys
    import time as _time

    import numpy as np

    from paddle_tpu.distributed.fleet.ps import SparseTable
    from paddle_tpu.distributed.fleet.ps_service import PSServer

    steps = 5 if smoke else 30
    batch = 256 if smoke else 1024
    n_slots = 4 if smoke else 26
    dim = 8 if smoke else 16
    vocab = 50_000

    def run(n_workers):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        table = SparseTable(dim, optimizer="sgd", lr=1.0)
        srv = PSServer({"emb": table}, port=port,
                       expected_workers=n_workers)
        srv.start()
        ep = f"127.0.0.1:{port}"
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        code = ("import bench; bench._ps_scaling_worker("
                f"{ep!r}, {steps}, {batch}, {n_slots}, {dim}, {vocab}, "
                "{wid!r})")
        procs = []
        try:
            procs = [subprocess.Popen(
                [sys.executable, "-c", code.format(wid=f"w{i}")],
                env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
                stdout=subprocess.PIPE, text=True)
                for i in range(n_workers)]
            outs = [p.communicate(timeout=900)[0] for p in procs]
            rcs = [p.returncode for p in procs]
        finally:
            for p in procs:          # a hung sibling must not leak
                if p.poll() is None:
                    p.kill()
            srv.stop()
        if any(rcs):
            raise RuntimeError(f"ps scaling worker failed: {rcs}")
        # span from the workers' OWN post-barrier clocks: the parent's
        # window would include subprocess interpreter + jax import time
        spans = []
        for o in outs:
            for line in o.splitlines():
                if line.startswith("PSW "):
                    _, a, b = line.split()
                    spans.append((float(a), float(b)))
        if len(spans) != n_workers:
            raise RuntimeError(
                f"ps scaling: {len(spans)}/{n_workers} workers reported "
                f"timing lines; outputs: {outs!r}")
        dt = max(b for _, b in spans) - min(a for a, _ in spans)
        return n_workers * steps * batch / dt

    one = run(1)
    two = run(2)
    return {
        "metric": "ps_multi_trainer_throughput",
        "value": round(two, 2),
        "unit": "examples/sec_2workers",
        "vs_baseline": None,
        "one_worker_ex_s": round(one, 2),
        "scaling_2w_over_1w": round(two / one, 3) if one else None,
        "steps_per_worker": steps, "batch": batch, "n_slots": n_slots,
        "note": ("single-core host: server+workers timeshare one CPU; "
                 "ratio reflects IO overlap, not ideal scaling"),
    }


def _ps_read_worker(cfg_json, worker_id):
    """Subprocess body for _bench_ps_read: bounded-staleness pulls
    through the consistent-hash read fan-out (numpy only, no device)."""
    import json as _json
    import time as _time
    import zlib

    import numpy as np

    from paddle_tpu.distributed.fleet.ps_service import PSClient

    cfg = _json.loads(cfg_json)
    c = PSClient([cfg["primary"]], mode="read",
                 max_lag=cfg["max_lag"],
                 read_replicas=[cfg["replicas"]],
                 worker_id=worker_id)
    rng = np.random.RandomState(zlib.crc32(worker_id.encode()))
    c.pull("emb", np.arange(64, dtype=np.int64))    # warmup/connect
    c.worker_barrier(timeout=60.0)                  # simultaneous start
    t0 = _time.time()
    for _ in range(cfg["steps"]):
        ids = (np.clip(rng.zipf(1.3, cfg["batch"]), 1, cfg["vocab"])
               - 1).astype(np.int64)
        c.pull("emb", ids)
    t1 = _time.time()
    stale = c.stale_retries
    c.worker_barrier(timeout=600.0)                 # simultaneous finish
    c.close()
    print(f"PSR {t0:.6f} {t1:.6f} {stale}", flush=True)


_PS_READ_REPLICA_SRC = r"""
import json, os, sys
sys.path.insert(0, sys.argv[1])
cfg = json.loads(sys.argv[2])
from paddle_tpu.distributed.fleet.ps import SparseTable
from paddle_tpu.distributed.fleet.ps_service import PSServer
srv = PSServer({"emb": SparseTable(**cfg["table"])}, host="127.0.0.1",
               replica_of=cfg["replica_of"], replica_mode="read")
srv.start()
srv.replica_ready.wait(60.0)
print(json.dumps({"port": srv.port}), flush=True)
srv._stop.wait()
"""


def _bench_ps_read(smoke, peak_tflops):
    """Online serving tier read QPS vs read-replica count (ISSUE 10):
    one primary holds a seeded embedding table; N read replicas catch
    up over the async mutation stream; 2 reader PROCESSES fan
    bounded-staleness pulls (max_lag) across the replicas by consistent
    hash.  Reported: combined pulls/sec for 1 and 2 replicas.

    CPU-only by design (it measures the serving tier, not the chip).
    Honesty note: the bench host has ONE core — primary + replicas +
    readers timeshare it, so the 2-replica ratio records protocol/IO
    overlap, NOT the ~linear core-level scaling the fan-out gives a
    real fleet (each replica is its own process doing an independent C
    gather; on separate hosts the aggregate scales with replica
    count)."""
    import subprocess
    import sys
    import time as _time

    import numpy as np

    from paddle_tpu.distributed.fleet.ps import SparseTable
    from paddle_tpu.distributed.fleet.ps_service import PSServer

    steps = 20 if smoke else 100
    batch = 512 if smoke else 2048
    dim = 8 if smoke else 16
    vocab = 50_000
    n_readers = 2
    spec = dict(dim=dim, optimizer="sgd", lr=0.05, seed=0)
    here = os.path.dirname(os.path.abspath(__file__))

    def run(n_replicas):
        table = SparseTable(**spec)
        # seed every row the zipf draw can touch BEFORE replicas attach
        all_ids = np.arange(vocab, dtype=np.int64)
        table.push(all_ids, np.full((vocab, dim), 0.01, np.float32))
        prim = PSServer({"emb": table}, expected_workers=n_readers)
        prim.start()
        pep = f"127.0.0.1:{prim.port}"
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("PADDLE_CHAOS", None)
        rep_procs, rep_eps = [], []
        reader_procs = []
        try:
            for _ in range(n_replicas):
                cfg = {"table": spec, "replica_of": pep}
                p = subprocess.Popen(
                    [sys.executable, "-c", _PS_READ_REPLICA_SRC, here,
                     json.dumps(cfg)], stdout=subprocess.PIPE,
                    text=True, env=env)
                rep_procs.append(p)
                rep_eps.append(
                    f"127.0.0.1:{json.loads(p.stdout.readline())['port']}")
            rcfg = json.dumps({"primary": pep,
                               "replicas": "|".join(rep_eps),
                               "max_lag": 64, "steps": steps,
                               "batch": batch, "vocab": vocab})
            reader_procs = [subprocess.Popen(
                [sys.executable, "-c",
                 f"import bench; bench._ps_read_worker({rcfg!r}, "
                 f"{f'r{i}'!r})"],
                env=env, cwd=here, stdout=subprocess.PIPE, text=True)
                for i in range(n_readers)]
            outs = [p.communicate(timeout=900)[0] for p in reader_procs]
            rcs = [p.returncode for p in reader_procs]
        finally:
            for p in reader_procs + rep_procs:
                if p.poll() is None:
                    p.kill()
            prim.stop()
        if any(rcs):
            raise RuntimeError(f"ps read worker failed: {rcs} {outs}")
        spans, stale = [], 0
        for o in outs:
            for line in o.splitlines():
                if line.startswith("PSR "):
                    _, a, b, s = line.split()
                    spans.append((float(a), float(b)))
                    stale += int(s)
        if len(spans) != n_readers:
            raise RuntimeError(
                f"ps read: {len(spans)}/{n_readers} workers reported; "
                f"outputs: {outs!r}")
        dt = max(b for _, b in spans) - min(a for a, _ in spans)
        return n_readers * steps * batch / dt, stale

    one, stale1 = run(1)
    two, stale2 = run(2)
    return {
        "metric": "ps_read_replica_throughput",
        "value": round(two, 2),
        "unit": "pulls/sec_2replicas",
        "vs_baseline": None,
        "one_replica_pulls_s": round(one, 2),
        "scaling_2r_over_1r": round(two / one, 3) if one else None,
        "stale_retries": [stale1, stale2],
        "steps_per_reader": steps, "batch": batch, "emb_dim": dim,
        "note": ("single-core host: primary+replicas+readers timeshare "
                 "one CPU; ratio reflects IO overlap, not the per-host "
                 "linear scaling of a real replica fleet"),
    }


def _bench_ps_scale(smoke, peak_tflops):
    """Tiered PS at rows-beyond-RAM scale (ISSUE 16): build a table
    whose row storage exceeds this process's resident memory by
    demoting cold rows to the mmap spill tier as they are admitted,
    then measure (a) cold-spill recovery time into a fresh table,
    (b) mixed hot/cold pull throughput + p99 over the service socket
    on the zero-copy ``zc`` wire vs the classic per-request ``row``
    wire, and (c) the int8 ``q8`` wire's egress-byte reduction with
    the pull-dequant kernel's parity pinned (interpret|xla_ref
    bit-identical).

    CPU-only by design (it measures the PS storage/wire tier, not the
    chip).  Honesty note: ONE core — server and client timeshare it,
    so absolute pulls/s undersell a real deployment; the zc-vs-row
    ratio is the honest signal (same contention both sides)."""
    import glob as _glob
    import tempfile
    import time as _time

    import numpy as np

    from paddle_tpu.distributed.fleet.ps import (SparseTable,
                                                 dequantize_rows_q8)
    from paddle_tpu.distributed.fleet.ps_service import (PSClient,
                                                         PSServer,
                                                         _frame_bytes)

    def rss_bytes():
        with open("/proc/self/status") as f:
            for ln in f:
                if ln.startswith("VmRSS:"):
                    return int(ln.split()[1]) * 1024
        return 0

    base_rss = rss_bytes()
    if smoke:
        dim, batch, steps, hot_n = 16, 512, 10, 4_000
        n_rows = 40_000
    else:
        dim, batch, steps, hot_n = 64, 2048, 300, 50_000
        # size the table so its row storage tops the CURRENT resident
        # set: payload rides the spill tier, only slots + hot arena
        # stay in RAM
        rec = 8 + (dim + 1) * 4   # id + row/step payload, pre-align
        n_rows = int(min(max(2.2 * base_rss / rec, 1_500_000),
                         6_000_000))
    t = SparseTable(dim, optimizer="sgd", lr=0.1, init_std=0.05, seed=11)
    sdir = tempfile.mkdtemp(prefix="ps_scale_spill_")
    assert t.enable_spill(sdir)
    # build + demote interleaved: the hot arena only ever holds one
    # admission batch, so peak RSS tracks the SLOT directory, not the
    # row payload — that is the whole tiering claim
    t0 = _time.time()
    ids_all = np.arange(n_rows, dtype=np.int64)
    chunk = 100_000
    for lo in range(0, n_rows, chunk):
        t.pull(ids_all[lo:lo + chunk])          # admission
        t.spill_sweep(int(_time.time() * 1000) + 10_000)  # demote all
    t.spill_advise()                            # msync + drop page cache
    build_s = _time.time() - t0
    spill_bytes = sum(os.path.getsize(p) for p in
                      _glob.glob(os.path.join(sdir, "*.spill")))
    rss = rss_bytes()
    stats = t.spill_stats()

    # (a) cold recovery: a fresh table re-mmaps the spill files and
    # rebuilds its directory from the committed records alone
    t2 = SparseTable(dim, optimizer="sgd", lr=0.1, init_std=0.05,
                     seed=11)
    t0 = _time.time()
    recovered = t2.recover_spill(sdir)
    recovery_s = _time.time() - t0
    probe = np.asarray([0, n_rows // 2, n_rows - 1], np.int64)
    if not np.array_equal(t.pull(probe), t2.pull(probe)):
        raise RuntimeError("ps_scale: recovered rows differ from source")
    del t2

    # (b) mixed hot/cold serving over the socket, zc vs row wire
    rng = np.random.RandomState(7)
    hot_ids = rng.choice(n_rows, hot_n, replace=False).astype(np.int64)
    def make_batches():
        r = np.random.RandomState(1234)
        out = []
        for _ in range(steps):
            hot = hot_ids[np.minimum(r.zipf(1.3, batch) - 1, hot_n - 1)]
            n_cold = max(batch // 10, 1)
            hot[:n_cold] = r.randint(0, n_rows, n_cold)
            out.append(np.ascontiguousarray(hot))
        return out
    srv = PSServer({"emb": t}, port=0)
    srv.start()
    ep = f"127.0.0.1:{srv.port}"
    lat = {}
    thru = {}
    reps = 1 if smoke else 2
    samples = {"zc": [], "row": []}
    try:
        # PAIRED design: both wires pull the SAME batch back to back,
        # alternating which wire leads.  A shared one-core host drifts
        # by +-20% across ~250ms windows (scheduler, page cache,
        # frequency), so separate per-wire passes measure the window,
        # not the wire; pairing puts both wires inside the same window
        # and the ratio comes from steps*reps matched samples.  The
        # LEADER of each pair pays the batch's cold-row promotion and
        # page faults; the follower hits the arena — alternating
        # leadership splits that bill evenly.  Tier state is reset once
        # up front (demote all, drop spill page cache, promote the hot
        # set) so the stream starts from the documented hot/cold mix.
        t.spill_sweep(int(_time.time() * 1000) + 10_000)
        t.spill_advise()
        t.pull(hot_ids)
        cli = {w: PSClient([ep], pull_wire=w) for w in ("zc", "row")}
        batches = [b for _ in range(reps) for b in make_batches()]
        for w in cli:
            cli[w].pull("emb", batches[0])      # connect + warm
        for i, b in enumerate(batches):
            pair = ("zc", "row") if i % 2 == 0 else ("row", "zc")
            for w in pair:
                a = _time.perf_counter()
                cli[w].pull("emb", b)
                samples[w].append(_time.perf_counter() - a)
        for w, c in cli.items():
            c.close()
        for wire, ts in samples.items():
            pool = np.asarray(ts)
            lat[wire] = (float(np.percentile(pool, 50) * 1e3),
                         float(np.percentile(pool, 99) * 1e3))
            thru[wire] = batch / float(pool.mean())
    finally:
        srv.stop()

    # (c) int8 wire: measured egress bytes for the same request, and
    # the on-device dequant kernel's bit-parity
    uniq = np.unique(batches[0])
    f32_bytes = len(_frame_bytes({"vals": t.pull(batches[0])}))
    codes, scales = t.pull_q8(uniq)
    inv = np.searchsorted(uniq, batches[0]).astype(np.int32)
    q8_bytes = len(_frame_bytes({"inv": inv, "codes": codes,
                                 "scales": scales}))
    egress_ratio = f32_bytes / q8_bytes
    from paddle_tpu.ops.pallas import registry as _preg
    k_int = np.asarray(_preg.dispatch("pull_dequant", codes, scales,
                                      mode="interpret"))
    k_ref = np.asarray(_preg.dispatch("pull_dequant", codes, scales,
                                      mode="xla_ref"))
    parity = (np.array_equal(k_int, k_ref)
              and np.array_equal(k_ref, dequantize_rows_q8(codes,
                                                           scales)))

    for p in _glob.glob(os.path.join(sdir, "*.spill")):
        os.unlink(p)
    os.rmdir(sdir)
    return {
        "metric": "ps_scale",
        "value": round(thru["zc"], 2),
        "unit": "pulls/sec_zc_mixed",
        "vs_baseline": None,
        "rows_total": n_rows,
        "emb_dim": dim,
        "spill_mb": round(spill_bytes / 2**20, 1),
        "rss_mb": round(rss / 2**20, 1),
        "beyond_ram": bool(spill_bytes > rss),
        "hot_rows": int(stats["hot"]), "cold_rows": int(stats["cold"]),
        "build_s": round(build_s, 2),
        "recovery_s": round(recovery_s, 3),
        "recovered_rows": int(recovered),
        "p50_ms_mixed": round(lat["zc"][0], 3),
        "p99_ms": round(lat["zc"][1], 3),
        "row_p50_ms": round(lat["row"][0], 3),
        "row_p99_ms": round(lat["row"][1], 3),
        "row_wire_pulls_s": round(thru["row"], 2),
        "zc_over_row": round(thru["zc"] / thru["row"], 3),
        "zc_over_row_p50": round(lat["row"][0] / lat["zc"][0], 3),
        # the paired statistic: per-batch row_time/zc_time, median over
        # all matched pairs — immune to drift that spans batches
        "zc_over_row_paired": round(float(np.median(
            np.asarray(samples["row"]) / np.asarray(samples["zc"]))), 3),
        "half_pulls_s": {w: [round(batch * (len(ts) // 2) /
                                   sum(ts[:len(ts) // 2]), 0),
                             round(batch * (len(ts) - len(ts) // 2) /
                                   sum(ts[len(ts) // 2:]), 0)]
                         for w, ts in samples.items()},
        "q8_egress_ratio": round(egress_ratio, 2),
        "q8_parity_bitexact": bool(parity),
        "batch": batch, "steps": steps,
        "note": ("single-core host: server+client timeshare one CPU; "
                 "zc_over_row is the honest wire comparison (same "
                 "contention both sides)"),
    }


def _bench_online(smoke, peak_tflops):
    """Online learning loop freshness (ISSUE 14): a StreamingTrainer
    consumes a live event feed (each event stamped with its ingest
    time at the source) and pushes to a PS primary while a read
    replica rides the async mutation stream; the replica observes
    event-ingested -> applied-at-THIS-replica latency per record into
    ``ps_freshness_ms`` — the REAL watermark path, not a synthetic
    probe.  A TTL sweeper runs concurrently (the full loop, not a
    stripped-down one).  Reported: freshness p50/p99 + events/s.

    CPU-only by design (it measures the loop's freshness plumbing, not
    the chip).  Honesty note: trainer + primary + replica + sweeper
    timeshare this host's ONE core, so the percentiles bound what the
    protocol adds when everything contends — on a real fleet each role
    owns cores and the stream latency (here loopback) dominates."""
    import time as _time

    import numpy as np

    from paddle_tpu.distributed.fleet.ps import SparseTable
    from paddle_tpu.distributed.fleet.ps_service import PSClient, PSServer
    from paddle_tpu.framework import monitor
    from paddle_tpu.io.dataloader import DataLoader
    from paddle_tpu.io.dataset import IterableDataset
    from paddle_tpu.online import FeatureLifecycle, StreamingTrainer

    batches = 100 if smoke else 400
    batch = 64 if smoke else 256
    dim = 8 if smoke else 16
    vocab = 20_000
    monitor.enable_metrics(True)

    spec = dict(dim=dim, optimizer="adagrad", lr=0.05, seed=0)
    primary = PSServer({"emb": SparseTable(**spec)}, host="127.0.0.1")
    primary.start()
    pep = f"127.0.0.1:{primary.port}"
    replica = PSServer({"emb": SparseTable(**spec)}, host="127.0.0.1",
                       replica_of=pep, replica_mode="read",
                       wm_interval_s=0.05)
    replica.start()
    if not replica.replica_ready.wait(30):
        raise RuntimeError("online bench: replica never attached")

    class Events(IterableDataset):
        def __iter__(self):
            rng = np.random.default_rng(0)
            while True:   # unbounded — the trainer bounds the run
                yield {"ids": np.clip(rng.zipf(1.3, batch), 1,
                                      vocab).astype(np.int64),
                       "ingest_ts": _time.time()}

    def collate(items):
        # ingest_ts rides as a python float: the loader's device
        # transfer narrows float64 ARRAYS to f32, which at epoch-second
        # magnitude (~2^31) rounds to ±128 s — useless as a watermark
        return {"ids": np.concatenate([d["ids"] for d in items]),
                "ingest_ts": max(d["ingest_ts"] for d in items)}

    loader = DataLoader(Events(), batch_size=1, collate_fn=collate)
    cli = PSClient([pep], mode="sync")

    def step(b, pull):
        ids = b["ids"]
        rows = pull(ids)
        return ids, np.sign(rows) * 0.05 + 0.01   # proxy grads

    sweeper = FeatureLifecycle(primary, ttl_s=3600.0,
                               interval_s=0.2).start()
    trainer = StreamingTrainer(loader, cli, "emb", step)
    t0 = _time.perf_counter()
    trainer.run(max_batches=batches)
    train_dt = _time.perf_counter() - t0
    # drain: the replica must have APPLIED everything pushed
    deadline = _time.monotonic() + 60.0
    while _time.monotonic() < deadline:
        st = replica._stats()
        if st["watermark"] >= trainer.seq:
            break
        _time.sleep(0.02)
    wall = _time.perf_counter() - t0
    sweeper.stop()
    snap = monitor.metrics_snapshot()
    h = snap.get("histograms", {}).get("ps_freshness_ms")
    cli.close()
    replica.stop()
    primary.stop()
    if not h or h["count"] == 0:
        raise RuntimeError("online bench: freshness histogram empty "
                           "(no iwm-stamped record reached the "
                           "replica)")
    hist = monitor.Histogram.from_snapshot(h)
    return {
        "metric": "online_freshness",
        "value": round(hist.percentile(99.0), 3),
        "unit": "ms_p99_ingest_to_servable_at_replica",
        "vs_baseline": None,
        "freshness_p50_ms": round(hist.percentile(50.0), 3),
        "freshness_samples": int(h["count"]),
        "events_per_s": round(trainer.events / train_dt, 1),
        "batches": batches, "events_per_batch": batch, "emb_dim": dim,
        "drain_wall_s": round(wall, 3),
        "ttl_sweeps": sweeper.sweeps,
        "note": ("single-core host: trainer/primary/replica/sweeper "
                 "timeshare one CPU — percentiles bound the protocol "
                 "under full contention, not a fleet's steady state"),
    }


def _bench_elastic(smoke, peak_tflops):
    """Elastic data-plane engine A/B (ISSUE 17): the same world-1
    deterministic run — in-process coordinator, linear model over a
    flat vector, bootstrap save + restore + train + one streamed
    checkpoint — once on the HOST engine (PR 9 flat-numpy reference)
    and once on the DEVICE engine (compiled slot-ordered reduce +
    fused opt_apply + streamed/ ranged checkpoints, the new default).
    Reported: steps/s per engine, the reshard-window decomposition
    (restore ms, compile ms, bytes) off the flight ring, and the
    device path's measured staging peak (the O(max shard) meter).

    Honesty note: on this single-core CPU host the device engine pays
    jit dispatch per step against numpy's in-cache loops, and world-1
    makes every exchange a loopback self-gather — the A/B bounds
    engine overhead, it does not demonstrate TPU speedup (re-measure
    on real chips)."""
    import tempfile
    import time as _time

    import numpy as np

    from paddle_tpu.distributed.fleet.elastic import (ElasticCoordinator,
                                                      ElasticTrainer)
    from paddle_tpu.io.dataloader import DataLoader
    from paddle_tpu.io.dataset import Dataset
    from paddle_tpu.observability import flight_recorder as _flight

    numel = 20_000 if smoke else 200_000
    steps = 6 if smoke else 30

    class Xs(Dataset):
        def __init__(self, n=64):
            rng = np.random.default_rng(5)
            self.x = rng.standard_normal(n).astype(np.float32)

        def __len__(self):
            return self.x.size

        def __getitem__(self, i):
            return self.x[i]

    def grad(params, batch):
        s = np.float32(np.mean(batch))
        return {"w": (params["w"] * np.float32(1e-3)
                      + s * np.float32(1e-2)).astype(np.float32),
                "b": np.asarray(s, np.float32).reshape(())}

    def run(engine):
        coord = ElasticCoordinator(expected_world=1).start()
        with tempfile.TemporaryDirectory() as ck:
            loader = DataLoader(Xs(), batch_size=8, shuffle=True,
                                seed=3, drop_last=True)
            tr = ElasticTrainer(
                {"w": np.zeros(numel - 1, np.float32),
                 "b": np.zeros((), np.float32)},
                grad, loader, ckpt_dir=ck, optimizer="adam", lr=0.01,
                micro_batches=2, ckpt_every=steps,
                coordinator=f"127.0.0.1:{coord.port}",
                expected_world=1, client_timeout=60.0, engine=engine)
            n0 = len(_flight.events()) if _flight.enabled() else 0
            t0 = _time.perf_counter()
            tr.run(steps)
            wall = _time.perf_counter() - t0
            evs = _flight.events()[n0:] if _flight.enabled() else []
        coord.stop()
        restore_ms = sum(e.get("ms", 0.0) for e in evs
                         if e.get("kind") == "elastic.reshard")
        compile_ms = sum(e.get("ms", 0.0) for e in evs
                         if e.get("kind") == "elastic.reshard.compile")
        rbytes = sum(e.get("bytes", 0) for e in evs
                     if e.get("kind") == "elastic.reshard")
        return {"steps_per_s": steps / wall, "wall_s": wall,
                "restore_ms": restore_ms, "compile_ms": compile_ms,
                "reshard_bytes": rbytes,
                "meter_peak_bytes": tr.reshard_meter.peak_bytes}

    host = run("host")
    dev = run("device")
    return {
        "metric": "elastic_engine",
        "value": round(dev["steps_per_s"], 2),
        "unit": "steps_per_s_device_engine_world1",
        "vs_baseline": None,
        "host_steps_per_s": round(host["steps_per_s"], 2),
        "device_vs_host_x": round(dev["steps_per_s"]
                                  / host["steps_per_s"], 3),
        "numel": numel, "steps": steps,
        "restore_ms": {"host": round(host["restore_ms"], 2),
                       "device": round(dev["restore_ms"], 2)},
        "device_compile_ms": round(dev["compile_ms"], 2),
        "device_reshard_bytes": dev["reshard_bytes"],
        "device_meter_peak_bytes": dev["meter_peak_bytes"],
        "host_meter_peak_bytes": host["meter_peak_bytes"],
        "note": ("1-core CPU + world-1 loopback: bounds engine "
                 "overhead only — compiled-path wins need real chips "
                 "(TPU re-measure flagged)"),
    }


def _bench_plan(smoke, peak_tflops):
    """Auto-sharding planner (ISSUE 15): per-proxy wall time of the
    ANALYTIC phase (pure python: enumerate + score every valid mesh)
    vs the VERIFY phase (AOT lower + XLA memory analysis of the top
    lowerable candidates), and the analytic model's predicted-vs-XLA
    peak-memory relative error over the proxy suite's verified plans.

    Runs each proxy through ``tools/plan.py --verify --json`` in a
    subprocess (the CLI re-execs itself onto an 8-device virtual CPU
    mesh; the bench child's backend has 1 device).  CPU-only by design
    — the verify phase is compile-time work, identical on any host.

    Honesty note: the error reported here is the TINY-proxy regime
    (hidden 256-512); at 7B scale the same model lands within ~4% of
    the MULTICHIP_r05 XLA records (pinned by tests/test_planner.py) —
    small programs keep relatively more buffers live than the chunked
    large-model paths, so proxy error is the model's worst case."""
    import subprocess
    import sys
    import time as _time

    from paddle_tpu.distributed.planner.memory_model import PROXY_SUITE

    entries = PROXY_SUITE[:2] if smoke else PROXY_SUITE
    top_k = 2 if smoke else 3
    here = os.path.dirname(os.path.abspath(__file__))
    errs, analytic_s, verify_s, n_rejected, per_entry = \
        [], 0.0, 0.0, 0, {}
    for entry in entries:
        t0 = _time.perf_counter()
        proc = subprocess.run(
            [sys.executable, os.path.join(here, "tools", "plan.py"),
             "--model", entry["name"], "--chips", "8", "--verify",
             "--top-k", str(top_k), "--json"],
            capture_output=True, text=True, timeout=900, cwd=here)
        if proc.returncode != 0:
            raise RuntimeError(
                f"plan bench: {entry['name']} failed rc="
                f"{proc.returncode}:\n{proc.stderr[-1500:]}")
        out = json.loads(proc.stdout.strip().splitlines()[-1])
        analytic_s += float(out.get("analytic_s") or 0.0)
        verify_s += float(out.get("verify_s") or 0.0)
        n_rejected += int(out.get("n_rejected") or 0)
        entry_errs = []
        for p in out["plans"]:
            if not p.get("verified"):
                continue
            xla = p["verified_peak_gib"]
            pred = p["analytic_peak_gib"]
            if xla:
                entry_errs.append(abs(pred - xla) / xla)
        errs.extend(entry_errs)
        per_entry[entry["name"]] = {
            "plans": [p["mesh"] for p in out["plans"]],
            "abs_rel_err": [round(e, 4) for e in entry_errs],
            "wall_s": round(_time.perf_counter() - t0, 2)}
    if not errs:
        raise RuntimeError("plan bench: no verified plan produced an "
                           "error sample")
    errs.sort()
    med = errs[len(errs) // 2]
    return {
        "metric": "plan_peak_prediction_error",
        "value": round(100.0 * med, 2),
        "unit": "median_abs_rel_err_pct_vs_xla_proxy_suite",
        "vs_baseline": None,
        "max_abs_rel_err_pct": round(100.0 * errs[-1], 2),
        "error_samples": len(errs),
        "analytic_phase_s": round(analytic_s, 4),
        "verify_phase_s": round(verify_s, 2),
        "verify_rejected_candidates": n_rejected,
        "per_entry": per_entry,
        "note": ("analytic phase scores EVERY valid mesh in "
                 "milliseconds; verify compiles only the top-k. "
                 "candidates that fail to lower are dropped and "
                 "counted — every RETURNED plan lowered"),
    }


def _bench_inference(smoke, peak_tflops):
    """Inference latency (reference analog: the analyzer_*_tester.cc
    latency gates + mkldnn int8 deploy): ResNet-50 and BERT-base
    batch-1 forward under jit, p50/p99 over repeated calls, in TWO
    weight formats — bf16, and EXECUTED int8 weights
    (quantization.convert_to_int8_inference; batch-1 matmuls/convs are
    weight-HBM-bound, so int8 halves the streamed bytes)."""
    import time as _time

    import numpy as np

    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.framework.core import Tensor, no_grad
    from paddle_tpu.quantization import convert_to_int8_inference

    iters = 10 if smoke else 50

    def latency_ms(model, x):
        """(chained_mean_ms, sync_p50_ms): per-call wall clock of a
        batch-1 forward includes the host dispatch and the result
        fetch.  The device-side latency is measured with a dependency
        CHAIN — each call's input consumes a scalar from the previous
        output, forcing sequential device execution, with ONE fetch at
        the end — and the synchronous dispatch-and-fetch p50 is
        reported alongside."""
        model.eval()
        st = model.state_dict()
        names = sorted(st)
        vals = {n: st[n]._value for n in names}
        import jax.numpy as jnp

        def fn(vals_, xv, eps):
            xv = xv + eps.astype(xv.dtype)
            old = {n: st[n]._value for n in names}
            try:
                for n in names:
                    st[n]._value = vals_[n]
                with no_grad():
                    out = model(Tensor(xv))
            finally:
                for n in names:
                    st[n]._value = old[n]
            if not isinstance(out, Tensor):
                out = out[0] if isinstance(out, (tuple, list)) else out
            ov = out._value if isinstance(out, Tensor) else out
            return ov, (ov.reshape(-1)[0] * 0.0).astype(jnp.float32)

        jf = jax.jit(fn)
        eps = jnp.zeros((), jnp.float32)
        o, eps = jf(vals, x, eps)
        np.asarray(o)
        t0 = _time.perf_counter()
        for _ in range(iters):
            o, eps = jf(vals, x, eps)
        np.asarray(o)          # one fetch closes the dependency chain
        chained = (_time.perf_counter() - t0) * 1e3 / iters
        sync = []
        for _ in range(5):
            t0 = _time.perf_counter()
            o, _e = jf(vals, x, eps)
            np.asarray(o)
            sync.append((_time.perf_counter() - t0) * 1e3)
        return float(chained), float(np.percentile(sync, 50))

    def cast_bf16(model):
        for n, t in model.state_dict().items():
            # per-channel dequant scales stay f32 (the int8 layers'
            # documented contract); everything else float goes bf16
            if n.endswith("w_scale"):
                continue
            if hasattr(t._value, "dtype") and \
                    t._value.dtype == jnp.float32:
                t._value = t._value.astype(jnp.bfloat16)
        return model

    out = []
    rng = np.random.RandomState(0)
    # VERDICT r4 item 7: int8's regime is batch-dependent (batch 1 is
    # weight-streaming-bound, large batch compute-bound) — sweep it
    batches = [int(b) for b in os.environ.get(
        "BENCH_INFER_BATCHES", "1" if smoke else "1,8,32,128").split(",")]

    # -- ResNet-50 ------------------------------------------------------
    from paddle_tpu.vision.models import resnet18, resnet50
    hw = 32 if smoke else 224

    def resnet_pair():
        paddle.seed(0)
        m = (resnet18(num_classes=10) if smoke
             else resnet50(num_classes=1000))
        cast_bf16(m)
        paddle.seed(0)
        q = (resnet18(num_classes=10) if smoke
             else resnet50(num_classes=1000))
        convert_to_int8_inference(q)
        cast_bf16(q)   # non-conv params (BN) to bf16; qweights int8
        return m, q

    def sweep(pair_fn, mk_input):
        m, q = pair_fn()
        rows = []
        for b in batches:
            x = mk_input(b)
            bf_ms, bf_rtt = latency_ms(m, x)
            q_ms, q_rtt = latency_ms(q, x)
            rows.append({
                "batch": b, "bf16_ms": round(bf_ms, 3),
                "int8_ms": round(q_ms, 3),
                "int8_speedup": round(bf_ms / q_ms, 3) if q_ms else None,
                "bf16_sync_rtt_p50_ms": round(bf_rtt, 3),
                "int8_sync_rtt_p50_ms": round(q_rtt, 3),
            })
        return rows

    rows = sweep(resnet_pair,
                 lambda b: jnp.asarray(
                     rng.standard_normal((b, 3, hw, hw)), jnp.bfloat16))
    r0 = rows[0]
    out.append({
        "metric": "resnet50_infer_latency" if not smoke
                  else "resnet18_infer_latency",
        "value": r0["bf16_ms"], "unit": "ms_chained_batch1",
        "vs_baseline": None,
        "sync_rtt_p50_ms": r0["bf16_sync_rtt_p50_ms"],
        "int8_weight_ms": r0["int8_ms"],
        "int8_speedup": r0["int8_speedup"],
        "batch_sweep": rows,
    })

    # -- BERT-base encoder ---------------------------------------------
    from paddle_tpu.text.models.bert import BertModel, bert_base, bert_tiny
    seq = 32 if smoke else 128
    cfg = bert_tiny() if smoke else bert_base()

    def bert_pair():
        paddle.seed(0)
        bm = BertModel(cfg)
        cast_bf16(bm)
        paddle.seed(0)
        qm = BertModel(cfg)
        convert_to_int8_inference(qm)
        cast_bf16(qm)
        return bm, qm

    rows = sweep(bert_pair,
                 lambda b: jnp.asarray(
                     rng.randint(0, cfg.vocab_size, (b, seq)), jnp.int32))
    r0 = rows[0]
    out.append({
        "metric": "bert_base_infer_latency" if not smoke
                  else "bert_tiny_infer_latency",
        "value": r0["bf16_ms"], "unit": "ms_chained_batch1",
        "vs_baseline": None,
        "sync_rtt_p50_ms": r0["bf16_sync_rtt_p50_ms"],
        "int8_weight_ms": r0["int8_ms"],
        "int8_speedup": r0["int8_speedup"],
        "seq_len": seq,
        "batch_sweep": rows,
    })
    return out


def _bench_serve(smoke, peak_tflops):
    """AOT serving engine (ISSUE 2 tentpole): BERT and ResNet exports
    served two ways on the same compile-once Predictor —

    - SEQUENTIAL batch-1 ``Predictor.run()`` loop (the deploy pattern
      every per-request client gets), and
    - ``PredictorServer``: N concurrent batch-1 clients whose requests
      coalesce under a max-wait deadline into power-of-2 padded bucket
      batches, one pre-warmed executable per bucket.

    Reports examples/sec for both, the speedup, client-observed p50/p99
    latency, the bucket hit distribution, and the compile counter
    (steady-state zero-retrace evidence).  (The cold-load record that
    used to ride here spawned two chip-needing children from this
    chip-holding process — one process per chip; it is gone, and a
    second run's compile time against the fixed cache directory is what
    ``chip_smoke.py`` reports.)

    Env knobs: BENCH_SERVE_REQS (total requests), BENCH_SERVE_CLIENTS,
    BENCH_SERVE_MAXB (top bucket), BENCH_SERVE_WAIT_MS.
    """
    import tempfile
    import threading
    import time as _time

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.inference import Config, PredictorServer, \
        create_predictor
    from paddle_tpu.static import InputSpec

    tmp = tempfile.mkdtemp(prefix="ptpu_serve_")   # exported artifacts
    # BENCH_SMOKE keeps the methodology on proxy models (a CPU cannot
    # batch-compile + serve BERT-base/ResNet-50 inside a smoke budget)
    reduced = smoke
    n_reqs = int(os.environ.get("BENCH_SERVE_REQS",
                                "128" if reduced else "192"))
    clients = int(os.environ.get("BENCH_SERVE_CLIENTS", "16"))
    max_batch = int(os.environ.get("BENCH_SERVE_MAXB",
                                   "16" if reduced else "32"))
    wait_ms = float(os.environ.get("BENCH_SERVE_WAIT_MS", "1"))

    def export_bert():
        from paddle_tpu.text.models.bert import (BertModel, bert_base,
                                                 bert_tiny)
        seq = 32 if reduced else 128
        cfg = bert_tiny() if reduced else bert_base()
        paddle.seed(0)
        m = BertModel(cfg)
        m.eval()
        path = os.path.join(tmp, "bert")
        paddle.jit.save(m, path,
                        input_spec=[InputSpec([None, seq], "int32",
                                              "ids")])
        rng = np.random.RandomState(0)

        def mk(b):
            return [rng.randint(0, cfg.vocab_size, (b, seq))
                    .astype("int32")]
        name = "bert_base_serve" if not reduced else "bert_tiny_serve"
        return name, path, mk, {"seq_len": seq}

    def export_resnet():
        from paddle_tpu.vision.models import resnet18, resnet50
        hw = 32 if reduced else 224
        paddle.seed(0)
        m = (resnet18(num_classes=10) if reduced
             else resnet50(num_classes=1000))
        m.eval()
        path = os.path.join(tmp, "resnet")
        paddle.jit.save(m, path,
                        input_spec=[InputSpec([None, 3, hw, hw],
                                              "float32", "img")])
        rng = np.random.RandomState(0)

        def mk(b):
            return [rng.standard_normal((b, 3, hw, hw))
                    .astype("float32")]
        name = "resnet50_serve" if not reduced else "resnet18_serve"
        return name, path, mk, {"image_size": hw}

    def measure(name, path, mk_input, extra):
        pred = create_predictor(Config(path))
        x1 = mk_input(1)
        pred.run(x1)                       # warm the batch-1 executable

        # sequential batch-1 loop (per-request deployment baseline)
        t0 = _time.perf_counter()
        for _ in range(n_reqs):
            pred.run(x1)
        dt_seq = _time.perf_counter() - t0
        batch1_ex_s = n_reqs / dt_seq

        # concurrent clients against the micro-batching server
        per_client = n_reqs // clients
        server = PredictorServer(pred, max_batch=max_batch,
                                 max_wait_ms=wait_ms, max_queue=1024,
                                 request_timeout_s=600.0)
        server.start()                     # prewarms every bucket
        n_warm = pred.num_compiles()
        lats = [[] for _ in range(clients)]

        def worker(ci):
            x = mk_input(1)
            for _ in range(per_client):
                t = _time.perf_counter()
                server.infer(x, timeout_s=600.0)
                lats[ci].append(_time.perf_counter() - t)

        threads = [threading.Thread(target=worker, args=(ci,))
                   for ci in range(clients)]
        t0 = _time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt_srv = _time.perf_counter() - t0
        st = server.stats()
        server.stop()
        assert pred.num_compiles() == n_warm, \
            "serving traffic compiled — bucket prewarm is broken"
        served = clients * per_client
        lat_ms = sorted(l * 1e3 for ls in lats for l in ls)
        speedup = (served / dt_srv) / batch1_ex_s if batch1_ex_s else None
        return {
            "metric": f"{name}_throughput",
            "value": round(served / dt_srv, 2),
            "unit": "examples/sec",
            "vs_baseline": None,
            "batch1_ex_s": round(batch1_ex_s, 2),
            "serve_speedup_vs_batch1": round(speedup, 3),
            "p50_ms": round(lat_ms[len(lat_ms) // 2], 3),
            "p99_ms": round(lat_ms[min(len(lat_ms) - 1,
                                       int(len(lat_ms) * 0.99))], 3),
            "clients": clients, "requests": served,
            "max_batch": max_batch, "max_wait_ms": wait_ms,
            "batches": st["batches"],
            "bucket_hits": {str(k): v for k, v in
                            st["bucket_hits"].items() if v},
            "padded_frac": round(st["padded_examples"]
                                 / max(st["examples"], 1), 4),
            "num_compiles": st["num_compiles"],
            **extra,
        }

    out = []
    # resnet leads: per-image conv work at batch 1 underutilizes any
    # backend, so it shows the serving engine's regime cleanly
    rn_name, rn_path, rn_mk, rn_extra = export_resnet()
    out.append(measure(rn_name, rn_path, rn_mk, rn_extra))
    bert_name, bert_path, bert_mk, bert_extra = export_bert()
    out.append(measure(bert_name, bert_path, bert_mk, bert_extra))

    return out


def _bench_llama_serve(smoke, peak_tflops):
    """Continuous-batching generative serving (ISSUE 8 tentpole):
    N concurrent MIXED-LENGTH streamed generations through
    ``GenerationServer`` (block-paged KV cache + iteration-level decode
    scheduler) vs a sequential ``generate()`` loop over the exact same
    requests (which already uses the contiguous KV-cache fast path —
    the honest batch-1 decode baseline).

    The win is the decode regime the round-7 bench flagged as
    pathological: batch-1 decode underutilizes ANY backend, so batching
    N streams into ONE fixed-shape decode program should approach
    batch-width speedup in aggregate tokens/s.  Also reports eviction /
    retrace counters: steady state must run zero compiles.

    Env knobs: BENCH_LLAMA_SERVE_STREAMS, BENCH_LLAMA_SERVE_NEW.
    """
    import time as _time

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.inference import GenerationServer
    from paddle_tpu.text.models import LlamaForCausalLM, llama_tiny

    reduced = smoke
    n_streams = int(os.environ.get("BENCH_LLAMA_SERVE_STREAMS",
                                   "8" if reduced else "16"))
    max_new = int(os.environ.get("BENCH_LLAMA_SERVE_NEW",
                                 "24" if reduced else "64"))
    paddle.seed(0)
    if reduced:
        cfg = llama_tiny(vocab_size=256, hidden_size=64,
                         intermediate_size=128, num_hidden_layers=2,
                         num_attention_heads=4, num_key_value_heads=2,
                         max_position_embeddings=512)
    else:
        cfg = llama_tiny(vocab_size=32000, hidden_size=1024,
                         intermediate_size=2816, num_hidden_layers=8,
                         num_attention_heads=16, num_key_value_heads=8,
                         max_position_embeddings=1024)
    model = LlamaForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(0)
    # mixed prompt lengths: the regime a fixed-batch server can't pack
    lens = [(8, 24, 16, 12)[i % 4] for i in range(n_streams)]
    prompts = [rng.randint(1, cfg.vocab_size, (L,)).astype("int32")
               for L in lens]
    total_new = n_streams * max_new

    # sequential generate() loop (KV-cache fast path, batch-1 decode).
    # Warm EVERY distinct prompt-length's eager dispatch caches first:
    # the measured pass must time steady-state decode, not first-call
    # per-shape compiles (which the server side also pays outside its
    # timed window, via prewarm)
    for L in sorted(set(lens)):
        model.generate(paddle.to_tensor(
            prompts[lens.index(L)][None, :]), max_new_tokens=2)
    t0 = _time.perf_counter()
    for p in prompts:
        model.generate(paddle.to_tensor(p[None, :]),
                       max_new_tokens=max_new)
    dt_seq = _time.perf_counter() - t0
    seq_tok_s = total_new / dt_seq

    max_len = max(lens) + max_new
    server = GenerationServer(
        model, num_slots=n_streams, block_size=8 if reduced else 16,
        max_model_len=max_len, request_timeout_s=600.0)
    server.start()        # prewarms prefill buckets + the decode program
    n_warm = server.num_compiles()
    streams = [server.submit(p, max_new_tokens=max_new)
               for p in prompts]
    t0 = _time.perf_counter()
    outs = [s.result(timeout=600.0) for s in streams]
    dt_srv = _time.perf_counter() - t0
    st = server.stats()
    server.stop()
    assert server.num_compiles() == n_warm, \
        "serving traffic compiled — decode/prefill prewarm is broken"
    assert all(len(o) == max_new for o in outs)
    srv_tok_s = total_new / dt_srv

    # single-slot server arm: same compiled-step machinery, batch
    # width 1 — isolates the BATCHING win from the compiled-program-
    # vs-eager-dispatch win (the generate() gap includes both; the
    # ~batch-width claim is this ratio)
    s1 = GenerationServer(model, num_slots=1,
                          block_size=8 if reduced else 16,
                          max_model_len=max_len,
                          request_timeout_s=600.0)
    s1.start()
    t0 = _time.perf_counter()
    for p in prompts:
        s1.submit(p, max_new_tokens=max_new).result(timeout=600.0)
    dt_one = _time.perf_counter() - t0
    s1.stop()
    one_tok_s = total_new / dt_one
    return {
        "metric": "llama_serve_tokens_per_s",
        "value": round(srv_tok_s, 2),
        "unit": "aggregate_new_tokens/sec",
        "vs_baseline": None,
        "sequential_tok_s": round(seq_tok_s, 2),
        "serve_speedup_vs_sequential": round(srv_tok_s / seq_tok_s, 3),
        "single_slot_server_tok_s": round(one_tok_s, 2),
        "serve_speedup_vs_single_slot": round(srv_tok_s / one_tok_s, 3),
        "streams": n_streams, "max_new_tokens": max_new,
        "prompt_lens": sorted(set(lens)),
        "decode_steps": st["decode_steps"],
        "decode_ms_per_step": round(
            st["decode_ms"] / max(st["decode_steps"], 1), 3),
        "prefill_bucket_hits": {str(k): v for k, v in
                                st["prefill_bucket_hits"].items() if v},
        "evicted": st["evicted"],
        "num_compiles": st["num_compiles"],
        "traffic_compiles": st["traffic_compiles"],
        "block_size": st["block_size"],
        "total_blocks": st["total_blocks"],
    }


def _bench_llama_gateway(smoke, peak_tflops):
    """Inference gateway A/B (ISSUE 11 tentpole): a shared-system-
    prompt chat workload — 8 streams whose prompts share a 75% prefix
    (24-token system prompt + 8-token unique tail), two waves so the
    prefix cache serves warm traffic — through three arms on the SAME
    target model:

    - ``plain``   — the PR 8 ``llama_serve`` server (B=1 prefill, no
      sharing, no speculation): the baseline;
    - ``prefix``  — copy-on-write prefix sharing + batched prefill;
    - ``gateway`` — prefix + speculative decoding with a 1-layer
      draft sharing the target's embeddings/head/first layer.

    Honest decomposition: prefix-vs-plain isolates the prefill-
    compute/TTFT win; gateway-vs-prefix isolates the speculation win
    AT THE MEASURED ACCEPT RATE.  The proxy pair is constructed for
    the trained-model regime (the draft must approximate the target
    for speculation to pay): decoder-layer weights are damped so the
    residual stream is embedding-dominated, giving a measured accept
    rate instead of the ~0 a pair of independent random nets shows.
    Prefix/gateway arm outputs are asserted bit-identical (cold ==
    warm == speculated) and every arm must run ZERO steady-state
    compiles.  Budget: honored by the parent driver's trial/timeout
    machinery (this metric is in ``_FRESH_PROCESS_TRIALS``).

    REGIME NOTE (same class as round 12's batching factor): on a
    1-core CPU every FLOP is serial, so a verify forward costs ~S x a
    decode forward and the draft's dispatches are not hidden — wall-
    clock speculation speedup here is bounded near 1.0 no matter the
    accept rate.  The quantity that transfers to accelerators is
    ``target_iteration_speedup`` (plain decode steps / verify steps):
    batch-1/short-S decode underutilizes the MXU, so verifying k+1
    positions rides compute the TPU was wasting.  Both numbers are
    reported; PERF.md round 14 carries the full caveat.

    Env knobs: BENCH_GATEWAY_STREAMS, BENCH_GATEWAY_NEW.
    """
    import time as _time

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.inference import GenerationServer
    from paddle_tpu.text.models import LlamaForCausalLM, llama_tiny

    reduced = smoke
    n_streams = int(os.environ.get("BENCH_GATEWAY_STREAMS", "8"))
    max_new = int(os.environ.get("BENCH_GATEWAY_NEW",
                                 "24" if reduced else "64"))
    paddle.seed(0)
    if reduced:
        cfg = llama_tiny(vocab_size=256, hidden_size=128,
                         intermediate_size=256, num_hidden_layers=4,
                         num_attention_heads=8, num_key_value_heads=4,
                         max_position_embeddings=256)
    else:
        cfg = llama_tiny(vocab_size=32000, hidden_size=1024,
                         intermediate_size=2816, num_hidden_layers=8,
                         num_attention_heads=16, num_key_value_heads=8,
                         max_position_embeddings=1024)
    import dataclasses
    model = LlamaForCausalLM(cfg)
    model.eval()
    # damp decoder layers: embedding-dominated residual stream = the
    # regime where a truncated draft approximates the target (see
    # docstring) — applied to the TARGET, so every arm shares it
    for name, p in model.state_dict().items():
        if ".layers." in name and "layernorm" not in name:
            p._value = p._value * 0.15
    draft = LlamaForCausalLM(dataclasses.replace(
        cfg, num_hidden_layers=1))
    draft.eval()
    sd_t = dict(model.state_dict())
    for name, p in draft.state_dict().items():
        if name in sd_t:
            p._value = sd_t[name]._value

    rng = np.random.RandomState(0)
    shared = rng.randint(1, cfg.vocab_size, (24,)).astype("int32")
    prompts = [np.concatenate([
        shared, rng.randint(1, cfg.vocab_size, (8,)).astype("int32")])
        for _ in range(n_streams)]
    max_len = 32 + max_new
    bs = 8 if reduced else 16

    def run_wave(server):
        t0 = _time.perf_counter()
        marks = []
        streams = []
        for p in prompts:
            ts = _time.perf_counter()
            st = server.submit(p, max_new_tokens=max_new)
            streams.append((ts, st))
        outs = []
        for ts, st in streams:
            it = iter(st)
            next(it)
            marks.append((_time.perf_counter() - ts) * 1e3)
            outs.append([st.tokens[0]] + list(it))
        return _time.perf_counter() - t0, marks, outs

    def run_arm(**kw):
        srv = GenerationServer(model, num_slots=n_streams,
                               block_size=bs, max_model_len=max_len,
                               request_timeout_s=600.0, **kw)
        srv.start()
        n_warm = srv.num_compiles()
        w1, ttft1, out1 = run_wave(srv)        # cold
        w2, ttft2, out2 = run_wave(srv)        # warm (prefix hits)
        st = srv.stats()
        srv.stop()
        assert srv.num_compiles() == n_warm, \
            "gateway traffic compiled — prewarm is broken"
        total = 2 * n_streams * max_new
        return {"tok_s": total / (w1 + w2), "wall_cold": w1,
                "wall_warm": w2, "ttft_cold": ttft1,
                "ttft_warm": ttft2, "out_cold": out1,
                "out_warm": out2, "stats": st}

    plain = run_arm(max_prefill_batch=1)
    prefix = run_arm(prefix_cache=True, max_prefill_batch=4)
    gateway = run_arm(prefix_cache=True, max_prefill_batch=4,
                      draft_model=draft, spec_k=3)
    # bit-exactness inside the chunked-prefill family: cold == warm,
    # and speculation changes NOTHING but speed
    assert prefix["out_cold"] == prefix["out_warm"]
    assert gateway["out_cold"] == prefix["out_cold"]
    assert gateway["out_warm"] == prefix["out_warm"]

    def pct(xs, q):
        return float(np.percentile(np.asarray(xs), q))

    gst, pst = gateway["stats"], prefix["stats"]
    return {
        "metric": "llama_gateway_tokens_per_s",
        "value": round(gateway["tok_s"], 2),
        "unit": "aggregate_new_tokens/sec",
        "vs_baseline": None,
        "plain_tok_s": round(plain["tok_s"], 2),
        "prefix_tok_s": round(prefix["tok_s"], 2),
        "gateway_speedup_vs_plain": round(
            gateway["tok_s"] / plain["tok_s"], 3),
        "prefix_speedup_vs_plain": round(
            prefix["tok_s"] / plain["tok_s"], 3),
        "spec_speedup_vs_prefix": round(
            gateway["tok_s"] / prefix["tok_s"], 3),
        "ttft_ms_plain_p50": round(pct(plain["ttft_cold"]
                                       + plain["ttft_warm"], 50), 2),
        "ttft_ms_plain_p99": round(pct(plain["ttft_cold"]
                                       + plain["ttft_warm"], 99), 2),
        "ttft_ms_warm_p50": round(pct(prefix["ttft_warm"], 50), 2),
        "ttft_ms_warm_p99": round(pct(prefix["ttft_warm"], 99), 2),
        "prefix_hit_rate": round(pst["prefix_hit_rate"], 3),
        "prefill_tokens_skipped": pst["prefill_tokens_skipped"],
        "prefill_tokens_computed": pst["prefill_tokens"],
        "prefill_batches": pst["prefill_batches"],
        "spec_accept_rate": round(gst["spec_accept_rate"], 3),
        "spec_verify_steps": gst["spec_verify_steps"],
        "plain_decode_steps": plain["stats"]["decode_steps"],
        # target-model iterations per emitted token: the accelerator-
        # transferable speculation win (see docstring regime note)
        "target_iteration_speedup": round(
            plain["stats"]["decode_steps"]
            / max(gst["spec_verify_steps"], 1), 3),
        "decode_ms_per_tok_plain": round(
            plain["stats"]["decode_ms"]
            / max(plain["stats"]["tokens_generated"]
                  - plain["stats"]["admitted"], 1), 3),
        "decode_ms_per_tok_gateway": round(
            gst["decode_ms"]
            / max(gst["tokens_generated"] - gst["admitted"], 1), 3),
        "cow_forks": gst["cow_forks"],
        "streams": n_streams, "max_new_tokens": max_new,
        "shared_prefix_tokens": 24, "prompt_len": 32,
        "num_compiles_gateway": gst["num_compiles"],
        "traffic_compiles": gst["traffic_compiles"],
    }


# ---------------------------------------------------------------------
# kernels metric (ISSUE 13 satellite): per-kernel A/B microbench rows
# ---------------------------------------------------------------------

# Central analytic FLOP/byte accounting for the Pallas tier.  XLA's
# cost analysis CANNOT see inside custom calls — BENCH_r04 recorded
# flops_xla_vs_analytic ~= 0.22 when the flash kernel's FLOPs went
# missing — so every kernel row carries the analytic model as its
# flops/bytes source, handled here centrally instead of per-metric.
_KERNEL_SOURCE_NOTE = ("analytic (pallas custom-call flops/bytes are "
                       "invisible to XLA cost analysis — the "
                       "BENCH_r04 flops_xla_vs_analytic~=0.22 gotcha)")


def _kernel_flops_bytes(name, **p):
    """(flops, bytes) per single kernel invocation."""
    if name == "opt_apply":
        n, nslots = p["n"], p["nslots"]
        # adam: 2 muls+1 add per moment, rsqrt-ish chain ~5 flops
        return (11 * n, 4 * n * (2 + 2 * nslots + 1))
    if name == "int8_matmul":
        m, k, n = p["m"], p["k"], p["n"]
        return (2 * m * k * n, m * k + k * n + 4 * (m * n + n))
    if name == "int8_kv_attention":
        b, h, s, t, d, g = (p["b"], p["h"], p["s"], p["t"], p["d"],
                            p["g"])
        flops = 4 * b * h * s * t * d          # qk^T + pv
        bytes_ = (2 * b * t * g * d            # int8 k+v pools, read once
                  + 2 * 4 * b * t              # scales
                  + 4 * b * s * h * d * 2)     # q in, o out (f32)
        return (flops, bytes_)
    if name == "segment_sum":
        n, dim, nseg = p["n"], p["dim"], p["nseg"]
        return (n * dim, 4 * (n * dim + nseg * dim) + 8 * n)
    if name == "flash_attention":
        b, h, s, d = p["b"], p["h"], p["s"], p["d"]
        return (4 * b * h * s * s * d // 2,    # causal halves the work
                2 * 4 * b * h * s * d * 4)
    raise KeyError(name)


def _bench_kernels(smoke, peak_tflops):
    """A/B microbench of every Pallas-tier kernel vs its XLA reference
    (ISSUE 13 satellite): one row per kernel, median picked by the
    parent's trial machinery (``kernels`` is in
    ``_FRESH_PROCESS_TRIALS``), BENCH_TIME_BUDGET_S honored by the
    parent's timeout.

    Under BENCH_SMOKE (CPU) the "pallas" arm runs the INTERPRETER —
    that arm checks dispatch and parity plumbing, not kernel speed, and
    its rows are flagged ``regime: cpu-interpret``.  On TPU the same
    rows measure the real fused kernels; a kernel the registry parks
    behind its reference there (``tpu_default == "xla_ref"`` — Mosaic
    cannot lower it yet) reports its reference arm only.

    Every arm is jitted once and asserted to run ZERO steady-state
    retraces (the num_compiles-style trace counter rides inside the
    jitted callable).
    """
    import time as _time

    import numpy as np

    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import registry as kreg

    on_tpu = not smoke     # the non-smoke path only runs on a TPU
    pallas_mode = "pallas" if on_tpu else "interpret"
    steps = (int(os.environ.get("BENCH_STEPS"))
             if os.environ.get("BENCH_STEPS")
             else (20 if smoke else 50))
    rng = np.random.default_rng(0)

    def _case_opt_apply():
        from paddle_tpu.ops.pallas.opt_apply import pack_hyper
        n = (1 << 15) if not on_tpu else (1 << 22)
        args = (jnp.asarray(rng.standard_normal(n), jnp.float32),
                jnp.asarray(rng.standard_normal(n), jnp.float32),
                (jnp.zeros(n, jnp.float32), jnp.zeros(n, jnp.float32)),
                jnp.asarray(pack_hyper("adam", lr=1e-3, t=3)))
        fn = lambda *a: kreg.dispatch("opt_apply", "adam", *a)  # noqa: E731
        return fn, args, {"n": n, "nslots": 2}

    def _case_int8_matmul():
        m, k, n = (64, 256, 256) if not on_tpu else (512, 4096, 4096)
        xq = jnp.asarray(rng.integers(-127, 127, (m, k)), jnp.int8)
        qw = jnp.asarray(rng.integers(-127, 127, (k, n)), jnp.int8)
        sc = jnp.asarray(rng.random(n) * 0.01 + 1e-4, jnp.float32)
        xs = np.float32(0.02)
        fn = lambda a, b, c: kreg.dispatch(  # noqa: E731
            "int8_matmul", a, b, c, x_scale=xs,
            compute_dtype=jnp.float32)
        return fn, (xq, qw, sc), {"m": m, "k": k, "n": n}

    def _case_kv_attn():
        if on_tpu:
            b, s, g, r, d, bs, m, nb = 8, 1, 8, 4, 128, 16, 64, 2048
        else:
            b, s, g, r, d, bs, m, nb = 2, 1, 2, 2, 64, 16, 8, 64
        h = g * r
        qh = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
        kp = jnp.asarray(rng.integers(-127, 127, (nb, bs, g, d)),
                         jnp.int8)
        vp = jnp.asarray(rng.integers(-127, 127, (nb, bs, g, d)),
                         jnp.int8)
        ks = jnp.asarray(rng.random((nb, bs)) * 0.01 + 1e-4, jnp.float32)
        vs = jnp.asarray(rng.random((nb, bs)) * 0.01 + 1e-4, jnp.float32)
        tbl = jnp.asarray(rng.integers(1, nb, (b, m)), jnp.int32)
        pos = jnp.full((b, s), bs * m - 1, jnp.int32)
        fn = lambda *a: kreg.dispatch(  # noqa: E731
            "int8_kv_attention", *a, g)
        return fn, (qh, kp, vp, ks, vs, tbl, pos), {
            "b": b, "h": h, "s": s, "t": bs * m, "d": d, "g": g}

    def _case_segment_sum():
        n, dim, nseg = ((1024, 16, 128) if not on_tpu
                        else (8192, 64, 1024))
        g = jnp.asarray(rng.standard_normal((n, dim)), jnp.float32)
        inv = jnp.asarray(rng.integers(0, nseg, n), jnp.int32)
        fn = lambda a, b: kreg.dispatch(  # noqa: E731
            "segment_sum", a, b, num_segments=nseg)
        return fn, (g, inv), {"n": n, "dim": dim, "nseg": nseg}

    def _case_flash():
        from paddle_tpu.ops.flash_attention import flash_attention_bhsd
        b, h, s, d = (1, 2, 256, 64) if not on_tpu else (4, 16, 2048, 128)
        q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
        fn = lambda *a: flash_attention_bhsd(  # noqa: E731
            *a, causal=True, block_q=128, block_k=128)
        return fn, (q, k, v), {"b": b, "h": h, "s": s, "d": d}

    cases = {"opt_apply": _case_opt_apply,
             "int8_matmul": _case_int8_matmul,
             "int8_kv_attention": _case_kv_attn,
             "segment_sum": _case_segment_sum,
             "flash_attention": _case_flash}

    def _arm_ms(name, mode, fn, args):
        kreg.set_mode(name, mode)
        traces = []
        try:
            def wrapped(*a):
                traces.append(1)     # ticks per TRACE, not per call
                return fn(*a)

            jf = jax.jit(wrapped)
            out = jf(*args)          # compile
            jax.block_until_ready(out)
            t0 = _time.perf_counter()
            for _ in range(steps):
                out = jf(*args)
            jax.block_until_ready(out)
            dt = (_time.perf_counter() - t0) / steps
        finally:
            kreg.set_mode(name, None)
        assert len(traces) == 1, (
            f"kernel {name} arm {mode} retraced: {len(traces)} traces")
        return dt * 1e3, len(traces)

    rows = []
    speedups = []
    for name, make in cases.items():
        fn, args, params = make()
        ref_ms, _ = _arm_ms(name, "xla_ref", fn, args)
        parked = on_tpu and kreg.kernels()[name].tpu_default == "xla_ref"
        pal_ms = (None if parked
                  else _arm_ms(name, pallas_mode, fn, args)[0])
        flops, bytes_ = _kernel_flops_bytes(name, **params)
        speed = ref_ms / pal_ms if pal_ms else None
        if speed is not None:
            speedups.append(speed)
        rows.append({
            "metric": f"kernel_{name}",
            "value": round(speed, 4) if speed is not None else None,
            "unit": "x_speedup_vs_xla_ref",
            "vs_baseline": None,
            "pallas_arm": ("none: defaulted to xla_ref on TPU"
                           if parked else pallas_mode),
            "pallas_ms": (round(pal_ms, 4) if pal_ms is not None
                          else None),
            "xla_ref_ms": round(ref_ms, 4),
            "flops_analytic": flops,
            "bytes_analytic": bytes_,
            "arith_intensity": round(flops / bytes_, 3),
            "ref_gflops": round(flops / (ref_ms * 1e-3) / 1e9, 2),
            "ref_gbps": round(bytes_ / (ref_ms * 1e-3) / 1e9, 2),
            "flops_source": _KERNEL_SOURCE_NOTE,
            "steady_state_traces": 1,
            "shape_params": params,
            "regime": ("tpu" if on_tpu else
                       "cpu-interpret (correctness arm, not a perf "
                       "claim)"),
        })
    geo = float(np.exp(np.mean(np.log(speedups))))
    counts = kreg.dispatch_counts()
    head = {
        "metric": "kernels",
        "value": round(geo, 4),
        "unit": "x_geomean_speedup_vs_xla_ref",
        "vs_baseline": None,
        "kernels": sorted(cases),
        "pallas_arm": pallas_mode,
        "dispatch_counts": {k: counts.get(k, {}) for k in cases},
    }
    return [head] + rows


# Repeated fresh-process trials: host-timing-sensitive metrics re-run in
# N fresh subprocesses (fresh backend each — an earlier round's artifacts
# showed a 1.8x spread between single-trial runs of identical code); the
# reported object is the median-by-value trial, annotated with every
# trial's value and the spread.
_FRESH_PROCESS_TRIALS = {"wide_deep": 3, "infer": 3, "serve": 3,
                  "llama_serve": 3, "llama_gateway": 3, "ps_read": 3,
                  "kernels": 3, "online": 3, "plan": 3, "elastic": 3}


def _flatten(out):
    """One child JSON object -> ordered list of metric dicts."""
    rest = out.pop("extra_metrics", [])
    return [out] + list(rest)


def _merge_trials(trial_lists):
    """Median-by-value merge of N trials' flattened metric lists.

    Trials are paired by metric NAME, not list position (ADVICE r5: a
    trial whose child emitted fewer sub-metrics would otherwise get
    DIFFERENT metrics' values silently merged into one row)."""
    order, by_name = [], {}
    for t in trial_lists:
        for c in t:
            name = c.get("metric") or "?"
            if name not in by_name:
                by_name[name] = []
                order.append(name)
            by_name[name].append(c)
    merged = []
    for name in order:
        cands = by_name[name]
        vals = [c.get("value") for c in cands
                if isinstance(c.get("value"), (int, float))]
        if not vals:
            merged.append(cands[0])
            continue
        vals_sorted = sorted(vals)
        med = vals_sorted[len(vals_sorted) // 2]
        pick = dict(next(c for c in cands if c.get("value") == med))
        pick["trials"] = len(vals)
        pick["trial_values"] = [round(v, 3) for v in vals]
        if med:
            pick["trial_spread_pct"] = round(
                100.0 * (max(vals) - min(vals)) / med, 1)
        merged.append(pick)
    return merged


# bench.py's own headline metrics: NEVER dropped by the time budget —
# these are the artifact's reason to exist (VERDICT r5 weak #1-2)
_HEADLINE = ("resnet", "bert", "llama", "wide_deep")


def main():
    """Parent: run each metric in its OWN subprocess and merge.

    One process per chip: this parent never imports JAX, and its
    children run strictly one after another, so each metric gets the
    chip to itself with a fresh backend (metrics run late in one
    long-lived backend session once degraded badly, and a crashed
    metric takes only itself down).

    Output contract: each metric's full-detail JSON line is printed
    AND FLUSHED the moment its trials complete — never buffered to the
    end — and every child result is appended to ``BENCH_partial.jsonl``
    on disk as it returns, so a killed run still leaves every finished
    metric on record twice.  A COMPACT summary goes last so a driver
    capturing only the tail of stdout records every value.  A metric
    that fails both attempts leaves an explicit placeholder (value
    null + error) instead of silently shifting which metric sits in
    the primary slot — and makes the run exit non-zero after the
    summary is printed.

    Wall-clock budget: ``BENCH_TIME_BUDGET_S`` bounds the whole run and
    degrades gracefully — past 50% of the budget every remaining metric
    drops to 1 trial; past 80%, llama_long/llama_8k are skipped; past
    100%, everything but the headline four (resnet/bert/llama/
    wide_deep) is skipped.  The headline four always run (with a
    per-child timeout floor) even if the budget is already spent —
    better a slightly-late artifact than an empty one.
    """
    import subprocess
    import sys
    import time as _time

    if os.environ.get("BENCH_CHILD") == "1":
        _main()
        return
    default = ("resnet,bert,llama,llama_long,llama_8k,wide_deep,infer,"
               "serve,llama_serve,llama_gateway,kernels")
    known = set(default.split(",")) | {"ps_scaling", "ps_read",
                                       "ps_scale", "online", "plan",
                                       "elastic"}
    which = [w.strip() for w in
             os.environ.get("BENCH_METRICS", default).split(",")
             if w.strip()] or default.split(",")
    unknown = [w for w in which if w not in known]
    if unknown:
        print(f"bench: ignoring unknown metrics {unknown}",
              file=sys.stderr)
    which = [w for w in which if w in known] or default.split(",")
    here = os.path.abspath(__file__)

    budget = float(os.environ.get("BENCH_TIME_BUDGET_S", "0") or 0) or None
    t_start = _time.monotonic()

    def remaining():
        return (None if budget is None
                else budget - (_time.monotonic() - t_start))

    partial_path = os.path.join(os.path.dirname(here),
                                "BENCH_partial.jsonl")
    with open(partial_path, "w"):
        pass   # fresh artifact per run; children append below

    def run_child(m, timeout_s):
        env = dict(os.environ)
        env["BENCH_CHILD"] = "1"
        env["BENCH_METRICS"] = m
        detail = ""
        for attempt in (1, 2):
            try:
                proc = subprocess.run(
                    [sys.executable, here], env=env,
                    cwd=os.path.dirname(here), capture_output=True,
                    text=True, timeout=timeout_s)
                line = (proc.stdout.strip().splitlines() or [""])[-1]
                if proc.returncode == 0 and line.startswith("{"):
                    return json.loads(line), None
                detail = f"rc={proc.returncode}: {proc.stderr[-400:]}"
            except (subprocess.TimeoutExpired,
                    json.JSONDecodeError) as e:
                detail = f"{type(e).__name__}: {str(e)[:200]}"
            sys.stderr.write(
                f"bench metric {m!r} attempt {attempt} failed "
                f"({detail})\n")
        return None, detail

    def emit(r):
        print(json.dumps(r), flush=True)

    results = []
    for m in which:
        rem = remaining()
        if rem is not None:
            over_hard = rem <= 0 and m not in _HEADLINE
            over_soft = rem < 0.2 * budget and m in ("llama_long",
                                                     "llama_8k")
            if over_hard or over_soft:
                r = {"metric": m, "value": None, "unit": None,
                     "vs_baseline": None, "skipped": True,
                     "error": "BENCH_TIME_BUDGET_S exhausted"}
                results.append(r)
                emit(r)
                continue
        trials = _FRESH_PROCESS_TRIALS.get(m, 1)
        if rem is not None and rem < 0.5 * budget:
            trials = 1   # first degradation step: median-of-1
        timeout_s = 3000
        if budget is not None:
            # headline metrics keep a usable window even past budget
            floor = 300 if m in _HEADLINE else 60
            timeout_s = min(3000, max(rem or 0, floor))
        trial_lists, err = [], None
        for _ in range(trials):
            out, err = run_child(m, timeout_s)
            if out is not None:
                flat = _flatten(out)
                trial_lists.append(flat)
                with open(partial_path, "a") as f:
                    for d in flat:
                        f.write(json.dumps(d) + "\n")
            rem = remaining()
            if rem is not None and rem <= 0:
                break   # budget gone mid-metric: no more trials
        if not trial_lists:
            r = {"metric": m, "value": None, "unit": None,
                 "vs_baseline": None, "failed": True, "error": err}
            results.append(r)
            emit(r)
            continue
        merged = _merge_trials(trial_lists)
        results.extend(merged)
        for r in merged:   # stream NOW — never buffer to the end
            emit(r)
    primary = next((r for r in results if not r.get("failed")
                    and not r.get("skipped")), results[0])
    summary = {}
    for r in results:
        s = {"value": r.get("value"), "unit": r.get("unit")}
        for k in ("ms_per_step", "plausible", "trials",
                  "trial_spread_pct", "int8_speedup",
                  "flash_speedup_vs_xla", "serve_speedup_vs_batch1",
                  "p99_ms", "error"):
            if r.get(k) is not None:
                s[k] = r[k]
        summary[r.get("metric") or "?"] = s
    final = {"metric": primary.get("metric"),
             "value": primary.get("value"),
             "unit": primary.get("unit"),
             "vs_baseline": primary.get("vs_baseline"),
             "summary": summary,
             "detail_lines_above": len(results)}
    print(json.dumps(final), flush=True)
    failed = [r.get("metric") for r in results if r.get("failed")]
    if failed:
        raise SystemExit(f"bench: metric group(s) failed: {failed}")


def _main():
    import jax

    from paddle_tpu.framework.compile_cache import ensure_compile_cache

    smoke = os.environ.get("BENCH_SMOKE") == "1"
    if smoke:
        jax.config.update("jax_platforms", "cpu")
    dev = jax.devices()       # no chip / chip held: the backend's error
    device = {"platform": dev[0].platform,
              "device_kind": dev[0].device_kind,
              "device_count": len(dev)}
    if smoke:
        print(f"bench: BENCH_SMOKE=1 — a CPU plumbing run on {device}, "
              "not a measurement", file=sys.stderr)
        peak = None
    elif device["platform"] != "tpu":
        raise SystemExit(
            f"bench: the measurement path needs a TPU, found {device}; "
            "BENCH_SMOKE=1 runs the CPU plumbing check instead")
    else:
        peak = _detect_peak_tflops()
    ensure_compile_cache()
    default = ("resnet,bert,llama,llama_long,llama_8k,wide_deep,infer,"
               "serve,llama_serve,llama_gateway,kernels")
    which = [w.strip() for w in
             os.environ.get("BENCH_METRICS", default).split(",")]
    which = [w for w in which if w] or default.split(",")

    results = []
    if "resnet" in which:
        results.append(_bench_resnet(smoke, peak))
    if "bert" in which:
        results.append(_bench_bert(smoke, peak))
    if "llama" in which:
        results.append(_bench_llama(smoke, peak))
    if "llama_long" in which:
        results.append(_bench_llama_long(smoke, peak))
    if "llama_8k" in which:
        results.append(_bench_llama_8k(smoke, peak))
    if "wide_deep" in which:
        results.append(_bench_wide_deep(smoke, peak))
    if "infer" in which:
        results.extend(_bench_inference(smoke, peak))
    if "serve" in which:
        results.extend(_bench_serve(smoke, peak))
    if "llama_serve" in which:
        results.append(_bench_llama_serve(smoke, peak))
    if "llama_gateway" in which:
        results.append(_bench_llama_gateway(smoke, peak))
    if "kernels" in which:
        results.extend(_bench_kernels(smoke, peak))
    if "ps_scaling" in which:
        results.append(_bench_ps_scaling(smoke, peak))
    if "ps_read" in which:
        results.append(_bench_ps_read(smoke, peak))
    if "ps_scale" in which:
        results.append(_bench_ps_scale(smoke, peak))
    if "online" in which:
        results.append(_bench_online(smoke, peak))
    if "plan" in which:
        results.append(_bench_plan(smoke, peak))
    if "elastic" in which:
        results.append(_bench_elastic(smoke, peak))
    if not results:  # unknown names: still honor the one-JSON-line contract
        results.append(_bench_resnet(smoke, peak))

    # every row names the device it ran on
    results = [{**r, **device} for r in results]
    primary = dict(results[0])
    if len(results) > 1:
        primary["extra_metrics"] = results[1:]
    print(json.dumps(primary))


if __name__ == "__main__":
    main()
