"""Continuous-batching generative serving tests (ISSUE 8 tentpole).

Acceptance contracts, tested directly:
- paged decode matches single-stream ``generate()`` token-for-token;
- concurrent mixed-length streams are bit-identical to the same
  requests run one at a time (slot math is per-sequence);
- eviction (block-pool exhaustion) + re-admission is BIT-IDENTICAL to
  uninterrupted decode, for greedy AND seeded sampling (the RNG stream
  position survives eviction), with ``check_replay`` asserting every
  replayed token live;
- block-pool accounting is exact: no leaked blocks after N
  mixed-length streams, trash block never handed out;
- steady-state decode performs ZERO retraces (``num_compiles`` delta
  is 0 after warmup, for any mix of live slots);
- typed shed semantics: ``ServerOverloaded`` at the waiting cap,
  ``RequestTimeout`` for a request whose deadline passes while waiting;
- the scan_layers stacked decoder raises the typed
  ``KVCacheUnsupportedError`` naming the workaround.
"""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import (GenerationServer, RequestTimeout,
                                  ServerClosed, ServerOverloaded)
from paddle_tpu.text.models import (KimiLinearForCausalLM,
                                    Lfm2MoeForCausalLM, LlamaForCausalLM,
                                    kimi_linear_tiny, lfm2_moe_tiny,
                                    llama_tiny)
from paddle_tpu.text.models.llama import KVCacheUnsupportedError


@pytest.fixture(scope="module")
def lm():
    paddle.seed(0)
    cfg = llama_tiny(vocab_size=64, hidden_size=32, intermediate_size=64,
                     num_hidden_layers=2, num_attention_heads=4,
                     num_key_value_heads=2, max_position_embeddings=64)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture(scope="module")
def server(lm):
    """Ample pool: no eviction possible (4 slots x full-length fit)."""
    srv = GenerationServer(lm, num_slots=4, block_size=4,
                           max_model_len=32, check_replay=True,
                           request_timeout_s=120.0)
    srv.start()
    yield srv
    srv.stop()


def _prompts(seed=0, lens=(5, 9, 3, 12)):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 64, (l,)).astype("int32") for l in lens]


# -- correctness vs the single-stream reference ----------------------

def test_single_stream_matches_generate_greedy(lm, server):
    for p in _prompts():
        ref = lm.generate(paddle.to_tensor(p[None, :]),
                          max_new_tokens=6).numpy()[0, len(p):]
        got = server.submit(p, max_new_tokens=6).result(timeout=120)
        assert got == ref.tolist()


def test_concurrent_mixed_lengths_match_sequential(server):
    prompts = _prompts(seed=3)
    base = [server.submit(p, max_new_tokens=4 + i).result(timeout=120)
            for i, p in enumerate(prompts)]
    streams = [server.submit(p, max_new_tokens=4 + i)
               for i, p in enumerate(prompts)]
    conc = [s.result(timeout=120) for s in streams]
    assert conc == base
    assert [len(o) for o in conc] == [4, 5, 6, 7]


def test_eos_ends_stream_early(lm, server):
    p = _prompts(seed=4, lens=(6,))[0]
    first = server.submit(p, max_new_tokens=1).result(timeout=120)[0]
    out = server.submit(p, max_new_tokens=8,
                        eos_token_id=first).result(timeout=120)
    assert out == [first]          # eos emitted, stream ends, slot freed
    st = server.stats()
    assert st["active"] == 0


def test_stream_iterates_incrementally(server):
    p = _prompts(seed=5, lens=(4,))[0]
    stream = server.submit(p, max_new_tokens=5)
    seen = [tok for tok in stream]
    assert seen == stream.tokens
    assert len(seen) == 5
    assert stream.finish_reason == "length"


def test_temperature_zero_is_exact_greedy(server):
    p = _prompts(seed=6, lens=(5,))[0]
    greedy = server.submit(p, max_new_tokens=5).result(timeout=120)
    cold = server.submit(p, max_new_tokens=5, do_sample=True,
                         temperature=0.0, top_k=3,
                         seed=7).result(timeout=120)
    assert cold == greedy


def test_sampling_deterministic_per_seed(server):
    p = _prompts(seed=7, lens=(6,))[0]
    a = server.submit(p, max_new_tokens=6, do_sample=True,
                      temperature=0.8, top_k=8, seed=42).result(timeout=120)
    b = server.submit(p, max_new_tokens=6, do_sample=True,
                      temperature=0.8, top_k=8, seed=42).result(timeout=120)
    c = server.submit(p, max_new_tokens=6, do_sample=True,
                      temperature=0.8, top_k=8, seed=43).result(timeout=120)
    assert a == b
    assert a != c      # 6 draws over 8 candidates: collision ~8^-6


# -- zero-retrace + accounting contracts ------------------------------

def test_steady_state_decode_never_retraces(server):
    # warmup happened at start() + earlier tests; from here on, ANY mix
    # of prompt lengths within the prewarmed buckets and any number of
    # live slots must reuse the same executables
    n = server.num_compiles()
    streams = [server.submit(p, max_new_tokens=3 + i, do_sample=i % 2,
                             temperature=0.9, seed=i)
               for i, p in enumerate(_prompts(seed=8, lens=(4, 7, 11, 2)))]
    for s in streams:
        s.result(timeout=120)
    assert server.num_compiles() == n
    st = server.stats()
    assert st["traffic_compiles"] == 0
    assert all(v["cause"] == "prewarm"
               for v in st["bucket_compiles"].values())


def test_block_accounting_exact_after_mixed_streams(server):
    st0 = server.stats()
    streams = [server.submit(p, max_new_tokens=2 + 3 * i)
               for i, p in enumerate(_prompts(seed=9, lens=(3, 8, 13, 5)))]
    for s in streams:
        s.result(timeout=120)
    st = server.stats()
    assert st["free_blocks"] == st["total_blocks"]
    assert st["allocated_blocks"] == 0
    assert st["active"] == 0 and st["waiting"] == 0
    emitted = st["tokens_generated"] - st0["tokens_generated"]
    assert emitted == 2 + 5 + 8 + 11


# -- eviction + re-admission bit-identity -----------------------------

@pytest.fixture(scope="module")
def scarce(lm):
    """13 allocatable blocks for 4 sequences that can each grow to 6:
    concurrent traffic MUST evict."""
    srv = GenerationServer(lm, num_slots=4, block_size=4,
                           max_model_len=24, num_blocks=14,
                           check_replay=True, request_timeout_s=120.0)
    srv.start()
    yield srv
    srv.stop()


def _run_scarce(srv, do_sample, concurrent, prio=(0, 1, 2, 3)):
    prompts = _prompts(seed=1, lens=(6, 10, 4, 8))
    kw = dict(max_new_tokens=12, do_sample=do_sample, temperature=0.9,
              top_k=8)
    if concurrent:
        streams = [srv.submit(p, seed=100 + i, priority=prio[i], **kw)
                   for i, p in enumerate(prompts)]
        return [s.result(timeout=120) for s in streams]
    return [srv.submit(p, seed=100 + i, **kw).result(timeout=120)
            for i, p in enumerate(prompts)]


def test_eviction_readmission_bit_identical_greedy(scarce):
    base = _run_scarce(scarce, do_sample=False, concurrent=False)
    ev0 = scarce.stats()["evicted"]
    conc = _run_scarce(scarce, do_sample=False, concurrent=True)
    st = scarce.stats()
    assert st["evicted"] > ev0, \
        "pool was never exhausted — eviction untested"
    assert st["replay_steps"] > 0
    # check_replay=True additionally asserted every replayed token
    # inside the scheduler; this is the end-to-end stream equality
    assert conc == base


def test_eviction_readmission_bit_identical_sampling(scarce):
    """Seeded sampling across eviction: the RNG key of token j is
    fold_in(request_key, j-1) — a pure function of stream position —
    so the resumed stream must reproduce the uninterrupted draw
    exactly."""
    base = _run_scarce(scarce, do_sample=True, concurrent=False)
    ev0 = scarce.stats()["evicted"]
    conc = _run_scarce(scarce, do_sample=True, concurrent=True)
    st = scarce.stats()
    assert st["evicted"] > ev0
    assert conc == base


def test_no_leaked_blocks_after_evictions(scarce):
    st = scarce.stats()
    assert st["free_blocks"] == st["total_blocks"]
    assert st["allocated_blocks"] == 0
    assert st["readmitted"] >= st["evicted"] - st["shed_timeout"]


def test_eviction_emits_flight_events(scarce):
    from paddle_tpu.observability import flight_recorder as flight
    if scarce.stats()["evicted"] == 0:   # e.g. run in isolation
        _run_scarce(scarce, do_sample=False, concurrent=True)
    kinds = {e.get("kind") for e in flight.events()}
    assert "serve.admit" in kinds
    assert "serve.evict" in kinds
    assert "serve.stream_end" in kinds
    ev = [e for e in flight.events() if e.get("kind") == "serve.evict"]
    assert all(e.get("reason") == "pool_exhausted" for e in ev)


def test_postmortem_classifies_pool_exhaustion_bad():
    """tools/postmortem.py autopsies a pool-exhaustion shed: eviction
    and shed events sort the process to the front of the report
    (first divergence first), admit/stream_end render as context."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import postmortem
    assert postmortem._is_bad({"kind": "serve.evict"})
    assert postmortem._is_bad({"kind": "serve.shed"})
    assert not postmortem._is_bad({"kind": "serve.admit"})
    assert not postmortem._is_bad({"kind": "serve.stream_end"})
    assert not postmortem._is_bad({"kind": "serve.decode"})
    # the generation scheduler's heartbeats feed the stall watchdog
    from paddle_tpu.observability.flight_recorder import _PROGRESS_KINDS
    assert {"serve.decode", "serve.admit"} <= set(_PROGRESS_KINDS)


# -- typed shed semantics ---------------------------------------------

def test_overload_sheds_typed(lm):
    srv = GenerationServer(lm, num_slots=1, block_size=4,
                           max_model_len=64, prompt_buckets=[8],
                           max_waiting=2, request_timeout_s=60.0)
    # not started: submissions must fail closed, not queue silently
    with pytest.raises(ServerClosed):
        srv.submit(np.ones(4, np.int32), max_new_tokens=2)
    srv.start()
    try:
        p = _prompts(seed=11, lens=(4,))[0]
        # long enough to hold the slot through the three submits below,
        # however fast a decode step is
        first = srv.submit(p, max_new_tokens=56)
        next(iter(first))      # admitted: the only slot is now busy
        waiters = [srv.submit(p, max_new_tokens=8) for _ in range(2)]
        # waiting queue at its cap of 2 -> typed shed
        with pytest.raises(ServerOverloaded, match="back off"):
            srv.submit(p, max_new_tokens=8)
        assert srv.stats()["shed_overload"] >= 1
        for s in [first] + waiters:
            s.result(timeout=120)
    finally:
        srv.stop()


def test_waiting_deadline_times_out_typed(lm):
    srv = GenerationServer(lm, num_slots=1, block_size=4,
                           max_model_len=32, prompt_buckets=[8],
                           request_timeout_s=60.0)
    srv.start()
    try:
        p = _prompts(seed=12, lens=(4,))[0]
        long = srv.submit(p, max_new_tokens=24)      # hogs the only slot
        quick = srv.submit(p, max_new_tokens=4, timeout_s=0.0)
        with pytest.raises(RequestTimeout, match="deadline"):
            quick.result(timeout=120)
        assert long.result(timeout=120)              # victim unaffected
        assert srv.stats()["shed_timeout"] == 1
    finally:
        srv.stop()


def test_submit_validation(lm, server):
    with pytest.raises(ValueError, match="empty prompt"):
        server.submit(np.zeros((0,), np.int32))
    with pytest.raises(ValueError, match="max_model_len"):
        server.submit(np.ones(30, np.int32), max_new_tokens=30)


def test_scan_layers_raises_typed_error():
    paddle.seed(1)
    cfg = llama_tiny(vocab_size=32, hidden_size=32, intermediate_size=64,
                     num_hidden_layers=2, num_attention_heads=4,
                     num_key_value_heads=2, max_position_embeddings=32,
                     scan_layers=True)
    m = LlamaForCausalLM(cfg)
    m.eval()
    with pytest.raises(KVCacheUnsupportedError,
                       match="scan_layers=False"):
        GenerationServer(m, num_slots=1, block_size=4)
    # and the model-level cache entry points agree (typed subclass of
    # NotImplementedError, message pins the workaround)
    assert issubclass(KVCacheUnsupportedError, NotImplementedError)
    with pytest.raises(KVCacheUnsupportedError,
                       match="scan_layers=False"):
        m.init_paged_cache(4, 4)
    with pytest.raises(NotImplementedError, match="scan_layers=False"):
        m.model(paddle.to_tensor(np.ones((1, 2), np.int32)),
                caches=[None, None])


# -- ISSUE 18 satellites: typed stop/drain admission + deadline epoch --

def test_replay_drain_stop_admission_lifecycle_typed(lm):
    """ISSUE 18 satellites on ONE server (compiles dominate on this
    1-core box), in lifecycle order:

    1. replay_tokens — the gateway failover primitive at the server
       boundary: a submit carrying ``replay_tokens`` re-prefills,
       replays through the normal decode path WITHOUT re-emitting, and
       continues the stream token-identically (greedy AND seeded
       sampling); ``len(replay) >= max_new_tokens`` is a ValueError.
    2. drain_begin — live sequences run to completion, NEW admission
       raises typed ServerDraining and bumps the shed counter.
    3. stop — submit after stop() used to check ``_running`` OUTSIDE
       the scheduler lock, so a submit racing stop could enqueue a
       stream that never starts and hang the caller until its
       deadline.  The check now lives under the lock: stopped server
       => typed ServerClosed, immediately."""
    import time
    from paddle_tpu.inference import ServerDraining
    srv = GenerationServer(lm, num_slots=2, block_size=4,
                           max_model_len=32, max_prefill_batch=1,
                           check_replay=True, request_timeout_s=60.0)
    srv.start()
    p = _prompts(seed=14, lens=(6,))[0]
    for kw in (dict(max_new_tokens=12),
               dict(max_new_tokens=12, do_sample=True,
                    temperature=0.9, top_k=8)):
        full = srv.submit(p, seed=321, **kw).result(timeout=60)
        resumed = srv.submit(p, seed=321, replay_tokens=full[:5],
                             **kw).result(timeout=60)
        assert resumed == full[5:], "replay re-emitted or diverged"
    with pytest.raises(ValueError, match="replay"):
        srv.submit(p, max_new_tokens=4, replay_tokens=[1, 2, 3, 4])

    live = srv.submit(p, max_new_tokens=8)       # admitted pre-drain
    srv.drain_begin()
    assert srv.draining and srv.stats()["draining"]
    with pytest.raises(ServerDraining):
        srv.submit(p, max_new_tokens=4)
    assert srv.stats()["shed_draining"] == 1
    # live sequences run to completion; only NEW admission closes
    assert len(live.result(timeout=60)) == 8

    srv.stop()
    t0 = time.monotonic()
    with pytest.raises(ServerClosed):
        srv.submit(np.ones(4, np.int32), max_new_tokens=4)
    assert time.monotonic() - t0 < 5.0, \
        "submit-after-stop blocked instead of failing typed"


def test_submit_stop_race_no_hung_streams(lm):
    """Hammer the submit/stop race: every submit must either raise a
    typed error or return a stream that terminates."""
    import threading
    import time
    srv = GenerationServer(lm, num_slots=2, block_size=4,
                           max_model_len=32, max_prefill_batch=1,
                           request_timeout_s=60.0)
    srv.start()
    streams, errors = [], []

    def spam():
        p = np.ones(4, np.int32)
        for _ in range(200):
            try:
                streams.append(srv.submit(p, max_new_tokens=2))
            except ServerClosed:
                errors.append(1)

    t = threading.Thread(target=spam)
    t.start()
    time.sleep(0.05)
    srv.stop()
    t.join(timeout=30)
    assert not t.is_alive()
    for s in streams:       # accepted => must terminate, never hang
        try:
            s.result(timeout=30)
        except (ServerClosed, RequestTimeout):
            pass


def test_eviction_deadline_epoch_is_submit_time(lm):
    """ISSUE 18 satellite pin: time spent evicted-awaiting-readmission
    counts against the ORIGINAL deadline exactly once — re-admission
    must not re-anchor it.  Sampled live: every sequence observed
    mid-run (including ones that have been evicted) carries
    ``deadline == t_submit + timeout_s`` to within clock noise."""
    import time
    srv = GenerationServer(lm, num_slots=4, block_size=4,
                           max_model_len=24, num_blocks=14,
                           check_replay=True, request_timeout_s=120.0)
    srv.start()
    try:
        T = 77.0
        prompts = _prompts(seed=1, lens=(6, 10, 4, 8))
        streams = [srv.submit(p, seed=100 + i, max_new_tokens=12,
                              timeout_s=T)
                   for i, p in enumerate(prompts)]
        saw_evicted = False
        deadline = time.monotonic() + 60
        while any(s.finish_reason is None and s._exc is None
                  for s in streams):
            assert time.monotonic() < deadline
            with srv._lock:
                seqs = list(srv._active.values()) + list(srv._waiting)
            for seq in seqs:
                saw_evicted = saw_evicted or seq.evictions > 0
                assert abs(seq.deadline - (seq.t_submit + T)) < 0.25, \
                    "deadline drifted from the submit epoch"
            # coarse sampling: a tighter loop steals the 1-core GIL
            # from the scheduler and doubles the test's wall time
            time.sleep(0.002)
        assert srv.stats()["evicted"] > 0, \
            "pool was never exhausted — eviction untested"
        assert saw_evicted, "never sampled an evicted-and-waiting seq"
        for s in streams:
            s.result(timeout=60)
    finally:
        srv.stop()


# -- ISSUE 32: the decode loop is a pipeline of depth one --------------
# Step n+1 is dispatched before step n is read; each row's last token
# stays on the device.  Since ISSUE 34 a prefill call's first tokens stay
# there too: the step behind the call is dispatched before the call is
# read.  The reference below reads every call and every step at once, as
# the loop did before: the streams have to be the same token for token
# whatever ends, joins, is evicted or cancelled meanwhile.

class _ReadAtOnce(GenerationServer):
    """The synchronous reference: every prefill call and every decode
    step is read before anything else happens."""

    def _prefill_batch(self, seqs, bucket):
        super()._prefill_batch(seqs, bucket)
        self._read_unread()

    def _decode_once(self):
        super()._decode_once()
        self._read_unread()


def _wait_until(cond, timeout=60.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, "waited too long"
        time.sleep(0.0005)


def _gated(cls, at_prefill=None):
    """``cls`` with a scheduler that admits nothing until ``gate`` is
    set: what was submitted before is admitted together, and from there
    on the schedule is the scheduler's own, the same in every run.
    ``at_prefill(server, n, seqs)`` runs on the scheduler thread right
    behind the dispatch of prefill call ``n``: in the pipelined server
    the call is unread then."""
    class Gated(cls):
        gate = threading.Event()
        calls = 0

        def _admit(self):
            self.gate.wait(60)
            return super()._admit()

        def _prefill_batch(self, seqs, bucket):
            super()._prefill_batch(seqs, bucket)
            self.calls += 1
            if at_prefill is not None:
                at_prefill(self, self.calls, seqs)
    return Gated


class _TalliedLM(LlamaForCausalLM):
    """What the server holds for a model with per-slot state and step
    counters, at toy size: beside the K/V pools each slot's tally of
    the token ids it was fed since its prefill began, which picks the
    next token (tally mod vocabulary) — a step fed twice or not at
    all, a token fed at the wrong row, or a slot that kept its last
    owner's tally changes the stream — and two counters a decode step
    returns behind its tokens: 1, and its live rows."""

    def has_recurrent_state(self):
        return True

    def step_counters(self):
        return ("toy_steps", "toy_rows")

    def init_paged_cache(self, num_blocks, block_size, num_slots):
        import jax.numpy as jnp
        return super().init_paged_cache(num_blocks, block_size) + [
            {"state": jnp.zeros((num_slots,), jnp.int32)}]

    def forward_paged(self, input_ids, positions, pools, block_tables,
                      write_mask, gather_at=None, verify_mode=False,
                      slots=None):
        import jax
        import jax.numpy as jnp
        *kv, st = pools
        logits, kv = super().forward_paged(
            input_ids, positions, kv, block_tables, write_mask,
            gather_at=gather_at, verify_mode=verify_mode)
        ids, pos = input_ids._value, positions._value
        rows = jnp.arange(ids.shape[0]) if slots is None else slots
        live = write_mask.any(-1)
        # a prefill that starts a sequence starts from zero
        old = jnp.where(pos[:, 0] == 0, 0, st["state"].at[rows].get(
            mode="fill", fill_value=0))
        tally = old + jnp.where(write_mask, ids, 0).sum(-1)
        n = st["state"].shape[0]
        state = st["state"].at[jnp.where(live, rows, n)].set(
            tally, mode="drop")
        lv = logits._value
        V = lv.shape[-1]
        lv = lv + 100.0 * jax.nn.one_hot(1 + tally % (V - 1), V,
                                         dtype=lv.dtype)[:, None, :]
        counts = jnp.stack([jnp.int32(1), live.sum().astype(jnp.int32)])
        return lv, kv + [{"state": state}], counts


@pytest.fixture(scope="module")
def tallied(lm):
    paddle.seed(0)
    m = _TalliedLM(lm.config)
    m.eval()
    return m


def _first_seen_at(stream, lo, hi, skip=()):
    """An index in [lo, hi) whose token the stream had not shown
    before, or None."""
    for k in range(lo, min(hi, len(stream))):
        if stream[k] not in stream[:k] and k not in skip:
            return k
    return None


def _case_eos_reuse(cls, lm, tallied):
    """More requests than slots, a pool that holds two sequences and no
    more, and an ``eos`` that ends rows while the next step, in which
    they ride, is in flight: slot and blocks go to a waiting request at
    once, behind that step."""
    model = tallied       # its tokens vary, so an ``eos`` is first seen
    prompts = _prompts(seed=21, lens=(6, 5, 7, 6, 5, 7))
    kw = dict(num_slots=2, block_size=4, max_model_len=24, num_blocks=13,
              max_prefill_batch=1, check_replay=True,
              request_timeout_s=120.0)
    with _ReadAtOnce(model, **kw) as probe:
        free = [probe.submit(p, max_new_tokens=12).result(timeout=120)
                for p in prompts]
    ends = [_first_seen_at(s, 2, 9) for s in free]
    assert sum(k is not None for k in ends) >= 4
    with cls(model, **kw) as srv:
        streams = [srv.submit(p, max_new_tokens=12,
                              eos_token_id=None if k is None else s[k])
                   for p, s, k in zip(prompts, free, ends)]
        outs = [s.result(timeout=120) for s in streams]
    st = srv.stats()   # after stop(): the last step is read
    for out, s, k, stream in zip(outs, free, ends, streams):
        assert out == (s if k is None else s[:k + 1])
        assert stream.finish_reason == ("length" if k is None else "eos")
    assert st["free_blocks"] == st["total_blocks"] and st["evicted"] == 0
    assert st["toy_steps"] == st["decode_steps"]
    return outs, st


def _case_evict_replay(cls, lm, tallied):
    """13 allocatable blocks for 4 sequences that each grow to 6: a row
    is evicted while its token of the step in flight is unread, then
    re-admitted and replayed under ``check_replay``."""
    with cls(lm, num_slots=4, block_size=4, max_model_len=24,
             num_blocks=14, check_replay=True,
             request_timeout_s=120.0) as srv:
        outs = _run_scarce(srv, do_sample=True, concurrent=True)
    st = srv.stats()   # after stop(): the last step is read
    assert st["evicted"] > 0 and st["replay_steps"] > 0
    assert st["free_blocks"] == st["total_blocks"]
    return outs, st


def _case_sampled_beside_greedy(cls, lm, tallied):
    prompts = _prompts(seed=23, lens=(5, 9, 3, 12, 7, 4))
    with cls(lm, num_slots=4, block_size=4, max_model_len=32,
             check_replay=True, request_timeout_s=120.0) as srv:
        streams = [srv.submit(p, max_new_tokens=6 + 2 * i, seed=50 + i,
                              **kw)
                   for i, (p, kw) in enumerate(zip(prompts, [
                       {}, dict(do_sample=True, temperature=0.8),
                       dict(do_sample=True, temperature=1.3, top_k=8),
                       {}, dict(do_sample=True, top_p=0.7),
                       dict(do_sample=True, temperature=0.9, top_k=12,
                            top_p=0.8)]))]
        outs = [s.result(timeout=120) for s in streams]
    st = srv.stats()   # after stop(): the last step is read
    assert [len(o) for o in outs] == [6, 8, 10, 12, 14, 16]
    assert 0 < st["sampled_steps"] <= st["decode_steps"] \
        + st["prefill_batches"]
    return outs, st


def _case_state_and_counters(cls, lm, tallied):
    """Per-slot state and step counters: the tally picks every token,
    slots are reused, and the counters add up to one a step."""
    prompts = _prompts(seed=24, lens=(5, 9, 3, 12, 7, 4, 6))
    with cls(tallied, num_slots=3, block_size=4, max_model_len=32,
             check_replay=True, request_timeout_s=120.0) as srv:
        streams = [srv.submit(p, max_new_tokens=4 + 2 * i)
                   for i, p in enumerate(prompts)]
        outs = [s.result(timeout=120) for s in streams]
    st = srv.stats()   # after stop(): the last step is read
    for p, out in zip(prompts, outs):        # the tally, by hand
        fed = [int(p.sum())]
        for t in out[:-1]:
            fed.append(fed[-1] + t)
        assert out == [1 + f % 63 for f in fed]
    assert st["state_slots"] == 3 and st["state_resets"] == 7
    assert st["toy_steps"] == st["decode_steps"] > 0
    # every token but a request's first came from a decode step's row
    assert st["toy_rows"] == sum(len(o) - 1 for o in outs)
    return outs, st


def _case_prefix_cache(cls, lm, tallied):
    """Prefix sharing on: a second turn's prompt is a first turn's
    prompt and answer, so it aliases blocks indexed when that turn
    ended by ``eos`` with a step in flight.  (Not an answer whose last
    token completes a block: no step of the synchronous loop feeds a
    sequence's last token, yet ``_finish`` indexes the block, and the
    next turn attends to K/V that was never written — ROADMAP D22.
    The step in flight does feed it, so there the pipeline agrees
    with a cold server and the reference does not.)"""
    first = _prompts(seed=25, lens=(7, 6, 9))
    kw = dict(num_slots=2, block_size=4, max_model_len=48,
              prefix_cache=True, check_replay=True,
              request_timeout_s=120.0)
    with _ReadAtOnce(lm, **kw) as probe:
        free = [probe.submit(p, max_new_tokens=10).result(timeout=120)
                for p in first]
    ends = [_first_seen_at(s, 1, 9, skip=[k for k in range(9)
                                          if (len(p) + k + 1) % 4 == 0])
            for p, s in zip(first, free)]
    assert any(k is not None for k in ends)
    with cls(lm, **kw) as srv:
        turn1 = [srv.submit(p, max_new_tokens=10,
                            eos_token_id=None if k is None else s[k])
                 for p, s, k in zip(first, free, ends)]
        outs = [s.result(timeout=120) for s in turn1]
        turn2 = [srv.submit(np.concatenate(
            [p, np.asarray(o, np.int32), np.asarray([5, 9], np.int32)]),
            max_new_tokens=6) for p, o in zip(first, outs)]
        outs += [s.result(timeout=120) for s in turn2]
    st = srv.stats()   # after stop(): the last step is read
    assert st["prefix_hits"] >= 3 and st["prefix_hit_tokens"] >= 3 * 8
    return outs, st


def _case_cancel(cls, lm, tallied):
    """``cancel()`` with a step in flight: the cancelled stream keeps a
    prefix of what it would have been, its slot goes to the next
    request, the others never notice."""
    prompts = _prompts(seed=26, lens=(5, 8, 6, 7))
    with cls(lm, num_slots=3, block_size=4, max_model_len=40,
             check_replay=True, request_timeout_s=120.0) as srv:
        streams = [srv.submit(p, max_new_tokens=30) for p in prompts[:3]]
        it = iter(streams[0])
        head = [next(it) for _ in range(3)]
        assert srv.cancel(streams[0].request_id)
        late = srv.submit(prompts[3], max_new_tokens=8)
        outs = [s.result(timeout=120) for s in streams[1:] + [late]]
    st = srv.stats()   # after stop(): the last step is read
    got = streams[0].tokens
    assert streams[0].finish_reason == "cancelled" and got[:3] == head
    assert st["cancelled"] == 1 and st["finished"] == 3
    assert st["free_blocks"] == st["total_blocks"]
    return [got[:3]] + outs, st, got


def _case_drain(cls, lm, tallied):
    """``stop(drain=True)`` with steps in flight and requests waiting:
    every stream runs to its end."""
    prompts = _prompts(seed=27, lens=(5, 8, 6, 7, 4))
    srv = cls(lm, num_slots=2, block_size=4, max_model_len=32,
              check_replay=True, request_timeout_s=120.0).start()
    try:
        streams = [srv.submit(p, max_new_tokens=9 + i)
                   for i, p in enumerate(prompts)]
        next(iter(streams[0]))
        srv.stop(drain=True, timeout=120)
        outs = [s.result(timeout=5) for s in streams]
    finally:
        srv.stop()
    assert [s.finish_reason for s in streams] == ["length"] * 5
    return outs, srv.stats()


def _case_first_token_is_eos(cls, lm, tallied):
    """A first token that is its request's ``eos`` cannot be counted:
    the row rides the step dispatched behind its prefill call and that
    step's token for it is dropped; slot and blocks go to a waiting
    request behind that step."""
    prompts = _prompts(seed=31, lens=(6, 5, 7, 9, 5, 8))
    kw = dict(num_slots=2, block_size=4, max_model_len=24,
              max_prefill_batch=1, check_replay=True,
              request_timeout_s=120.0)
    with _ReadAtOnce(tallied, **kw) as probe:
        free = [probe.submit(p, max_new_tokens=8).result(timeout=120)
                for p in prompts]
    ends = {1, 2, 4}           # these end on their first token
    with cls(tallied, **kw) as srv:
        streams = [srv.submit(p, max_new_tokens=8,
                              eos_token_id=s[0] if i in ends else None)
                   for i, (p, s) in enumerate(zip(prompts, free))]
        outs = [s.result(timeout=120) for s in streams]
    st = srv.stats()   # after stop(): the last step is read
    for i, (out, s, stream) in enumerate(zip(outs, free, streams)):
        assert out == (s[:1] if i in ends else s)
        assert stream.finish_reason == ("eos" if i in ends else "length")
    assert st["free_blocks"] == st["total_blocks"]
    assert st["toy_steps"] == st["decode_steps"]
    # a row whose first token ended it rode one step all the same
    extra = st["toy_rows"] - sum(len(o) - 1 for o in outs)
    assert extra == (len(ends) if cls is GenerationServer else 0)
    return outs, st


def _case_one_token_requests(cls, lm, tallied):
    """``max_new_tokens == 1`` is known by counting: the row rides no
    step, and a call of such rows alone is read with no step behind
    it."""
    prompts = _prompts(seed=32, lens=(6, 5, 7, 9, 5, 8, 4))
    new = (1, 6, 1, 1, 5, 1, 7)
    with cls(tallied, num_slots=3, block_size=4, max_model_len=24,
             max_prefill_batch=2, check_replay=True,
             request_timeout_s=120.0) as srv:
        streams = [srv.submit(p, max_new_tokens=n)
                   for p, n in zip(prompts, new)]
        outs = [s.result(timeout=120) for s in streams]
        lone = srv.submit(prompts[0], max_new_tokens=1).result(120)
    st = srv.stats()   # after stop(): the last step is read
    assert [len(o) for o in outs] == list(new) and lone == outs[0]
    assert st["toy_steps"] == st["decode_steps"]
    # every token but a request's first came from a decode step's row
    assert st["toy_rows"] == sum(len(o) - 1 for o in outs)
    assert st["free_blocks"] == st["total_blocks"]
    return outs + [lone], st


def _case_evict_behind_prefill(cls, lm, tallied):
    """The pool runs dry in the iteration that dispatched a prefill
    call: the call is read before anything is evicted, its row can be
    the victim, and a re-admitted row replays tokens the host holds
    beside rows that take theirs on the device."""
    prompts = _prompts(seed=33, lens=(6, 7, 5, 6, 7, 5))
    Gated = _gated(cls)
    with Gated(lm, num_slots=3, block_size=4, max_model_len=24,
               num_blocks=9, check_replay=True,
               request_timeout_s=120.0) as srv:
        streams = [srv.submit(p, max_new_tokens=10 + i, seed=70 + i,
                              priority=i % 3, do_sample=True,
                              temperature=0.9, top_k=8)
                   for i, p in enumerate(prompts)]
        Gated.gate.set()
        outs = [s.result(timeout=120) for s in streams]
    st = srv.stats()   # after stop(): the last step is read
    assert st["evicted"] > 0 and st["replay_steps"] > 0
    assert st["readmitted"] > 0
    assert st["free_blocks"] == st["total_blocks"]
    if cls is GenerationServer:
        # every request asks for more than one token, so a call read
        # with no step behind it was read because the pool was dry
        assert 0 < st["prefills_overlapped"] < st["prefill_batches"]
    return outs, st


def _case_replay_submit(cls, lm, tallied):
    """A request submitted with tokens its stream already emitted
    elsewhere (failover) joins mid-flight: its row stages the tokens
    the host holds and the feed's value for it goes unused."""
    prompts = _prompts(seed=34, lens=(6, 9, 5, 7))
    kw = dict(do_sample=True, temperature=0.8, top_k=12)
    with cls(tallied, num_slots=3, block_size=4, max_model_len=32,
             check_replay=True, request_timeout_s=120.0) as srv:
        whole = srv.submit(prompts[0], max_new_tokens=12, seed=5,
                           **kw).result(timeout=120)
        streams = [srv.submit(p, max_new_tokens=14, seed=80 + i, **kw)
                   for i, p in enumerate(prompts[1:])]
        it = iter(streams[0])
        [next(it) for _ in range(2)]
        moved = srv.submit(prompts[0], max_new_tokens=12, seed=5,
                           replay_tokens=whole[:5], **kw)
        outs = [s.result(timeout=120) for s in streams]
        rest = moved.result(timeout=120)
    st = srv.stats()   # after stop(): the last step is read
    assert rest == whole[5:] and st["replay_steps"] >= 4
    assert st["toy_steps"] == st["decode_steps"]
    return [whole, rest] + outs, st


def _case_shared_prefix_joins(cls, lm, tallied):
    """Prefix sharing on, more requests than slots, one system prompt:
    a prompt is indexed when its call is read, behind the step
    dispatched after it and before the next admission, so later
    requests alias it and prefill their suffix (with a copy-on-write
    fork where the shared tail block is written) while others decode.
    (``max_new`` and the lengths keep every answer's last token off a
    block's last position: ROADMAP D22.)"""
    rng = np.random.RandomState(35)
    system = rng.randint(1, 64, (10,)).astype("int32")
    prompts = [np.concatenate([system, rng.randint(1, 64, (n,))
                               .astype("int32")]) for n in (3, 5, 2, 4, 3)]
    new = (5, 4, 7, 4, 6)
    assert all((len(p) + n) % 4 for p, n in zip(prompts, new))
    with cls(lm, num_slots=2, block_size=4, max_model_len=48,
             prefix_cache=True, max_prefill_batch=1, check_replay=True,
             request_timeout_s=120.0) as srv:
        streams = [srv.submit(p, max_new_tokens=n)
                   for p, n in zip(prompts, new)]
        outs = [s.result(timeout=120) for s in streams]
    st = srv.stats()   # after stop(): the last step is read
    assert st["prefix_hits"] >= 3 and st["prefix_hit_tokens"] >= 3 * 8
    assert st["cow_forks"] >= 1
    return outs, st


def _case_cancel_behind_prefill(cls, lm, tallied):
    """``cancel()`` is queued while a prefill call is unread: the
    iteration goes on (the step behind the call, then the reads), the
    next one reads the step in flight and only then runs the command.
    The cancelled request is the one that call prefilled."""
    prompts = _prompts(seed=36, lens=(5, 8, 6, 7))
    box = {}

    def at_prefill(srv, n, seqs):
        if n == 2:            # the late request's call
            rid = seqs[0].rid
            t = threading.Thread(
                target=lambda: box.update(ok=srv.cancel(rid)))
            t.start()
            box["thread"] = t
            _wait_until(lambda: not srv._cmds.empty())
            box["unread"] = len(srv._prefills)

    Gated = _gated(cls, at_prefill)
    with Gated(lm, num_slots=3, block_size=4, max_model_len=40,
               check_replay=True, request_timeout_s=120.0) as srv:
        streams = [srv.submit(p, max_new_tokens=30) for p in prompts[:2]]
        Gated.gate.set()
        it = iter(streams[0])
        [next(it) for _ in range(3)]
        late = srv.submit(prompts[2], max_new_tokens=12)
        got = late.result(timeout=120)
        box["thread"].join(60)
        after = srv.submit(prompts[3], max_new_tokens=8)
        outs = [s.result(timeout=120) for s in streams + [after]]
    st = srv.stats()   # after stop(): the last step is read
    assert box["ok"] and late.finish_reason == "cancelled"
    assert box["unread"] == (1 if cls is GenerationServer else 0)
    # its first token, and the token of the step behind its call
    assert len(got) == 2
    assert st["cancelled"] == 1 and st["finished"] == 3
    assert st["free_blocks"] == st["total_blocks"]
    return [got] + outs, st


def _case_stop_behind_prefill(cls, lm, tallied, drain=True):
    """``stop()`` arrives while a prefill call is unread.  With
    ``drain`` every stream runs to its end; without, what was
    dispatched is delivered (the call's first tokens and the step
    behind it) and the streams then fail typed."""
    prompts = _prompts(seed=37, lens=(5, 8, 6, 7))
    box = {}

    def at_prefill(srv, n, seqs):
        if n == 2:
            box["went"] = threading.Event()

            def stop():
                box["went"].set()
                srv.stop(drain=drain, timeout=120)
            t = threading.Thread(target=stop)
            t.start()
            box["thread"] = t
            box["went"].wait(60)
            _wait_until(lambda: drain or not srv._running)
            box["unread"] = len(srv._prefills)

    Gated = _gated(cls, at_prefill)
    srv = Gated(lm, num_slots=2, block_size=4, max_model_len=32,
                check_replay=True, request_timeout_s=120.0).start()
    try:
        streams = [srv.submit(p, max_new_tokens=9 + i)
                   for i, p in enumerate(prompts)]
        Gated.gate.set()
        outs = []
        for s in streams:
            try:
                outs.append(s.result(timeout=120))
            except ServerClosed:
                outs.append(list(s.tokens))
        box["thread"].join(120)
    finally:
        srv.stop()
    st = srv.stats()
    assert box["unread"] == (1 if cls is GenerationServer else 0)
    if drain:
        assert [s.finish_reason for s in streams] == ["length"] * 4
    else:
        # the call's request: its first token and the token of the
        # step behind the call; the one still waiting: nothing
        assert [len(o) for o in outs[2:]] == [2, 0]
        assert streams[2].tokens == outs[2]
        assert st["free_blocks"] == st["total_blocks"]
    return outs, st


def _case_stop_at_once_behind_prefill(cls, lm, tallied):
    return _case_stop_behind_prefill(cls, lm, tallied, drain=False)


@pytest.mark.parametrize("case", [
    _case_eos_reuse, _case_evict_replay, _case_sampled_beside_greedy,
    _case_state_and_counters, _case_prefix_cache, _case_cancel,
    _case_drain, _case_first_token_is_eos, _case_one_token_requests,
    _case_evict_behind_prefill, _case_replay_submit,
    _case_shared_prefix_joins, _case_cancel_behind_prefill,
    _case_stop_behind_prefill, _case_stop_at_once_behind_prefill],
    ids=lambda f: f.__name__[6:])
def test_pipelined_streams_equal_the_synchronous_reference(
        case, lm, tallied):
    want, ref, *cut_ref = case(_ReadAtOnce, lm, tallied)
    got, st, *cut = case(GenerationServer, lm, tallied)
    assert got == want
    assert ref["decode_steps_overlapped"] == 0 < ref["decode_steps"]
    assert 0 < st["decode_steps_overlapped"] < st["decode_steps"]
    assert ref["prefills_overlapped"] == 0 < ref["prefill_batches"]
    assert 0 < st["prefills_overlapped"] <= st["prefill_batches"]
    assert st["traffic_compiles"] == ref["traffic_compiles"] == 0
    if cut:       # a cancelled stream: as far as both got, the same
        n = min(len(cut[0]), len(cut_ref[0]))
        assert n >= 3 and cut[0][:n] == cut_ref[0][:n]
    else:
        assert st["tokens_generated"] == ref["tokens_generated"]


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
def test_overlap_counter_says_whether_the_pipeline_engaged(lm, spec):
    """A decode-only run overlaps every step but the first; a server
    in speculative mode reads every verify step at once.  Neither
    compiles a program the loop before it did not."""
    kw = dict(draft_model=lm, spec_k=3) if spec else {}
    with GenerationServer(lm, num_slots=4, block_size=4, max_model_len=48,
                          request_timeout_s=120.0, **kw) as srv:
        n = srv.num_compiles()
        streams = [srv.submit(p, max_new_tokens=30)
                   for p in _prompts(seed=28, lens=(5, 9, 3, 12))]
        assert all(len(s.result(timeout=120)) == 30 for s in streams)
    st = srv.stats()   # after stop(): the last step is read
    assert srv.num_compiles() == n and st["traffic_compiles"] == 0
    if spec:
        assert st["decode_steps_overlapped"] == 0 < st["decode_steps"]
    else:
        # 29 steps a request; one that was admitted alone starts early
        assert 29 <= st["decode_steps"] <= 29 + 3
        assert st["decode_steps_overlapped"] >= 0.9 * st["decode_steps"]


# -- ISSUE 34: a prefill call joins the pipeline ------------------------

def _tiny(name, lm):
    if name == "llama":
        return lm
    paddle.seed(0)
    if name == "kimi":
        m = KimiLinearForCausalLM(kimi_linear_tiny())
    else:
        m = Lfm2MoeForCausalLM(lfm2_moe_tiny())
        for _, p in m.named_parameters():     # or every greedy stream
            if p._value.ndim >= 2:            # repeats one token
                p._value = p._value * 3.0
    m.eval()
    return m


@pytest.fixture(scope="module", params=["llama", "kimi", "lfm2"])
def joined(request, lm):
    """More requests than slots on a K/V model and on the two models
    with per-slot state, so that requests join while others decode:
    greedy and sampled traffic through the synchronous reference and
    through the pipelined server."""
    model = _tiny(request.param, lm)
    rng = np.random.RandomState(41)
    V = model.config.vocab_size
    prompts = [rng.randint(1, V, (n,)).astype("int32")
               for n in (5, 9, 14, 7, 20, 11, 6, 13)]
    sampled = dict(do_sample=True, temperature=0.8, top_k=20)
    got = {}
    for cls in (_ReadAtOnce, GenerationServer):
        with cls(model, num_slots=3, block_size=4, max_model_len=64,
                 prompt_buckets=[16, 32], max_prefill_batch=2,
                 check_replay=True, request_timeout_s=300.0) as srv:
            for mode, kw in (("greedy", {}), ("sampled", sampled)):
                before = srv.stats()
                streams = [srv.submit(p, max_new_tokens=4 + 2 * i,
                                      seed=40 + i, **kw)
                           for i, p in enumerate(prompts)]
                outs = [s.result(timeout=300) for s in streams]
                # the step in flight is read once the loop idles
                _wait_until(lambda: not srv.stats()["active"]
                            and srv._inflight is None)
                after = srv.stats()
                got[cls, mode] = outs, {
                    k: after[k] - before[k] for k in (
                        "prefill_batches", "prefills_overlapped",
                        "traffic_compiles", "state_resets")}
    return got


@pytest.mark.parametrize("mode", ["greedy", "sampled"])
def test_rows_that_join_mid_flight_stream_what_the_reference_streams(
        joined, mode):
    want, ref = joined[_ReadAtOnce, mode]
    outs, st = joined[GenerationServer, mode]
    assert [len(o) for o in outs] == [4 + 2 * i for i in range(8)]
    assert outs == want
    assert len({tuple(o[:4]) for o in outs}) == 8    # not one stream
    # every call had a decode step dispatched behind it before it was
    # read; the reference read every call at once
    assert st["prefills_overlapped"] == st["prefill_batches"] >= 4
    assert ref["prefills_overlapped"] == 0 < ref["prefill_batches"]
    assert st["traffic_compiles"] == ref["traffic_compiles"] == 0
    assert st["state_resets"] == ref["state_resets"]


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
def test_prefill_counter_says_whether_the_calls_joined_the_pipeline(
        lm, spec):
    """Under steady joins every prefill call of a plain server is read
    behind the decode step dispatched after it; a server in
    speculative mode drafts from the first token on the host and reads
    every call at once.  Both stream the same tokens."""
    kw = dict(draft_model=lm, spec_k=3) if spec else {}
    with GenerationServer(lm, num_slots=2, block_size=4, max_model_len=48,
                          max_prefill_batch=1, check_replay=True,
                          request_timeout_s=120.0, **kw) as srv:
        n = srv.num_compiles()
        streams = [srv.submit(p, max_new_tokens=6 + i)
                   for i, p in enumerate(_prompts(
                       seed=42, lens=(5, 9, 3, 12, 7, 4, 10)))]
        outs = [s.result(timeout=120) for s in streams]
    st = srv.stats()   # after stop(): the last step is read
    assert srv.num_compiles() == n and st["traffic_compiles"] == 0
    assert st["prefill_batches"] == 7
    assert st["prefills_overlapped"] == (0 if spec else 7)
    progs = {k.split(":")[0] for k in st["bucket_compiles"]}
    assert ("feed" in progs) is not spec
    with _ReadAtOnce(lm, num_slots=2, block_size=4, max_model_len=48,
                     max_prefill_batch=1,
                     request_timeout_s=120.0) as ref:
        assert outs == [ref.submit(p, max_new_tokens=6 + i).result(120)
                        for i, p in enumerate(_prompts(
                            seed=42, lens=(5, 9, 3, 12, 7, 4, 10)))]
