"""paddle_tpu.inference.generation_server — the inference gateway:
continuous-batching LLM serving with copy-on-write prefix sharing,
batched prefill, and speculative decoding (ISSUE 8 engine, grown by
ISSUE 11; ROADMAP item 4).

``PredictorServer`` micro-batches FIXED-shape requests; generative
decoding is the other regime: every sequence advances one token per
model call, sequences finish at different times, and a dedicated
``[B, Smax]`` KV buffer per conversation would cap concurrency at
HBM / (Smax * layers * heads).  This module is the Orca-style
iteration-level scheduler + vLLM-style paged KV cache built on the
same AOT discipline as the rest of ``inference/``:

- **block-paged KV cache** — K/V live in per-layer pools
  ``[num_blocks, block_size, KH, D]`` shared by every sequence; a
  sequence owns a list of physical blocks and its cache reads are a
  gather over its block table (``LlamaAttention.forward_paged``).
  Physical block 0 is the TRASH block: never allocated, the target of
  masked writes (prompt padding, idle decode slots), never read (the
  slot <= position mask).  Thousands of conversations share one HBM
  budget and freeing is O(blocks), not O(bytes).
- **copy-on-write prefix sharing** (``prefix_cache=True``) — a
  content-hash chain index over the pools (``prefix_cache.py``) maps
  full blocks of token prefix to physical blocks; a new prompt's
  cached prefix blocks are ALIASED (refcounted) instead of re-
  prefilled, so a shared system prompt is ONE set of physical blocks
  across every conversation and prefill only processes the uncached
  suffix.  A write into a shared block (refcount > 1, which includes
  the index's own reference) forks it first: allocate, device-copy,
  remap the block table — the trash-block and slot<=position
  invariants are untouched because tables only ever remap.  Prefill
  on a prefix-sharing server runs the CHUNKED program (the cache-
  gather attention path) for cold prompts too, so cold and warm runs
  of the same stream are bit-identical (the flash prefill path is a
  different floating-point formulation — measured ~1e-4 apart on this
  container — so it stays reserved for prefix_cache=False servers).
- **batched prefill** — prefill compiles one program per (power-of-2
  prompt bucket, power-of-2 batch <= ``max_prefill_batch``) shape, so
  a burst of short prompts costs ONE dispatch instead of B; padding
  rows write only to the trash block.  Verified bit-equal to B=1
  prefill row-for-row (same program family, row-independent math).
- **speculative decoding** (``draft_model=``) — a draft model rides
  the same block tables with its own (smaller) pools; each iteration
  it proposes up to ``spec_k`` tokens autoregressively, and the
  target model scores all of them in ONE verify forward (an S=k+1
  block through the cache-gather attention — bit-identical per
  position to S=1 decode, measured).  Deterministic positional-
  stream acceptance keeps the output BIT-IDENTICAL to plain decode:
  the verify program samples the target's own token at every
  position with the same ``fold_in(request_key, position)`` stream
  plain decode uses, a proposal is accepted iff it EQUALS that
  token, and the first mismatch simply emits the target's token (the
  classical stochastic accept/resample of Leviathan et al. trades
  that bit-identity for a higher accept rate; this repo's replay and
  eviction contracts are built on bit-identity, so determinism
  wins).  Rejected tokens' pool writes are invisible by
  construction: slot index == position, the next write at that
  position lands first, and slot <= position masks the rest.
- **iteration-level scheduling** — admission/eviction decisions happen
  every decode step, not per request: finished sequences free their
  blocks immediately and waiting requests are admitted mid-flight.
  DECODE is ONE fixed-shape program over all ``num_slots`` batch slots
  regardless of how many are live — steady state never retraces
  (``num_compiles()`` is the proof, same contract as ``Predictor``).
- **typed shed semantics** shared with ``PredictorServer``
  (:class:`ServerOverloaded` at the waiting-queue cap,
  :class:`RequestTimeout` for requests whose deadline passes while
  waiting) extended with **block-pool-exhaustion eviction**: when a
  running sequence needs a block and the pool is dry, the
  lowest-priority sequence is evicted (blocks freed, back to the
  waiting queue) and later re-admitted.
- **bit-identical re-admission** — re-admission re-runs the ORIGINAL
  prompt through the same prefill family (same bucket, same inputs =>
  identical K/V and logits; with prefix sharing the cached prefix is
  aliased back and only the suffix recomputes), then replays the
  already-emitted tokens through the normal decode/verify program with
  the sampled token overridden by the stored one.  The RNG key for
  token j is ``fold_in(request_key, j-1)``, a pure function of the
  stream position, so the RNG stream position survives eviction by
  construction.
- **streaming responses** — :meth:`GenerationServer.submit` returns a
  :class:`GenerationStream` immediately; tokens arrive on it as each
  decode step completes (iterate it, or ``result()`` to block for the
  full continuation).

Observability rides the existing seams: serve histograms
(``decode_step_ms`` / ``prefill_ms`` / ``serve_ttft_ms``), counters
and gauges in the StatRegistry (always-on ``serve_prefix_hits`` /
``serve_cow_forks`` / ``serve_spec_proposed`` / ``serve_spec_accepted``
counters; ``serve_prefix_hit_rate`` / ``serve_spec_accept_rate``
gauges on the /metrics endpoint), and flight-recorder events
(``serve.admit`` / ``serve.evict`` / ``serve.stream_end`` /
``serve.prefix_hit`` / ``serve.cow_fork`` + sampled ``serve.decode``
and ``serve.spec_verify``) so ``tools/postmortem.py`` can autopsy a
pool-exhaustion shed.

Plain decode is a pipeline of depth one (ISSUE 32): an iteration
dispatches decode step n+1 and only then reads step n, which the
device ran meanwhile.  Each row's last token stays on the device (the
decode program takes the vector the step before returned, and a token
from the host only for a row that joined since or replays); what else
a step needs the host knows one step early, a request's end by length
included.  An ``eos`` cannot be counted: its row rides one step more
and that step's token for it is dropped.  What reads or rewrites
sequence state as if no step were in flight (a queued command, an
eviction, ``stop()``) reads the step in flight first; speculative mode
reads every iteration at once.  ``stats()["decode_steps_overlapped"]``
counts the steps dispatched over an unread one.

A prefill call joins that pipeline (ISSUE 34): its first tokens stay on
the device too.  One tiny jitted scatter (``feed_fn``) writes them into
the token feed at their rows' slots, the next decode step is dispatched
with those rows riding in it, and only then are the results read: the
prefill calls in the order dispatched (counters, the prefix index, the
first tokens out), then the decode step that was in flight, as the
synchronous loop delivered them.  A first token that ends its request
by length is known by counting and its row rides no step; one that is
its ``eos`` rides the step already dispatched, and that token is
dropped.  Whatever reads the step in flight first reads the unread
prefill calls too; speculative mode reads a call at once.
``stats()["prefills_overlapped"]`` counts the prefill calls read behind
a decode dispatch that followed them.

The scheduler loop itself is on the profiler's clock (ISSUE 25): one
``StepTimeline("serve")`` step per iteration with work, its phases
``serve.admit`` -> ``serve.prefill.stage|dispatch`` (per batch) ->
``serve.decode.grow|stage|dispatch`` -> ``serve.prefill.fetch|post``
(per batch) -> ``serve.decode.fetch|emit`` (or, in speculative mode,
each batch's four phases and then one ``serve.spec``), and
``serve.idle`` while nothing is in flight; the decode ``fetch`` and
``emit`` of an iteration are those of the step dispatched one
iteration earlier.  Each is a
``jax.profiler.TraceAnnotation`` on this thread's line of a profiler
trace and a row of ``observability.timeline.spans("serve")``; the rows
of ``serve.admit`` carry ``queue_wait_ms`` (one value per admitted
sequence), those of ``serve.prefill.stage`` the padded ``batch`` x
``bucket``, the useful ``tokens`` and, for a model that answers
``prefill_attn_pairs(batch, bucket)`` (the latent models, whose
prefill attention skips the keys no query of a group may see),
``attn_pairs_share``: the share of the square's (query, key) pairs
the call multiplies (``stats()`` adds both counts up as
``prefill_attn_pairs_multiplied`` and ``prefill_attn_pairs_square``;
equal: the mechanism does not engage), those of
``serve.decode.dispatch`` and ``serve.prefill.fetch`` ``overlapped``
(0 or 1).  ``decode_ms`` / ``prefill_ms`` are read off the dispatch and
fetch spans' own clock reads and count no instant twice: a decode step
or a prefill call runs from its dispatch, or from the end of the fetch
before it where that is later, to the end of its own fetch.

ISSUE 12 (fleet observatory) adds the REQUEST dimension:
``submit(tenant=...)`` tags a request for usage accounting (always-on
labeled counters ``serve_tenant_tokens_in/out`` /
``serve_tenant_sheds`` / ``serve_tenant_prefix_hit_tokens`` plus the
untagged ``serve_tokens_in/out`` totals they sum to), and with tracing
on every request gets its own span lane
(:mod:`~paddle_tpu.observability.request_trace`): submit -> queue ->
admit[cold/prefix-hit/readmit] -> prefill -> sampled decode steps ->
first_token/evict/finish, one Perfetto lane per request, with the
span-carried ``ttft_ms`` equal BY CONSTRUCTION to the value
``serve_ttft_ms`` observed.  ``serve_admit_rollbacks`` and
``serve_spec_index_withheld_tokens`` (PR 11 review fixes) are
always-on counters too, the rollback also a flight event.
"""
from __future__ import annotations

import queue as _queue
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from ..framework import monitor as _monitor
from ..observability import flight_recorder as _flight
from ..observability import trace as _trace
from ..observability.request_trace import RequestTrace
from ..observability.timeline import StepTimeline
from . import recurrent_state as _rs
from .prefix_cache import PrefixCache
from .serving import (RequestTimeout, ServeError, ServerClosed,
                      ServerDraining, ServerOverloaded)

__all__ = ["GenerationServer", "GenerationStream", "ServeError",
           "ServerOverloaded", "ServerClosed", "ServerDraining",
           "RequestTimeout"]

_chaos_mod = None


def _gw_chaos():
    """Lazy handle on :mod:`~paddle_tpu.distributed.fleet.chaos` (the
    package root has loaded it long before any server runs, so this is
    a cached-global lookup per decode step, not an import)."""
    global _chaos_mod
    if _chaos_mod is None:
        try:
            from ..distributed.fleet import chaos as _c
        except Exception:        # pragma: no cover - import-order guard
            return None
        _chaos_mod = _c
    return _chaos_mod

# one serve.decode ring event per this many decode steps: the ring is
# postmortem context, not a per-token log (progress() still ticks the
# stall watchdog every step).  serve.spec_verify samples on the same
# cadence, offset so the FIRST verify step is always recorded.
_FLIGHT_DECODE_EVERY = 32

_END = object()


class GenerationStream:
    """Streaming handle for one generation request.

    Iterating yields token ids as the scheduler produces them; the
    iterator ends when the sequence finishes (``eos`` or
    ``max_new_tokens``).  Errors (timeout while waiting, server
    stopped) raise from the iterator / :meth:`result`.  ``tokens``
    holds everything yielded so far.
    """

    def __init__(self, request_id: int):
        self.request_id = request_id
        self._q: _queue.Queue = _queue.Queue()
        self.tokens: List[int] = []
        self._exc: Optional[BaseException] = None
        self._ended = False
        self.finish_reason: Optional[str] = None

    # -- producer side (scheduler thread) ----------------------------
    def _emit(self, tok: int):
        self.tokens.append(int(tok))
        self._q.put(int(tok))

    def _end(self, reason: str):
        self.finish_reason = reason
        self._q.put(_END)

    def _fail(self, exc: BaseException):
        self._exc = exc
        self._q.put(_END)

    # -- consumer side -----------------------------------------------
    def __iter__(self):
        return self

    def __next__(self, timeout: float = 600.0):
        if self._ended:
            raise StopIteration
        item = self._q.get(timeout=timeout)
        if item is _END:
            self._ended = True
            if self._exc is not None:
                raise self._exc
            raise StopIteration
        return item

    def result(self, timeout: Optional[float] = None) -> List[int]:
        """Block until the stream ends; returns the full token list."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while not self._ended:
            rem = (None if deadline is None
                   else max(deadline - time.monotonic(), 0.0))
            try:
                self.__next__(timeout=600.0 if rem is None else rem)
            except StopIteration:
                break
            except _queue.Empty:
                raise RequestTimeout(
                    f"stream {self.request_id} did not finish within "
                    f"{timeout}s") from None
        return list(self.tokens)


class _GenSeq:
    """Scheduler-internal sequence state (one per request)."""

    __slots__ = (
        "rid", "prompt", "L", "max_new", "eos", "do_sample", "temp",
        "top_k", "top_p", "key_data", "priority", "arrival", "deadline",
        "stream", "generated", "decoded", "blocks", "slot", "evictions",
        "t_submit", "t_first_tok", "cached", "draft_decoded", "tenant",
        "rt", "unread")

    def __init__(self, rid, prompt, max_new, eos, do_sample, temp,
                 top_k, top_p, key_data, priority, arrival, deadline,
                 tenant=None):
        self.rid = rid
        self.prompt = prompt                  # np.int32 [L]
        self.L = int(prompt.shape[0])
        self.max_new = int(max_new)
        self.eos = eos
        self.do_sample = bool(do_sample)
        self.temp = float(temp)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.key_data = key_data              # np.uint32 [W]
        self.priority = int(priority)
        self.arrival = arrival
        self.deadline = deadline
        self.stream = GenerationStream(rid)
        self.generated: List[int] = []        # emitted tokens t1..tn
        self.decoded = 0          # decode steps READ since (re)prefill
        self.unread = 0           # 1 while it rides in the step in flight
        self.blocks: List[int] = []
        self.slot: Optional[int] = None
        self.evictions = 0
        self.t_submit = time.monotonic()
        self.t_first_tok: Optional[float] = None
        self.cached = 0           # prefix tokens aliased at admission
        self.draft_decoded = 0    # generated tokens the draft consumed
        self.tenant = tenant      # usage-accounting tag (ISSUE 12)
        # per-request span lane; None keeps the traced-off path at one
        # attribute check per site
        self.rt: Optional[RequestTrace] = None


class _Unread(NamedTuple):
    """A decode step that is dispatched and not yet read."""
    rows: List[_GenSeq]    # the sequences that ride in it
    t0: float              # the clock at the start of its dispatch
    sampled: bool          # a row of it samples
    out: object            # what it returned, still on the device


class _UnreadPrefill(NamedTuple):
    """A prefill call that is dispatched and not yet read."""
    seqs: List[_GenSeq]    # its rows' sequences, in row order
    t0: float              # the clock at the start of its dispatch
    bucket: int
    tokens: int            # real prompt tokens in it
    sampled: bool          # a row of it samples
    first: object          # each row's first token, still on the device
    attn_pairs: Tuple[int, int]   # (query, key) pairs multiplied, and
    #                               of the whole square (0, 0: no plan)


def _pow2_buckets(lo: int, hi: int) -> List[int]:
    out, b = [], lo
    while b < hi:
        out.append(b)
        b *= 2
    out.append(hi)
    return sorted(set(out))


def _sample_rows(lg, kd, rng_steps, temp, top_k, top_p, do_sample):
    """Per-row next-token selection over float32 logits ``lg [B, V]``:
    exact argmax for greedy rows, temperature/top-k/top-p categorical
    for sampling rows — one program covers any mix, at the price of one
    vocabulary sort, softmax, cumsum, the keys and the draw for every
    row.  The key for token j of a request is
    fold_in(request_key, j-1): a pure function of the stream position,
    so replay after eviction — and spec-decode verification, which
    samples the same stream at many positions in one call — reproduce
    the draw exactly."""
    import jax
    import jax.numpy as jnp

    V = lg.shape[-1]
    x = lg / jnp.maximum(temp, 1e-6)[:, None]
    srt = jnp.sort(x, axis=-1)[:, ::-1]
    kk = jnp.clip(top_k, 1, V).astype(jnp.int32)
    kth = jnp.take_along_axis(srt, (kk - 1)[:, None], axis=-1)
    use_k = ((top_k > 0) & (top_k < V))[:, None]
    x = jnp.where(use_k & (x < kth), -jnp.inf, x)
    # the top-k mask is monotone (it takes the tail of srt, ties at kth
    # stay), so this IS the masked x sorted: no second sort
    srt = jnp.where(use_k & (srt < kth), -jnp.inf, srt)
    probs = jax.nn.softmax(srt, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = jnp.maximum((cum < top_p[:, None]).sum(-1) + 1, 1)
    kth2 = jnp.take_along_axis(srt, (keep - 1)[:, None], axis=-1)
    use_p = (top_p < 1.0)[:, None]
    x = jnp.where(use_p & (x < kth2), -jnp.inf, x)
    impl = {2: "threefry2x32", 4: "rbg"}.get(
        int(kd.shape[-1]), "threefry2x32")
    base = jax.random.wrap_key_data(kd, impl=impl)
    keys = jax.vmap(jax.random.fold_in)(base, rng_steps)
    sampled = jax.vmap(jax.random.categorical)(keys, x)
    return jnp.where(do_sample, sampled.astype(jnp.int32),
                     jnp.argmax(lg, axis=-1).astype(jnp.int32))


def _sample_tokens(lg, kd, rng_steps, temp, top_k, top_p, do_sample):
    """:func:`_sample_rows` behind a ``lax.cond`` on ``any(do_sample)``,
    decided on the device: a step whose rows are all greedy runs the
    argmax alone, and a batch that gains or loses its last sampling row
    never compiles."""
    import jax
    import jax.numpy as jnp

    return jax.lax.cond(
        jnp.any(do_sample),
        lambda: _sample_rows(lg, kd, rng_steps, temp, top_k, top_p,
                             do_sample),
        lambda: jnp.argmax(lg, axis=-1).astype(jnp.int32))


class GenerationServer:
    """Continuous-batching generative gateway over a KV-cache-capable
    causal LM (``supports_kv_cache()`` / ``forward_paged``).

    Usage::

        server = GenerationServer(model, num_slots=8, block_size=16,
                                  num_blocks=256, max_model_len=512,
                                  prefix_cache=True,
                                  draft_model=small_lm, spec_k=4)
        server.start()                    # prewarms every program
        stream = server.submit(prompt_ids, max_new_tokens=64)
        for tok in stream:                # tokens stream per step
            ...
        server.stop()

    Knobs:

    - ``num_slots``: decode batch width — the ONE fixed-shape decode
      (or spec-verify) program runs over this many slots every step,
      live or idle.
    - ``block_size`` / ``num_blocks``: KV pool geometry.  Block 0 is
      the trash block, so ``num_blocks - 1`` blocks are allocatable;
      default ``num_blocks`` sizes the pool for ``num_slots``
      full-length sequences (no oversubscription — oversubscribe
      deliberately to exercise eviction).
    - ``max_model_len``: prompt + generation cap per sequence; fixes
      the block-table width ``ceil(max_model_len / block_size)``.
    - ``prompt_buckets``: prefill compiles one program per (bucket,
      batch) pair (default buckets: powers of two up to
      ``max_model_len``).
    - ``max_prefill_batch``: widest batched-prefill program (powers of
      two up to this; 1 restores the ISSUE 8 one-prompt-per-dispatch
      behavior).
    - ``prefix_cache``: enable copy-on-write prefix sharing.  Changes
      pool accounting semantics: finished conversations' full blocks
      stay cached (recyclable under pressure) instead of returning to
      the free list, and ALL prefill runs the chunked cache-gather
      program so cold and warm runs are bit-identical.
    - ``draft_model`` / ``spec_k``: speculative decoding — the draft
      model (same vocab, typically far smaller) proposes up to
      ``spec_k`` tokens per iteration, verified in one target forward.
      Greedy and seeded-sampling outputs are bit-identical to plain
      decode by construction (deterministic positional-stream accept).
    - ``max_waiting``: waiting-queue depth cap; past it ``submit``
      sheds with :class:`ServerOverloaded`.
    - ``request_timeout_s``: deadline enforced while a request WAITS
      (initial queue or evicted); admitted sequences run to
      completion.
    - ``check_replay``: assert that every replayed (post-eviction)
      step reproduces the stored token — the bit-identity contract
      checked live, at one host compare per replayed token.
    """

    def __init__(self, model, num_slots: int = 8, block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 max_model_len: Optional[int] = None,
                 prompt_buckets: Optional[Sequence[int]] = None,
                 max_waiting: int = 256,
                 request_timeout_s: float = 300.0,
                 seed: int = 0, check_replay: bool = False,
                 max_prefill_batch: int = 4,
                 prefix_cache: bool = False,
                 draft_model=None, spec_k: int = 4):
        if not bool(getattr(model, "supports_kv_cache",
                            lambda: False)()):
            # surface the model's own typed error (names the
            # scan_layers=False workaround for stacked llamas)
            init = getattr(model, "init_paged_cache", None)
            if init is not None:
                init(1, 1)   # raises KVCacheUnsupportedError
            raise ServeError(
                "GenerationServer requires a KV-cache-capable model "
                "(supports_kv_cache() is False)")
        if num_slots < 1:
            raise ValueError("num_slots must be >= 1")
        if block_size < 1:
            raise ValueError("block_size must be >= 1")
        self._model = model
        self._draft = draft_model
        self._spec = draft_model is not None
        self._k = int(spec_k)
        if self._spec:
            if self._k < 1:
                raise ValueError("spec_k must be >= 1")
            if not bool(getattr(draft_model, "supports_kv_cache",
                                lambda: False)()):
                raise ServeError(
                    "draft_model must be KV-cache-capable "
                    "(supports_kv_cache() is False)")
            if (getattr(draft_model.config, "vocab_size", None)
                    != getattr(model.config, "vocab_size", None)):
                raise ValueError(
                    "draft_model vocab_size must match the target's")
        self._num_slots = int(num_slots)
        self._bs = int(block_size)
        if max_model_len is None:
            max_model_len = int(getattr(model.config,
                                        "max_position_embeddings", 2048))
        self._max_len = int(max_model_len)
        self._M = -(-self._max_len // self._bs)   # block-table width
        if num_blocks is None:
            num_blocks = self._num_slots * self._M + 1
        self._num_blocks = int(num_blocks)
        if self._num_blocks < self._M + 1:
            raise ValueError(
                f"num_blocks={self._num_blocks} cannot hold even one "
                f"max-length sequence ({self._M} blocks) plus the "
                "trash block; raise num_blocks or lower max_model_len")
        bks = sorted(set(int(b) for b in (
            prompt_buckets or _pow2_buckets(min(8, self._max_len),
                                            self._max_len))))
        if bks[-1] < self._max_len:
            bks.append(self._max_len)
        self._buckets = bks
        if max_prefill_batch < 1:
            raise ValueError("max_prefill_batch must be >= 1")
        self._pbatches = _pow2_buckets(
            1, min(int(max_prefill_batch), self._num_slots))
        self._max_waiting = int(max_waiting)
        self._timeout_s = float(request_timeout_s)
        self._seed = int(seed)
        self._check_replay = bool(check_replay)
        self._prefix_on = bool(prefix_cache)
        # per-slot recurrent state beside the paged pools
        # (inference/recurrent_state.py): prefix sharing and speculation
        # raise RecurrentStateUnsupported for such a model
        self._stateful = _rs.check_features(self._model, prefix_cache,
                                            draft_model)
        # what the model's decode step counts, fetched behind the tokens
        self._step_counters = tuple(
            getattr(self._model, "step_counters", tuple)())
        # (multiplied, whole square) (query, key) pairs of a prefill
        # call's attention, where the model can say
        self._prefill_attn_pairs = getattr(
            self._model, "prefill_attn_pairs", lambda batch, bucket: (0, 0))

        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._waiting: List[_GenSeq] = []
        self._active: Dict[int, _GenSeq] = {}
        self._free_slots = list(range(self._num_slots))
        # block 1..num_blocks-1 are allocatable (0 is trash); the
        # PrefixCache is the one accounting path for both modes —
        # with the index disabled it IS the ISSUE 8 free list
        self._cache = PrefixCache(self._num_blocks - 1, self._bs,
                                  index_enabled=self._prefix_on,
                                  first_block=1)
        self._running = False
        self._draining = False
        self._thread: Optional[threading.Thread] = None
        # scheduler command queue (ISSUE 18): cancel/export/import
        # mutate sequence + slot state that _decode_once snapshots
        # without the lock, so they run ON the scheduler thread between
        # steps rather than growing the lock graph
        self._cmds: _queue.Queue = _queue.Queue()
        # the scheduler loop's phases (serve.admit, serve.decode.* ...):
        # profiler spans + the in-memory ring of observability.timeline
        self._tl = StepTimeline("serve")
        self._rid = 0
        self._arrival = 0
        self._compiles = 0
        self._compile_records: List[dict] = []
        self._stats = {
            "submitted": 0, "admitted": 0, "readmitted": 0,
            "evicted": 0, "finished": 0, "shed_overload": 0,
            "shed_timeout": 0, "tokens_generated": 0,
            "decode_steps": 0, "replay_steps": 0,
            # decode steps dispatched while the step before was unread
            "decode_steps_overlapped": 0,
            "decode_ms": 0.0, "prefill_ms": 0.0,
            "prefill_batches": 0, "prefill_tokens": 0,
            # prefill calls read behind a decode dispatch that followed
            # them
            "prefills_overlapped": 0,
            # (query, key) pairs the prefill calls' attention
            # multiplied, and those of the whole squares, of a model
            # that answers ``prefill_attn_pairs``: equal where no key
            # is skipped
            "prefill_attn_pairs_multiplied": 0,
            "prefill_attn_pairs_square": 0,
            # decode, verify and prefill dispatches that held a
            # sampling row (a decode or verify dispatch that held none
            # ran the sampler's argmax alone)
            "sampled_steps": 0,
            "prefill_tokens_skipped": 0, "state_resets": 0,
            "spec_verify_steps": 0, "draft_steps": 0,
            "spec_proposed": 0, "spec_accepted": 0,
            "admit_rollbacks": 0, "spec_index_withheld_tokens": 0,
            "shed_draining": 0, "migrated_in": 0, "migrated_out": 0,
            "cancelled": 0, "moe_picks_here": 0, "moe_max_expert_load": 0,
            "prefill_bucket_hits": {b: 0 for b in self._buckets},
            # whatever else the model's decode program counts
            **{name: 0 for name in self._step_counters},
        }

        # device state: params + pools + compiled step fns (lazy so the
        # constructor stays cheap; start() builds everything)
        self._pvals = None
        self._pools = None
        self._dvals = None
        self._dpools = None
        self._decode_fn = None
        self._prefill_fn = None
        self._draft_prefill_fn = None
        self._draft_decode_fn = None
        self._verify_fn = None
        self._fork_fn = None
        self._feed_fn = None
        self._prev = None
        # the decode step that is dispatched and not yet read, and the
        # prefill calls dispatched behind it
        self._inflight: Optional[_Unread] = None
        self._prefills: List[_UnreadPrefill] = []
        # decode_ms and prefill_ms count no instant twice: the end of
        # the last fetch either of them counted
        self._timed_until = 0.0

    # -- program construction ----------------------------------------
    def _build_programs(self):
        import jax
        import jax.numpy as jnp

        from ..framework.core import Tensor, no_grad

        server = self
        prefix_on = self._prefix_on

        def make_call(model):
            counted = bool(getattr(model, "step_counters", tuple)())

            def call_model(pvals, ids, pos, pools, tables, wm,
                           gather_at=None, verify_mode=False, **rows):
                """(logits, pools, the model's step counters or None);
                ``rows`` is ``slots=`` for a model with per-slot
                state, nothing otherwise."""
                st = model.state_dict()
                old = {k: t._value for k, t in st.items()}
                try:
                    for k, t in st.items():
                        if k in pvals:
                            t._value = pvals[k]
                    with no_grad():
                        out = model.forward_paged(
                            Tensor(ids), Tensor(pos), pools, tables, wm,
                            gather_at=gather_at, verify_mode=verify_mode,
                            **rows)
                    logits, pools = out[0], out[1]
                finally:
                    for k, t in st.items():
                        t._value = old[k]
                lv = logits._value if isinstance(logits, Tensor) \
                    else logits

                def raw(v):
                    return v._value if isinstance(v, Tensor) else v
                pools = [{kk: raw(vv) for kk, vv in d.items()}
                         for d in pools]
                return lv, pools, (raw(out[2]) if counted else None)
            return call_model

        def make_sample(model):
            loops = getattr(model, "loops_on_device", None)

            def sample(n_tokens, *args):
                """Each row's next token in a program whose model part
                runs ``n_tokens`` tokens: :func:`_sample_tokens`.  A
                ``conditional`` behind a device loop whose steps branch
                stopped a v5e (PERF.md section 7, X), so where the
                model says its program holds such a loop
                (``loops_on_device``) the program gets the sampler
                without the branch."""
                if loops is not None and loops(n_tokens):
                    return _sample_rows(*args)
                return _sample_tokens(*args)
            return sample

        call_model = make_call(self._model)
        sample = make_sample(self._model)
        self._pvals = {k: t._value
                       for k, t in self._model.state_dict().items()}
        self._pools = self._model.init_paged_cache(
            self._num_blocks, self._bs,
            **({"num_slots": self._num_slots} if self._stateful else {}))
        if self._spec:
            call_draft = make_call(self._draft)
            draft_sample = make_sample(self._draft)
            self._dvals = {k: t._value
                           for k, t in self._draft.state_dict().items()}
            self._dpools = self._draft.init_paged_cache(
                self._num_blocks, self._bs)
        else:
            self._dpools = []
        # the next decode step's token feed, on the device: what the
        # last one returned, and since then the first tokens of the
        # prefill calls behind it at their rows' slots (``feed_fn``)
        self._prev = np.zeros(
            (self._num_slots + len(self._step_counters),), np.int32)

        def decode_fn(pvals, pools, prev, tokens, positions, tables, wm,
                      kd, rng_steps, temp, top_k, top_p, do_sample):
            # python side effect runs at TRACE time only: the counter
            # proves steady-state decode never retraces
            server._compiles += 1
            server._note_compile("decode", 1, tokens.shape[0])
            # the token feed stays on the device: ``prev`` is what the
            # step before returned (tokens first, the model's step
            # counters behind them), and a row takes its token from it
            # unless the host staged one (>= 0): a row that joined
            # since, or one that replays tokens the host holds
            tokens = jnp.where(tokens >= 0, tokens,
                               prev[:tokens.shape[0], None])
            logits, pools, counts = call_model(pvals, tokens, positions,
                                               pools, tables, wm)
            lg = logits[:, -1, :].astype(jnp.float32)
            nxt = sample(tokens.size, lg, kd, rng_steps, temp, top_k,
                         top_p, do_sample)
            if counts is not None:
                # behind the tokens: one fetch, no second transfer
                nxt = jnp.concatenate([nxt, counts.astype(nxt.dtype)])
            return nxt, pools

        def make_prefill(call, sample, name):
            def prefill_fn(pvals, pools, prompt, start, length, table,
                           kd, temp, top_k, top_p, do_sample, **rows):
                server._compiles += 1
                server._note_compile(name, prompt.shape[1],
                                     prompt.shape[0])
                B, Lb = prompt.shape
                pos = start[:, None] + jnp.broadcast_to(
                    jnp.arange(Lb, dtype=jnp.int32)[None, :], (B, Lb))
                wm = (jnp.arange(Lb, dtype=jnp.int32)[None, :]
                      < length[:, None])
                gather_at = jnp.clip(length - 1, 0, Lb - 1)
                # prefix-sharing servers run ALL prefill through the
                # cache-gather path (verify_mode) so a cold full
                # prefill and a warm suffix prefill are the same
                # floating-point program per position — the bit-
                # identity the shared-prefix contract rests on
                logits, pools, _ = call(pvals, prompt, pos, pools, table,
                                        wm, gather_at=gather_at,
                                        verify_mode=prefix_on, **rows)
                lg = logits[:, -1, :].astype(jnp.float32)
                first = sample(prompt.size, lg, kd,
                               jnp.zeros_like(length), temp, top_k,
                               top_p, do_sample)
                return first, pools
            return prefill_fn

        def verify_fn(pvals, pools, tokens, positions, tables, wm, kd,
                      rng_steps, temp, top_k, top_p, do_sample):
            """Score S=spec_k+1 fed tokens in one forward and sample
            the target's OWN token at every position with its
            positional key — the deterministic accept reference."""
            server._compiles += 1
            server._note_compile("verify", tokens.shape[1],
                                 tokens.shape[0])
            B, S = tokens.shape
            logits, pools, _ = call_model(pvals, tokens, positions, pools,
                                          tables, wm, verify_mode=True)
            lg = logits.astype(jnp.float32).reshape(B * S, -1)
            rep = lambda a: jnp.repeat(a, S, axis=0)
            sampled = sample(
                tokens.size, lg, rep(kd), rng_steps.reshape(B * S),
                rep(temp), rep(top_k), rep(top_p), rep(do_sample))
            return sampled.reshape(B, S), pools

        def feed_fn(prev, first, slots):
            """A prefill call's first tokens into the decode program's
            token feed, each at its row's slot; a row that holds no
            sequence names a slot past the end and is dropped."""
            server._compiles += 1
            server._note_compile("feed", 1, first.shape[0])
            return prev.at[slots].set(first, mode="drop")

        def fork_fn(pools, dpools, src, dst):
            """Copy-on-write fork: duplicate one physical block across
            every pool tensor (target + draft, K/V + int8 scales).
            Physical ids never enter the attention math, so remapping
            the table to the copy is invisible to the stream."""
            server._compiles += 1
            server._note_compile("fork", 1, 1)

            def cp(d):
                return {k: v.at[dst].set(v[src]) for k, v in d.items()}
            return [cp(d) for d in pools], [cp(d) for d in dpools]

        # donate the pools: each step consumes the previous pool
        # buffers in place (the CPU backend can't donate — skip the
        # unusable-donation warning there)
        from ..distributed.mesh import target_platform
        on_cpu = target_platform() == "cpu"
        donate = () if on_cpu else (1,)
        self._decode_fn = jax.jit(decode_fn, donate_argnums=donate)
        self._prefill_fn = jax.jit(
            make_prefill(call_model, sample, "prefill"),
            donate_argnums=donate)
        if not self._spec:
            self._feed_fn = jax.jit(feed_fn)
        if self._prefix_on:
            dfork = () if on_cpu else (0, 1)
            self._fork_fn = jax.jit(fork_fn, donate_argnums=dfork)
        if self._spec:
            self._draft_prefill_fn = jax.jit(
                make_prefill(call_draft, draft_sample, "draft_prefill"),
                donate_argnums=donate)

            def draft_decode_fn(dvals, dpools, tokens, positions,
                                tables, wm, kd, rng_steps, temp, top_k,
                                top_p, do_sample):
                server._compiles += 1
                server._note_compile("draft_decode", 1, tokens.shape[0])
                logits, dpools, _ = call_draft(dvals, tokens, positions,
                                               dpools, tables, wm)
                lg = logits[:, -1, :].astype(jnp.float32)
                nxt = draft_sample(tokens.size, lg, kd, rng_steps, temp,
                                   top_k, top_p, do_sample)
                return nxt, dpools
            self._draft_decode_fn = jax.jit(draft_decode_fn,
                                            donate_argnums=donate)
            self._verify_fn = jax.jit(verify_fn, donate_argnums=donate)

    def _note_compile(self, program: str, width: int, batch: int = 1):
        """Runs inside a trace: log the compile to the server's shared
        bucket-compile table and the flight recorder's observatory."""
        cause = "prewarm" if not self._running else "new_shape_bucket"
        self._compile_records.append(
            {"program": program, "bucket": int(width),
             "batch": int(batch), "cause": cause})
        _flight.note_compile(f"GenerationServer[{program}]", cause, 0.0,
                             key=(program, int(width), int(batch)),
                             n_buckets=self._compiles)

    # -- lifecycle ---------------------------------------------------
    def start(self, prewarm: bool = True) -> "GenerationServer":
        if self._running:
            return self
        if self._decode_fn is None:
            from ..framework.compile_cache import ensure_compile_cache
            ensure_compile_cache()
            self._build_programs()
        if prewarm:
            self._prewarm()
        self._draining = False
        self._running = True
        self._thread = threading.Thread(target=self._loop,
                                        name="generation-server",
                                        daemon=True)
        self._thread.start()
        return self

    def _prewarm(self):
        """Compile every program before traffic: each (prompt bucket,
        prefill batch) pair's prefill (target + draft), the decode /
        draft-decode / verify programs, and the COW fork.  Dummy calls
        write only to the trash block (write masks all False), so the
        pools' live contents are untouched by construction."""
        W = int(np.asarray(self._seq_key_data(0)).shape[-1])
        firsts = {}
        for b in self._buckets:
            for pb in self._pbatches:
                args = (np.zeros((pb, b), np.int32),
                        np.zeros((pb,), np.int32),
                        np.zeros((pb,), np.int32),
                        np.zeros((pb, self._M), np.int32),
                        np.zeros((pb, W), np.uint32),
                        np.ones((pb,), np.float32),
                        np.zeros((pb,), np.int32),
                        np.ones((pb,), np.float32),
                        np.zeros((pb,), bool))
                firsts[pb], self._pools = self._prefill_fn(
                    self._pvals, self._pools, *args,
                    **self._row_slots([], pb))
                if self._spec:
                    _, self._dpools = self._draft_prefill_fn(
                        self._dvals, self._dpools, *args)
        B = self._num_slots
        dec_args = (np.zeros((B, 1), np.int32),
                    np.zeros((B, 1), np.int32),
                    np.zeros((B, self._M), np.int32),
                    np.zeros((B, 1), bool),
                    np.zeros((B, W), np.uint32),
                    np.zeros((B,), np.int32),
                    np.ones((B,), np.float32),
                    np.zeros((B,), np.int32),
                    np.ones((B,), np.float32),
                    np.zeros((B,), bool))
        # the decode program's token feed is the vector the step before
        # returned: warm it up on a host array and then on its own
        # result, the only form traffic ever passes
        for _ in range(2):
            self._prev, self._pools = self._decode_fn(
                self._pvals, self._pools, self._prev, *dec_args)
        if self._feed_fn is not None:
            # as traffic passes them: the feed and a prefill's result,
            # both on the device; every row dropped
            for pb, first in firsts.items():
                self._prev = self._feed_fn(
                    self._prev, first, self._feed_slots([], pb))
        nxt = self._prev
        if self._spec:
            dn, self._dpools = self._draft_decode_fn(
                self._dvals, self._dpools, *dec_args)
            S = self._k + 1
            sv, self._pools = self._verify_fn(
                self._pvals, self._pools,
                np.zeros((B, S), np.int32), np.zeros((B, S), np.int32),
                np.zeros((B, self._M), np.int32),
                np.zeros((B, S), bool), np.zeros((B, W), np.uint32),
                np.zeros((B, S), np.int32), np.ones((B,), np.float32),
                np.zeros((B,), np.int32), np.ones((B,), np.float32),
                np.zeros((B,), bool))
            np.asarray(sv)
        if self._fork_fn is not None:
            self._pools, self._dpools = self._fork_fn(
                self._pools, self._dpools, np.int32(0), np.int32(0))
        np.asarray(nxt)   # block until the warmup steps really ran

    def stop(self, drain: bool = False, timeout: float = 30.0):
        if not self._running:
            return
        if drain:
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                with self._lock:
                    if not self._active and not self._waiting:
                        break
                time.sleep(0.005)
        with self._cond:
            self._running = False
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=timeout)
            self._thread = None
        # commands enqueued in the stop window would otherwise strand
        # their callers: the scheduler thread is gone, so run them here
        self._drain_cmds()
        with self._lock:
            leftovers = list(self._waiting) + list(self._active.values())
            self._waiting.clear()
        for seq in leftovers:
            self._release(seq)
            if seq.rt is not None:
                seq.rt.finish("server_stopped")
            seq.stream._fail(ServerClosed("server stopped"))

    def drain_begin(self):
        """Stop admitting NEW requests (``submit`` raises
        :class:`ServerDraining`) while the scheduler keeps running what
        it already owns — the first half of a graceful drain; KV
        migration / ``stop(drain=True)`` is the second."""
        with self._cond:
            self._draining = True

    @property
    def draining(self) -> bool:
        return self._draining

    # -- scheduler command queue (ISSUE 18) ---------------------------
    def _run_on_scheduler(self, fn, timeout: float = 30.0):
        """Run ``fn()`` on the scheduler thread between steps (sequence
        and slot state is only coherent there — _decode_once indexes
        its snapshot by ``seq.slot`` without holding the lock).  Runs
        inline when the scheduler is not running (stopped server) or
        when already ON the scheduler thread."""
        if not self._running or self._thread is None \
                or threading.current_thread() is self._thread:
            return fn()
        box: Dict = {}
        done = threading.Event()
        with self._cond:
            self._cmds.put((fn, box, done))
            self._cond.notify_all()
        if not done.wait(timeout):
            raise ServeError("scheduler command timed out "
                             f"after {timeout}s")
        if "exc" in box:
            raise box["exc"]
        return box.get("val")

    def _drain_cmds(self):
        while True:
            try:
                fn, box, done = self._cmds.get_nowait()
            except _queue.Empty:
                return
            try:
                box["val"] = fn()
            except BaseException as e:   # noqa: BLE001 — to the caller
                box["exc"] = e
            finally:
                done.set()

    def cancel(self, request_id: int, reason: str = "cancelled") -> bool:
        """Remove a request (waiting or active) WITHOUT failing its
        stream: blocks + slot free immediately and the stream ends with
        ``finish_reason == reason``.  Returns False when the request is
        unknown (already finished).  Runs on the scheduler thread."""
        def _do():
            with self._lock:
                seq = next((s for s in self._waiting
                            if s.rid == request_id), None)
                if seq is not None:
                    self._waiting.remove(seq)
                else:
                    seq = next((s for s in self._active.values()
                                if s.rid == request_id), None)
            if seq is None:
                return False
            self._release(seq)
            with self._lock:
                self._stats["cancelled"] += 1
            if seq.rt is not None:
                seq.rt.finish(reason, tokens=len(seq.generated))
            seq.stream._end(reason)
            return True
        return self._run_on_scheduler(_do)

    def __enter__(self) -> "GenerationServer":
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    # -- client surface ----------------------------------------------
    def _seq_key_data(self, seed: int):
        from ..framework.random import key_to_data, make_key
        return np.asarray(key_to_data(make_key(seed))).astype(np.uint32)

    def submit(self, prompt, max_new_tokens: int = 32,
               do_sample: bool = False, temperature: float = 1.0,
               top_k: int = 0, top_p: float = 1.0,
               eos_token_id: Optional[int] = None,
               seed: Optional[int] = None, priority: int = 0,
               timeout_s: Optional[float] = None,
               tenant: Optional[str] = None,
               replay_tokens: Optional[Sequence[int]] = None,
               ) -> GenerationStream:
        """Enqueue one generation request; returns a
        :class:`GenerationStream` that yields tokens as decode steps
        complete.  ``priority``: lower = more important (evicted last).
        ``seed`` fixes the request's sampling RNG stream (default:
        derived from the server seed + request id).  ``tenant`` tags
        the request for usage accounting: always-on labeled counters
        (``serve_tenant_tokens_in/out``, ``serve_tenant_sheds``,
        ``serve_tenant_prefix_hit_tokens`` + a ``serve_tenant_queue_ms``
        gauge) accumulate per tenant, and — tagged or not — the request
        also counts into the untagged ``serve_tokens_in/out`` totals,
        so all-tagged traffic's tenant series sum EXACTLY to the
        totals.  Raises :class:`ServerOverloaded` at the waiting-queue
        cap, :class:`ServerDraining` on a draining server and
        :class:`ServerClosed` on a stopped one — all IMMEDIATELY, from
        under the scheduler lock, so a submit racing ``stop()`` can
        never enqueue a stream that will never start.

        ``replay_tokens`` (ISSUE 18 failover recovery): tokens this
        request's stream ALREADY emitted elsewhere — admission re-runs
        the prompt through prefill, then replays them through the
        normal decode path without re-emitting (``check_replay``
        asserts each one), and new tokens continue the stream from
        there.  The caller must pass the ORIGINAL request's explicit
        ``seed`` for the replayed stream to be the same RNG stream."""
        p = np.asarray(prompt.numpy() if hasattr(prompt, "numpy")
                       else prompt).astype(np.int32).reshape(-1)
        if p.size < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if p.size + max_new_tokens > self._max_len:
            raise ValueError(
                f"prompt ({p.size}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_model_len={self._max_len}")
        replay = [int(t) for t in replay_tokens] if replay_tokens \
            else []
        if len(replay) >= max_new_tokens:
            raise ValueError(
                f"replay_tokens ({len(replay)}) must be shorter than "
                f"max_new_tokens ({max_new_tokens}) — that stream is "
                "already complete")
        if do_sample and float(temperature) == 0.0:
            do_sample = False      # temperature 0.0 IS greedy (exact)
        to = self._timeout_s if timeout_s is None else float(timeout_s)
        with self._cond:
            # liveness checks INSIDE the lock: ``stop()`` flips
            # _running and sweeps leftovers under this same lock, so a
            # racing submit either lands before the sweep (its stream
            # fails typed) or observes the stop here — the pre-ISSUE-18
            # lock-free check let it enqueue AFTER the sweep, leaving a
            # stream nothing would ever end (caller hung to deadline)
            if not self._running:
                raise ServerClosed(
                    "server not running — submit refused (the stream "
                    "could never start)")
            if self._draining:
                self._stats["shed_draining"] += 1
                shed = ("draining", len(self._waiting))
            elif len(self._waiting) >= self._max_waiting:
                self._stats["shed_overload"] += 1
                shed = ("overload", len(self._waiting))
            else:
                self._rid += 1
                self._arrival += 1
                key_data = self._seq_key_data(
                    self._seed * 1000003 + self._rid
                    if seed is None else int(seed))
                seq = _GenSeq(self._rid, p, max_new_tokens,
                              eos_token_id, do_sample, temperature,
                              top_k, top_p, key_data, priority,
                              self._arrival, time.monotonic() + to,
                              tenant=tenant)
                if replay:
                    seq.generated = list(replay)
                if _trace.enabled():
                    seq.rt = RequestTrace("gen", seq.rid, tenant)
                    seq.rt.instant("submit", prompt_len=seq.L,
                                   max_new=seq.max_new)
                    seq.rt.begin("queue")
                self._waiting.append(seq)
                self._stats["submitted"] += 1
                self._cond.notify_all()
                shed = None
        if shed is not None:
            reason, depth = shed
            _monitor.stat_add("serve_shed_" + reason)
            if tenant is not None:
                _monitor.stat_add("serve_tenant_sheds",
                                  labels={"tenant": tenant,
                                          "reason": reason})
            _flight.record("serve.shed", reason=reason,
                           depth=depth, server="generation")
            if reason == "draining":
                raise ServerDraining(
                    "server is draining — submit this request to "
                    "another replica") from None
            _flight.maybe_dump("ServerOverloaded")
            raise ServerOverloaded(
                f"waiting-queue cap {self._max_waiting} reached; "
                "request shed — back off and retry") from None
        if _monitor.metrics_enabled():
            _monitor.gauge_set("serve_gen_waiting", len(self._waiting))
        return seq.stream

    def generate_sync(self, prompt, timeout: Optional[float] = None,
                      **kw) -> List[int]:
        """Blocking submit + collect (the per-client bench call)."""
        return self.submit(prompt, **kw).result(timeout=timeout)

    def num_compiles(self) -> int:
        """Distinct program traces (prefill grid + decode + spec/fork
        programs).  Steady state after warmup: delta == 0."""
        return self._compiles

    def flush_prefix_cache(self):
        """Drop every prefix-index entry (active sequences keep their
        references; cached-only blocks return to the free list)."""
        with self._lock:
            self._cache.flush()

    def stats(self) -> Dict:
        with self._lock:
            s = {k: (dict(v) if isinstance(v, dict) else v)
                 for k, v in self._stats.items()}
            s["waiting"] = len(self._waiting)
            s["active"] = len(self._active)
            s["draining"] = self._draining
            cache = self._cache.snapshot()
            records = list(self._compile_records)
        # "free" keeps its ISSUE 8 meaning — allocatable right now —
        # which with prefix sharing includes cached blocks (they
        # recycle on demand); "cached_blocks" is the subset holding
        # reusable prefix content
        s["free_blocks"] = cache["free"] + cache["cached"]
        s["allocated_blocks"] = cache["in_use"]
        s["cached_blocks"] = cache["cached"]
        s["prefix_entries"] = cache["entries"]
        s["prefix_hits"] = cache["hits"]
        s["prefix_hit_tokens"] = cache["hit_tokens"]
        s["prefix_queries"] = cache["queries"]
        s["prefix_hit_rate"] = (cache["hit_tokens"]
                                / max(cache["query_tokens"], 1))
        s["prefix_recycled"] = cache["recycled"]
        s["cow_forks"] = cache["cow_forks"]
        s["total_blocks"] = self._num_blocks - 1   # trash excluded
        s["block_size"] = self._bs
        s["num_slots"] = self._num_slots
        s["num_compiles"] = self._compiles
        s["spec_enabled"] = self._spec
        s["spec_k"] = self._k if self._spec else 0
        s["spec_accept_rate"] = (s["spec_accepted"]
                                 / max(s["spec_proposed"], 1))
        s["prefix_cache_enabled"] = self._prefix_on
        # per-slot recurrent state beside the paged pools (zeros for a
        # K/V model, and before start() has built the pools)
        s.update(_rs.pool_bytes(self._pools or []))
        s["server"] = "generation"   # provenance, see PredictorServer
        # shared bucket-compile accounting shape with
        # PredictorServer.stats() (ISSUE 8 satellite; ISSUE 11 adds
        # the batch axis): per program "name:bucketxbatch" ->
        # {count, cause}
        bc: Dict = {}
        for r in records:
            key = f"{r['program']}:{r['bucket']}x{r.get('batch', 1)}"
            ent = bc.setdefault(key, {"count": 0, "cause": r["cause"]})
            ent["count"] += 1
        s["bucket_compiles"] = bc
        s["prewarm_compiles"] = sum(1 for r in records
                                    if r["cause"] == "prewarm")
        s["traffic_compiles"] = sum(1 for r in records
                                    if r["cause"] != "prewarm")
        return s

    def _row_slots(self, seqs, B: int) -> dict:
        """``slots=`` of a batched prefill for a model with per-slot
        state: rows are not slots there, so each row names its own (a
        row that holds no sequence names ``num_slots``, which no write
        reaches).  Nothing for a K/V model."""
        if not self._stateful:
            return {}
        return {"slots": self._slots_of(seqs, B, self._num_slots)}

    def _feed_slots(self, seqs, B: int):
        """Where :attr:`_feed_fn` writes a prefill call's ``B`` first
        tokens: each sequence's slot, and past the end of the feed for
        a row that holds none."""
        return self._slots_of(seqs, B, self._prev.shape[0])

    @staticmethod
    def _slots_of(seqs, B: int, pad: int):
        return np.asarray([s.slot for s in seqs] + [pad] * (B - len(seqs)),
                          np.int32)

    # -- scheduler ---------------------------------------------------
    def _loop(self):
        tl = self._tl
        step_i = 0
        try:
            while True:
                with self._cond:
                    running = self._running
                    if running and not self._active and not self._waiting \
                            and self._cmds.empty() \
                            and self._inflight is None:
                        with tl.phase("idle"):
                            self._cond.wait(timeout=0.05)
                        continue
                if not running:
                    # stop(): what was dispatched is delivered (a
                    # prefill call is read by the iteration that
                    # dispatched it); commands still queued are run by
                    # stop()
                    if self._inflight is not None:
                        with tl.step(step_i):
                            self._read_unread()
                    return
                with tl.step(step_i):
                    step_i += 1
                    if not self._cmds.empty():
                        # a command (cancel, migration) reads and
                        # rewrites sequence state as if no step were in
                        # flight: read it first
                        self._read_unread()
                    with tl.phase("admit") as ph:
                        if self._inflight is None:
                            self._drain_cmds()
                        self._expire_waiting()
                        batches, waits = self._admit()
                        ph.set(queue_wait_ms=waits)
                    for bucket, seqs in batches:
                        self._prefill_batch(seqs, bucket)
                    if self._spec:
                        if self._active:
                            with tl.phase("spec"):
                                self._spec_once()
                    elif self._active or self._inflight is not None:
                        self._decode_once()
        except BaseException as e:   # noqa: BLE001 — fail streams loudly
            with self._lock:
                victims = (list(self._waiting)
                           + list(self._active.values()))
                self._waiting.clear()
                self._active.clear()
                self._running = False
            self._inflight = None    # its tokens go with the streams
            self._prefills = []
            for seq in victims:
                if seq.rt is not None:
                    seq.rt.finish("scheduler_error")
                seq.stream._fail(ServeError(
                    f"generation scheduler died: {e!r}"))
            raise

    def _expire_waiting(self):
        now = time.monotonic()
        with self._lock:
            expired = [s for s in self._waiting if now > s.deadline]
            if not expired:
                return
            self._waiting = [s for s in self._waiting
                             if now <= s.deadline]
            for s in expired:
                self._stats["shed_timeout"] += 1
        for s in expired:
            _monitor.stat_add("serve_shed_timeout")
            if s.tenant is not None:
                _monitor.stat_add("serve_tenant_sheds",
                                  labels={"tenant": s.tenant,
                                          "reason": "timeout"})
            _flight.record("serve.shed", reason="timeout", rid=s.rid,
                           waited_ms=round((now - s.t_submit) * 1e3, 1),
                           evictions=s.evictions, server="generation")
            _flight.record("serve.stream_end", rid=s.rid,
                           reason="timeout", tokens=len(s.generated))
            if s.rt is not None:
                s.rt.finish("shed_timeout", tokens=len(s.generated))
            s.stream._fail(RequestTimeout(
                f"request {s.rid} spent its whole deadline "
                + ("evicted and waiting for re-admission"
                   if s.evictions else "queued")
                + " — pool/slots overloaded"))

    # -- admission + prefill -----------------------------------------
    def _admit(self):
        """Admit as many waiting sequences as slots + blocks allow, in
        strict (priority, arrival) order, and group them into prefill
        batches by prompt/suffix bucket (ONE dispatch per group chunk
        — the batched-prefill win).  Returns ``(batches, waits)``:
        ``[(bucket, seqs), ...]`` for :meth:`_prefill_batch` and each
        admitted sequence's milliseconds since ``submit()``."""
        taken: List[_GenSeq] = []
        forks: List[tuple] = []
        rollback: Optional[_GenSeq] = None
        with self._lock:
            while self._waiting and self._free_slots:
                self._waiting.sort(key=lambda s: (s.priority, s.arrival))
                seq = self._waiting[0]
                hit_blocks, matched = self._cache.match(seq.prompt)
                cached = min(matched, seq.L - 1)
                a = len(hit_blocks)
                nb = -(-seq.L // self._bs)
                fresh = nb - a
                # +1 headroom when the first decode write lands on a
                # block boundary; +1 more when the tail alias must COW-
                # fork before the suffix prefill writes into it
                w = cached // self._bs
                fork = bool(a and w < a)
                need = fresh + (1 if seq.L % self._bs == 0 else 0) \
                    + (1 if fork else 0)
                # available() counts LRU-cached blocks, but ref()ing
                # the hits below pins exactly the LRU ones out of the
                # recyclable pool — count only what alloc() can still
                # hand out afterwards (a warm cache under
                # oversubscription routinely has hits as the BULK of
                # the recyclable pool)
                pinned = sum(1 for b in hit_blocks
                             if b in self._cache.lru)
                if self._cache.available() - pinned < max(need, 0):
                    # pinning the hits + the fork destination can make
                    # the warm path need MORE allocatable blocks than
                    # a cold admission (which recycles the hit blocks
                    # as fresh ones) — fall back rather than starve
                    cold = nb + (1 if seq.L % self._bs == 0 else 0)
                    if self._cache.available() < cold:
                        break   # strict priority: no queue jumping
                    hit_blocks, cached = [], 0
                    fresh, fork = nb, False
                    need = cold
                self._waiting.pop(0)
                for b in hit_blocks:
                    self._cache.ref(b)
                seq.blocks = list(hit_blocks)
                # fresh blocks, plus the COW destination reserved
                # UNDER the admission check's lock — a same-round
                # sibling's fresh allocations must not eat the block
                # the check just promised this fork
                grabbed: List[int] = []
                dst = None
                for _ in range(fresh):
                    blk = self._cache.alloc()
                    if blk is None:
                        break
                    grabbed.append(blk)
                if fork and len(grabbed) == fresh:
                    dst = self._cache.alloc()
                if len(grabbed) < fresh or (fork and dst is None):
                    # the capacity check miscounted: roll back (free
                    # the grabs, unpin the hits, requeue) so one shed
                    # admission never kills the scheduler thread
                    for b in grabbed:
                        self._cache.unref(b)
                    for b in hit_blocks:
                        self._cache.unref(b)
                    seq.blocks = []
                    self._waiting.insert(0, seq)
                    self._stats["admit_rollbacks"] += 1
                    rollback = seq
                    break
                seq.blocks.extend(grabbed)
                seq.cached = cached
                self._cache.note_query(seq.L, cached)
                if fork:
                    self._cache.stats["cow_forks"] += 1
                    forks.append((seq, w, seq.blocks[w], dst))
                seq.slot = self._free_slots.pop()
                self._active[seq.slot] = seq
                taken.append(seq)
        if rollback is not None:
            # shed-class anomaly (ISSUE 12 satellite): the capacity
            # check miscounted and one admission was rolled back —
            # always-on counter + flight event (postmortem _BAD_KINDS)
            _monitor.stat_add("serve_admit_rollbacks")
            _flight.record("serve.admit_rollback", rid=rollback.rid,
                           prompt_len=rollback.L,
                           available=self._cache.available())
            if rollback.rt is not None:
                rollback.rt.instant("admit_rollback")
        waits: List[float] = []
        for seq in taken:
            # usage accounting at admission: queue age per wait,
            # prompt tokens once per REQUEST (re-admissions re-alias,
            # they don't re-ingest)
            queue_ms = (time.monotonic() - seq.t_submit) * 1e3
            waits.append(queue_ms)
            if seq.evictions == 0:
                _monitor.stat_add("serve_tokens_in", seq.L)
            if seq.tenant is not None:
                lab = {"tenant": seq.tenant}
                if seq.evictions == 0:
                    # first admission: queue age == submit -> now; a
                    # re-admission's wait shows on its req.queue span
                    _monitor.stat_add("serve_tenant_tokens_in", seq.L,
                                      labels=lab)
                    _monitor.gauge_add("serve_tenant_queue_ms",
                                       queue_ms, labels=lab)
                if seq.cached:
                    _monitor.stat_add("serve_tenant_prefix_hit_tokens",
                                      seq.cached, labels=lab)
            if seq.rt is not None:
                seq.rt.end("queue", evictions=seq.evictions)
                kind = ("readmit" if seq.evictions
                        else "prefix-hit" if seq.cached else "cold")
                seq.rt.instant("admit", kind=kind, cached=seq.cached,
                               blocks=len(seq.blocks), slot=seq.slot)
        if not taken:
            return [], waits
        # COW-fork each aliased tail block the suffix prefill will
        # write into (refcount > 1 counts the index entry, so an
        # indexed original is never clobbered): device-copy into the
        # reserved block, remap the table, drop the alias reference
        for seq, w, src, dst in forks:
            self._pools, self._dpools = self._fork_fn(
                self._pools, self._dpools, np.int32(src),
                np.int32(dst))
            with self._lock:
                seq.blocks[w] = dst
                self._cache.unref(src)
            _monitor.stat_add("serve_cow_forks")
            _flight.record("serve.cow_fork", rid=seq.rid, src=src,
                           dst=dst, logical=w)
        # group by suffix bucket and dispatch in chunks
        groups: Dict[int, List[_GenSeq]] = {}
        for seq in taken:
            groups.setdefault(self._bucket_for(seq.L - seq.cached),
                              []).append(seq)
        step = self._pbatches[-1]
        return [(bucket, seqs[i:i + step])
                for bucket, seqs in sorted(groups.items())
                for i in range(0, len(seqs), step)], waits

    def _bucket_for(self, L: int) -> int:
        for b in self._buckets:
            if L <= b:
                return b
        return self._buckets[-1]

    def _pbatch_for(self, n: int) -> int:
        for b in self._pbatches:
            if n <= b:
                return b
        return self._pbatches[-1]

    def _prefill_batch(self, seqs: List[_GenSeq], bucket: int):
        """One prefill dispatch for up to max_prefill_batch sequences
        sharing a bucket; padding rows (length 0) write only trash.
        Its first tokens go into the decode program's token feed on the
        device and the call is left unread (:meth:`_read_unread`), so
        the next decode step can be dispatched behind it; speculative
        mode, which drafts from the first token on the host, reads it
        at once."""
        tl = self._tl
        with tl.phase("prefill.stage") as ph:
            B = self._pbatch_for(len(seqs))
            W = int(seqs[0].key_data.shape[-1])
            prompt = np.zeros((B, bucket), np.int32)
            start = np.zeros((B,), np.int32)
            length = np.zeros((B,), np.int32)
            tables = np.zeros((B, self._M), np.int32)
            kd = np.zeros((B, W), np.uint32)
            temp = np.ones((B,), np.float32)
            top_k = np.zeros((B,), np.int32)
            top_p = np.ones((B,), np.float32)
            do_sample = np.zeros((B,), bool)
            for i, seq in enumerate(seqs):
                sfx = seq.prompt[seq.cached:]
                prompt[i, :sfx.shape[0]] = sfx
                start[i] = seq.cached
                length[i] = sfx.shape[0]
                tables[i, :len(seq.blocks)] = seq.blocks
                kd[i] = seq.key_data
                temp[i] = seq.temp
                top_k[i] = seq.top_k
                top_p[i] = seq.top_p
                do_sample[i] = seq.do_sample
                # the decode step behind this call is staged before the
                # call is read: a re-admitted row replays from its start
                seq.decoded = 0
                seq.draft_decoded = 0
                if seq.rt is not None:
                    seq.rt.begin("prefill")
            tokens = int(length.sum())
            ph.set(bucket=bucket, batch=B, tokens=tokens)
            pairs = self._prefill_attn_pairs(B, bucket)
            if pairs[1]:
                ph.set(attn_pairs_share=pairs[0] / pairs[1])
        if self._stateful and start.any():
            raise _rs.RecurrentStateUnsupported(
                "a prefill that starts mid-sequence: the latent layers "
                "attend over the fresh block only")
        with tl.phase("prefill.dispatch") as disp:
            first, self._pools = self._prefill_fn(
                self._pvals, self._pools, prompt, start, length, tables,
                kd, temp, top_k, top_p, do_sample,
                **self._row_slots(seqs, B))
            if self._spec:
                _, self._dpools = self._draft_prefill_fn(
                    self._dvals, self._dpools, prompt, start, length,
                    tables, kd, temp, top_k, top_p, do_sample)
            else:
                self._prev = self._feed_fn(self._prev, first,
                                           self._feed_slots(seqs, B))
        self._prefills.append(_UnreadPrefill(
            seqs, disp.t0, bucket, tokens, bool(do_sample.any()), first,
            pairs))
        if self._spec:
            self._read_unread()

    def _read_prefill(self, call: _UnreadPrefill, overlapped: int):
        """Fetch a prefill call's first tokens and do what follows from
        them: counters, the prefix index, the tokens out.
        ``overlapped``: a decode step was dispatched behind the call."""
        tl = self._tl
        seqs, bucket = call.seqs, call.bucket
        with tl.phase("prefill.fetch", overlapped=overlapped) as fetch:
            first = np.asarray(call.first)
        # like a decode step's: from its dispatch, or from the end of
        # the fetch before it where that is later, to its fetch's end
        # (the rest of the step in flight ahead of it included)
        dt_ms = (fetch.t1 - max(call.t0, self._timed_until)) * 1e3
        self._timed_until = fetch.t1
        with tl.phase("prefill.post"):
            with self._lock:
                self._stats["prefill_ms"] += dt_ms
                self._stats["prefill_batches"] += 1
                self._stats["prefills_overlapped"] += overlapped
                self._stats["prefill_attn_pairs_multiplied"] += \
                    call.attn_pairs[0]
                self._stats["prefill_attn_pairs_square"] += \
                    call.attn_pairs[1]
                self._stats["sampled_steps"] += call.sampled
                self._stats["prefill_bucket_hits"][bucket] = \
                    self._stats["prefill_bucket_hits"].get(bucket, 0) \
                    + len(seqs)
                self._stats["prefill_tokens"] += call.tokens
                self._stats["prefill_tokens_skipped"] += int(
                    sum(s.cached for s in seqs))
                if self._stateful:   # each started from zero state
                    self._stats["state_resets"] += len(seqs)
            if _monitor.metrics_enabled():
                _monitor.hist_observe("prefill_ms", dt_ms)
            for seq in seqs:
                if seq.rt is not None:
                    seq.rt.end("prefill", bucket=bucket, batch=len(seqs),
                               suffix=seq.L - seq.cached)
            for i, seq in enumerate(seqs):
                self._post_prefill(seq, int(first[i]), bucket)

    def _post_prefill(self, seq: _GenSeq, first: int, bucket: int):
        # a replay-submitted request (ISSUE 18 failover: generated
        # pre-seeded, zero evictions) takes the same no-re-emit path as
        # a re-admission; "readmitted" keeps counting evictions only
        readmit = seq.evictions > 0 or bool(seq.generated)
        with self._lock:
            self._stats["admitted"] += 1
            self._stats["readmitted"] += int(seq.evictions > 0)
            # index the prompt's full blocks for future sharing; the
            # aliased ones are already indexed (insert is idempotent)
            self._cache.insert(seq.prompt.tolist(), seq.blocks)
        _monitor.stat_add("serve_gen_admitted")
        _flight.record("serve.admit", rid=seq.rid, prompt_len=seq.L,
                       bucket=bucket, blocks=len(seq.blocks),
                       slot=seq.slot, readmit=readmit,
                       priority=seq.priority, cached=seq.cached)
        if seq.cached:
            _monitor.stat_add("serve_prefix_hits")
            _monitor.stat_add("serve_prefix_hit_tokens", seq.cached)
            _flight.record("serve.prefix_hit", rid=seq.rid,
                           cached_tokens=seq.cached,
                           prompt_len=seq.L)
        if _monitor.metrics_enabled():
            _monitor.gauge_set("serve_gen_active", len(self._active))
            _monitor.gauge_set("serve_gen_free_blocks",
                               self._cache.available())
            st = self._cache.stats
            _monitor.gauge_set("serve_prefix_hit_rate",
                               st["hit_tokens"]
                               / max(st["query_tokens"], 1))
        if readmit:
            # replay: prefill re-derives t1 from the identical program
            # + inputs; the stored token is authoritative either way
            if self._check_replay and first != seq.generated[0]:
                raise AssertionError(
                    f"re-prefill of request {seq.rid} resampled token 1 "
                    f"as {first}, stream already emitted "
                    f"{seq.generated[0]} — paged prefill is not "
                    "bit-stable")
        else:
            self._emit(seq, first)

    # -- emission / release ------------------------------------------
    def _emit(self, seq: _GenSeq, tok: int):
        seq.generated.append(tok)
        if seq.t_first_tok is None:
            seq.t_first_tok = time.monotonic()
            # ONE ttft value feeds both the histogram and the span
            # lane: the span view and serve_ttft_ms must agree exactly
            # (the ISSUE 12 consistency contract)
            ttft_ms = (seq.t_first_tok - seq.t_submit) * 1e3
            if _monitor.metrics_enabled():
                _monitor.hist_observe("serve_ttft_ms", ttft_ms)
            if seq.rt is not None:
                seq.rt.instant("first_token", ttft_ms=ttft_ms)
        seq.stream._emit(tok)
        with self._lock:
            self._stats["tokens_generated"] += 1
        if (seq.eos is not None and tok == seq.eos) \
                or len(seq.generated) >= seq.max_new:
            reason = ("eos" if seq.eos is not None and tok == seq.eos
                      else "length")
            self._finish(seq, reason)

    def _finish(self, seq: _GenSeq, reason: str):
        withheld = 0
        with self._lock:
            # index completed full blocks (prompt + generated): the
            # next turn of this conversation aliases them — multi-turn
            # chat is the prefix cache's defining traffic
            toks = seq.prompt.tolist() + seq.generated
            if self._spec and self._prefix_on:
                # the draft pools hold valid KV only through position
                # L + draft_decoded - 1 (capped/rejected proposals
                # leave the draft behind the emitted stream); indexing
                # past that would hand a future alias stale draft-KV —
                # output stays bit-correct via the deterministic
                # accept, but the accept rate silently sinks for
                # exactly the warm multi-turn traffic the cache
                # targets.  Withhold the tail and count it.
                valid = seq.L + seq.draft_decoded
                withheld = max(len(toks) - valid, 0)
                self._stats["spec_index_withheld_tokens"] += withheld
                toks = toks[:valid]
            self._cache.insert(toks, seq.blocks)
        self._release(seq)
        with self._lock:
            self._stats["finished"] += 1
        if withheld:
            # stats()-only until ISSUE 12: the accept-rate sink is a
            # fleet-visible signal, so it counts on /metrics too
            _monitor.stat_add("serve_spec_index_withheld_tokens",
                              withheld)
        _monitor.stat_add("serve_gen_finished")
        _monitor.stat_add("serve_tokens_out", len(seq.generated))
        if seq.tenant is not None:
            _monitor.stat_add("serve_tenant_tokens_out",
                              len(seq.generated),
                              labels={"tenant": seq.tenant})
        _flight.record("serve.stream_end", rid=seq.rid, reason=reason,
                       tokens=len(seq.generated),
                       evictions=seq.evictions)
        if seq.rt is not None:
            seq.rt.finish(reason, tokens=len(seq.generated),
                          evictions=seq.evictions)
        seq.stream._end(reason)

    def _release(self, seq: _GenSeq):
        """Drop the sequence's block references + slot immediately
        (shared blocks survive through their other references; indexed
        blocks stay cached until recycled)."""
        with self._lock:
            if seq.blocks:
                for b in seq.blocks:
                    self._cache.unref(b)
                seq.blocks = []
            if seq.slot is not None:
                self._active.pop(seq.slot, None)
                self._free_slots.append(seq.slot)
                seq.slot = None
            seq.unread = 0

    def _evict(self, seq: _GenSeq):
        """Block-pool exhaustion: free the victim's blocks and send it
        back to the waiting queue (its generated tokens are kept; re-
        admission re-prefills + replays them bit-identically)."""
        freed = len(seq.blocks)
        self._release(seq)
        seq.decoded = 0
        seq.draft_decoded = 0
        seq.cached = 0
        seq.evictions += 1
        with self._lock:
            self._stats["evicted"] += 1
            self._waiting.append(seq)
        _monitor.stat_add("serve_gen_evicted")
        _flight.record("serve.evict", rid=seq.rid,
                       reason="pool_exhausted", freed_blocks=freed,
                       tokens_so_far=len(seq.generated),
                       priority=seq.priority, evictions=seq.evictions)
        if seq.rt is not None:
            seq.rt.instant("evict", tokens=len(seq.generated))
            seq.rt.begin("queue")   # waiting for re-admission
        _flight.maybe_dump("BlockPoolExhausted")

    def _blocks_lacking(self, seq, ahead=0):
        """How many blocks the sequence lacks for the position its next
        step writes, counted past the step in flight (``ahead``
        positions further for a spec iteration)."""
        p = min(seq.L + seq.decoded + seq.unread + ahead,
                self._max_len - 1)
        return p // self._bs + 1 - len(seq.blocks)

    def _grow_or_evict(self, rows=None):
        """Before a decode/verify step every sequence of it (``rows``;
        every live one by default) must own the blocks its next K/V
        writes land in (one position for plain decode, counted past the
        step in flight; up to spec_k+1 for a spec iteration); a dry
        pool evicts the lowest-priority sequence (highest priority
        number, then youngest)."""
        ahead = self._k if self._spec else 0
        if rows is None:
            rows = sorted(self._active.values(), key=lambda s: s.slot)
        for seq in rows:
            # a sequence evicted below us this round has no slot
            while seq.slot is not None \
                    and self._blocks_lacking(seq, ahead) > 0:
                with self._lock:
                    blk = self._cache.alloc()
                    if blk is not None:
                        seq.blocks.append(blk)
                        continue
                victim = max(self._active.values(),
                             key=lambda s: (s.priority, s.arrival))
                self._evict(victim)
                # the growing sequence itself can be the lowest
                # priority: it re-queues and this slot sits out

    # -- plain decode: a pipeline of depth one -------------------------
    def _riders(self):
        """The sequences of the next decode step, by slot, and whether
        the pool holds the blocks they lack.  A sequence whose step in
        flight yields its last token by length rides no further: the
        host counts tokens, it need not read one to know.  An ``eos``
        cannot be counted, so such a sequence rides on, and if the step
        in flight turns out to have ended it, the next step's token for
        it is dropped (:meth:`_read_inflight`)."""
        with self._lock:
            rows = sorted((s for s in self._active.values()
                           if s.decoded + s.unread + 1 < s.max_new),
                          key=lambda s: s.slot)
            free = self._cache.available()
        lack = sum(max(self._blocks_lacking(s), 0) for s in rows)
        return rows, lack <= free

    def _decode_once(self):
        """Dispatch decode step n+1, then read step n, which the device
        ran while the host staged n+1.  All that n+1 needs of n is each
        row's token, and that stays on the device (``decode_fn``'s
        ``prev``); positions, tables, keys and lengths the host knows
        one step early.  The rows of this iteration's prefill calls ride
        in n+1 as well: their first tokens are in ``prev`` already
        (:meth:`_prefill_batch`), and the calls are read ahead of n.  With
        nothing in flight the step is dispatched and left unread.  What
        must see sequence state as if nothing were in flight reads it
        first, and this iteration then runs at depth zero: an eviction
        here, a command in :meth:`_loop`."""
        tl = self._tl
        rows, fits = self._riders()
        if (self._inflight is not None or self._prefills) \
                and not (rows and fits):
            # nothing rides on, or the pool is dry: the read may finish
            # sequences and free their blocks before anything is evicted
            self._read_unread()
            rows, fits = self._riders()
        if not rows:
            return
        with tl.phase("decode.grow"):
            self._grow_or_evict(rows)
        with tl.phase("decode.stage"):
            if not fits:              # evictions: see who is left
                rows, _ = self._riders()
                if not rows:
                    return
            B, M = self._num_slots, self._M
            W = rows[0].key_data.shape[-1]
            tokens = np.zeros((B, 1), np.int32)
            positions = np.zeros((B, 1), np.int32)
            tables = np.zeros((B, M), np.int32)
            wm = np.zeros((B, 1), bool)
            kd = np.zeros((B, W), np.uint32)
            rng_steps = np.zeros((B,), np.int32)
            temp = np.ones((B,), np.float32)
            top_k = np.zeros((B,), np.int32)
            top_p = np.ones((B,), np.float32)
            do_sample = np.zeros((B,), bool)
            for seq in rows:
                s = seq.slot
                d = seq.decoded + seq.unread      # the token it feeds
                # -1: the token the step in flight, or the prefill
                # call behind it, is producing, taken on the device
                tokens[s, 0] = seq.generated[d] \
                    if d < len(seq.generated) else -1
                positions[s, 0] = seq.L + d
                tables[s, :len(seq.blocks)] = seq.blocks
                wm[s, 0] = True
                kd[s] = seq.key_data
                rng_steps[s] = d + 1
                temp[s] = seq.temp
                top_k[s] = seq.top_k
                top_p[s] = seq.top_p
                do_sample[s] = seq.do_sample
                seq.unread += 1
        overlapped = int(self._inflight is not None)
        with tl.phase("decode.dispatch", overlapped=overlapped) as disp:
            nxt, self._pools = self._decode_fn(
                self._pvals, self._pools, self._prev, tokens, positions,
                tables, wm, kd, rng_steps, temp, top_k, top_p, do_sample)
        with self._lock:
            self._stats["decode_steps_overlapped"] += overlapped
        # this iteration's prefill calls, then step n: both ran meanwhile
        self._read_unread(overlapped=1)
        self._prev = nxt
        self._inflight = _Unread(rows, disp.t0, bool(do_sample.any()), nxt)

    def _read_unread(self, overlapped: int = 0):
        """Read what is dispatched and unread: the prefill calls, in the
        order dispatched, then the decode step in flight, which the
        device ran ahead of them.  A request's first token waits for
        nothing but its own call, and the step's tokens go out behind
        it, as from the synchronous loop: a client whose request ends
        in that step and who sends the next one at once finds the
        scheduler about to admit, not blocked in a call's fetch.
        ``overlapped``: the next decode step went out first."""
        if self._prefills:
            calls, self._prefills = self._prefills, []
            for call in calls:
                self._read_prefill(call, overlapped)
        self._read_inflight()

    def _read_inflight(self):
        """Fetch the tokens of the decode step in flight, if there is
        one, and emit them.  A row whose sequence ended by ``eos`` in
        the step before rode in this one all the same: its token is
        dropped.  That step fed the sequence's real last token at its
        real position into a block the sequence still owned when the
        step was dispatched, and every later program (the prefill of
        the slot's and the blocks' next owner among them) follows it
        through the donated pools: whatever reuses the block finds
        what the synchronous loop left there plus that token's K/V,
        and a slot's recurrent state is reset by its next prefill."""
        step, self._inflight = self._inflight, None
        if step is None:
            return
        tl = self._tl
        with tl.phase("decode.fetch") as fetch:
            nxt = np.asarray(step.out)
        # a step's decode_ms runs to the end of its fetch from its
        # dispatch, or from the end of the fetch before it (the step's
        # before it, a prefill's) where that is later: decode_ms and
        # prefill_ms count no instant twice
        dt_ms = (fetch.t1 - max(step.t0, self._timed_until)) * 1e3
        self._timed_until = fetch.t1
        with tl.phase("decode.emit"):
            replays = 0
            every = _trace.trace_every()
            for seq in step.rows:
                s = seq.slot
                if s is None:
                    continue                 # ended in the step before
                seq.decoded += 1
                seq.unread -= 1
                if seq.rt is not None and seq.decoded % every == 0:
                    # sampled per-request decode span (PADDLE_TRACE_EVERY)
                    seq.rt.span_at("decode", dt_ms, step=seq.decoded)
                j = seq.decoded + 1          # 1-based index produced
                if j <= len(seq.generated):
                    replays += 1             # catching up after eviction
                    if self._check_replay \
                            and int(nxt[s]) != seq.generated[j - 1]:
                        raise AssertionError(
                            f"replayed decode step for request {seq.rid} "
                            f"produced {int(nxt[s])}, stream already "
                            f"emitted {seq.generated[j - 1]} — paged "
                            "decode is not bit-stable")
                else:
                    self._emit(seq, int(nxt[s]))
            if self._step_counters:
                B = self._num_slots
                with self._lock:
                    for i, name in enumerate(self._step_counters):
                        self._stats[name] = self._stats.get(name, 0) \
                            + int(nxt[B + i])
            self._after_step(len(step.rows), replays, dt_ms, step.sampled)

    def _after_step(self, n_live: int, replays: int, dt_ms: float,
                    sampled: bool):
        with self._lock:
            self._stats["decode_steps"] += 1
            self._stats["sampled_steps"] += sampled
            self._stats["replay_steps"] += replays
            self._stats["decode_ms"] += dt_ms
            n_steps = self._stats["decode_steps"]
            free_now = self._cache.available()
        _flight.progress("serve.decode")
        if n_steps % _FLIGHT_DECODE_EVERY == 0:
            _flight.record("serve.decode", steps=n_steps, live=n_live,
                           free_blocks=free_now, ms=round(dt_ms, 3))
        if _monitor.metrics_enabled():
            _monitor.hist_observe("decode_step_ms", dt_ms)
            _monitor.gauge_set("serve_gen_active", len(self._active))
            _monitor.gauge_set("serve_gen_free_blocks", free_now)
        # gateway chaos site (ISSUE 18): a seeded ``kill:gen_step``
        # plan SIGKILLs this replica process at an exact decode step —
        # the acceptance fault for router failover.  No plan installed
        # => one cached-module call per step.
        ch = _gw_chaos()
        if ch is not None:
            ch.maybe_kill_replica()

    # -- speculative decode -------------------------------------------
    def _spec_once(self):
        """One spec iteration: k batched draft steps propose, one
        target verify forward scores k+1 positions, the accepted
        prefix advances.  Bit-identical to plain decode: every
        candidate is the target's own positional-stream token, and a
        proposal is accepted only when it EQUALS that token."""
        self._grow_or_evict()
        with self._lock:
            live = sorted(self._active.values(), key=lambda s: s.slot)
        if not live:
            return
        B, M, k = self._num_slots, self._M, self._k
        W = live[0].key_data.shape[-1]
        t0 = time.perf_counter()

        # ---- draft phase: k batched draft-decode steps.  Per slot the
        # feed is the next unconsumed token: stored tokens first
        # (catch-up after eviction or a rejected round), then its own
        # proposal chain.  Chain outputs past the end of the stored
        # stream are this round's proposals.
        chains: Dict[int, List[int]] = {s.slot: [] for s in live}
        draft_feeds: Dict[int, List[int]] = {s.slot: [] for s in live}
        for _ in range(k):
            tokens = np.zeros((B, 1), np.int32)
            positions = np.zeros((B, 1), np.int32)
            tables = np.zeros((B, M), np.int32)
            wm = np.zeros((B, 1), bool)
            kd = np.zeros((B, W), np.uint32)
            rng_steps = np.zeros((B,), np.int32)
            temp = np.ones((B,), np.float32)
            top_k = np.zeros((B,), np.int32)
            top_p = np.ones((B,), np.float32)
            do_sample = np.zeros((B,), bool)
            fed_any = False
            fed_this: Dict[int, int] = {}
            for seq in live:
                s = seq.slot
                f = seq.draft_decoded + len(draft_feeds[s])
                pos = seq.L + f
                gen, chain = seq.generated, chains[s]
                # accepting m proposals emits m+1 tokens, so proposals
                # beyond max_new - len(gen) - 1 can never be consumed —
                # don't draft them (they'd be fed to verify, counted
                # rejected, and waste a draft dispatch)
                cap = max(seq.max_new - len(gen) - 1, 0)
                if f < len(gen):
                    if f >= len(gen) - 1 and len(chain) >= cap:
                        continue      # proposal budget spent
                    tok = gen[f]
                elif f - len(gen) < len(chain):
                    if len(chain) >= cap:
                        continue      # proposal budget spent
                    tok = chain[f - len(gen)]
                else:
                    continue          # chain exhausted (position cap)
                if pos >= self._max_len:
                    continue          # context full: draft idles
                tokens[s, 0] = tok
                positions[s, 0] = pos
                tables[s, :len(seq.blocks)] = seq.blocks
                wm[s, 0] = True
                kd[s] = seq.key_data
                rng_steps[s] = f + 1
                temp[s] = seq.temp
                top_k[s] = seq.top_k
                top_p[s] = seq.top_p
                do_sample[s] = seq.do_sample
                fed_any = True
                fed_this[s] = (f, int(tok))
            if not fed_any:
                break
            nxt, self._dpools = self._draft_decode_fn(
                self._dvals, self._dpools, tokens, positions, tables,
                wm, kd, rng_steps, temp, top_k, top_p, do_sample)
            nxt = np.asarray(nxt)
            with self._lock:
                self._stats["draft_steps"] += 1
            for seq in live:
                s = seq.slot
                if s not in fed_this:
                    continue
                f, ftok = fed_this[s]
                draft_feeds[s].append(ftok)
                # outputs from the last stored token onward extend the
                # proposal chain
                if f >= len(seq.generated) - 1:
                    chains[s].append(int(nxt[s]))

        # ---- verify phase: one S=k+1 target forward over [last
        # stored suffix ++ proposals] per slot
        S = k + 1
        tokens = np.zeros((B, S), np.int32)
        positions = np.zeros((B, S), np.int32)
        tables = np.zeros((B, M), np.int32)
        wm = np.zeros((B, S), bool)
        kd = np.zeros((B, W), np.uint32)
        rng_steps = np.zeros((B, S), np.int32)
        temp = np.ones((B,), np.float32)
        top_k = np.zeros((B,), np.int32)
        top_p = np.ones((B,), np.float32)
        do_sample = np.zeros((B,), bool)
        feeds: Dict[int, List[int]] = {}
        n_props: Dict[int, int] = {}
        for seq in live:
            s = seq.slot
            f0 = seq.decoded
            known = seq.generated[f0:]       # >= 1 (last emitted)
            fed = (known + chains[s])[:S]
            cap = self._max_len - (seq.L + f0)   # positions available
            # candidates beyond the replay region + remaining token
            # budget can never be consumed — don't feed them
            useful = (len(known) - 1) + max(
                seq.max_new - len(seq.generated), 0)
            fed = fed[:max(min(len(fed), cap, useful), 0)]
            if not fed:
                continue      # context full: nothing to verify
            feeds[s] = fed
            n_props[s] = max(len(fed) - len(known), 0)
            for o, tok in enumerate(fed):
                tokens[s, o] = tok
                positions[s, o] = seq.L + f0 + o
                wm[s, o] = True
                rng_steps[s, o] = f0 + o + 1
            tables[s, :len(seq.blocks)] = seq.blocks
            kd[s] = seq.key_data
            temp[s] = seq.temp
            top_k[s] = seq.top_k
            top_p[s] = seq.top_p
            do_sample[s] = seq.do_sample
        if not feeds:
            return
        cand, self._pools = self._verify_fn(
            self._pvals, self._pools, tokens, positions, tables, wm,
            kd, rng_steps, temp, top_k, top_p, do_sample)
        cand = np.asarray(cand)
        dt_ms = (time.perf_counter() - t0) * 1e3

        # ---- host accept: candidate o realizes generated index
        # f0+o+1.  Stored region => replay check; beyond => emit the
        # target's token, continue only while the NEXT fed proposal
        # equals it (the deterministic accept).
        replays = 0
        accepted_total = 0
        proposed_total = 0
        for seq in live:
            s = seq.slot
            if s not in feeds or seq.slot is None:
                continue
            fed = feeds[s]
            f0 = seq.decoded
            proposed_total += n_props[s]
            valid_fed = 0
            for o in range(len(fed)):
                if seq.slot is None:
                    break           # finished mid-verify
                tok = int(cand[s, o])
                idx = f0 + o + 1    # 0-based generated index realized
                if idx < len(seq.generated):
                    replays += 1
                    valid_fed += 1
                    if self._check_replay \
                            and tok != seq.generated[idx]:
                        raise AssertionError(
                            f"replayed verify step for request "
                            f"{seq.rid} produced {tok}, stream "
                            f"already emitted {seq.generated[idx]} — "
                            "paged verify is not bit-stable")
                    continue
                valid_fed += 1      # fed token o was gen[f0+o]
                self._emit(seq, tok)
                if o + 1 < len(fed) and fed[o + 1] == tok:
                    accepted_total += 1
                    continue        # proposal matched: keep going
                break               # mismatch or out of proposals
            if seq.slot is not None:
                seq.decoded = min(f0 + valid_fed,
                                  len(seq.generated) - 1)
                if seq.rt is not None \
                        and seq.decoded % _trace.trace_every() == 0:
                    seq.rt.span_at("decode", dt_ms, step=seq.decoded,
                                   spec=True)
                # draft validity: a fed token counts while it matches
                # the FINAL stream at its index (stored feeds match by
                # construction; proposal feeds match iff accepted) —
                # the draft's KV at those positions is then correct
                df0 = seq.draft_decoded
                nvalid = 0
                for t, ftok in enumerate(draft_feeds[s]):
                    i2 = df0 + t
                    if i2 < len(seq.generated) \
                            and seq.generated[i2] == ftok:
                        nvalid += 1
                    else:
                        break
                seq.draft_decoded = min(df0 + nvalid,
                                        len(seq.generated) - 1)
        with self._lock:
            self._stats["spec_verify_steps"] += 1
            self._stats["spec_proposed"] += proposed_total
            self._stats["spec_accepted"] += accepted_total
            n_verify = self._stats["spec_verify_steps"]
            p_tot = self._stats["spec_proposed"]
            a_tot = self._stats["spec_accepted"]
        _monitor.stat_add("serve_spec_proposed", proposed_total)
        _monitor.stat_add("serve_spec_accepted", accepted_total)
        if _monitor.metrics_enabled():
            _monitor.gauge_set("serve_spec_accept_rate",
                               a_tot / max(p_tot, 1))
        if n_verify % _FLIGHT_DECODE_EVERY == 1:
            _flight.record("serve.spec_verify", steps=n_verify,
                           proposed=proposed_total,
                           accepted=accepted_total,
                           accept_rate=round(a_tot / max(p_tot, 1), 3))
        self._after_step(len(live), replays, dt_ms,
                         bool(do_sample.any()))
