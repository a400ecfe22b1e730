"""paddle_tpu.inference.migration — live-sequence KV migration for
graceful replica drain (ISSUE 18).

``drain(replica)`` on the gateway must move a replica's live
conversations elsewhere without the client seeing anything but a short
stall.  Two mechanisms, in preference order:

- **KV migration** (:func:`export_sequence` / :func:`import_sequence`):
  serialize the sequence's scheduler state (prompt, emitted tokens,
  RNG key data, priorities, the REMAINING deadline) plus the physical
  pool rows its block table points at, then rebuild it on the target —
  fresh block ids, same bytes.  Physical block ids never enter the
  attention math (tables are gather indices) and every pool tensor
  round-trips through numpy at its own dtype, so a migrated sequence's
  continuation is BIT-IDENTICAL to never having moved: the target's
  next decode step reads exactly the K/V the source would have read.
- **token replay** (the cheap fallback the gateway uses when the
  target lacks capacity, the geometries differ, or the blob carries no
  KV because the sequence was waiting/evicted): ship only the prompt +
  emitted tokens and re-submit with ``replay_tokens=`` — re-prefill
  recomputes the KV and the ISSUE 8 replay contract makes the
  continuation token-identical (``check_replay`` asserts it live).

Speculative-decoding servers take the replay path by construction: the
draft model's pools trail the emitted stream (``draft_decoded``), and
shipping target KV without coherent draft KV would silently sink the
accept rate — :class:`MigrationUnsupported` routes those to replay.

Everything here runs ON the scheduler thread of the server it touches
(via ``_run_on_scheduler``): sequence/slot/pool state is only coherent
between decode steps, and keeping mutation there keeps the lock graph
exactly as the lint baseline declares it.
"""
from __future__ import annotations

import time
from typing import Dict, Optional

import numpy as np

from .serving import ServeError

__all__ = ["MigrationUnsupported", "export_sequence", "import_sequence"]


class MigrationUnsupported(ServeError):
    """The KV path cannot carry this sequence (no capacity on the
    target, mismatched pool geometry, a spec-decode server, or a blob
    with no KV) — the caller falls back to token replay."""


def export_sequence(server, request_id: int) -> Optional[dict]:
    """Serialize one live request off ``server`` and REMOVE it there
    (its stream ends with ``finish_reason="migrated"``).  Returns the
    blob, or None when the request is unknown (already finished).

    An ACTIVE sequence ships its pool rows (KV valid through position
    ``L + decoded - 1``); a WAITING one (queued or evicted) has no
    blocks to ship and returns a tokens-only blob (``kv is None``) for
    the replay fallback.  ``deadline_remaining`` is measured here and
    re-anchored at import — the wall time a migration takes counts
    against the request's budget, it does not reset it.
    """
    from .recurrent_state import refuse_migration
    refuse_migration(server)   # per-slot recurrent state has no export

    def _do():
        with server._lock:
            seq = next((s for s in server._active.values()
                        if s.rid == request_id), None)
            waiting = None
            if seq is None:
                waiting = next((s for s in server._waiting
                                if s.rid == request_id), None)
                if waiting is not None:
                    server._waiting.remove(waiting)
            active = seq is not None
            if not active:
                seq = waiting
        if seq is None:
            return None
        blob: Dict = {
            "prompt": np.asarray(seq.prompt, np.int32),
            "generated": list(seq.generated),
            "decoded": int(seq.decoded),
            "max_new": seq.max_new,
            "eos": seq.eos,
            "do_sample": seq.do_sample,
            "temp": seq.temp,
            "top_k": seq.top_k,
            "top_p": seq.top_p,
            "key_data": np.asarray(seq.key_data),
            "priority": seq.priority,
            "tenant": seq.tenant,
            "evictions": seq.evictions,
            "deadline_remaining": max(
                seq.deadline - time.monotonic(), 0.0),
            "block_size": server._bs,
            "kv": None,
        }
        if active and not server._spec and seq.blocks:
            # gather the pool rows BEFORE releasing: an unreffed block
            # is recyclable the moment another admission wants it
            idx = np.asarray(seq.blocks, np.int64)
            blob["kv"] = [{k: np.asarray(v)[idx]
                           for k, v in layer.items()}
                          for layer in server._pools]
            blob["n_blocks"] = len(seq.blocks)
        server._release(seq)
        with server._lock:
            server._stats["migrated_out"] += 1
        if seq.rt is not None:
            seq.rt.finish("migrated", tokens=len(seq.generated))
        seq.stream._end("migrated")
        return blob
    return server._run_on_scheduler(_do)


def import_sequence(server, blob: dict):
    """Rebuild an exported sequence on ``server``: allocate fresh
    blocks, write the shipped pool rows at them, and enter the
    sequence directly into the active set (no prefill — its KV is
    already valid through ``L + decoded - 1``; a mid-replay sequence
    keeps replaying on the target).  Returns the new
    :class:`~paddle_tpu.inference.generation_server.GenerationStream`.

    Raises :class:`MigrationUnsupported` when the KV path cannot apply
    (the caller re-submits with ``replay_tokens=`` instead); the
    server is left exactly as found.
    """
    from .generation_server import _GenSeq
    from .recurrent_state import refuse_migration
    refuse_migration(server)

    kv = blob.get("kv")
    if kv is None:
        raise MigrationUnsupported("blob carries no KV (sequence was "
                                   "waiting) — replay it instead")
    if server._spec:
        raise MigrationUnsupported(
            "target runs speculative decoding (draft KV cannot be "
            "reconstructed) — replay instead")
    if int(blob["block_size"]) != server._bs \
            or len(kv) != len(server._pools) \
            or any(v.shape[1:] != np.asarray(
                server._pools[i][k]).shape[1:]
                for i, layer in enumerate(kv)
                for k, v in layer.items()):
        raise MigrationUnsupported("pool geometry mismatch — replay "
                                   "instead")

    def _do():
        import jax.numpy as jnp

        n = int(blob["n_blocks"])
        with server._lock:
            if not server._free_slots:
                raise MigrationUnsupported("no free slot on target")
            got = []
            for _ in range(n):
                b = server._cache.alloc()
                if b is None:
                    break
                got.append(b)
            if len(got) < n:
                for b in got:
                    server._cache.unref(b)
                raise MigrationUnsupported(
                    f"target pool has room for {len(got)}/{n} blocks")
            slot = server._free_slots.pop()
            server._rid += 1
            server._arrival += 1
            rid, arrival = server._rid, server._arrival
        # device writes outside the lock, on the scheduler thread:
        # nothing else touches the pools between steps
        idx = np.asarray(got, np.int32)
        server._pools = [
            {k: v.at[idx].set(jnp.asarray(rows[k]))
             for k, v in layer.items()}
            for layer, rows in zip(server._pools, kv)]
        now = time.monotonic()
        prompt = np.asarray(blob["prompt"], np.int32)
        seq = _GenSeq(rid, prompt, blob["max_new"], blob["eos"],
                      blob["do_sample"], blob["temp"], blob["top_k"],
                      blob["top_p"],
                      np.asarray(blob["key_data"], np.uint32),
                      blob["priority"], arrival,
                      now + float(blob["deadline_remaining"]),
                      tenant=blob.get("tenant"))
        seq.generated = list(blob["generated"])
        seq.decoded = int(blob["decoded"])
        seq.evictions = int(blob.get("evictions", 0))
        seq.blocks = got
        seq.slot = slot
        seq.t_first_tok = now    # first token long since delivered
        with server._lock:
            server._active[slot] = seq
            server._stats["migrated_in"] += 1
            # index the KV-valid full blocks (prompt + replayed
            # tokens) so survivors' traffic can alias them
            server._cache.insert(
                prompt.tolist() + seq.generated[:seq.decoded], got)
        return seq.stream
    return server._run_on_scheduler(_do)
