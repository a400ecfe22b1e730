"""What ``GenerationServer`` does for a model whose cache is not only
keys and values: a model with ``has_recurrent_state()`` (KDA layers:
a recurrent matrix and convolution tails per SLOT, fixed in size
whatever the context; LFM2's gated short convolutions: tails alone)
beside block-paged pools (latent pages, or plain K/V pages in the
layers that attend).

- The per-slot state lives in the same ``pools`` list as the paged
  pools (``model.init_paged_cache(num_blocks, block, num_slots)``), is
  donated through every step like them, and is never gathered or
  copied by the scheduler.  In a decode step row i IS slot i, so
  nothing new is staged; batched prefill passes each row's slot as one
  ``[B] int32``, ``forward_paged(..., slots=)`` (a row that holds no
  sequence names ``num_slots``, which no write reaches).
- A slot's state needs no zeroing on release: a prefill that starts a
  sequence (``start == 0``) starts from zero whatever the slot's last
  owner left, and every admission is such a prefill, since nothing
  below shares prefixes.  Eviction drops the state with the slot;
  re-admission re-prefills and replays, which rebuilds it.
- Prefix sharing, speculation and migration know K/V blocks only: they
  would need snapshots of the state at block boundaries to alias, roll
  back or ship.  They raise :class:`RecurrentStateUnsupported`.

For a model without such state none of this runs: the server builds,
stages and dispatches exactly what it did.

A latent-attention model with no per-slot state at all (every layer
pages a latent row a token) is served as a K/V model is, pools and
migration included, with one limit of its own: a multi-token step
expands K and V from its own block and attends over that alone, so it
has to start its sequence.  Such a model says so
(``prefill_starts_sequences_only()``), and prefix sharing and
speculation, which run a multi-token step from the middle of a
sequence, raise :class:`MidSequenceStepUnsupported` at construction:
under a trace that step would serve NaN, it cannot raise.

Apart from the state: a model whose ``step_counters()`` names int32
counters returns them as ``forward_paged``'s third value; the decode
program hands them back in the same vector as the sampled tokens (one
fetch) and ``stats()`` adds them up under their names.  And a model
with ``loops_on_device(n_tokens)`` says which programs hold a device
loop whose steps branch; the server's sampler puts no ``cond`` behind
those (``GenerationServer._build_programs``).
"""
from __future__ import annotations

__all__ = ["RecurrentStateUnsupported", "MidSequenceStepUnsupported",
           "is_stateful", "starts_sequences_only", "check_features",
           "refuse_migration", "pool_bytes"]


class RecurrentStateUnsupported(NotImplementedError):
    """A feature that knows K/V blocks only was asked of a model with
    per-slot recurrent state."""


class MidSequenceStepUnsupported(NotImplementedError):
    """A feature that runs a multi-token step from the middle of a
    sequence was asked of a model whose multi-token steps attend over
    their own block only."""


def is_stateful(model) -> bool:
    return bool(getattr(model, "has_recurrent_state", lambda: False)())


def starts_sequences_only(model) -> bool:
    return bool(getattr(model, "prefill_starts_sequences_only",
                        lambda: False)())


def check_features(model, prefix_cache, draft_model) -> bool:
    """Refuse what cannot hold for ``model``; returns whether it keeps
    recurrent state."""
    if not is_stateful(model):
        for m in (model, draft_model):
            if m is None or not starts_sequences_only(m):
                continue
            if prefix_cache:
                raise MidSequenceStepUnsupported(
                    "prefix_cache=True: a warm admission prefills the "
                    "suffix behind a shared prefix, and this model's "
                    "latent layers attend over the fresh block only "
                    "(needs latent prefill over earlier pages)")
            if draft_model is not None:
                raise MidSequenceStepUnsupported(
                    "speculative decoding: verification scores several "
                    "tokens from the middle of a sequence, and this "
                    "model's latent layers attend over the fresh block "
                    "only (needs latent prefill over earlier pages)")
        return False
    if prefix_cache:
        raise RecurrentStateUnsupported(
            "prefix_cache=True: a shared prefix's blocks carry no "
            "recurrent state to resume from (needs state snapshots at "
            "block boundaries)")
    if draft_model is not None:
        raise RecurrentStateUnsupported(
            "speculative decoding: a rejected proposal would have to "
            "roll the recurrent state back (needs state snapshots)")
    return True


def refuse_migration(server):
    if server._stateful:
        raise RecurrentStateUnsupported(
            "live migration ships K/V blocks; this model's per-slot "
            "recurrent state has no export yet")


def pool_bytes(pools) -> dict:
    """Bytes of the per-slot state, of the latent pages and of the K/V
    pages (scales and pooled keys included) in ``pools``, and the number
    of state slots."""
    out = {"state_slots": 0, "state_bytes": 0, "latent_pool_bytes": 0,
           "kv_pool_bytes": 0}
    for d in pools:
        for k, v in d.items():
            if k in ("state", "conv"):
                out["state_bytes"] += int(v.nbytes)
                out["state_slots"] = int(v.shape[0])
            elif k == "latent":
                out["latent_pool_bytes"] += int(v.nbytes)
            elif k in ("k", "v", "k_scale", "v_scale", "kmax"):
                out["kv_pool_bytes"] += int(v.nbytes)
    return out
