"""The harness sees a broken timed path: each test skips the look for
a chip and drives the rest of a run with one fault planted underneath,
and ``correct`` comes out false."""
import numpy as np
import pytest


def _unchanged_state(step, model, opt):
    """A step that returns its state unchanged."""
    import jax
    import jax.numpy as jnp

    def call(*args):
        # the step donates its state: keep copies
        params = {n: jnp.copy(p._value)
                  for n, p in model.named_parameters()}
        states = jax.tree_util.tree_map(
            jnp.copy, opt.opt_state()) if call.built else None
        loss = step(*args)
        call.built = True
        if states is not None:
            for n, p in model.named_parameters():
                p._value = params[n]
            opt.load_opt_state(states)
        return loss
    call.built = False
    return call


def _half_batch(step, model, opt):
    """Half of the batch left out, the mean taken over the rest."""
    import paddle_tpu as paddle

    def call(*args):
        half = [paddle.to_tensor(np.asarray(a._value)[:a.shape[0] // 2])
                for a in args]
        return step(*half)
    return call


def _no_exchange(step, model, opt):
    """Every chip computes on the first chip's rows: the gradient is
    that of one shard, as if the exchange between chips were left out."""
    import paddle_tpu as paddle

    def call(*args):
        n = args[0].shape[0] // 4
        rep = [paddle.to_tensor(np.tile(
            np.asarray(a._value)[:n], (4,) + (1,) * (a._value.ndim - 1)))
            for a in args]
        return step(*rep)
    return call


def _altered_token(server):
    """One token of every answer altered where it is produced."""
    real = server.submit

    def submit(*a, **kw):
        stream = real(*a, **kw)
        emit, n = stream._emit, [0]

        def bad_emit(tok):
            n[0] += 1
            emit((tok + 7) % 251 + 1 if n[0] == 2 else tok)
        stream._emit = bad_emit
        return stream
    server.submit = submit


@pytest.mark.parametrize("workload,sabotage", [
    ("bert-base-train", _unchanged_state),
    ("bert-base-train", _half_batch),
    ("mistral7b-train-fsdp4", _unchanged_state),
    ("mistral7b-train-fsdp4", _half_batch),
    ("mistral7b-train-fsdp4", _no_exchange),
    ("mistral7b-serve-decode", _altered_token),
    ("mistral7b-serve-prefill", _altered_token),
], ids=lambda x: getattr(x, "__name__", x))
def test_fault_is_seen(run_toy, workload, sabotage):
    res = run_toy(workload, seed=9, seconds=0.5, sabotage=sabotage)
    assert res["correct"] is False, res["compared"]
    over = [k for k, (v, lim) in res["compared"].items()
            if lim is not None and v is not None and v > lim]
    assert over, res["compared"]
