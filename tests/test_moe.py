"""MoE layer + expert parallelism tests.

The reference has no MoE (SURVEY §2.6 marks expert parallelism absent);
built greenfield GShard-style. Tests assert the routing semantics the
GShard paper defines and numeric equality between expert-parallel and
single-device execution on the virtual mesh.
"""
import jax
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.distributed import mesh as mesh_mod


@pytest.fixture(autouse=True)
def fresh_mesh():
    mesh_mod.set_mesh(None)
    yield
    mesh_mod.set_mesh(None)


def _x(b=2, s=8, d=16, seed=0):
    return paddle.to_tensor(
        np.random.RandomState(seed).randn(b, s, d).astype("float32"))


def test_forward_shape_and_aux():
    paddle.seed(0)
    moe = nn.MoELayer(16, 32, num_experts=4, top_k=2)
    x = _x()
    y = moe(x)
    assert y.shape == [2, 8, 16]
    assert moe.l_aux is not None and float(moe.l_aux) > 0


def test_top1_routes_to_argmax_expert():
    paddle.seed(1)
    moe = nn.MoELayer(8, 16, num_experts=4, top_k=1,
                      capacity_factor=100.0)  # no drops
    moe.eval()
    x = _x(1, 4, 8, seed=2)
    y = moe(x)
    # manual: tokens routed by argmax of softmax(x @ gate_w)
    tok = x.numpy().reshape(4, 8)
    logits = tok @ moe.gate_weight.numpy()
    idx = logits.argmax(-1)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs = probs / probs.sum(-1, keepdims=True)
    gate = probs[np.arange(4), idx]
    # cross-check the expert FFN per token (gelu recomputed via jax)
    w1, b1 = moe.w1.numpy(), moe.b1.numpy()
    w2, b2 = moe.w2.numpy(), moe.b2.numpy()
    ref = []
    for t in range(4):
        e = idx[t]
        h = np.asarray(jax.nn.gelu(tok[t] @ w1[e] + b1[e]))
        ref.append((h @ w2[e] + b2[e]) * gate[t])
    np.testing.assert_allclose(y.numpy().reshape(4, 8), np.stack(ref),
                               rtol=2e-4, atol=1e-5)


def test_capacity_drops_overflow_tokens():
    paddle.seed(3)
    d = 8
    moe = nn.MoELayer(d, 16, num_experts=2, top_k=1, capacity_factor=0.25)
    moe.eval()
    # force ALL tokens to expert 0: positive tokens + a gate that scores
    # expert 0 by +10*sum(token), expert 1 by -10*sum(token)
    moe.gate_weight._value = moe.gate_weight._value * 0 + \
        np.array([[10.0, -10.0]] * d, dtype="float32")
    x = paddle.to_tensor(
        np.random.RandomState(4).rand(1, 8, d).astype("float32"))
    y = moe(x).numpy().reshape(8, d)
    # capacity = max(ceil(8/2 * 0.25 * 1), 2) = 2 slots (the _capacity
    # floor) -> first 2 tokens served, the rest dropped to zero
    # (residual path is the caller's job)
    assert np.abs(y[:2]).sum() > 0
    np.testing.assert_allclose(y[2:], 0.0, atol=1e-6)


def test_aux_loss_trains_toward_balance():
    paddle.seed(5)
    moe = nn.MoELayer(8, 16, num_experts=4, top_k=1)
    opt = paddle.optimizer.Adam(learning_rate=0.05,
                                parameters=[moe.gate_weight])
    x = _x(4, 16, 8, seed=6)
    aux0 = None
    for _ in range(30):
        moe(x)
        loss = moe.l_aux
        if aux0 is None:
            aux0 = float(loss)
        loss.backward()
        opt.step()
        opt.clear_grad()
    assert float(loss) < aux0  # router balances (1.0 is the uniform limit)


def test_moe_in_training_loop_decreases_loss():
    paddle.seed(7)
    moe = nn.MoELayer(8, 32, num_experts=2, top_k=2)
    head = nn.Linear(8, 1)
    params = list(moe.parameters()) + list(head.parameters())
    opt = paddle.optimizer.Adam(learning_rate=0.01, parameters=params)
    rng = np.random.RandomState(8)
    x = paddle.to_tensor(rng.rand(4, 8, 8).astype("float32"))
    y = paddle.to_tensor(rng.rand(4, 8, 1).astype("float32"))
    l0 = None
    for _ in range(40):
        out = head(moe(x) + x)  # residual carries dropped tokens
        loss = F.mse_loss(out, y) + 0.01 * moe.l_aux
        if l0 is None:
            l0 = float(loss)
        loss.backward()
        opt.step()
        opt.clear_grad()
    assert float(loss) < l0


def test_expert_parallel_matches_single_device():
    paddle.seed(9)
    x = _x(2, 8, 16, seed=10)
    moe = nn.MoELayer(16, 32, num_experts=4, top_k=2)
    moe.eval()
    y_single = moe(x).numpy()

    # same layer under an ep=4 mesh: weights sharded over experts
    devs = np.array(jax.devices()[:4]).reshape(4)
    from jax.sharding import Mesh
    mesh_mod.set_mesh(Mesh(devs.reshape(1, 4), ("dp", "ep")))
    from paddle_tpu.distributed.meta_parallel import mark_sharding
    from jax.sharding import PartitionSpec as P
    for p, spec in ((moe.w1, P("ep", None, None)),
                    (moe.b1, P("ep", None)),
                    (moe.w2, P("ep", None, None)),
                    (moe.b2, P("ep", None))):
        mark_sharding(p, spec)
    y_ep = moe(x).numpy()
    np.testing.assert_allclose(y_ep, y_single, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------
# DroplessMoELayer: the line between its two forms (PR 33)
# ---------------------------------------------------------------------
@pytest.mark.parametrize("top_k,num_experts,tokens,masked", [
    # the Kimi cell's router, 8 of 256: its 128-row decode step keeps
    # the masked pass, every prefill shape (256 tokens up) the blocks
    (8, 256, 128, True), (8, 256, 256, False), (8, 256, 2048, False),
    # the LFM2 cell's, 4 of 32: its 256-row step takes the pass too
    (4, 32, 128, True), (4, 32, 256, True), (4, 32, 384, False),
    # every expert chosen by every token: nothing is multiplied in vain
    (4, 4, 100000, True),
])
def test_the_masked_pass_follows_the_routers_shape_and_the_ridge(
        top_k, num_experts, tokens, masked):
    from paddle_tpu.nn.layer import moe as MOE
    assert MOE._RIDGE == pytest.approx(240.5, abs=0.1)     # TPU v5e
    assert MOE.masked_pass_pays(tokens, top_k, num_experts) is masked
    layer = MOE.DroplessMoELayer(8, 4, num_experts, top_k=top_k,
                                 held_experts=(0, min(num_experts, 4)))
    assert layer.loops_on_device(tokens) is not masked


def test_the_routers_normalising_constant_is_the_models():
    """``w = s / (sum(s) + eps)``: 1e-20 unless the model says
    otherwise (Kimi-Linear's; LFM2's published constant is 1e-6), and
    the constant given is the one used."""
    import jax.numpy as jnp
    from paddle_tpu.nn.layer import moe as MOE
    assert MOE.DroplessMoELayer(8, 4, 4, top_k=2).norm_eps == 1e-20
    x = jnp.ones((3, 8), jnp.float32)
    router = jnp.zeros((8, 4), jnp.float32)        # every score is 0.5
    rb = jnp.asarray([3.0, 2.0, 1.0, 0.0])
    for eps in (1e-20, 1e-6, 1.0):
        _, w, here = MOE._route(x, router, rb, 2, 1.0, (0, 4), eps)
        np.testing.assert_allclose(w, 0.5 / (1.0 + eps), rtol=1e-6)
        assert bool(here.all())
    paddle.seed(0)
    m = MOE.DroplessMoELayer(8, 4, 4, top_k=2, norm_eps=1.0)
    m.router._value = jnp.zeros_like(m.router._value)
    halved = m.apply_values(x)[0]
    m.norm_eps = 1e-20
    np.testing.assert_allclose(halved * 2, m.apply_values(x)[0], rtol=1e-5)


def test_row_products_are_counted_only_when_asked():
    import jax.numpy as jnp
    from paddle_tpu.nn.layer import moe as MOE
    paddle.seed(0)
    m = MOE.DroplessMoELayer(8, 4, 8, top_k=2, held_experts=(0, 4))
    x = jnp.ones((5, 8), jnp.float32)
    assert len(m.apply_values(x)) == 3
    y, picks, load, rows = m.apply_values(x, count_rows=True)
    assert rows == 5 * 4 and int(picks) <= 5 * 2
