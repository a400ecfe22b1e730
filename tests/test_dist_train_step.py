"""DistributedTrainStep: hybrid-parallel compiled step on the 8-device mesh.

The reference's equivalents are meta-optimizer graph rewrites asserted by
test_fleet_sharding_meta_optimizer.py / test_fleet_pipeline_meta_optimizer.py
(op-presence checks); here we can assert the strong property instead:
*sharded training numerics equal single-device numerics* for every
strategy combination, on simulated 8-device meshes (SURVEY.md §4 lesson).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.distributed as dist
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.distributed.fleet import DistributedStrategy, \
    DistributedTrainStep


@pytest.fixture(autouse=True)
def fresh_mesh():
    mesh_mod.set_mesh(None)
    yield
    mesh_mod.set_mesh(None)


def _build(seed=11):
    paddle.seed(seed)
    m = nn.Sequential(nn.Linear(16, 64), nn.GELU(), nn.Linear(64, 8))
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=m.parameters())
    return m, opt


def _loss_fn(model):
    def f(x, y):
        return ((model(x) - y) ** 2).mean()
    return f


def _data(n=6, b=16):
    rng = np.random.default_rng(5)
    return (rng.normal(size=(n, b, 16)).astype(np.float32),
            rng.normal(size=(n, b, 8)).astype(np.float32))


def _train_single(n_steps=6):
    m, opt = _build()
    xs, ys = _data(n_steps)
    losses = []
    for x, y in zip(xs, ys):
        loss = _loss_fn(m)(paddle.to_tensor(x), paddle.to_tensor(y))
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss._value))
    return m, losses


def _train_dist(strategy, n_steps=6):
    m, opt = _build()
    step = DistributedTrainStep(m, _loss_fn(m), opt, strategy)
    xs, ys = _data(n_steps)
    losses = []
    for x, y in zip(xs, ys):
        losses.append(float(step(paddle.to_tensor(x),
                                 paddle.to_tensor(y))._value))
    return m, losses


def _assert_same(m1, m2, rtol=2e-4, atol=2e-4):
    for (n1, p1), (n2, p2) in zip(m1.named_parameters(),
                                  m2.named_parameters()):
        np.testing.assert_allclose(np.asarray(p1._value),
                                   np.asarray(p2._value),
                                   rtol=rtol, atol=atol, err_msg=n1)


def test_plain_dp_step_matches_eager():
    m1, l1 = _train_single()
    m2, l2 = _train_dist(DistributedStrategy())
    np.testing.assert_allclose(l1, l2, rtol=2e-4, atol=2e-4)
    _assert_same(m1, m2)


@pytest.mark.parametrize("stage", [1, 2, 3])
def test_zero_sharding_stages_match(stage):
    s = DistributedStrategy()
    s.sharding = True
    s.sharding_configs = {"stage": stage, "sharding_degree": 8}
    s.hybrid_configs = {"dp_degree": 1}
    m1, l1 = _train_single()
    m2, l2 = _train_dist(s)
    np.testing.assert_allclose(l1, l2, rtol=2e-4, atol=2e-4)
    _assert_same(m1, m2)
    if stage >= 3:
        # parameters must actually be sharded over fsdp
        specs = [getattr(p._value, "sharding", None)
                 for _, p in m2.named_parameters()]
        assert any(sp is not None and "fsdp" in str(sp.spec)
                   for sp in specs), specs


def test_zero3_opt_state_is_sharded():
    s = DistributedStrategy()
    s.sharding = True
    s.sharding_configs = {"stage": 3, "sharding_degree": 8}
    s.hybrid_configs = {"dp_degree": 1}
    m, _ = _train_dist(s, n_steps=2)


def test_gradient_merge_matches_big_batch():
    """k_steps micro-batches must equal one big-batch step (the reference's
    GradientMergeOptimizer contract, gradient_merge_optimizer.py)."""
    xs, ys = _data(4, 16)

    # big batch: one step on all 64 rows with SGD
    paddle.seed(9)
    m1 = nn.Linear(16, 8)
    o1 = paddle.optimizer.SGD(learning_rate=0.1, parameters=m1.parameters())
    X = np.concatenate(xs), np.concatenate(ys)
    loss = ((m1(paddle.to_tensor(X[0])) - paddle.to_tensor(X[1])) ** 2).mean()
    loss.backward()
    o1.step()

    # gradient merge: 4 micro-steps, avg
    paddle.seed(9)
    m2 = nn.Linear(16, 8)
    o2 = paddle.optimizer.SGD(learning_rate=0.1, parameters=m2.parameters())
    s = DistributedStrategy()
    s.gradient_merge = True
    s.gradient_merge_configs = {"k_steps": 4, "avg": True}
    step = DistributedTrainStep(m2, _loss_fn(m2), o2, s)
    for x, y in zip(xs, ys):
        step(paddle.to_tensor(x), paddle.to_tensor(y))
    _assert_same(m1, m2, rtol=1e-4, atol=1e-4)


def test_recompute_strategy_matches():
    s = DistributedStrategy()
    s.recompute = True
    m1, l1 = _train_single()
    m2, l2 = _train_dist(s)
    np.testing.assert_allclose(l1, l2, rtol=2e-4, atol=2e-4)
    _assert_same(m1, m2)


def test_recompute_function_inside_jit():
    """fleet.utils.recompute must be numerically transparent: a step
    through the remat block equals a step without it (remat trades memory
    for FLOPs, never math)."""
    from paddle_tpu.distributed.fleet import recompute
    rng = np.random.default_rng(4)
    x = rng.normal(size=(4, 8)).astype(np.float32)
    y = rng.normal(size=(4, 4)).astype(np.float32)

    def run(use_remat):
        paddle.seed(2)
        inner = nn.Linear(8, 8)
        outer = nn.Linear(8, 4)
        model = nn.LayerList([inner, outer])

        def loss_fn(xx, yy):
            h = recompute(inner, xx) if use_remat else inner(xx)
            return ((outer(h) - yy) ** 2).mean()

        opt = paddle.optimizer.SGD(learning_rate=0.05,
                                   parameters=model.parameters())
        step = DistributedTrainStep(model, loss_fn, opt,
                                    DistributedStrategy())
        losses = [float(step(paddle.to_tensor(x),
                             paddle.to_tensor(y))._value)
                  for _ in range(3)]
        return model, losses

    m1, l1 = run(False)
    m2, l2 = run(True)
    np.testing.assert_allclose(l1, l2, rtol=1e-5, atol=1e-6)
    _assert_same(m1, m2, rtol=1e-5, atol=1e-6)


def test_step_is_collectable_after_last_reference():
    """The compile observatory's process-global log must not pin a
    step: its lazy memory-analysis thunk once held the jitted step
    strongly, keeping the model and its optimizer state on the device
    for the life of the process (found on a v5e as 6.6 GB piled up on
    chip 0 after the one-chip phase of chip_smoke.py)."""
    import gc
    import weakref
    paddle.seed(3)
    model = nn.Linear(8, 4)
    opt = paddle.optimizer.Adam(learning_rate=0.01,
                                parameters=model.parameters())
    step = DistributedTrainStep(
        model, lambda x, y: ((model(x) - y) ** 2).mean(), opt,
        DistributedStrategy())
    step(paddle.to_tensor(np.ones((4, 8), np.float32)),
         paddle.to_tensor(np.ones((4, 4), np.float32)))
    ref = weakref.ref(step)
    del step, model, opt
    gc.collect()
    assert ref() is None, "a dead DistributedTrainStep is still pinned"


def test_recompute_eager_is_transparent(monkeypatch):
    """Outside any trace there is no residual graph to trade: recompute
    must call the function directly and hand back ITS result (jax 0.9
    dropped ``jax.core.trace_state_clean``, and the old ``except
    AttributeError`` then treated every eager call as traced)."""
    import jax

    from paddle_tpu.distributed.fleet import recompute
    monkeypatch.setattr(jax, "checkpoint", lambda *a, **k: (_ for _ in ())
                        .throw(AssertionError("eager call took the "
                                              "checkpoint branch")))
    sentinel = object()
    assert recompute(lambda a, b=None: (sentinel, a, b), 3, b=4) \
        == (sentinel, 3, 4)


def test_tp_plus_fsdp_composed():
    """ZeRO-3 composed with tensor parallelism (the reference cannot do
    this — sharding_optimizer is DP-only; north-star configs[4])."""
    paddle.seed(21)

    class TPModel(nn.Layer):
        def __init__(self):
            super().__init__()
            self.col = dist.ColumnParallelLinear(16, 64,
                                                 gather_output=False)
            self.row = dist.RowParallelLinear(64, 8)

        def forward(self, x):
            return self.row(F.gelu(self.col(x)))

    s = DistributedStrategy()
    s.sharding = True
    s.sharding_configs = {"stage": 3, "sharding_degree": 2}
    s.tensor_parallel = True
    s.tensor_parallel_configs = {"tensor_parallel_degree": 2}
    s.hybrid_configs = {"dp_degree": 2, "mp_degree": 2,
                        "sharding_degree": 2}

    mesh_mod.init_mesh({"dp": 2, "fsdp": 2, "tp": 2})
    m = TPModel()
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=m.parameters())
    step = DistributedTrainStep(m, _loss_fn(m), opt, s,
                                mesh=mesh_mod.get_mesh())
    xs, ys = _data(3)
    losses = [float(step(paddle.to_tensor(x), paddle.to_tensor(y))._value)
              for x, y in zip(xs, ys)]
    assert losses[-1] < losses[0]


def test_rng_state_resume_bit_exact():
    # review r3: the device-resident key chain must checkpoint/resume so
    # dropout streams continue bit-exactly
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    from paddle_tpu.distributed import fleet, mesh as mesh_mod
    from paddle_tpu.distributed.fleet.dist_step import DistributedTrainStep

    def build():
        paddle.seed(11)
        net = nn.Sequential(nn.Linear(8, 32), nn.Dropout(0.5),
                            nn.Linear(32, 1))
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        def loss_fn(x, y):
            return F.mse_loss(net(x), y)
        strategy = fleet.DistributedStrategy()
        mesh_mod.set_mesh(None)
        mesh = mesh_mod.init_mesh({"dp": -1})
        return net, DistributedTrainStep(net, loss_fn, opt, strategy,
                                         mesh=mesh)

    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.standard_normal((16, 8)).astype("float32"))
    y = paddle.to_tensor(rng.standard_normal((16, 1)).astype("float32"))

    net_a, step_a = build()
    ref = [float(step_a(x, y)) for _ in range(6)]

    net_b, step_b = build()
    got = [float(step_b(x, y)) for _ in range(3)]
    saved = step_b.rng_state()
    params = {k: v.numpy() for k, v in net_b.state_dict().items()}
    # "resume": fresh everything, restore params + rng chain
    net_c, step_c = build()
    paddle.seed(999)   # resumed process has a different global stream
    net_c.set_state_dict({k: paddle.to_tensor(v)
                          for k, v in params.items()})
    step_c.load_rng_state(saved)
    got += [float(step_c(x, y)) for _ in range(3)]
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-7)


def test_seed_reseeds_step_dropout_chain():
    # review r3: paddle.seed() mid-session must re-deterministize the
    # compiled step's dropout stream
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    from paddle_tpu.distributed import fleet, mesh as mesh_mod
    from paddle_tpu.distributed.fleet.dist_step import DistributedTrainStep

    paddle.seed(5)
    net = nn.Sequential(nn.Linear(4, 64), nn.Dropout(0.5), nn.Linear(64, 1))
    opt = paddle.optimizer.SGD(learning_rate=0.0,
                               parameters=net.parameters())
    def loss_fn(x, y):
        return F.mse_loss(net(x), y)
    strategy = fleet.DistributedStrategy()
    mesh_mod.set_mesh(None)
    mesh = mesh_mod.init_mesh({"dp": -1})
    step = DistributedTrainStep(net, loss_fn, opt, strategy, mesh=mesh)
    rng = np.random.RandomState(1)
    x = paddle.to_tensor(rng.standard_normal((8, 4)).astype("float32"))
    y = paddle.to_tensor(np.zeros((8, 1), np.float32))
    paddle.seed(77)
    a = [float(step(x, y)) for _ in range(3)]   # lr=0: loss varies only
    paddle.seed(77)                             # through dropout masks
    b = [float(step(x, y)) for _ in range(3)]
    np.testing.assert_allclose(a, b, rtol=1e-7)
