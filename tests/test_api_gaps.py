"""Small API-parity additions: addmm, SiLU, weight_norm/spectral_norm,
temporal_shift, get_cudnn_version."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F


def test_addmm():
    inp = paddle.ones([2, 2])
    x = paddle.to_tensor(np.arange(6, dtype="float32").reshape(2, 3))
    y = paddle.ones([3, 2])
    out = paddle.addmm(inp, x, y, beta=2.0, alpha=0.5)
    ref = 2.0 * np.ones((2, 2)) + 0.5 * (x.numpy() @ np.ones((3, 2)))
    np.testing.assert_allclose(out.numpy(), ref)


def test_silu_alias():
    assert nn.SiLU is nn.Silu
    x = paddle.to_tensor(np.array([1.0], dtype="float32"))
    np.testing.assert_allclose(nn.SiLU()(x).numpy(),
                               x.numpy() / (1 + np.exp(-x.numpy())),
                               rtol=1e-6)


def test_weight_norm_roundtrip():
    lin = nn.Linear(4, 3)
    w0 = lin.weight.numpy().copy()
    nn.utils.weight_norm(lin, dim=0)
    names = dict(lin.named_parameters())
    assert "weight_g" in names and "weight_v" in names
    x = paddle.to_tensor(np.random.RandomState(0).rand(2, 4)
                         .astype("float32"))
    out1 = lin(x)
    # effective weight equals the original right after reparameterization
    np.testing.assert_allclose(lin.weight.numpy(), w0, atol=1e-5)
    # grads flow into g and v
    out1.sum().backward()
    assert names["weight_g"].grad is not None
    assert names["weight_v"].grad is not None
    nn.utils.remove_weight_norm(lin)
    names = dict(lin.named_parameters())
    assert "weight_g" not in names and "weight" in names
    np.testing.assert_allclose(lin.weight.numpy(), w0, atol=1e-5)


def test_weight_norm_trains():
    paddle.seed(0)
    lin = nn.Linear(4, 1)
    nn.utils.weight_norm(lin)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=lin.parameters())
    rng = np.random.RandomState(1)
    x = paddle.to_tensor(rng.rand(16, 4).astype("float32"))
    y = paddle.to_tensor(rng.rand(16, 1).astype("float32"))
    l0 = None
    for _ in range(30):
        loss = F.mse_loss(lin(x), y)
        if l0 is None:
            l0 = float(loss)
        loss.backward()
        opt.step()
        opt.clear_grad()
    assert float(loss) < l0


def test_spectral_norm_bounds_sigma():
    paddle.seed(2)
    lin = nn.Linear(8, 8)
    # inflate the weight so sigma >> 1
    lin.weight._value = lin.weight._value * 50.0
    nn.utils.spectral_norm(lin, n_power_iterations=20)
    w = np.asarray(lin.weight.numpy())
    sigma = np.linalg.svd(w, compute_uv=False)[0]
    assert sigma == pytest.approx(1.0, rel=5e-2)


def test_temporal_shift():
    t, n, c = 4, 1, 4
    x = np.arange(t * c, dtype="float32").reshape(t, c, 1, 1)
    out = F.temporal_shift(paddle.to_tensor(x), seg_num=t,
                           shift_ratio=0.25).numpy()
    # channel 0 shifts backward: out[t] = x[t+1], last zero
    np.testing.assert_allclose(out[:-1, 0, 0, 0], x[1:, 0, 0, 0])
    assert out[-1, 0, 0, 0] == 0.0
    # channel 1 shifts forward: out[t] = x[t-1], first zero
    np.testing.assert_allclose(out[1:, 1, 0, 0], x[:-1, 1, 0, 0])
    assert out[0, 1, 0, 0] == 0.0
    # remaining channels unchanged
    np.testing.assert_allclose(out[:, 2:], x[:, 2:])


def test_get_cudnn_version():
    assert paddle.get_cudnn_version() is None


def test_remove_weight_norm_keeps_last_update():
    """Folding must derive from the CURRENT g/v, not a stale cache."""
    paddle.seed(4)
    lin = nn.Linear(3, 2)
    nn.utils.weight_norm(lin)
    x = paddle.to_tensor(np.ones((1, 3), np.float32))
    opt = paddle.optimizer.SGD(learning_rate=0.5,
                               parameters=lin.parameters())
    lin(x).sum().backward()
    opt.step()  # g/v move AFTER the last forward
    g = dict(lin.named_parameters())["weight_g"].numpy()
    v = dict(lin.named_parameters())["weight_v"].numpy()
    expect = g * v / np.maximum(
        np.sqrt((v * v).sum(axis=1, keepdims=True)), 1e-12)
    nn.utils.remove_weight_norm(lin)
    np.testing.assert_allclose(lin.weight.numpy(), expect, atol=1e-6)


def test_spectral_norm_zero_iterations():
    lin = nn.Linear(4, 4)
    nn.utils.spectral_norm(lin, n_power_iterations=0)
    out = lin(paddle.to_tensor(np.ones((1, 4), np.float32)))
    assert np.isfinite(out.numpy()).all()


def test_temporal_shift_validation():
    x = paddle.to_tensor(np.ones((10, 4, 1, 1), np.float32))
    with pytest.raises(ValueError, match="divisible"):
        F.temporal_shift(x, seg_num=4)
    with pytest.raises(ValueError, match="shift_ratio"):
        F.temporal_shift(paddle.to_tensor(np.ones((8, 4, 1, 1),
                                                  np.float32)),
                         seg_num=4, shift_ratio=0.6)


def test_require_version_warns_both_bounds():
    # ADVICE r2: max_version used to disable ALL checking
    import warnings
    from paddle_tpu.utils import require_version
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert require_version("9.0", "10.0") is True
    assert any("min=" in str(x.message) for x in w)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert require_version("0.1", "0.2") is True
    assert any("max=" in str(x.message) for x in w)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert require_version("0.1") is True
    assert not w


def test_rng_impl_flag_typed_keys():
    # FLAGS_rng_impl=rbg mints typed keys that split/draw consistently
    import jax
    import jax.numpy as jnp
    from paddle_tpu.framework import flags
    from paddle_tpu.framework.random import make_key
    old = flags.get_flags("FLAGS_rng_impl")["FLAGS_rng_impl"]
    try:
        flags.set_flags({"FLAGS_rng_impl": "rbg"})
        k = make_key(7)
        k1, k2 = jax.random.split(k)
        a = jax.random.bernoulli(k1, 0.5, (128,))
        assert a.dtype == jnp.bool_
        flags.set_flags({"FLAGS_rng_impl": "threefry2x32"})
        kt = make_key(7)
        b1 = jax.random.uniform(jax.random.split(kt)[0], (4,))
        b2 = jax.random.uniform(jax.random.split(make_key(7))[0], (4,))
        assert (jnp.asarray(b1) == jnp.asarray(b2)).all()   # reproducible
    finally:
        flags.set_flags({"FLAGS_rng_impl": old})


def test_rng_state_serializable_roundtrip(tmp_path):
    import numpy as np
    import paddle_tpu as paddle
    st = paddle.get_cuda_rng_state()
    arr = np.asarray(st)           # must be numpy-convertible
    np.save(tmp_path / "rng.npy", arr)
    before = paddle.rand([4]).numpy()
    paddle.set_cuda_rng_state(np.load(tmp_path / "rng.npy"))
    after = paddle.rand([4]).numpy()
    np.testing.assert_allclose(before, after)


def test_round3_legacy_compat_surface():
    import numpy as np
    import paddle_tpu as paddle
    assert paddle.VarBase is paddle.Tensor
    assert paddle.in_dygraph_mode() is True
    paddle.enable_dygraph(); paddle.disable_dygraph()
    paddle.monkey_patch_math_varbase(); paddle.monkey_patch_variable()
    x = paddle.to_tensor(np.arange(24).reshape(2, 3, 4).astype("float32"))
    c = paddle.crop_tensor(x, shape=[1, 2, 2], offsets=[1, 0, 1])
    np.testing.assert_array_equal(
        c.numpy(), np.arange(24).reshape(2, 3, 4)[1:2, 0:2, 1:3])
    import paddle_tpu.nn.functional.extension as ext
    assert hasattr(ext, "diag_embed")
    import paddle_tpu.nn.utils.weight_norm_hook as wnh
    assert hasattr(wnh, "weight_norm")
    from paddle_tpu import static
    assert static.xpu_places() == static.cuda_places()
    # a place is a binding to ONE device: no accelerator, or an index
    # past the device count, is an error — never the host CPU or chip 0
    import jax
    from paddle_tpu.framework.place import Place
    n = jax.device_count()
    assert Place(n - 1).jax_device() == jax.devices()[n - 1]
    with pytest.raises(ValueError, match="out of range"):
        Place(n).jax_device()              # past the count: not clamped
    with pytest.raises(ValueError, match="out of range"):
        paddle.TPUPlace(0).jax_device()    # no accelerator: not the CPU
    assert paddle.CPUPlace().jax_device() == jax.devices("cpu")[0]
    import paddle_tpu.nn as nn
    assert hasattr(nn, "extension")
