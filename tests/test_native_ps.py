"""Native C++ sparse-table core tests (paddle_tpu/native/ps_core.cc).

Parity model: reference distributed/table/common_sparse_table tests —
lazy row init, optimizer update semantics vs a numpy oracle, geo delta
push, save/load, concurrency.
"""
import threading

import numpy as np
import pytest

from paddle_tpu.distributed.fleet.ps import SparseTable
from paddle_tpu.native import ps_core


requires_native = pytest.mark.skipif(ps_core() is None,
                                     reason="no C++ toolchain")


@requires_native
def test_native_backend_selected():
    t = SparseTable(8)
    assert t._native is not None


@requires_native
def test_pull_deterministic_and_lazy():
    t = SparseTable(16, seed=42)
    ids = np.array([5, 99, 5, 12345678901], np.int64)
    out = t.pull(ids)
    assert out.shape == (4, 16)
    # same id -> same row, regardless of position
    np.testing.assert_array_equal(out[0], out[2])
    assert len(t) == 3
    # re-pull is stable
    np.testing.assert_array_equal(t.pull(ids), out)
    # a fresh table with the same seed materialises identical rows even
    # when ids arrive in a different order (deterministic per-id init)
    t2 = SparseTable(16, seed=42)
    out2 = t2.pull(ids[::-1].copy())
    np.testing.assert_array_equal(out2[::-1], out)
    # init is ~ normal(0, 0.01)
    big = t.pull(np.arange(4096, dtype=np.int64))
    assert abs(float(big.mean())) < 1e-3
    assert 0.008 < float(big.std()) < 0.012


@requires_native
def test_sgd_push_matches_oracle():
    t = SparseTable(4, optimizer="sgd", lr=0.1)
    ids = np.array([1, 2], np.int64)
    before = t.pull(ids).copy()
    g = np.array([[1, 2, 3, 4], [5, 6, 7, 8]], np.float32)
    t.push(ids, g)
    np.testing.assert_allclose(t.pull(ids), before - 0.1 * g, rtol=1e-6)


@requires_native
def test_adagrad_push_matches_python_fallback():
    ids = np.array([7, 8, 7], np.int64)
    g = np.random.RandomState(0).randn(3, 6).astype(np.float32)
    tn = SparseTable(6, optimizer="adagrad", lr=0.05)
    tp = SparseTable(6, optimizer="adagrad", lr=0.05, backend="python",
                     initializer=lambda: np.zeros(6, np.float32))
    # align initial rows: zero them via import
    zeros = np.zeros((2, 6), np.float32)
    uniq = np.array([7, 8], np.int64)
    tn.load_from_arrays = None  # no-op guard
    import ctypes
    tn._lib.pts_import(tn._native, tn._c(uniq, ctypes.c_int64), 2,
                       tn._c(zeros, ctypes.c_float))
    for _ in range(3):
        tn.push(ids, g)
        tp.push(ids, g)
    np.testing.assert_allclose(tn.pull(uniq), tp.pull(uniq),
                               rtol=1e-5, atol=1e-6)


@requires_native
def test_adam_push_matches_python_fallback():
    ids = np.array([3, 4], np.int64)
    g = np.random.RandomState(1).randn(2, 5).astype(np.float32)
    tn = SparseTable(5, optimizer="adam", lr=0.01)
    tp = SparseTable(5, optimizer="adam", lr=0.01, backend="python",
                     initializer=lambda: np.zeros(5, np.float32))
    import ctypes
    zeros = np.zeros((2, 5), np.float32)
    tn._lib.pts_import(tn._native, tn._c(ids, ctypes.c_int64), 2,
                       tn._c(zeros, ctypes.c_float))
    for _ in range(5):
        tn.push(ids, g)
        tp.push(ids, g)
    np.testing.assert_allclose(tn.pull(ids), tp.pull(ids),
                               rtol=1e-4, atol=1e-6)


@requires_native
def test_push_delta_and_len():
    t = SparseTable(3)
    ids = np.array([10, 11], np.int64)
    base = t.pull(ids).copy()
    d = np.ones((2, 3), np.float32)
    t.push_delta(ids, d)
    np.testing.assert_allclose(t.pull(ids), base + 1.0, rtol=1e-6)
    assert len(t) == 2


@requires_native
def test_save_load_roundtrip(tmp_path):
    t = SparseTable(4, seed=1)
    ids = np.array([100, 200, 300], np.int64)
    t.push(ids, np.ones((3, 4), np.float32))
    vals = t.pull(ids).copy()
    p = str(tmp_path / "table")
    t.save(p)
    t2 = SparseTable(4, seed=999)   # different seed: rows must come from file
    t2.load(p)
    assert len(t2) == 3
    np.testing.assert_array_equal(t2.pull(ids), vals)
    # python-backend can read the same file (shared format)
    t3 = SparseTable(4, backend="python")
    t3.load(p + ".npz")
    np.testing.assert_allclose(t3.pull(ids), vals, rtol=1e-6)


@requires_native
def test_concurrent_push_pull():
    t = SparseTable(8, optimizer="sgd", lr=0.001)
    errs = []

    def worker(seed):
        try:
            rng = np.random.RandomState(seed)
            for _ in range(50):
                ids = rng.randint(0, 1000, size=64).astype(np.int64)
                t.pull(ids)
                t.push(ids, rng.randn(64, 8).astype(np.float32))
        except Exception as e:   # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(s,)) for s in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errs
    assert len(t) <= 1000
    out = t.pull(np.arange(1000, dtype=np.int64))
    assert np.isfinite(out).all()


@requires_native
def test_large_batch_threads():
    """Exercise the multi-threaded shard fan-out path (n >= 4096)."""
    t = SparseTable(16)
    ids = np.random.RandomState(3).randint(0, 10**12, size=20000)
    ids = ids.astype(np.int64)
    out = t.pull(ids)
    assert out.shape == (20000, 16)
    t.push(ids, np.ones((20000, 16), np.float32))
    assert np.isfinite(t.pull(ids)).all()


def test_python_fallback_still_works():
    t = SparseTable(4, backend="python", optimizer="adam", lr=0.01)
    ids = np.array([1, 2], np.int64)
    t.push(ids, np.ones((2, 4), np.float32))
    assert len(t) == 2
    assert np.isfinite(t.pull(ids)).all()


@requires_native
def test_load_replaces_not_merges(tmp_path):
    t = SparseTable(4, seed=1)
    t.pull(np.array([1, 2], np.int64))
    p = str(tmp_path / "snap")
    t.save(p)
    t2 = SparseTable(4, seed=2)
    t2.pull(np.array([7, 8, 9], np.int64))   # pre-existing rows
    t2.load(p)
    assert len(t2) == 2                      # replaced, not merged


def test_load_replaces_python_backend(tmp_path):
    t = SparseTable(4, backend="python", seed=1)
    t.pull(np.array([1, 2], np.int64))
    p = str(tmp_path / "snap")
    t.save(p)
    t2 = SparseTable(4, backend="python", optimizer="adam")
    t2.push(np.array([7], np.int64), np.ones((1, 4), np.float32))
    t2.load(p + ".npz")
    assert len(t2) == 2
    assert not t2._moments                   # optimizer state reset


# ---------------------------------------------------------------------
# r6: native-vs-Python parity for the full data plane (fused push,
# admission entries, moments, cross-backend checkpoints) + wide_deep
# e2e smoke — the ISSUE-1 acceptance tests.
# ---------------------------------------------------------------------

def _zero_native(t, ids):
    """Force a native table's rows for ``ids`` to zeros so both backends
    start from identical state (their default inits differ by design)."""
    import ctypes
    ids = np.ascontiguousarray(ids, np.int64)
    z = np.zeros((ids.size, t.dim), np.float32)
    t._lib.pts_import(t._native, t._c(ids, ctypes.c_int64), ids.size,
                      t._c(z, ctypes.c_float))


@requires_native
@pytest.mark.parametrize("opt", ["sgd", "adagrad", "adam"])
def test_pull_after_push_parity(opt):
    """Pull-after-push parity, duplicates included: the fused native
    push (dedup + segment-sum + single apply) must match the Python
    reference path bit-for-tolerance across every optimizer."""
    ids = np.array([3, 9, 3, 42, 9, 3], np.int64)
    uniq = np.array([3, 9, 42], np.int64)
    g = np.random.RandomState(7).randn(6, 5).astype(np.float32)
    tn = SparseTable(5, optimizer=opt, lr=0.03)
    tp = SparseTable(5, optimizer=opt, lr=0.03, use_native=False,
                     initializer=lambda: np.zeros(5, np.float32))
    _zero_native(tn, uniq)
    for _ in range(4):
        tn.push(ids, g)
        tp.push(ids, g)
    np.testing.assert_allclose(tn.pull(uniq), tp.pull(uniq),
                               rtol=1e-4, atol=1e-6)


@requires_native
def test_fused_push_equals_presummed_push():
    """The fused-push contract, stated directly: pushing duplicate ids
    equals pushing their summed gradient once (NOT sequential applies —
    the distinction matters for adagrad/adam)."""
    g = np.random.RandomState(2).randn(3, 4).astype(np.float32)
    ta = SparseTable(4, optimizer="adam", lr=0.01)
    tb = SparseTable(4, optimizer="adam", lr=0.01)
    one = np.array([11], np.int64)
    _zero_native(ta, one)
    _zero_native(tb, one)
    ta.push(np.array([11, 11, 11], np.int64), g)
    tb.push(one, g.sum(axis=0, keepdims=True))
    np.testing.assert_allclose(ta.pull(one), tb.pull(one),
                               rtol=1e-5, atol=1e-7)


@requires_native
def test_native_count_entry_matches_python():
    """CountFilterEntry admission runs inside C: threshold counting,
    one-sighting-per-unique-id-per-pull, and grad dropping must all
    match the Python reference decisions."""
    from paddle_tpu.distributed import CountFilterEntry
    tn = SparseTable(4, entry=CountFilterEntry(3), lr=1.0)
    tp = SparseTable(4, entry=CountFilterEntry(3), lr=1.0,
                     use_native=False)
    assert tn._native_entry
    ids = np.array([7, 8, 7], np.int64)     # 7 twice = ONE sighting
    for _ in range(2):                       # sightings 1, 2: rejected
        on, op = tn.pull(ids), tp.pull(ids)
        assert not on.any() and not op.any()
        assert len(tn) == 0 and len(tp._rows) == 0
    # grads before admission are dropped by both
    tn.push(ids, np.ones((3, 4), np.float32))
    tp.push(ids, np.ones((3, 4), np.float32))
    assert len(tn) == 0 and len(tp._rows) == 0
    # 3rd sighting admits in both; duplicate positions serve one row
    on, op = tn.pull(ids), tp.pull(ids)
    assert on.any() and op.any()
    np.testing.assert_array_equal(on[0], on[2])
    assert len(tn) == 2 and len(tp._rows) == 2
    # post-admission push applies (lr=1, grads summed over duplicates)
    before = tn.pull(ids).copy()
    tn.push(ids, np.ones((3, 4), np.float32))
    got = tn.pull(ids)
    np.testing.assert_allclose(got[1], before[1] - 1.0, rtol=1e-5)
    np.testing.assert_allclose(got[0], before[0] - 2.0, rtol=1e-5)


@requires_native
def test_native_probability_entry_matches_python():
    """ProbabilityEntry's C hash is bit-exact with entry.py: both
    backends must admit the IDENTICAL subset, and rejected ids must
    leave no slot behind (len == admitted rows only)."""
    from paddle_tpu.distributed import ProbabilityEntry
    tn = SparseTable(4, entry=ProbabilityEntry(0.5))
    tp = SparseTable(4, entry=ProbabilityEntry(0.5), use_native=False)
    assert tn._native_entry
    ids = np.arange(500, dtype=np.int64)
    on, op = tn.pull(ids), tp.pull(ids)
    zn = ~on.any(axis=1)
    zp = ~op.any(axis=1)
    np.testing.assert_array_equal(zn, zp)
    assert len(tn) == len(tp._rows) == int((~zn).sum())
    st = tn._entry_state()
    assert set(st["admitted"].tolist()) == tp._admitted
    assert st["seen_ids"].size == 0          # count-independent entry


@requires_native
def test_entry_state_roundtrip_cross_backend(tmp_path):
    """Checkpoint format parity including admission state: save from
    either backend, load into the other, admission picks up where it
    left off (trained rows served immediately, counters survive)."""
    from paddle_tpu.distributed import CountFilterEntry
    for src_native in (True, False):
        t = SparseTable(4, entry=CountFilterEntry(2), lr=1.0,
                        use_native=src_native)
        hot = np.asarray([5], np.int64)
        t.pull(hot)
        t.pull(hot)                          # admitted at sighting 2
        t.push(hot, np.ones((1, 4), np.float32))
        trained = t.pull(hot).copy()
        warm = np.asarray([9], np.int64)
        t.pull(warm)                         # 1 sighting, not admitted
        p = str(tmp_path / f"ck{src_native}")
        t.save(p)
        for dst_native in (True, False):
            t2 = SparseTable(4, entry=CountFilterEntry(2), lr=1.0,
                             use_native=dst_native)
            t2.load(p)
            np.testing.assert_allclose(t2.pull(hot), trained)
            t2.pull(warm)                    # counter survived: admits
            assert t2.pull(warm).any(), (src_native, dst_native)


@requires_native
def test_use_native_flag():
    assert SparseTable(4, use_native=True).is_native
    assert not SparseTable(4, use_native=False).is_native
    # use_native=False must still be a fully working table
    t = SparseTable(4, use_native=False)
    t.push(np.array([1], np.int64), np.ones((1, 4), np.float32))
    assert len(t) == 1


@requires_native
def test_wide_deep_native_e2e_smoke():
    """wide&deep end to end through HeterTrainer over the native
    SparseTable (4 slots x dim 8, 13 dense features, hidden 64, 4 steps
    of 64): pull is one batched C gather, the pulled rows ride into the
    jitted dense step as an input, push is the fused native update.
    Native backend actually on, loss finite, examples flow."""
    import time

    import jax
    import jax.numpy as jnp

    from paddle_tpu.distributed.fleet.heter import HeterTrainer

    n_slots, dim, n_dense, hidden = 4, 8, 13, 64
    batch, steps, vocab = 64, 4, 1000
    table = SparseTable(dim, optimizer="sgd", lr=0.05)
    assert table.is_native
    rng = np.random.RandomState(0)
    state = {"losses": [], "params": (
        jnp.asarray(rng.randn(n_slots * dim + n_dense, hidden) * 0.05,
                    jnp.float32),
        jnp.zeros((hidden,), jnp.float32),
        jnp.asarray(rng.randn(hidden, 1) * 0.05, jnp.float32),
        jnp.asarray(rng.randn(n_dense, 1) * 0.05, jnp.float32))}

    @jax.jit
    def dense_fwd_bwd(params, emb, dense, label):
        def loss_of(params, emb):
            w1, b1, w2, wide_w = params
            deep_in = jnp.concatenate(
                [emb.reshape(batch, n_slots * dim), dense], axis=1)
            h = jax.nn.relu(deep_in @ w1 + b1)
            logit = jnp.clip((h @ w2 + dense @ wide_w)[:, 0], -15, 15)
            return jnp.mean(jnp.logaddexp(0.0, logit) - logit * label)
        loss, (gp, ge) = jax.value_and_grad(
            loss_of, argnums=(0, 1))(params, emb)
        return loss, tuple(p - 0.05 * g for p, g in zip(params, gp)), ge

    def dense_step(embs, batch_data):
        loss, state["params"], ge = dense_fwd_bwd(
            state["params"], embs["slots"], jnp.asarray(batch_data[1]),
            jnp.asarray(batch_data[2]))
        state["losses"].append(loss)
        return loss, {"slots": ge.reshape(-1, dim)}

    # Zipf-skewed ids, one id space per slot, a learnable label
    zipf = np.clip(rng.zipf(1.3, size=(steps, batch, n_slots)), 1, vocab)
    batches = []
    for i in range(steps):
        ids = ((zipf[i] - 1) + np.arange(n_slots) * vocab).astype(np.int64)
        dense = rng.rand(batch, n_dense).astype(np.float32)
        batches.append((ids, dense, (dense[:, 0] > 0.5).astype(np.float32)))

    tr = HeterTrainer({"slots": table}, dense_step, sync_mode=False,
                      push_lag=1)
    t0 = time.perf_counter()
    n = tr.run(batches, lambda b: {"slots": b[0].reshape(-1)})
    losses = [float(l) for l in state["losses"]]   # forces the chain
    dt = time.perf_counter() - t0
    tr.shutdown()
    assert n == steps and len(losses) == steps
    assert np.isfinite(losses).all()
    assert batch * n / dt > 0
    assert 0 < len(table) <= steps * batch * n_slots
