"""AOT serving engine tests (ISSUE 2 tentpole).

Covers the acceptance contracts directly:
- Predictor steady state does ZERO retracing — the compile counter
  shows one executable per (model, bucket) shape;
- the export meta carries input specs + output treedef;
- PredictorServer coalesces concurrent requests into bucketed batches
  and returns bit-identical results to unbatched runs;
- overload sheds with a TYPED error instead of unbounded queueing, and
  stale requests fail with a typed timeout;
- the persistent compile cache actually writes executables to disk.
"""
import os
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.inference import (Config, PredictorServer, RequestTimeout,
                                  ServerClosed, ServerOverloaded,
                                  create_predictor)
from paddle_tpu.static import InputSpec


class TwoOutNet(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(6, 16)
        self.fc2 = nn.Linear(16, 3)

    def forward(self, x):
        h = nn.functional.relu(self.fc1(x))
        return self.fc2(h), h.sum(axis=-1)


@pytest.fixture(scope="module")
def exported(tmp_path_factory):
    paddle.seed(3)
    model = TwoOutNet()
    model.eval()
    path = str(tmp_path_factory.mktemp("serve") / "twout")
    paddle.jit.save(model, path,
                    input_spec=[InputSpec([None, 6], "float32", "x")])
    return path, model


def _config(path, tmp_cache=None):
    cfg = Config(path)
    cfg.disable_gpu()
    if tmp_cache is not None:
        cfg.set_optim_cache_dir(str(tmp_cache))
    return cfg


def test_meta_carries_specs_and_output_treedef(exported):
    import pickle
    path, _ = exported
    with open(path + ".pdmeta", "rb") as f:
        meta = pickle.load(f)
    assert meta["input_names"] == ["x"]
    assert meta["input_shapes"] == [[-1, 6]]
    assert meta["input_dtypes"] == ["float32"]
    assert meta["n_outputs"] == 2
    # treedef rides as an index-leaved template + per-leaf specs
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(meta["output_template"])
    assert leaves == [0, 1]          # flat order preserved
    assert treedef.num_leaves == 2
    assert meta["output_shapes"] == [[-1, 3], [-1]]
    assert meta["output_dtypes"] == ["float32", "float32"]


def test_predictor_compiles_once_per_shape(exported):
    path, model = exported
    pred = create_predictor(_config(path))
    # load-time AOT already built the batch-1 executable
    assert pred.num_compiles() == 1
    x = np.random.RandomState(0).randn(1, 6).astype("float32")
    for _ in range(8):
        pred.run([x])
    assert pred.num_compiles() == 1, "steady state must not retrace"
    # a NEW shape compiles exactly once, then is cached
    xb = np.random.RandomState(1).randn(4, 6).astype("float32")
    for _ in range(4):
        pred.run([xb])
    assert pred.num_compiles() == 2
    # correctness vs eager
    ref = model(paddle.to_tensor(xb))[0].numpy()
    out = pred.run([xb])
    np.testing.assert_allclose(out[0], ref, rtol=1e-5, atol=1e-5)
    assert len(out) == 2 and out[1].shape == (4,)


def test_prewarm_builds_one_executable_per_bucket(exported):
    path, _ = exported
    pred = create_predictor(_config(path))
    n0 = pred.num_compiles()
    pred.prewarm([1, 2, 4, 8])
    # batch 1 was already compiled at load; 2/4/8 are new
    assert pred.num_compiles() == n0 + 3
    pred.prewarm([2, 4, 8])          # idempotent
    assert pred.num_compiles() == n0 + 3


def test_persistent_cache_writes_to_disk(exported, tmp_path):
    from paddle_tpu.framework import compile_cache
    path, _ = exported
    cache = tmp_path / "xla_cache"
    # the process-level cache dir is pinned by the environment
    # (conftest) or an earlier test (first caller wins); point at
    # whichever dir is live
    pred = create_predictor(_config(path, tmp_cache=cache))
    live = compile_cache.cache_dir()
    assert live, "persistent compile cache never enabled"
    pred.prewarm([16])
    entries = [f for f in os.listdir(live) if f.endswith("-cache")]
    assert entries, "AOT compile wrote no persistent cache entries"


def test_compile_cache_location_rule(monkeypatch, tmp_path):
    """ONE function places the cache: with JAX_COMPILATION_CACHE_DIR
    set it leaves ``jax_compilation_cache_dir`` alone (whatever path a
    caller passes); unset, it picks the caller's path or the FIXED
    in-checkout default — never a temp/pid/time-derived name."""
    import jax
    from jax._src import compilation_cache as _cc
    from paddle_tpu.framework import compile_cache
    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    monkeypatch.setattr(_cc, "reset_cache", lambda: None)
    monkeypatch.setattr(os, "makedirs", lambda *a, **k: None)

    def fresh(path=None):
        monkeypatch.setattr(compile_cache, "_enabled", None)
        updates.clear()
        return compile_cache.ensure_compile_cache(path)

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    assert fresh(str(tmp_path / "ignored")) == str(tmp_path / "env")
    assert "jax_compilation_cache_dir" not in updates
    # first caller wins for the process
    assert compile_cache.ensure_compile_cache("x") == str(tmp_path / "env")

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert fresh() == os.path.join(repo, ".jax_cache")
    assert updates["jax_compilation_cache_dir"] == \
        os.path.join(repo, ".jax_cache")
    assert fresh(str(tmp_path / "cfg")) == str(tmp_path / "cfg")


def test_server_coalesces_and_matches_unbatched(exported):
    path, model = exported
    pred = create_predictor(_config(path))
    rng = np.random.RandomState(7)
    reqs = [rng.randn(n, 6).astype("float32")
            for n in (1, 3, 1, 2, 4, 1, 1, 3)]
    refs = [model(paddle.to_tensor(x))[0].numpy() for x in reqs]

    with PredictorServer(pred, max_batch=8, max_wait_ms=20.0,
                         max_queue=64) as server:
        results = [None] * len(reqs)
        errs = []

        def client(i):
            try:
                results[i] = server.infer([reqs[i]], timeout_s=30.0)
            except Exception as e:      # noqa: BLE001
                errs.append((i, e))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(reqs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60.0)
        assert not errs, errs
        for i, (out, ref) in enumerate(zip(results, refs)):
            assert out is not None, i
            np.testing.assert_allclose(out[0], ref, rtol=1e-5,
                                       atol=1e-5, err_msg=str(i))
            assert out[1].shape == (reqs[i].shape[0],)
        st = server.stats()
    # coalescing happened: fewer batches than requests, and every batch
    # ran a pre-warmed power-of-2 bucket
    assert st["batches"] < len(reqs)
    assert st["requests"] == len(reqs)
    assert sum(st["bucket_hits"].values()) == st["batches"]
    # zero retracing: every bucket was compiled by prewarm, none by
    # traffic (buckets 1..8 + the load-time batch-1 program)
    assert st["num_compiles"] == len(server._buckets)


def test_server_zero_compiles_during_traffic(exported):
    path, _ = exported
    pred = create_predictor(_config(path))
    server = PredictorServer(pred, max_batch=4, max_wait_ms=1.0).start()
    try:
        n_warm = pred.num_compiles()
        rng = np.random.RandomState(0)
        for _ in range(10):
            server.infer([rng.randn(2, 6).astype("float32")])
        assert pred.num_compiles() == n_warm, \
            "serving traffic must never compile"
    finally:
        server.stop()


def test_server_overload_sheds_typed(exported):
    path, _ = exported
    pred = create_predictor(_config(path))
    # do NOT start the server: the queue fills and must shed, not grow
    server = PredictorServer(pred, max_batch=4, max_queue=2)
    server._running = True            # accept submits without a worker
    x = np.zeros((1, 6), np.float32)
    server.submit([x])
    server.submit([x])
    with pytest.raises(ServerOverloaded):
        server.submit([x])
    assert server.stats()["shed_overload"] == 1
    server._running = False


def test_server_request_timeout_typed(exported):
    path, _ = exported
    pred = create_predictor(_config(path))
    server = PredictorServer(pred, max_batch=4, max_queue=8,
                             request_timeout_s=0.0)
    server._running = True
    x = np.zeros((1, 6), np.float32)
    fut = server.submit([x])          # deadline already passed
    server._execute([server._q.get_nowait()])
    with pytest.raises(RequestTimeout):
        fut.result(timeout=1.0)
    assert server.stats()["shed_timeout"] == 1
    server._running = False


def test_server_rejects_bad_requests(exported):
    path, _ = exported
    pred = create_predictor(_config(path))
    server = PredictorServer(pred, max_batch=4)
    with pytest.raises(ServerClosed):
        server.infer([np.zeros((1, 6), np.float32)])
    server.start()
    try:
        with pytest.raises(ValueError, match="max_batch"):
            server.submit([np.zeros((9, 6), np.float32)])
        with pytest.raises(ValueError):
            server.submit([])
    finally:
        server.stop()


def test_server_stop_fails_queued_requests(exported):
    path, _ = exported
    pred = create_predictor(_config(path))
    server = PredictorServer(pred, max_batch=4, max_queue=8)
    server._running = True            # no worker thread
    fut = server.submit([np.zeros((1, 6), np.float32)])
    server.stop(drain=False)
    with pytest.raises(ServerClosed):
        fut.result(timeout=1.0)


def test_server_stats_expose_per_bucket_compiles(exported):
    """ISSUE 8 satellite: stats() reports per-bucket compile
    provenance (prewarm vs traffic), not just hit counts — shared
    shape with GenerationServer.stats()["bucket_compiles"]."""
    path, _ = exported
    pred = create_predictor(_config(path))
    server = PredictorServer(pred, max_batch=4, max_wait_ms=1.0).start()
    try:
        server.infer([np.zeros((1, 6), np.float32)])
        st = server.stats()
        # load-time batch-1 AOT + prewarmed buckets (1 shared with
        # load) -> every record is load/prewarm, none from traffic
        assert st["prewarm_compiles"] == st["num_compiles"]
        assert st["traffic_compiles"] == 0
        causes = {k: v["cause"] for k, v in st["bucket_compiles"].items()}
        assert causes.pop("run:1") == "load"      # load batch first
        assert set(causes.values()) == {"prewarm"}
        assert {k for k in st["bucket_compiles"]} == \
            {f"run:{b}" for b in (1, 2, 4)}
        # an unwarmed shape arriving as traffic is attributed as such
        pred.run([np.zeros((3, 6), np.float32)])
        st = server.stats()
        assert st["traffic_compiles"] == 1
        assert st["bucket_compiles"]["run:3"]["cause"] == \
            "new_shape_bucket"
    finally:
        server.stop()
