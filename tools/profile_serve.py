"""Phase attribution for the AOT serving engine (ISSUE 2 tentpole).

Where does a served request's wall time go?  The PredictorServer
accumulates per-phase timers as it batches, so every millisecond of a
synchronous serve workload attributes to exactly one of:

  queue_ms   request sat in the submit queue / coalescing window
             (summed per REQUEST — concurrency makes this > wall time
             under load; that is the point of batching)
  pad_ms     host-side concatenate + pad-to-bucket (per batch)
  xla_ms     the compiled executable call, device compute + dispatch
             (the server's run phase)
  unpad_ms   splitting result rows back onto caller futures

Runs the same concurrent-batch-1-clients workload as ``bench.py``'s
serve metric against a ResNet export (BENCH_SMOKE=1 / --smoke for the
resnet18-at-32px proxy) and prints one JSON line per configuration
plus a phase-share summary, with the sequential batch-1 loop as the
baseline row.

Usage: JAX_PLATFORMS=cpu python tools/profile_serve.py [--smoke]
Env: PROFILE_REQS, PROFILE_CLIENTS, PROFILE_MAXB, PROFILE_WAIT_MS.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    smoke = "--smoke" in sys.argv or os.environ.get("BENCH_SMOKE") == "1"
    if smoke:
        import jax
        jax.config.update("jax_platforms", "cpu")

    import paddle_tpu as paddle
    from paddle_tpu.inference import (Config, PredictorServer,
                                      create_predictor)
    from paddle_tpu.static import InputSpec
    from paddle_tpu.vision.models import resnet18, resnet50

    n_reqs = int(os.environ.get("PROFILE_REQS",
                                "128" if smoke else "192"))
    clients = int(os.environ.get("PROFILE_CLIENTS", "16"))
    max_batch = int(os.environ.get("PROFILE_MAXB",
                                   "16" if smoke else "32"))
    wait_ms = float(os.environ.get("PROFILE_WAIT_MS", "1"))
    hw = 32 if smoke else 224

    paddle.seed(0)
    model = (resnet18(num_classes=10) if smoke
             else resnet50(num_classes=1000))
    model.eval()
    tmp = tempfile.mkdtemp(prefix="ptpu_profile_serve_")
    path = os.path.join(tmp, "resnet")
    paddle.jit.save(model, path,
                    input_spec=[InputSpec([None, 3, hw, hw], "float32",
                                          "img")])
    pred = create_predictor(Config(path))
    rng = np.random.RandomState(0)
    x1 = [rng.standard_normal((1, 3, hw, hw)).astype("float32")]

    # baseline: sequential batch-1 loop (everything is "xla + dispatch")
    pred.run(x1)
    t0 = time.perf_counter()
    for _ in range(n_reqs):
        pred.run(x1)
    dt_seq = time.perf_counter() - t0
    print(json.dumps({
        "mode": "sequential_batch1",
        "examples_per_s": round(n_reqs / dt_seq, 2),
        "ms_per_request": round(dt_seq / n_reqs * 1e3, 3),
        "image_size": hw,
    }), flush=True)

    server = PredictorServer(pred, max_batch=max_batch,
                             max_wait_ms=wait_ms, max_queue=1024,
                             request_timeout_s=600.0)
    server.start()
    per_client = n_reqs // clients

    def worker():
        x = [rng.standard_normal((1, 3, hw, hw)).astype("float32")]
        for _ in range(per_client):
            server.infer(x, timeout_s=600.0)

    threads = [threading.Thread(target=worker) for _ in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    st = server.stats()
    server.stop()

    served = clients * per_client
    batches = max(st["batches"], 1)
    batch_ms = st["pad_ms"] + st["run_ms"] + st["unpad_ms"]
    rec = {
        "mode": "server",
        "examples_per_s": round(served / dt, 2),
        "speedup_vs_batch1": round((served / dt) / (n_reqs / dt_seq), 3),
        "clients": clients, "max_batch": max_batch,
        "max_wait_ms": wait_ms, "batches": st["batches"],
        "bucket_hits": {str(k): v for k, v in st["bucket_hits"].items()
                        if v},
        "padded_frac": round(st["padded_examples"]
                             / max(st["examples"], 1), 4),
        "num_compiles": st["num_compiles"],
        # per-batch phase attribution (the serving hot path)
        "pad_ms_per_batch": round(st["pad_ms"] / batches, 3),
        "xla_ms_per_batch": round(st["run_ms"] / batches, 3),
        "unpad_ms_per_batch": round(st["unpad_ms"] / batches, 3),
        # per-request queue time: how long batching held a request
        "queue_ms_per_request": round(st["queue_ms"]
                                      / max(st["requests"], 1), 3),
        "phase_shares_of_batch": {
            "pad": round(st["pad_ms"] / batch_ms, 4) if batch_ms else 0,
            "xla": round(st["run_ms"] / batch_ms, 4) if batch_ms else 0,
            "unpad": round(st["unpad_ms"] / batch_ms, 4)
            if batch_ms else 0,
        },
    }
    print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
