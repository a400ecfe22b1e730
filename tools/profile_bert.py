"""Per-op profile of the BERT-base pretrain step on the real chip.

The driver behind PERF.md's round-5 large-batch table (VERDICT r4 item
9: batch 384/512 degrade per-example vs 128 on "attention-probs
fusions").  Runs the bench-shaped step at env B=batch, traces 5 steps,
aggregates device-lane op durations.  A chip belongs to one process:
nothing else may hold it while this runs.
"""
import glob
import gzip
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax

import paddle_tpu as paddle
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.distributed.fleet.dist_step import DistributedTrainStep
from paddle_tpu.text.models.bert import (BertForPretraining,
                                         BertPretrainingCriterion,
                                         bert_base)

batch = int(os.environ.get("B", "512"))
seq = 128
n_mask = max(1, int(seq * 0.15))
paddle.seed(0)
cfg = bert_base()
model = BertForPretraining(cfg)
crit = BertPretrainingCriterion(cfg.vocab_size)
opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                             parameters=model.parameters())


def loss_fn(ids, mask_pos, mlm_labels, nsp_labels):
    mlm_logits, nsp_logits = model(ids, masked_positions=mask_pos)
    return crit(mlm_logits, nsp_logits, mlm_labels, nsp_labels)


strategy = fleet.DistributedStrategy()
strategy.amp = True
strategy.amp_configs = {"dtype": "bfloat16"}
mesh_mod.set_mesh(None)
mesh = mesh_mod.init_mesh({"dp": -1})
step = DistributedTrainStep(model, loss_fn, opt, strategy, mesh=mesh)
rng = np.random.RandomState(0)
ids = paddle.to_tensor(
    rng.randint(0, cfg.vocab_size, (batch, seq)).astype("int32"))
mask_pos = paddle.to_tensor(np.sort(
    rng.randint(0, seq, (batch, n_mask)), axis=1).astype("int32"))
mlm = paddle.to_tensor(
    rng.randint(0, cfg.vocab_size, (batch, n_mask)).astype("int64"))
nsp = paddle.to_tensor(rng.randint(0, 2, (batch,)).astype("int64"))
args = (ids, mask_pos, mlm, nsp)

for _ in range(3):
    loss = step(*args)
float(loss)
t0 = time.perf_counter()
for _ in range(10):
    loss = step(*args)
float(loss)
dt = (time.perf_counter() - t0) / 10
print(f"steady: {dt*1e3:.2f} ms/step, {batch*seq/dt:.0f} tok/s "
      f"({batch/dt:.1f} ex/s)")

logdir = f"/tmp/bertprof{batch}"
os.system(f"rm -rf {logdir}")
with jax.profiler.trace(logdir):
    for _ in range(5):
        loss = step(*args)
    float(loss)

files = glob.glob(f"{logdir}/**/*.trace.json.gz", recursive=True)
ev_by_name = {}
for f in files:
    tr = json.load(gzip.open(f, "rt"))
    for ev in tr.get("traceEvents", []):
        if ev.get("ph") != "X":
            continue
        pid = ev.get("pid")
        name = ev.get("name", "")
        dur = ev.get("dur", 0)
        key = (pid, name.split(".")[0])
        ev_by_name.setdefault(key, [0, 0])
        ev_by_name[key][0] += dur
        ev_by_name[key][1] += 1
rows = sorted(ev_by_name.items(), key=lambda kv: -kv[1][0])
print("\ntop 25 by total device-lane time (us over 5 steps):")
shown = 0
for (pid, name), (dur, n) in rows:
    if name in ("", "process_name", "thread_name"):
        continue
    print(f"  {dur:>10} us  x{n:<4} pid={pid}  {name}")
    shown += 1
    if shown >= 25:
        break
