#!/usr/bin/env python3
"""Are the device programs of the benchmark's serving cells the same in
two trees, and did compiling them get slower?  Without a chip: builds
each serving cell's server from <tree>, lowers and compiles
``decode_fn``, the smallest and largest ``prefill_fn`` and, where the
tree has it (ISSUE 34), ``feed_fn`` at every prefill batch width for a
described v5e, prints lowering and compile seconds per program, and
writes them with digests of the StableHLO and of the optimized HLO
(whole, and with what carries file paths and line numbers taken out:
the serialized Mosaic payload, op metadata), ``memory_analysis()`` and
every ``sort`` of the optimized HLO (its shape, and whether it sits in a
``conditional``'s branch) to <out>/serving_hlo_<tag>.json, the texts
beside it.

    JAX_PLATFORMS=cpu python3 tools/aot_serving_hlo.py <tree> <tag> <out> [cell ...]

No cell named: the two Mistral cells and the Kimi cell.  A cell named
``cell@3072x2,512x4`` compiles those prefill programs too.  Run it once
per tree (parent unpacked with ``git archive``, then the change), one
run at a time so that the seconds compare, and compare the two JSON
files: equal ``*_less_*`` digests, bytes and operation counts mean the
chip runs the same programs, and only the compile cache's key moved.
A compile is not a chip run: it says nothing about the program's time.
(PERF.md: PR 27 used it for the edits to ``generation_server.py``,
PR 30 for the sampler's ``cond``.)"""
import os, sys, time, json, hashlib, re
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT, TAG, OUT = os.path.abspath(sys.argv[1]), sys.argv[2], sys.argv[3]
CELLS = sys.argv[4:] or ["mistral7b-serve-decode", "mistral7b-serve-prefill", "kimi-linear-serve-decode"]
os.makedirs(OUT, exist_ok=True)
sys.path.insert(0, ROOT)
import numpy as np, jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding, Mesh
jax.config.update("jax_enable_compilation_cache", False)
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
dev = topo.devices[0]
one = SingleDeviceSharding(dev)
from paddle_tpu.distributed import mesh as mesh_mod
from perfbench.harness import manifest as M
from paddle_tpu.inference import GenerationServer
assert M.ROOT == ROOT, (M.ROOT, ROOT)
mesh_mod.set_mesh(Mesh(np.array([dev]), ("dp",)))
assert mesh_mod.target_platform() == "tpu"
man = M.load_manifest()
out = {}
def S(shape, dt): return jax.ShapeDtypeStruct(shape, dt, sharding=one)


def sorts_of(hlo: str):
    """[(shape, in a conditional's branch?)] for every sort of an
    optimized HLO module: a sort counts as inside when no path of calls
    reaches its computation from ENTRY but through a branch."""
    comps, entry, name = {}, None, None
    for ln in hlo.split("\n"):
        m = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{\s*$", ln)
        if m:
            name = m.group(2)
            comps[name] = []
            entry = name if m.group(1) else entry
        elif ln.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(ln)
    free = {c: set() for c in comps}     # callees reached without taking a branch
    edge = re.compile(r"(to_apply|calls|body|condition|true_computation|false_computation|branch_computations)=(\{[^}]*\}|%?[\w.\-]+)")
    for c, lines in comps.items():
        for kind, names in edge.findall("\n".join(lines)):
            if kind in ("to_apply", "calls", "body", "condition"):
                free[c] |= set(re.findall(r"[\w.\-]+", names)) & set(comps)
    outside, todo = set(), [entry]
    while todo:
        c = todo.pop()
        if c not in outside:
            outside.add(c)
            todo += list(free[c])
    found = []
    for c, lines in comps.items():
        for ln in lines:
            m = re.search(r"= \(?(\w+\[[\d,]*\])[^=]* sort\(", ln)
            if m:
                found.append((m.group(1), c not in outside))
    return sorted(found)


for cellname in CELLS:
    # "cell@3072x2,4096x1": those prefill programs beside the usual ones
    cellname, _, more = cellname.partition("@")
    cell = M.Cell(man, cellname)
    cfg, sv = cell.config, cell.spec["server"]
    model = cell.binding().build_serving(cfg, sv["max_model_len"])
    srv = GenerationServer(model, num_slots=sv["num_slots"], block_size=sv["block_size"], max_model_len=sv["max_model_len"],
                           prompt_buckets=sv["prompt_buckets"], max_prefill_batch=sv["max_prefill_batch"], prefix_cache=False)
    srv._build_programs()
    sds = lambda a, dt=None: jax.ShapeDtypeStruct(a.shape, dt or a.dtype, sharding=one)
    pv = {k: sds(v, jnp.bfloat16) for k, v in srv._pvals.items()}
    pools = [{k: sds(v) for k, v in d.items()} for d in srv._pools]
    B, Mx, W = sv["num_slots"], srv._M, 2
    bk = sv["prompt_buckets"]
    feeds = [f"feed{pb}" for pb in srv._pbatches] if getattr(srv, "_feed_fn", None) else []
    for w in ["decode", f"{bk[0]}x1", f"{bk[-1]}x{sv['max_prefill_batch']}"] + feeds + [p for p in more.split(",") if p]:
        t = time.time()
        if w.startswith("feed"):
            # a prefill call's first tokens into the decode program's token feed
            pb = int(w[4:])
            low = srv._feed_fn.lower(S(srv._prev.shape, jnp.int32), S((pb,), jnp.int32), S((pb,), jnp.int32))
        elif w == "decode":
            # the step before's result (tokens, then the model's step counters) comes first
            args = (S(srv._prev.shape, jnp.int32), S((B, 1), jnp.int32), S((B, 1), jnp.int32), S((B, Mx), jnp.int32), S((B, 1), jnp.bool_), S((B, W), jnp.uint32), S((B,), jnp.int32), S((B,), jnp.float32), S((B,), jnp.int32), S((B,), jnp.float32), S((B,), jnp.bool_))
            low = srv._decode_fn.lower(pv, pools, *args)
        else:
            b, pb = map(int, w.split("x"))
            args = (S((pb, b), jnp.int32), S((pb,), jnp.int32), S((pb,), jnp.int32), S((pb, Mx), jnp.int32), S((pb, W), jnp.uint32), S((pb,), jnp.float32), S((pb,), jnp.int32), S((pb,), jnp.float32), S((pb,), jnp.bool_))
            # a model with per-slot state takes each row's slot by name
            rows = {k: sds(v) for k, v in srv._row_slots([], pb).items()}
            low = srv._prefill_fn.lower(pv, pools, *args, **rows)
        shlo = low.as_text()
        t_low = time.time()
        # a Mosaic payload carries its call sites' lines: take the serialized kernel out of the comparison, keep its size
        nolines = re.sub(r'backend_config = "[^"]*"', lambda m: f'backend_config = <{len(m.group(0))} bytes>', shlo)
        c = low.compile()
        t_comp = time.time()
        opt = c.as_text()
        opt_nometa = re.sub(r', metadata=\{[^}]*\}', '', opt)
        # a Mosaic call's serialized kernel: keep its size only
        opt_nometa = re.sub(
            r'(custom_call_target="tpu_custom_call".*?)backend_config=.*',
            lambda m: f"{m.group(1)}backend_config=<{len(m.group(0))}>",
            opt_nometa)
        opt_nometa = re.sub(r'stack_frame_id=\d+', '', "\n".join(
            ln for ln in opt_nometa.split("\n")
            if not re.match(r'^\d+ ', ln) and ln not in (
                "FileNames", "FunctionNames", "FileLocations",
                "StackFrames")))
        ma = c.memory_analysis()
        out[f"{cellname}/{w}"] = {
            "lower_s": round(t_low - t, 1), "compile_s": round(t_comp - t_low, 1),
            "sorts": [{"shape": s, "in_conditional": i} for s, i in sorts_of(opt_nometa)],
            "stablehlo_sha": hashlib.sha256(shlo.encode()).hexdigest()[:16],
            "stablehlo_less_kernel_payload_sha": hashlib.sha256(nolines.encode()).hexdigest()[:16],
            "stablehlo_bytes": len(shlo), "optimized_sha": hashlib.sha256(opt.encode()).hexdigest()[:16],
            "optimized_less_metadata_sha": hashlib.sha256(opt_nometa.encode()).hexdigest()[:16],
            "optimized_bytes": len(opt_nometa), "branches_or_loops": len(re.findall(r"\b(conditional|while)\(", opt_nometa)),
            "custom_calls": opt.count("tpu_custom_call"), "temp_bytes": ma.temp_size_in_bytes, "arg_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes, "code_bytes": ma.generated_code_size_in_bytes,
            "flops": c.cost_analysis().get("flops", 0)}
        open(f"{OUT}/serving_hlo_{TAG}_{cellname}_{w}.stablehlo.txt", "w").write(shlo)
        open(f"{OUT}/serving_hlo_{TAG}_{cellname}_{w}.opt.txt", "w").write(opt_nometa)
        print(cellname, w, out[f"{cellname}/{w}"], flush=True)
    del srv, model
json.dump(out, open(f"{OUT}/serving_hlo_{TAG}.json", "w"), indent=1)
