#!/usr/bin/env python
"""Auto-sharding planner CLI (ISSUE 15) — ``fleet.auto`` from a shell.

Examples::

    # analytic ranking of every valid 8-chip mesh for the 7B config
    python tools/plan.py --model 7b --chips 8 --moments bfloat16

    # verify the top 3 by AOT lower + XLA memory analysis (re-execs
    # itself under a virtual CPU mesh of the right size; no TPUs
    # needed)
    python tools/plan.py --model proxy_fsdp --chips 8 --verify --top-k 3

    # machine-readable
    python tools/plan.py --model 7b --chips 16 --json

Model presets: ``7b`` / ``13b`` / ``tiny`` / the PROXY_SUITE names
(``proxy_fsdp``, ``proxy_tp``, ``proxy_wide``).

The ``--verify`` path needs the jax backend to expose ``--chips``
(virtual) devices: the CLI always re-execs itself in a subprocess with
``JAX_PLATFORMS=cpu`` and ``--xla_force_host_platform_device_count``
(plus the bf16-collective workaround flag the CPU dryruns use), and the
parent never imports JAX — on a host with a chip it must not claim it.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from dataclasses import replace as dataclasses_replace

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

# the CPU backend aborts promoting bf16 collectives; the TPU backend
# runs the same HLO unmodified (see __graft_entry__._dryrun_7b)
_BF16_FLAG = "--xla_disable_hlo_passes=all-reduce-promotion"


def _model_specs(name: str, args):
    """(ModelSpec, TrainSpec overrides) for a preset name."""
    from paddle_tpu.distributed.planner.memory_model import (
        PROXY_SUITE, ModelSpec, proxy_specs)
    for entry in PROXY_SUITE:
        if entry["name"] == name:
            return proxy_specs(entry)
    presets = {
        "7b": dict(name="llama7b", hidden=4096, intermediate=11008,
                   layers=32, heads=32, kv_heads=32, vocab=32000,
                   max_seq=2048, scan_layers=True),
        "13b": dict(name="llama13b", hidden=5120, intermediate=13824,
                    layers=40, heads=40, kv_heads=40, vocab=32000,
                    max_seq=2048, scan_layers=True),
        "tiny": dict(name="llama_tiny", hidden=256, intermediate=688,
                     layers=4, heads=8, kv_heads=4, vocab=1024,
                     max_seq=512, scan_layers=True),
    }
    if name not in presets:
        raise SystemExit(
            f"unknown --model {name!r}; presets: "
            f"{sorted(presets)} + proxy suite "
            f"{[e['name'] for e in PROXY_SUITE]}")
    return ModelSpec(**presets[name]), None


def _reexec(argv, chips: int) -> int:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["_PADDLE_PLAN_CHILD"] = "1"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f
             and f != _BF16_FLAG]
    flags += [f"--xla_force_host_platform_device_count={chips}",
              _BF16_FLAG]
    env["XLA_FLAGS"] = " ".join(flags)
    proc = subprocess.run([sys.executable, os.path.abspath(__file__)]
                          + argv, env=env, cwd=_REPO)
    return proc.returncode


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="auto-sharding planner (fleet.auto CLI)")
    ap.add_argument("--model", default="7b",
                    help="preset: 7b/13b/tiny or a proxy suite name")
    ap.add_argument("--chips", type=int, default=8)
    ap.add_argument("--hbm-gib", type=float, default=16.0,
                    help="per-device HBM budget (v5e default 16)")
    ap.add_argument("--moments", default="float32",
                    help="optimizer moment dtype "
                         "(float32/bfloat16/float16/int8)")
    ap.add_argument("--amp", default="auto",
                    help="compute dtype: auto/bfloat16/float16/none")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--verify", action="store_true",
                    help="AOT lower + XLA memory analysis of the "
                         "top-k (drops candidates that cannot lower)")
    ap.add_argument("--top-k", type=int, default=3,
                    help="verified plans to return with --verify")
    ap.add_argument("--include-dp", action="store_true",
                    help="also enumerate pure-dp factors")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    if args.verify and os.environ.get("_PADDLE_PLAN_CHILD") != "1":
        # the parent never imports JAX (it would claim the chip where
        # there is one); verification always runs in the CPU-pinned child
        return _reexec(list(argv if argv is not None
                            else sys.argv[1:]), args.chips)

    ms, ts = _model_specs(args.model, args)
    from paddle_tpu.distributed.planner.memory_model import TrainSpec
    from paddle_tpu.distributed.planner.search import (Planner,
                                                       _note_choice)

    if ts is not None:            # proxy entries pin their train spec
        amp, moments = ts.amp_dtype, ts.moments_dtype
        ts = dataclasses_replace(ts, batch=args.batch or ts.batch,
                                 seq=args.seq or ts.seq)
    else:
        amp = None if args.amp in ("none", "f32", "float32") else (
            "bfloat16" if args.amp == "auto" else args.amp)
        moments = args.moments
        ts = TrainSpec(batch=args.batch or args.chips * 2,
                       seq=args.seq or ms.max_seq, amp_dtype=amp,
                       moments_dtype=moments)
    planner = Planner(ms, ts, hbm_gib=args.hbm_gib)
    plans = planner.plan(args.chips,
                         verify_top_k=(args.top_k if args.verify
                                       else 0),
                         include_dp=args.include_dp)
    _note_choice(plans, planner, args.chips)

    if args.json:
        print(json.dumps({
            "model": args.model, "chips": args.chips,
            "hbm_gib": args.hbm_gib,
            "analytic_s": planner.last_analytic_s,
            "verify_s": planner.last_verify_s,
            "n_rejected": len(planner.rejected),
            "rejected": [{"mesh": p.tag, "error": p.verify_error}
                         for p in planner.rejected],
            "plans": [p.asdict() for p in plans]}))
        return 0 if plans else 1

    gib = 1024.0 ** 3
    print(f"# {args.model} on {args.chips} chips, "
          f"{args.hbm_gib:g} GiB HBM budget, moments={moments}, "
          f"amp={amp or 'f32'}")
    hdr = (f"{'rank':>4}  {'mesh':<18} {'verdict':<8} "
           f"{'peak GiB':>9} {'coll MiB/step':>13}  src")
    print(hdr)
    print("-" * len(hdr))
    for i, p in enumerate(plans):
        src = "xla" if p.verified else "analytic"
        print(f"{i:>4}  {p.tag:<18} {p.verdict:<8} "
              f"{p.predicted_peak_bytes / gib:>9.2f} "
              f"{p.collective_bytes / 2 ** 20:>13.1f}  {src}")
    if not plans:
        print("(no lowerable plan — see --verify rejects)")
    return 0 if plans else 1


if __name__ == "__main__":
    sys.exit(main())
