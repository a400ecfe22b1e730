"""Every served model writes the block scopes of
``paddle_tpu.observability.scopes``: the server's decode and prefill
programs, lowered for the benchmark's toy configurations, name exactly
one block on the path of every dot, gather, scatter and custom call.
A new model that writes no scope fails here, not on the chip, where a
reader of device time by block would find its time under no scope."""
import json
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from paddle_tpu.inference import GenerationServer  # noqa: E402
from paddle_tpu.observability.scopes import VOCABULARY  # noqa: E402

TOY = os.path.join(ROOT, "tests", "perfbench_tests", "toy")
CELLS = {"mistral7b-serve-decode": "manifest",
         "kimi-linear-serve-decode": "manifest_kimi",
         "lfm2-8b-a1b-serve-decode": "manifest_lfm2",
         "openpangu-ultra-moe-serve-decode": "manifest_pangu",
         "minimax-m3-serve-longctx": "manifest_minimax"}
HEAVY = ("dot", "gather", "scatter", "custom-call")
_COMP = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$")
_INST = re.compile(r"^\s*(?:ROOT )?%[\w.\-]+ = .*? ([\w\-]+)\(")
_CALLED = re.compile(r"(?:to_apply|calls|body|condition|true_computation"
                     r"|false_computation|branch_computations)="
                     r"(\{[^}]*\}|%[\w.\-]+)")


def op_paths(hlo: str):
    """[(opcode, op_name path)] of every instruction of an unoptimized
    HLO module.  A computation that a ``call`` reaches names its ops
    relative to the call, and XLA's inliner puts them under the call's
    own path: so does this."""
    comps, entry, name = {}, None, None
    for ln in hlo.split("\n"):
        m = _COMP.match(ln)
        if m:
            name = m.group(1)
            comps[name] = []
            entry = name if ln.startswith("ENTRY") else entry
        elif ln.startswith("}"):
            name = None
        elif name is not None and _INST.match(ln):
            mo = re.search(r'op_name="([^"]*)"', ln)
            comps[name].append((_INST.match(ln).group(1),
                                mo.group(1) if mo else "", ln))
    prefix, todo, out = {entry: ""}, [entry], []
    while todo:
        c = todo.pop()
        for opc, path, ln in comps[c]:
            full = "/".join(p for p in (prefix[c], path) if p)
            out.append((opc, full))
            for group in _CALLED.findall(ln):
                for d in re.findall(r"%([\w.\-]+)", group):
                    if d in comps and d not in prefix:
                        prefix[d] = full if opc == "call" else prefix[c]
                        todo.append(d)
    return out


def scopes_on(path: str):
    """The vocabulary names among the scopes of an op's path (the last
    part names the op itself)."""
    return [p for p in path.split("/")[:-1] if p in VOCABULARY]


@pytest.fixture(scope="module", params=sorted(CELLS))
def programs(request):
    """(decode program, largest prefill program) of one toy cell's
    server, as unoptimized HLO text."""
    from perfbench.harness import manifest as M
    name = request.param
    with open(os.path.join(TOY, CELLS[name] + ".json")) as f:
        cell = M.Cell(json.load(f), name, bench_dir=TOY)
    cfg, sv = cell.config, cell.spec["server"]
    model = cell.binding().build_serving(cfg, sv["max_model_len"])
    srv = GenerationServer(
        model, num_slots=sv["num_slots"], block_size=sv["block_size"],
        max_model_len=sv["max_model_len"],
        prompt_buckets=sv["prompt_buckets"],
        max_prefill_batch=sv["max_prefill_batch"], prefix_cache=False)
    srv._build_programs()
    S = jax.ShapeDtypeStruct
    aval = lambda a: S(a.shape, a.dtype)      # noqa: E731
    pv = {k: aval(v) for k, v in srv._pvals.items()}
    pools = [{k: aval(v) for k, v in d.items()} for d in srv._pools]
    B, M_, W = sv["num_slots"], srv._M, 2

    def rows(n, *tail):
        return (S((n, M_), jnp.int32),) + tail + (
            S((n, W), jnp.uint32),)

    i32, f32, b1 = jnp.int32, jnp.float32, jnp.bool_
    decode = srv._decode_fn.lower(
        pv, pools, S(srv._prev.shape, i32), S((B, 1), i32), S((B, 1), i32),
        *rows(B, S((B, 1), b1)), S((B,), i32), S((B,), f32), S((B,), i32),
        S((B,), f32), S((B,), b1))
    pb, bucket = sv["max_prefill_batch"], sv["prompt_buckets"][-1]
    prefill = srv._prefill_fn.lower(
        pv, pools, S((pb, bucket), i32), S((pb,), i32), S((pb,), i32),
        *rows(pb), S((pb,), f32), S((pb,), i32), S((pb,), f32),
        S((pb,), b1),
        **{k: aval(np.asarray(v))
           for k, v in srv._row_slots([], pb).items()})
    return name, [low.compiler_ir("hlo").as_hlo_module().to_string()
                  for low in (decode, prefill)]


# the blocks of each model beside the embedding, the head and the
# sampler: a mixer in the two hybrid models, experts in the four sparse
# ones behind a dense first layer (MiniMax's indexer sits inside
# ``attn`` under a name of its own that is not a block)
BLOCKS = {"mistral7b-serve-decode": {"attn", "ffn"},
          "kimi-linear-serve-decode": {"attn", "mixer", "moe", "ffn"},
          "lfm2-8b-a1b-serve-decode": {"attn", "mixer", "moe", "ffn"},
          "openpangu-ultra-moe-serve-decode": {"attn", "moe", "ffn"},
          "minimax-m3-serve-longctx": {"attn", "moe", "ffn"}}


def test_every_heavy_op_names_exactly_one_block(programs):
    name, texts = programs
    for text in texts:
        heavy = [(o, p) for o, p in op_paths(text) if o in HEAVY]
        bad = [(o, p) for o, p in heavy if len(scopes_on(p)) != 1]
        assert not bad, (name, bad[:5])
        assert {scopes_on(p)[0] for _, p in heavy} == BLOCKS[name] | {
            "embed", "head", "sample"}, name


def test_no_model_writes_a_scope_of_its_own(programs):
    name, texts = programs
    for text in texts:
        assert not [p for _, p in op_paths(text)
                    if re.search(r"(^|/)(lfm2|pangu)\.", p)], name


def test_a_call_puts_its_computations_ops_under_its_path():
    hlo = """ENTRY %main.4 (x.1: f32[4]) -> f32[2] {
  %x.1 = f32[4]{0} parameter(0), metadata={op_name="x"}
  ROOT %jit__take_.1 = f32[2]{0} call(%x.1), to_apply=%_take.3, metadata={op_name="jit(f)/attn/jit(_take)"}
}
"""
    callee = """%_take.3 (Arg_0.3: f32[4]) -> f32[2] {
  %Arg_0.3 = f32[4]{0} parameter(0)
  ROOT %gather.1 = f32[2]{0} gather(%Arg_0.3), metadata={op_name="gather"}
}
"""
    assert op_paths(callee + "\n" + hlo) == [
        ("parameter", "x"), ("call", "jit(f)/attn/jit(_take)"),
        ("parameter", "jit(f)/attn/jit(_take)"),
        ("gather", "jit(f)/attn/jit(_take)/gather")]
