"""How ``correct`` is decided: what the timed path produced against the
configuration's plain reference.

The reference is run after the window has closed, the peak memory has
been read and the program's state is freed; it makes its own weights
from the seed (layer by layer where that is what fits) and takes
nothing that the program has made.  Every number compared has a limit
of its own in the cell's file; a number whose limit is null is printed
and not compared (PERF.md says why).
"""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp

from . import weights as W
from .common import log

F32 = jnp.float32


# ---------------------------------------------------------------------
# serving: the widest gap by which a served token's logit lies below
# the reference's best
# ---------------------------------------------------------------------
def choose_sample(requests, n: int, seed: int):
    """``n`` finished requests drawn from the seed, the longest (prompt
    + served tokens) always among them."""
    done = [r for r in requests if r["done"] and not r.get("failed")
            and r["tokens"]]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFFFFFFFFFF, 7])
    pick = rng.permutation(len(rest))[:max(n - 1, 0)]
    return [longest] + [rest[i] for i in sorted(pick)]


def serve_gaps(ref, cfg, seed, dtype, sample, controls=(), pad_to=None):
    """For each sampled request run the reference ONCE over prompt +
    served tokens and read, at every served position, how far the
    served token's logit lies below the reference's best, in units of
    that position's logit standard deviation.  For each control
    arithmetic the same, for the token that arithmetic puts first.

    Returns {"program": {"gap", "mismatch_share", "tokens"},
             control: {"gap", ...}}.
    """
    specs = ref.param_specs(cfg)
    key = W.seed_key(seed)
    # one padded length for every run of a cell (the server's
    # max_model_len), so that the reference compiles once per cell
    T = max(len(r["prompt"]) + len(r["tokens"]) - 1 for r in sample)
    T = max(-(-T // 128) * 128, int(pad_to or 0))
    modes = (None,) + tuple(controls)

    def make(names):
        t = jax.jit(functools.partial(
            W.make_tree, specs, dtype=dtype, names=tuple(names)))(key)
        return {k.split(".")[-1]: v.astype(F32) for k, v in t.items()}

    ids, pos = [], jnp.arange(T)
    for r in sample:
        seq = np.concatenate([r["prompt"], np.asarray(r["tokens"][:-1],
                                                      np.int32)])
        ids.append(jnp.asarray(np.pad(seq, (0, T - len(seq)))))
    gp = make(ref.GLOBAL_LEAVES)
    hs = {m: [ref.embed(cfg, gp, i) for i in ids] for m in modes}
    layer = {m: jax.jit(functools.partial(ref.layer_apply, cfg, q=m))
             for m in modes}
    with jax.default_matmul_precision("highest"):
        for l in range(cfg["num_hidden_layers"]):
            lp = make(ref.layer_names(l))
            for m in modes:
                hs[m] = [layer[m](lp, h, pos) for h in hs[m]]
            del lp

        @jax.jit
        def read(gp, h_ref, h_ctl, toks):
            lr = ref.logits(cfg, gp, h_ref)
            best = lr.max(-1)
            sd = jnp.std(lr, -1)
            served = jnp.take_along_axis(lr, toks[:, None], -1)[:, 0]
            out = {"program": ((best - served) / sd,
                               jnp.argmax(lr, -1) != toks)}
            for m, h in h_ctl.items():
                c = jnp.argmax(ref.logits(cfg, gp, h, q=m), -1)
                cg = jnp.take_along_axis(lr, c[:, None], -1)[:, 0]
                out[m] = ((best - cg) / sd, jnp.argmax(lr, -1) != c)
            return out

        acc = {k: [[], []] for k in ("program",) + tuple(controls)}
        for i, r in enumerate(sample):
            L, n = len(r["prompt"]), len(r["tokens"])
            toks = np.zeros(T, np.int32)
            toks[L - 1:L - 1 + n] = r["tokens"]
            res = read(gp, hs[None][i], {m: hs[m][i] for m in controls},
                       jnp.asarray(toks))
            for k, (gap, mis) in res.items():
                acc[k][0].append(np.asarray(gap)[L - 1:L - 1 + n])
                acc[k][1].append(np.asarray(mis)[L - 1:L - 1 + n])
    out = {}
    for k, (gaps, mis) in acc.items():
        g, m = np.concatenate(gaps), np.concatenate(mis)
        out[k] = {"gap": float(g.max()), "mismatch_share": float(m.mean()),
                  "tokens": int(g.size)}
    return out


# ---------------------------------------------------------------------
# training: loss of the first three steps, the first gradient's norm
# and the parameters' change after the three, by the worst leaf
# ---------------------------------------------------------------------
def _norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(F32))))
            for k, v in tree.items()}


SAMPLE = 4096


def sample_indices(specs: dict, seed: int, n: int = SAMPLE) -> dict:
    """leaf -> flat indices of the elements whose first gradient is
    compared one by one: all of a small leaf, ``n`` drawn from the seed
    of a large one."""
    import zlib
    out = {}
    for name, (shape, _) in specs.items():
        size = int(np.prod(shape))
        if size <= n:
            out[name] = np.arange(size, dtype=np.int32)
        else:
            r = np.random.default_rng([int(seed) & 0xFFFFFFFFFFFFFFFF, 9,
                                       zlib.crc32(name.encode())])
            out[name] = np.sort(r.integers(0, size, n)).astype(np.int32)
    return out


def take_samples(tree: dict, idx: dict) -> dict:
    """The sampled elements of every leaf (on the device; small)."""
    return {k: jnp.take(v.reshape(-1).astype(F32), jnp.asarray(idx[k]))
            for k, v in tree.items()}


def _rows(batch, lo, hi):
    return {k: jnp.asarray(v[lo:hi]) for k, v in batch.items()}


def train_reference(ref, cfg, seed, batches, opt, rows_per_block: int,
                    q=None, fault=None, chips: int = 1, n_steps: int = 3):
    """The reference's first ``n_steps`` AdamW steps in float32 over
    the window's own first batches, in blocks of rows.  ``q`` is the
    arithmetic (None, or a control); ``fault`` plants one:

    - ``half_batch``: half of the batch left out, the mean taken over
      the rest;
    - ``no_exchange``: the gradient of the first chip's rows only (the
      exchange between chips left out);
    - ``state_unchanged``: a step that returns its state unchanged: the
      losses are those of the first parameters on every batch, and the
      state reads no gradient and no change.

    Returns {"loss": [...], "gnorm": {leaf: ..}, "dnorm": {leaf: ..},
    "gsample": {leaf: sampled elements of the first gradient}}.
    """
    specs = ref.param_specs(cfg)
    key = W.seed_key(seed)
    make = jax.jit(functools.partial(W.make_tree, specs, dtype=F32))
    p0 = make(key)
    B = len(next(iter(batches[0].values())))
    rows = B
    if fault == "half_batch":
        rows = B // 2
    elif fault == "no_exchange":
        rows = B // chips
    elif fault not in (None, "state_unchanged"):
        raise ValueError(f"unknown fault {fault!r}")
    b1, b2, eps = opt["beta1"], opt["beta2"], opt["eps"]
    lr, wd = opt["lr"], opt["weight_decay"]

    @jax.jit
    def grad_block(p, blk):
        return jax.value_and_grad(
            lambda p_: ref.loss_share(cfg, p_, blk, rows, q))(p)

    @jax.jit
    def add(a, b):
        return jax.tree_util.tree_map(jnp.add, a, b)

    @jax.jit
    def adamw(p, g, m, v, t):
        m = jax.tree_util.tree_map(lambda m_, g_: b1 * m_ + (1 - b1) * g_,
                                   m, g)
        v = jax.tree_util.tree_map(
            lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)

        def upd(p_, m_, v_):
            mh = m_ / (1 - b1 ** t)
            vh = v_ / (1 - b2 ** t)
            return p_ * (1 - lr * wd) - lr * mh / (jnp.sqrt(vh) + eps)
        return jax.tree_util.tree_map(upd, p, m, v), m, v

    out = {"loss": []}
    p = p0
    m = jax.tree_util.tree_map(jnp.zeros_like, p0)
    v = jax.tree_util.tree_map(jnp.zeros_like, p0)
    with jax.default_matmul_precision("highest"):
        for t in range(1, n_steps + 1):
            batch = batches[(t - 1) % len(batches)]
            loss, g = None, None
            for lo in range(0, rows, rows_per_block):
                l_, g_ = grad_block(p, _rows(batch, lo,
                                             min(lo + rows_per_block, rows)))
                loss = l_ if loss is None else loss + l_
                g = g_ if g is None else add(g, g_)
            out["loss"].append(float(loss))
            if t == 1:
                out["gnorm"] = {k: float(x) for k, x in
                                jax.jit(_norms)(g).items()}
                out["gsample"] = {
                    k: np.asarray(x) for k, x in jax.jit(take_samples)(
                        g, sample_indices(specs, seed)).items()}
            if fault != "state_unchanged":
                p, m, v = adamw(p, g, m, v, F32(t))
            del g
        out["dnorm"] = {k: float(x) for k, x in jax.jit(
            lambda a, b: _norms(jax.tree_util.tree_map(jnp.subtract, a, b))
        )(p, p0).items()}
    if fault == "state_unchanged":       # what the untouched state reads
        out["gnorm"] = {k: 0.0 for k in out["gnorm"]}
        out["gsample"] = {k: np.zeros_like(x)
                          for k, x in out["gsample"].items()}
    return out


def worst_leaf_gap(prog: dict, refd: dict, leaves=None):
    """max over leaves of |prog - ref| / max(ref, median leaf's ref):
    the gap between the norms, not the norm of a difference.  Returns
    (gap, leaf)."""
    leaves = list(leaves if leaves is not None else refd)
    med = float(np.median([refd[k] for k in leaves])) if leaves else 0.0
    worst, at = 0.0, None
    for k in leaves:
        d = abs(prog[k] - refd[k]) / max(refd[k], med, 1e-30)
        if d > worst or at is None:
            worst, at = d, k
    return worst, at


def train_numbers(prog: dict, refd: dict) -> dict:
    """The numbers compared for a training cell, from the program's
    readings and the reference's (same structure)."""
    loss = max(abs(a - b) / max(abs(b), 1e-30)
               for a, b in zip(prog["loss"], refd["loss"]))
    g, g_at = worst_leaf_gap(prog["gnorm"], refd["gnorm"])
    # a leaf whose gradient is nought to rounding in the reference
    # moves under Adam by round-off alone: left out of the change by a
    # rule on the reference's gradient, not by name
    med = float(np.median(list(refd["gnorm"].values())))
    moved = [k for k, x in refd["gnorm"].items() if x >= 1e-3 * med]
    d, d_at = worst_leaf_gap(prog["dnorm"], refd["dnorm"], moved)
    loss1 = abs(prog["loss"][0] - refd["loss"][0]) / abs(refd["loss"][0])
    # the first gradient element by element, on the sampled elements:
    # rms of the difference over rms of the reference (or of the median
    # leaf, whichever is larger), by the median leaf and by the worst
    rms = {k: float(np.sqrt(np.mean(np.square(v))))
           for k, v in refd["gsample"].items()}
    rmed = float(np.median(list(rms.values())))
    elem = {k: float(np.sqrt(np.mean(np.square(
        prog["gsample"][k] - refd["gsample"][k]))))
        / max(rms[k], rmed, 1e-30) for k in rms}
    e_at = max(elem, key=elem.get)
    return {"loss_gap": loss, "loss1_gap": loss1, "grad_norm_gap": g,
            "update_norm_gap": d,
            "grad_elem_gap_median": float(np.median(list(elem.values()))),
            "grad_elem_gap_worst": elem[e_at],
            "_at": {"grad_norm_gap": g_at, "update_norm_gap": d_at,
                    "grad_elem_gap_worst": e_at,
                    "left_out": sorted(set(refd["gnorm"]) - set(moved))}}


def judge(numbers: dict, limits: dict):
    """(correct, {name: [value, limit]}) -- every number beside its
    limit; a null limit means printed, not compared."""
    table, ok = {}, True
    for name, lim in limits.items():
        val = numbers.get(name)
        table[name] = [val, lim]
        if lim is None:
            continue
        if val is None or not math.isfinite(val) or val > lim:
            ok = False
    return ok, table


def judge_in_place(numbers: dict, limits: dict, what: str):
    """A control's or a planted fault's numbers put in the program's
    place: the same ``judge``, the cell's own limits.  Returns
    {"correct": bool, "failed": [names over their limit]} and logs the
    verdict."""
    ok, table = judge(numbers, limits)
    over = [k for k, (v, lim) in table.items()
            if lim is not None and not (v is not None and math.isfinite(v)
                                        and v <= lim)]
    log(f"{what} in the program's place: correct={ok}, over their limit "
        f"{ {k: table[k] for k in over} }")
    return {"correct": ok, "failed": over}
