"""GraftLint (ISSUE 6): jaxpr program auditor + AST framework linter.

Covers both pillars over the shared Finding format:

- jaxpr rules: each seeded known-bad program (undonated donor, bf16->f32
  state widening, f64 creep, host callback in step, oversized baked-in
  constant) is detected with the RIGHT rule id and exactly one finding;
  clean equivalents produce none.
- step/predictor integration: ``DistributedTrainStep.audit()`` reports
  donation status + the collective inventory for the plain data-parallel
  step, asserted against the mesh's expectation (one all-reduce per grad
  leaf + one for the loss mean); ``Predictor.audit()`` is clean on a
  saved artifact.
- AST rules: the checked-in PRE-FIX lock-cycle fixture is flagged while
  the current ``fleet/ps_service.py`` passes clean under its declared
  ``# lint: lock-order`` directives; tracing hazards (.item/float/np
  under jit, time/random/env under trace) and hot-loop rules fire on the
  hazard fixture; suppressions work.
- baseline: new findings fail, baselined findings (with reasons) pass,
  reason-less entries are rejected, and the real repo module set is
  clean outside ``tools/lint_baseline.json``.
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.analysis import (SEV_ERROR, apply_baseline, audit_fn,
                                 lint_file, lint_paths, lint_source,
                                 load_baseline)
from paddle_tpu.distributed.fleet.dist_step import DistributedTrainStep

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "graft_lint")
BASELINE = os.path.join(REPO, "tools", "lint_baseline.json")


def _rules(findings):
    return [f.rule for f in findings]


# ----------------------------------------------------------------------
# pillar 1: jaxpr audit rules
# ----------------------------------------------------------------------

class TestJaxprRules:
    P = jax.ShapeDtypeStruct((512, 512), jnp.float32)   # 1 MiB
    X = jax.ShapeDtypeStruct((8, 512), jnp.float32)

    @staticmethod
    def _train(params, x):
        g = jnp.mean(x) * params
        return params - 0.1 * g, jnp.mean(g)

    def test_undonated_buffer_flagged_once(self):
        rep = audit_fn(self._train, (self.P, self.X))
        assert _rules(rep.findings) == ["jaxpr.undonated-buffer"]
        assert rep.findings[0].severity == SEV_ERROR
        assert rep.donated_fraction() == 0.0

    def test_donated_equivalent_clean(self):
        rep = audit_fn(self._train, (self.P, self.X), donate_argnums=(0,))
        assert rep.findings == []
        assert rep.donated_fraction() > 0.9

    def test_small_undonated_buffer_below_threshold_ok(self):
        small = jax.ShapeDtypeStruct((8, 8), jnp.float32)
        rep = audit_fn(self._train, (small, self.X))
        assert rep.findings == []

    def test_widen_state_flagged_once(self):
        def widen(w, x):
            # bf16 state comes back f32: the silent upcast that doubles
            # the at-rest slot bytes
            return (w.astype(jnp.float32) + x.mean()), x

        w = jax.ShapeDtypeStruct((256, 16), jnp.bfloat16)
        x = jax.ShapeDtypeStruct((4,), jnp.float32)
        rep = audit_fn(widen, (w, x), donate_argnums=(0,))
        assert _rules(rep.findings) == ["jaxpr.dtype-widen-state"]

    def test_widen_state_roundtrip_clean(self):
        def keep(w, x):
            return (w.astype(jnp.float32)
                    + x.mean()).astype(jnp.bfloat16), x

        w = jax.ShapeDtypeStruct((256, 16), jnp.bfloat16)
        x = jax.ShapeDtypeStruct((4,), jnp.float32)
        rep = audit_fn(keep, (w, x), donate_argnums=(0,))
        assert rep.findings == []
        assert rep.widening_casts >= 1   # the working-form decode shows

    def test_f64_creep_flagged_once(self):
        with jax.enable_x64():
            def creep(x):
                return x.astype(jnp.float64) * 2.0

            rep = audit_fn(creep,
                           (jax.ShapeDtypeStruct((16,), jnp.float32),))
        assert _rules(rep.findings) == ["jaxpr.dtype-f64"]

    def test_host_callback_flagged_once(self):
        def cb(x):
            y = jax.pure_callback(
                lambda a: np.asarray(a) * 2,
                jax.ShapeDtypeStruct((16,), np.float32), x)
            return y + 1

        rep = audit_fn(cb, (jax.ShapeDtypeStruct((16,), jnp.float32),))
        assert _rules(rep.findings) == ["jaxpr.host-callback"]
        assert rep.findings[0].severity == SEV_ERROR

    def test_large_const_flagged_once(self):
        big = jnp.ones((256, 256), jnp.float32)

        def cc(x):
            return x @ big

        rep = audit_fn(cc, (jax.ShapeDtypeStruct((4, 256), jnp.float32),))
        assert _rules(rep.findings) == ["jaxpr.large-const"]
        assert rep.findings[0].data["bytes"] == 256 * 256 * 4

    def test_collective_inventory_shard_map(self):
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))

        def sm(x):
            return jax.shard_map(lambda v: jax.lax.psum(v, "dp"),
                                 mesh=mesh, in_specs=P("dp"),
                                 out_specs=P())(x)

        rep = audit_fn(sm, (jax.ShapeDtypeStruct((8, 4), jnp.float32),))
        assert rep.collectives["psum"]["count"] == 1
        assert rep.collectives["psum"]["bytes"] == 8 * 4 * 4
        assert rep.collective_count("psum") == 1

    # one case per family of COLLECTIVE_PRIMS a shard_map body can ask
    # for: (lax call, family, check_vma, bytes of the one result on a
    # 4-way axis over a global f32[8, 16]).  A primitive the installed
    # JAX renames shows here as a missing family, not as a program
    # "without collectives".
    _FAMILIES = {
        "psum": (lambda v: jax.lax.psum(v, "x"), "psum", True, 2 * 16),
        "psum_unchecked": (lambda v: jax.lax.psum(v, "x"), "psum",
                           False, 2 * 16),
        "pmean": (lambda v: jax.lax.pmean(v, "x"), "psum", True, 2 * 16),
        "pmax": (lambda v: jax.lax.pmax(v, "x"), "pmax", True, 2 * 16),
        "pmin": (lambda v: jax.lax.pmin(v, "x"), "pmin", True, 2 * 16),
        "all_gather": (lambda v: jax.lax.all_gather(v, "x", tiled=True),
                       "all_gather", True, 8 * 16),
        "psum_scatter": (
            lambda v: jax.lax.psum_scatter(v, "x", scatter_dimension=1,
                                           tiled=True),
            "reduce_scatter", True, 2 * 4),
        "ppermute": (
            lambda v: jax.lax.ppermute(
                v, "x", [(i, (i + 1) % 4) for i in range(4)]),
            "ppermute", True, 2 * 16),
        "all_to_all": (
            lambda v: jax.lax.all_to_all(v, "x", 1, 0, tiled=True),
            "all_to_all", True, 8 * 4),
    }

    @pytest.mark.parametrize("case", sorted(_FAMILIES))
    def test_collective_families_counted_in_shard_map(self, case):
        from jax.sharding import Mesh, PartitionSpec as P
        body, family, check_vma, n_out = self._FAMILIES[case]
        mesh = Mesh(np.array(jax.devices()[:4]), ("x",))

        def sm(x):
            return jax.shard_map(body, mesh=mesh, in_specs=P("x"),
                                 out_specs=P("x"),
                                 check_vma=check_vma)(x)

        rep = audit_fn(sm, (jax.ShapeDtypeStruct((8, 16), jnp.float32),))
        assert rep.collectives == {
            family: {"count": 1, "bytes": n_out * 4}}, rep.collectives

    def test_collectives_of_a_shard_map_gradient_are_counted(self):
        # the backward pass of a shard_map asks for a collective no line
        # of the body names: the cotangent of a replicated weight is
        # summed over the axis (the transpose of its pvary), beside the
        # forward pass's psum of the scalar
        from jax.sharding import Mesh, PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()[:4]), ("x",))

        def loss(w, x):
            return jax.shard_map(
                lambda w, v: jax.lax.psum(jnp.sum(v @ w), "x"),
                mesh=mesh, in_specs=(P(), P("x")), out_specs=P())(w, x)

        fwd = audit_fn(loss, (jax.ShapeDtypeStruct((16, 4), jnp.float32),
                              jax.ShapeDtypeStruct((8, 16), jnp.float32)))
        assert fwd.collectives == {"psum": {"count": 1, "bytes": 4}}
        bwd = audit_fn(jax.grad(loss),
                       (jax.ShapeDtypeStruct((16, 4), jnp.float32),
                        jax.ShapeDtypeStruct((8, 16), jnp.float32)))
        assert bwd.collectives == {
            "psum": {"count": 2, "bytes": 4 + 16 * 4 * 4}}, bwd.collectives

    def test_hlo_inventory_counts_the_arrays_of_a_combined_collective(
            self):
        from paddle_tpu.analysis.jaxpr_audit import \
            hlo_collective_inventory
        hlo = "\n".join([
            "  %ar.1 = (f32[16]{0}, f32[16,8]{1,0}) all-reduce(%a, %b), "
            "channel_id=3, to_apply=%add",
            "  %gte = f32[16]{0} get-tuple-element(%ar.1), index=0",
            "  %ar.2 = f32[] all-reduce(%c), channel_id=1, to_apply=%add",
            "  %ag = bf16[4,8]{1,0} all-gather(%d), dimensions={0}",
        ])
        assert hlo_collective_inventory(hlo) == {
            "all-reduce": {"count": 3, "bytes": (16 + 16 * 8 + 1) * 4},
            "all-gather": {"count": 1, "bytes": 4 * 8 * 2}}


# ----------------------------------------------------------------------
# pillar 1 integration: DistributedTrainStep.audit / Predictor.audit
# ----------------------------------------------------------------------

class _MLP(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(8, 16)
        self.fc2 = nn.Linear(16, 4)

    def forward(self, x):
        return self.fc2(nn.functional.relu(self.fc1(x)))


def _mlp_step(guard_health=False):
    paddle.seed(7)
    m = _MLP()
    opt = optimizer.Adam(parameters=m.parameters(), learning_rate=1e-3)
    ce = nn.CrossEntropyLoss()

    def loss_fn(x, y):
        return ce(m(x), y)

    return DistributedTrainStep(m, loss_fn, opt,
                                guard_health=guard_health), m


class TestStepAudit:
    BATCH = (np.zeros((8, 8), np.float32), np.zeros((8,), np.int64))

    def test_plain_dp_step_clean_and_collectives_match_mesh(self):
        step, m = _mlp_step()
        rep = step.audit(*self.BATCH, include_hlo=True)
        assert rep.errors() == [], rep.summary()
        # donation: every param/opt-state/buffer leaf donated; lr, the
        # RNG key and the batch legitimately are not
        for d in rep.donation:
            name = d["input"]
            if name.split("[")[0] in ("params", "buffers", "opt_state"):
                assert d["donated"], d
            else:
                assert not d["donated"], d
        # collective inventory vs the mesh expectation: the pure
        # data-parallel step reduces each grad leaf once, plus TWO
        # scalar reductions for the cross-entropy mean (loss sum and
        # valid-token count) — one all-reduce per parameter + 2 (XLA
        # emits them under dp=1 too, as degenerate single-participant
        # reductions)
        n_params = len(list(m.named_parameters()))
        assert rep.collective_count("psum") == n_params + 2
        param_bytes = sum(
            int(np.prod(p._value.shape)) * 4
            for _, p in m.named_parameters())
        assert rep.hlo_collectives["all-reduce"]["bytes"] == \
            param_bytes + 8
        # no other collective family appears in the plain DP step
        assert set(rep.hlo_collectives) == {"all-reduce"}

    def test_audit_before_and_after_first_step_agree(self):
        step, _ = _mlp_step()
        pre = step.audit(*self.BATCH, include_hlo=False)
        step(*self.BATCH)
        post = step.audit(include_hlo=False)
        assert pre.errors() == [] and post.errors() == []
        assert [d["donated"] for d in pre.donation] == \
            [d["donated"] for d in post.donation]

    def test_audit_before_first_step_requires_batch(self):
        step, _ = _mlp_step()
        with pytest.raises(RuntimeError, match="sample batch"):
            step.audit()

    def test_guard_health_step_audit_clean(self):
        # the fused health reduction compiles INTO the step and must not
        # introduce an undonated buffer or a host callback
        step, _ = _mlp_step(guard_health=True)
        rep = step.audit(*self.BATCH, include_hlo=False)
        assert rep.errors() == [], rep.summary()

    def test_host_callback_in_loss_is_caught(self):
        paddle.seed(7)
        m = _MLP()
        opt = optimizer.Adam(parameters=m.parameters(),
                             learning_rate=1e-3)
        ce = nn.CrossEntropyLoss()

        def poisoned_loss(x, y):
            # a host callback smuggled into the step (e.g. a data-
            # inspection fetch someone forgot): the auditor must flag
            # it.  It rides the (undifferentiated) label path so the
            # backward still traces.
            jax.pure_callback(lambda v: np.asarray(v)[:0].astype(
                np.float32), jax.ShapeDtypeStruct((0,), np.float32),
                y._value)
            return ce(m(x), y)

        step = DistributedTrainStep(m, poisoned_loss, opt)
        rep = step.audit(*self.BATCH, include_hlo=False)
        assert "jaxpr.host-callback" in _rules(rep.errors())


class TestPredictorAudit:
    def _save(self, tmp_path, bf16=False):
        from paddle_tpu.inference import Config, create_predictor
        from paddle_tpu.static import InputSpec

        paddle.seed(3)
        m = _MLP()
        m.eval()
        path = os.path.join(str(tmp_path), "m")
        paddle.jit.save(m, path,
                        input_spec=[InputSpec([None, 8], "float32", "x")])
        cfg = Config(path)
        if bf16:
            cfg.enable_bf16()
        return create_predictor(cfg)

    def test_predictor_audit_clean(self, tmp_path):
        pred = self._save(tmp_path)
        rep = pred.audit()
        assert rep.findings == [], rep.summary()
        assert rep.program.startswith("Predictor[")

    def test_bf16_predictor_upcasts_are_visible_not_flagged(self, tmp_path):
        # bf16 serving upcasts weights inside the program by design:
        # the report counts the widening casts but flags nothing (the
        # output is activations, not round-tripped state)
        pred = self._save(tmp_path, bf16=True)
        rep = pred.audit()
        assert rep.findings == [], rep.summary()
        assert rep.widening_casts >= 1


# ----------------------------------------------------------------------
# pillar 2: AST lint
# ----------------------------------------------------------------------

class TestLockRules:
    def test_prefix_lock_cycle_fixture_flagged_once(self):
        fs = lint_file(os.path.join(FIXTURES, "lock_cycle.py"))
        assert _rules(fs) == ["lock.order-cycle"]
        f = fs[0]
        assert f.severity == SEV_ERROR
        assert "_apply_lock" in f.detail and "rep[lock]" in f.detail
        # the stable key carries both locks, no line numbers
        assert "lock_cycle.py" in f.key and str(f.line) not in f.key

    def test_fixed_ordering_passes_clean(self):
        # the fix applied in the PR 3 review: release the sink lock
        # BEFORE re-taking the apply lock
        src = open(os.path.join(FIXTURES, "lock_cycle.py")).read()
        fixed = src.replace(
            """            with self._apply_lock:
                self._replicas.remove(rep)
            rep["lock"].release()""",
            """            rep["lock"].release()
            with self._apply_lock:
                self._replicas.remove(rep)""")
        assert fixed != src
        assert lint_source(fixed, "lock_cycle_fixed.py") == []

    def test_declared_order_violation_rule(self):
        src = open(os.path.join(FIXTURES, "lock_cycle.py")).read()
        declared = src.replace(
            "import threading",
            "import threading\n"
            "# lint: lock-order: Server._apply_lock -> rep[lock]")
        fs = lint_source(declared, "lock_cycle_declared.py")
        assert _rules(fs) == ["lock.order-violation"]

    def test_reentrant_plain_lock_flagged(self):
        src = (
            "import threading\n"
            "class A:\n"
            "    def __init__(self):\n"
            "        self._l = threading.Lock()\n"
            "    def f(self):\n"
            "        with self._l:\n"
            "            with self._l:\n"
            "                pass\n")
        assert _rules(lint_source(src, "re.py")) == \
            ["lock.reentrant-acquire"]
        # RLock is reentrant by design — clean
        assert lint_source(src.replace("Lock()", "RLock()"),
                           "re2.py") == []

    def test_ps_service_passes_clean_with_declared_order(self):
        path = os.path.join(REPO, "paddle_tpu", "distributed", "fleet",
                            "ps_service.py")
        assert lint_file(path) == []
        # the machine-readable declaration the linter verifies is there
        from paddle_tpu.analysis.ast_lint import _parse_directives
        _, declared = _parse_directives(open(path).read())
        assert ("PSServer._apply_lock", "rep[lock]") in \
            [(a, b) for a, b, _ in declared]


class TestTracingRules:
    def test_hazard_fixture_rules(self):
        fs = lint_file(os.path.join(FIXTURES, "traced_hazards.py"))
        by_rule = {}
        for f in fs:
            by_rule.setdefault(f.rule, []).append(f)
        assert len(by_rule["trace.host-sync"]) == 3   # item/float/np
        assert len(by_rule["trace.impure-time"]) == 1
        assert len(by_rule["trace.impure-random"]) == 1
        assert len(by_rule["trace.env-read"]) == 1
        assert len(by_rule["hot.env-read-loop"]) == 1
        assert len(by_rule["hot.host-sync-loop"]) == 1
        assert len(fs) == 8

    def test_item_under_jit_flagged(self):
        src = (
            "import jax\n"
            "def step(x):\n"
            "    return x.item() + 1\n"
            "step_c = jax.jit(step)\n")
        fs = lint_source(src, "item.py")
        assert _rules(fs) == ["trace.host-sync"]

    def test_same_code_outside_jit_not_flagged(self):
        src = (
            "def step(x):\n"
            "    return x.item() + 1\n")
        assert lint_source(src, "noitem.py") == []

    def test_traced_propagation_through_helper(self):
        src = (
            "import jax, time\n"
            "def helper(x):\n"
            "    return x * time.time()\n"
            "def step(x):\n"
            "    return helper(x)\n"
            "step_c = jax.jit(step)\n")
        assert "trace.impure-time" in _rules(lint_source(src, "p.py"))

    def test_int_on_shapes_not_flagged(self):
        src = (
            "import jax\n"
            "def step(x):\n"
            "    n = int(x.shape[0])\n"
            "    return x * float(x.shape[0]) * n\n"
            "step_c = jax.jit(step)\n")
        assert lint_source(src, "shapes.py") == []

    def test_suppression_directive(self):
        src = (
            "import jax\n"
            "def step(x):\n"
            "    return x.item()  # lint: ok(trace.host-sync)\n"
            "step_c = jax.jit(step)\n")
        assert lint_source(src, "sup.py") == []

    def test_callback_body_is_host_code_not_flagged(self):
        src = (
            "import jax\n"
            "import numpy as np\n"
            "def step(x):\n"
            "    return jax.pure_callback(\n"
            "        lambda a: np.asarray(a) * 2, x, x)\n"
            "step_c = jax.jit(step)\n")
        assert lint_source(src, "cb.py") == []

    def test_repo_default_set_clean_outside_baseline(self):
        # the whole point of the tier: the current repo produces no
        # unbaselined findings (file list per ISSUE 6 — threaded
        # modules + jit-adjacent hot paths)
        findings = lint_paths(root=REPO)
        new, _, _ = apply_baseline(findings, load_baseline(BASELINE))
        assert new == [], "\n".join(f.format() for f in new)


# ----------------------------------------------------------------------
# baseline machinery + CI gate wiring
# ----------------------------------------------------------------------

class TestBaseline:
    def test_apply_baseline_splits_and_reports_stale(self):
        fs = lint_file(os.path.join(FIXTURES, "lock_cycle.py"))
        assert fs
        new, acc, stale = apply_baseline(fs, {})
        assert new == fs and acc == [] and stale == []
        base = {fs[0].key: "known pre-fix fixture", "gone|x": "stale"}
        new, acc, stale = apply_baseline(fs, base)
        assert new == [] and acc == fs and stale == ["gone|x"]

    def test_baseline_reason_required(self, tmp_path):
        from paddle_tpu.analysis import baseline_entry
        fs = lint_file(os.path.join(FIXTURES, "lock_cycle.py"))
        with pytest.raises(ValueError, match="reason"):
            baseline_entry(fs[0], "")
        p = os.path.join(str(tmp_path), "b.json")
        with open(p, "w") as f:
            json.dump({"version": 1,
                       "entries": [{"key": "a|b", "reason": ""}]}, f)
        with pytest.raises(ValueError, match="reason"):
            load_baseline(p)

    def test_missing_baseline_is_empty(self, tmp_path):
        assert load_baseline(os.path.join(str(tmp_path), "nope.json")) \
            == {}

    def test_committed_baseline_loads_and_has_reasons(self):
        base = load_baseline(BASELINE)
        for k, reason in base.items():
            assert reason.strip(), k

    def test_cli_exits_nonzero_on_new_finding(self, tmp_path):
        # gate semantics end-to-end through the CLI module (in-process:
        # a subprocess would re-import jax)
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "graft_lint_cli", os.path.join(REPO, "tools",
                                           "graft_lint.py"))
        cli = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cli)
        bad = os.path.join(FIXTURES, "lock_cycle.py")
        empty = os.path.join(str(tmp_path), "empty.json")
        with open(empty, "w") as f:
            f.write('{"version": 1, "entries": []}\n')
        assert cli.main([bad, "--baseline", empty]) == 1
        # baselining the finding (with a reason) turns the gate green
        assert cli.main([bad, "--baseline", empty, "--write-baseline",
                         "--reason", "checked-in known-bad fixture"]) \
            == 0
        assert cli.main([bad, "--baseline", empty]) == 0
        doc = json.load(open(empty))
        assert all(e["reason"].strip() for e in doc["entries"])
        # reason-less --write-baseline is refused
        assert cli.main([bad, "--baseline", empty,
                         "--write-baseline"]) == 2
