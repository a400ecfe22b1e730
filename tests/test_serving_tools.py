"""The two chip scripts of the latent models' prefill attention
(``tools/attend_bench.py``, ``tools/serve_soak.py``) as far as a CPU can
run them: the microbench's reference is the whole square ``_attend``
replaced, and the soak's toy rehearsal runs to its end."""
import importlib.util
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.text.models import kimi_linear

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tool(name):
    spec = importlib.util.spec_from_file_location(
        f"tools_{name}", os.path.join(ROOT, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("S,chunk", [(48, 48), (96, 16), (192, 16)])
def test_the_microbenchs_reference_attends_like_the_tree(S, chunk):
    bench = _tool("attend_bench")
    r = np.random.RandomState(5)
    q, k = (jnp.asarray(r.randn(2, S, 3, 24), jnp.float32) for _ in "qk")
    v = jnp.asarray(r.randn(2, S, 3, 16), jnp.float32)
    np.testing.assert_allclose(
        bench.whole_square(q, k, v, 0.2, chunk),
        kimi_linear._attend(q, k, v, 0.2, chunk=chunk), atol=2e-6, rtol=0)
    # every shape it times divides into the plan's chunks
    for H, shapes in bench.SHAPES.items():
        for B, n in shapes:
            c, G, done, square = kimi_linear.attend_plan(B, n, H)
            assert n % c == 0 and (n // c) % G == 0 and done <= square


@pytest.mark.parametrize("cell", ["openpangu-ultra-moe-serve-decode",
                                  "kimi-linear-serve-decode"])
def test_the_soak_rehearses_on_the_cpu(cell):
    env = dict(os.environ, SOAK_TOY="1", JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "serve_soak.py"),
         cell, "7", "4", "6", "3"],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SOAK OK" in out.stdout and "STALL" not in out.stdout
    assert "decode-only" in out.stdout and "prefill-only" in out.stdout
