"""Gradient compression: wire-format and unbiasedness."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.optim.compression import make_error_feedback


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**16), steps=st.integers(5, 40))
def test_error_feedback_is_unbiased_over_time(seed, steps):
    """Averaging EF-compressed copies of a constant gradient converges to
    the true gradient ~1/steps, unlike plain round-to-nearest."""
    rng = np.random.default_rng(seed)
    g = {"w": jnp.asarray(rng.standard_normal((33, 17)), jnp.float32)}
    init, apply = make_error_feedback()
    res = init(g)
    acc = jax.tree.map(jnp.zeros_like, g)
    for _ in range(steps):
        comp, res = apply(g, res)
        acc = jax.tree.map(lambda a, c: a + c, acc, comp)
    err_ef = float(jnp.max(jnp.abs(acc["w"] / steps - g["w"])))
    one_shot, _ = apply(g, init(g))
    err_once = float(jnp.max(jnp.abs(one_shot["w"] - g["w"]))) + 1e-12
    assert err_ef <= err_once + 1e-6
    assert err_ef < 0.05 * float(jnp.max(jnp.abs(g["w"])))


COMPRESSED_AR = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_test_mesh
from repro.optim.compression import compressed_allreduce
from repro.launch.hlo import parse_collectives
mesh = make_test_mesh((8,), ("data",))
g = {"a": jnp.asarray(np.random.default_rng(0).standard_normal((64, 33)),
                      jnp.float32)}
out = compressed_allreduce(g, mesh, "data")
rel = float(jnp.max(jnp.abs(out["a"] - g["a"]))) / float(jnp.max(jnp.abs(g["a"])))
assert rel < 0.02, rel
txt = jax.jit(lambda t: compressed_allreduce(t, mesh, "data")).lower(g) \
        .compile().as_text()
ops = parse_collectives(txt)
assert any(o.kind == "all-gather" and "s8" in o.line for o in ops)
print("OK")
"""


@pytest.mark.slow
def test_compressed_allreduce_int8_wire_format():
    r = subprocess.run([sys.executable, "-c", COMPRESSED_AR],
                       capture_output=True, text=True, timeout=600,
                       env={**os.environ, "PYTHONPATH": "src",
                            "JAX_PLATFORMS": "cpu"}, cwd=".")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
