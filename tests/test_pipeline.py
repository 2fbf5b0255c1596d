"""Pipeline parallelism: GPipe schedule == sequential stage application,
forward and backward, in a 4-fake-device subprocess."""

import os
import subprocess
import sys

import pytest

CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_test_mesh
from repro.dist.pipeline import pipeline_apply, stack_stage_params

mesh = make_test_mesh((4,), ("pipe",))
S, B, D = 4, 8, 16
rng = np.random.default_rng(0)
stages = [{"w": jnp.asarray(rng.standard_normal((D, D)) / np.sqrt(D),
                            jnp.float32)} for _ in range(S)]
params = stack_stage_params(stages)
x = jnp.asarray(rng.standard_normal((B, D)), jnp.float32)

def stage_fn(p, h):
    return jnp.tanh(h @ p["w"])

# forward equivalence
y_pipe = pipeline_apply(stage_fn, params, x, mesh)
y_seq = x
for s in stages:
    y_seq = stage_fn(s, y_seq)
np.testing.assert_allclose(np.asarray(y_pipe), np.asarray(y_seq),
                           atol=1e-5, rtol=1e-5)

# backward equivalence (GPipe step is differentiable through shard_map)
def loss_pipe(p):
    return jnp.sum(pipeline_apply(stage_fn, p, x, mesh) ** 2)
def loss_seq(p):
    h = x
    for i in range(S):
        h = stage_fn(jax.tree.map(lambda a: a[i], p), h)
    return jnp.sum(h ** 2)
g_pipe = jax.grad(loss_pipe)(params)
g_seq = jax.grad(loss_seq)(params)
np.testing.assert_allclose(np.asarray(g_pipe["w"]), np.asarray(g_seq["w"]),
                           atol=1e-4, rtol=1e-4)

# the lowered HLO really moves activations via collective-permute
import sys; sys.path.insert(0, "src")
from repro.launch.hlo import parse_collectives
txt = jax.jit(loss_pipe).lower(params).compile().as_text()
kinds = {o.kind for o in parse_collectives(txt)}
assert "collective-permute" in kinds, kinds
print("OK")
"""


@pytest.mark.slow
def test_gpipe_matches_sequential():
    r = subprocess.run([sys.executable, "-c", CODE], capture_output=True,
                       text=True, timeout=900,
                       env={**os.environ, "PYTHONPATH": "src",
                            "JAX_PLATFORMS": "cpu"}, cwd=".")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
