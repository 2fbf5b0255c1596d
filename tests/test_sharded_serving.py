"""Sharded end-to-end decode: the continuous-batching engine under a
non-trivial Sharder on a small mesh produces the same tokens as the
``mesh=None`` replicated path, and the engine's load counters track work."""

import os
import subprocess
import sys

import pytest

from repro.dist.sharding import Sharder, make_rules
from repro.models.lm import build_model
from repro.serving import ServingEngine
from repro.testing import reduced_config


def test_engine_stats_counters_track_load():
    cfg = reduced_config("rwkv6-1.6b")
    model = build_model(cfg)
    import jax
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, Sharder(None, {}), max_batch=2,
                        max_len=32)
    reqs = [eng.submit([1, 2, 3, 4 + i], max_new_tokens=4) for i in range(3)]
    eng.run()
    s = eng.stats()
    assert s["completed"] == 3
    assert s["total_tokens"] == sum(len(r.output) for r in reqs) == 12
    assert s["active"] == 0 and s["queued"] == 0


def test_decode_rules_shard_cache_not_heads():
    """Decode needs no head divisibility: the cache dim takes the model
    axis; train/prefill give it to heads (or qseq) instead."""
    cfg = reduced_config("gemma3-12b")
    dec = make_rules(cfg, "decode")
    assert dec["cache_seq"] == ("model",)
    assert "heads" not in dec and "qseq" not in dec
    pre = make_rules(cfg, "prefill")
    assert pre["heads"] == ("model",)


SHARDED_DECODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
import jax.numpy as jnp
import numpy as np
from repro.dist.sharding import Sharder, make_sharder
from repro.launch.mesh import make_test_mesh
from repro.models.lm import build_model
from repro.serving import ServingEngine
from repro.testing import reduced_config

cfg = reduced_config("rwkv6-1.6b")
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
nosh = Sharder(None, {})
mesh = make_test_mesh((2, 2), ("data", "model"))
sharder = make_sharder(cfg, mesh, "decode")
# the rules must actually resolve on this mesh (non-trivial sharding)
assert sharder.resolve("batch", 2) == ("data",)
assert sharder.resolve("mlp", cfg.d_ff) == ("model",)

# --- numerical equivalence, teacher-forced over prefill + 4 decode steps.
# bf16 reductions reorder under sharding (~1e-2 logit wobble on a ~3 logit
# scale), so compare logits with tolerance rather than argmax'd tokens.
batch = {"tokens": jnp.asarray([[5, 9, 3, 7], [2, 4, 6, 8]], jnp.int32)}
c_r, l_r = jax.jit(lambda p, b: model.prefill(p, b, nosh, max_len=16))(
    params, batch)
c_s, l_s = jax.jit(lambda p, b: model.prefill(p, b, sharder, max_len=16))(
    params, batch)
np.testing.assert_allclose(np.asarray(l_r, np.float32),
                           np.asarray(l_s, np.float32), atol=0.15)
dec_r = jax.jit(lambda p, c, t: model.decode_step(p, c, t, nosh))
dec_s = jax.jit(lambda p, c, t: model.decode_step(p, c, t, sharder))
toks = jnp.argmax(l_r, -1).astype(jnp.int32)
for _ in range(4):
    c_r, l_r = dec_r(params, c_r, toks)
    c_s, l_s = dec_s(params, c_s, toks)
    np.testing.assert_allclose(np.asarray(l_r, np.float32),
                               np.asarray(l_s, np.float32), atol=0.15)
    toks = jnp.argmax(l_r, -1).astype(jnp.int32)

# --- the engine end-to-end under the sharded Sharder: continuous batching
# completes every request and the counters track the work
prompts = [[5, 9, 3, 7], [2, 4, 6, 8, 10], [11, 1, 12], [3, 3, 3, 3, 3, 3]]
eng = ServingEngine(model, params, sharder, max_batch=2, max_len=32)
reqs = [eng.submit(list(p), max_new_tokens=6) for p in prompts]
eng.run()
assert all(r.done and len(r.output) == 6 for r in reqs)
stats = eng.stats()
assert stats["completed"] == len(prompts)
assert stats["total_tokens"] == sum(len(r.output) for r in reqs)
print("OK")
"""


@pytest.mark.slow
def test_sharded_decode_matches_replicated():
    """Decode under a (data, model) mesh Sharder matches the mesh=None
    replicated path numerically (teacher-forced), and the engine serves
    end-to-end under the sharded layout."""
    r = subprocess.run([sys.executable, "-c", SHARDED_DECODE],
                       capture_output=True, text=True, timeout=900,
                       env={**os.environ, "PYTHONPATH": "src",
                            "JAX_PLATFORMS": "cpu"}, cwd=".")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


SHARDED_FLASH = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
import jax.numpy as jnp
import numpy as np
from repro.dist.sharding import make_sharder
from repro.kernels.flash_attention import ops
from repro.launch.mesh import make_test_mesh
from repro.testing import reduced_config

cfg = reduced_config("qwen2.5-14b")
mesh = make_test_mesh((1, 4), ("data", "model"))
key = jax.random.PRNGKey(0)
r = lambda i, s: jax.random.normal(jax.random.fold_in(key, i), s, jnp.bfloat16)

# decode: the cache's sequence axis splits over the model axis; each chip
# runs flash_decode on its slice and the partials merge across chips
B, S, H, K, d = 2, 512, 4, 2, 64
q, kc, vc = r(0, (B, H, d)), r(1, (B, S, K, d)), r(2, (B, S, K, d))
kv_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
kv_pos = jnp.where(jnp.arange(S) % 7 == 3, -1, kv_pos)      # ring holes
q_pos = jnp.asarray([300, 511], jnp.int32)
dec = make_sharder(cfg, mesh, "decode")
assert dec.spec(("batch", "cache_seq", None, None), kc.shape)[1] == "model"
plan = {"bk": 128}
one = jax.jit(lambda *a: ops.decode(*a, plan=plan))(q, kc, vc, kv_pos, q_pos)
four = jax.jit(lambda *a: ops.decode(*a, plan=plan, sharder=dec))(
    q, kc, vc, kv_pos, q_pos)
np.testing.assert_allclose(np.asarray(four, np.float32),
                           np.asarray(one, np.float32), atol=2e-2, rtol=2e-2)

# prefill: heads split over the model axis where the rules map them
pre = make_sharder(reduced_config("gemma3-12b"), mesh, "prefill")
assert pre.resolve("heads", 8) == pre.resolve("kv_heads", 4) == ("model",)
qs, ks, vs = r(3, (1, 256, 8, d)), r(4, (1, 256, 4, d)), r(5, (1, 256, 4, d))
pos = jnp.arange(256, dtype=jnp.int32)[None]
att = lambda sh: jax.jit(lambda *a: ops.attention(
    *a, q_pos=pos, kv_pos=pos, plan={"bq": 128, "bk": 128}, sharder=sh))
np.testing.assert_allclose(np.asarray(att(pre)(qs, ks, vs), np.float32),
                           np.asarray(att(None)(qs, ks, vs), np.float32),
                           atol=2e-2, rtol=2e-2)
print("OK")
"""


@pytest.mark.slow
def test_flash_kernels_under_shard_map_match_one_device():
    """The flash adapters under a mesh sharder (shard_map around the
    Pallas call, interpret mode here) match the one-device kernels:
    decode split over the cache's sequence axis with a cross-device
    log-sum-exp merge, prefill split over heads."""
    r = subprocess.run([sys.executable, "-c", SHARDED_FLASH],
                       capture_output=True, text=True, timeout=900,
                       env={**os.environ, "PYTHONPATH": "src",
                            "JAX_PLATFORMS": "cpu"}, cwd=".")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
