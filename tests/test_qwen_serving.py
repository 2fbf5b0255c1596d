"""Qwen2.5 served through the engine against the plain reference of the
chip benchmark (``chipbench/reference/qwen2.py``), and the storage dtype
of its weights.

A reduced Qwen2.5 with seeded random weights, stored at bf16 as the
published checkpoint is: the engine's prefill program fills its cache,
then decode steps read and extend that cache, teacher-forced; the logits
at every position match the reference's full forward pass over the same
tokens.  On one device, and on a 1 x 4 CPU mesh in a subprocess (the
four-chip layout: weights tensor-parallel, cache split by sequence, the
flash kernels in interpret mode with the cross-device merge).
"""

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models.lm import build_model
from repro.models.params import is_spec

ROOT = Path(__file__).resolve().parents[1]


def _leaves(arch):
    specs = build_model(get_config(arch)).param_specs()
    return jax.tree_util.tree_leaves_with_path(specs, is_leaf=is_spec)


@pytest.mark.parametrize("arch,weights,f32_leaves", [
    ("qwen2.5-14b", jnp.bfloat16,
     ("norm1", "norm2", "final_norm", "bq", "bk", "bv")),
    ("rwkv6-1.6b", jnp.float32, ("ln1", "ln2", "wkv_norm", "final_norm"))])
def test_storage_dtype(arch, weights, f32_leaves):
    """``compute_only`` leaves take the config's ``weight_dtype`` (bf16 for
    qwen2.5-14b, its checkpoint's; f32 for rwkv6-1.6b); norms, biases and
    every other leaf stay f32."""
    leaves = _leaves(arch)
    compute = {jnp.dtype(s.dtype) for _, s in leaves if s.compute_only}
    rest = {jax.tree_util.keystr(p): jnp.dtype(s.dtype) for p, s in leaves
            if not s.compute_only}
    assert compute == {jnp.dtype(weights)}
    assert set(rest.values()) == {jnp.dtype(jnp.float32)}
    names = " ".join(rest)
    assert all(f"'{leaf}'" in names for leaf in f32_leaves)


def test_qwen2_5_14b_holds_its_published_widths():
    """48 layers of 5120, 40 query and 8 KV heads of 128, SwiGLU 13824,
    vocab 152064, QKV bias, RoPE theta 1e6, untied head: 14.77 B
    parameters, 29.5 GB at bf16 with f32 norms and biases."""
    cfg = get_config("qwen2.5-14b")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.d_ff, cfg.vocab_size) == (
        48, 5120, 40, 8, 128, 13824, 152064)
    assert cfg.qkv_bias and cfg.rope_theta == 1e6 and cfg.mlp_gated
    assert not cfg.tie_embeddings and cfg.weight_dtype == "bfloat16"
    leaves = [s for _, s in _leaves("qwen2.5-14b")]
    assert sum(s.size for s in leaves) == 14_770_033_664
    assert 29.5e9 < sum(s.nbytes for s in leaves) < 29.6e9


# Served logits against the reference; the script prints the worst
# |served - reference| / max |reference| over every position.
TEACHER_FORCED = r"""
import importlib.util, os, sys
import jax, jax.numpy as jnp, numpy as np
from repro.dist.sharding import make_sharder
from repro.launch.mesh import make_test_mesh
from repro.models.lm import build_model
from repro.plan import ServingPlan
from repro.serving import ServingEngine
from repro.testing import reduced_config

spec = importlib.util.spec_from_file_location("qwen2_ref", sys.argv[1])
ref = importlib.util.module_from_spec(spec)
spec.loader.exec_module(ref)
chips = int(sys.argv[2])
if chips > 1:
    import repro.kernels.dispatch as dispatch
    dispatch.resolve_impl = lambda entry: "pallas"   # interpret mode here
cfg = reduced_config("qwen2.5-14b", weight_dtype="bfloat16", head_dim=32,
                     n_heads=4, n_kv_heads=2, d_model=128, d_ff=256,
                     vocab_size=512)
model = build_model(cfg)
mesh = make_test_mesh((1, chips), ("data", "model")) if chips > 1 else None
sharder = make_sharder(cfg, mesh, "decode")
shardings = (sharder.param_shardings(model.param_specs()) if mesh
             else None)
params = jax.jit(model.init, out_shardings=shardings)(jax.random.PRNGKey(3))
# nonzero norm gains and biases, so that their paths count
params = jax.tree_util.tree_map_with_path(
    lambda p, a: a + 0.3 * jax.random.normal(jax.random.PRNGKey(len(str(p))),
                                             a.shape, a.dtype)
    if a.dtype == jnp.float32 else a, params)
plan = ServingPlan(arch=cfg.name, max_batch=2, max_len=64,
                   tile_plans={"attn": {"bq": 16, "bk": 16}})
eng = ServingEngine.from_plan(plan, params, model=model, sharder=sharder)
assert eng.params["blocks"]["p0"]["attn"]["wq"].dtype == jnp.bfloat16
rng = np.random.default_rng(0)
seqs = [rng.integers(0, 512, n).tolist() for n in (30, 40)]
prompts = [9, 20]
decode = jax.jit(lambda p, c, t: eng.model.decode_step(p, c, t, sharder))
served = [[] for _ in seqs]
for slot, (seq, n) in enumerate(zip(seqs, prompts)):
    S = eng.bucket(n)
    rows = plan.prefill_rows(S)
    tokens = np.zeros((rows, S), np.int32)
    tokens[0, :n] = seq[:n]
    lengths = np.ones((rows,), np.int32)
    lengths[0] = n
    cacheN, logits = eng._prefill(eng.params, {
        "tokens": jnp.asarray(tokens), "lengths": jnp.asarray(lengths)})
    eng.sm.insert_from_prefill([slot], [0], cacheN)
    served[slot].append(np.asarray(logits[0], np.float32))
feed = [np.asarray(seqs[0][prompts[0]:]), np.asarray(seqs[1][prompts[1]:])]
for t in range(min(len(f) for f in feed) - 1):
    toks = jnp.asarray([f[t] for f in feed], jnp.int32)
    eng.sm.cache, logits = decode(eng.params, eng.sm.cache, toks)
    for slot in range(2):
        served[slot].append(np.asarray(logits[slot], np.float32))
# the reference: the full forward pass over each sequence, one at a time
host = jax.device_get(params)
worst = 0.0
for slot, (seq, n) in enumerate(zip(seqs, prompts)):
    T = len(served[slot]) + n - 1
    x = jnp.asarray(host["embedding"])[jnp.asarray([seq[:T]])].astype(
        jnp.float32)
    m = {"n_heads": 4, "n_kv_heads": 2, "head_dim": 32,
         "norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta}
    pad = -(-T // ref.Q_BLOCK) * ref.Q_BLOCK
    x = jnp.pad(x, ((0, 0), (0, pad - T), (0, 0)))
    layer = ref._layer_fn(m, None)
    for i in range(cfg.n_layers):
        x = layer(host["blocks"], i, x)
    want = ref.mm(ref.rmsnorm(x[0, n - 1:T], host["final_norm"],
                              cfg.norm_eps),
                  jnp.asarray(host["lm_head"], jnp.float32))
    got = np.stack(served[slot])
    worst = max(worst, float(np.max(np.abs(got - np.asarray(want)))
                             / np.max(np.abs(np.asarray(want)))))
print("WORST", worst, sum(len(s) for s in served))
"""

# bf16 activations against an f32 reference over the same bf16 weights:
# each matmul rounds its input to 8 significant bits, and two layers
# compound it.  The runs read 8.1e-3 (one device) and 8.3e-3 (four) of
# the largest logit, so 0.03 keeps over 3x room; a decode that drops the
# first cache shard's partial in the cross-device merge reads 0.93.
TOLERANCE = 0.03


def _teacher_forced(chips: int) -> float:
    env = {**os.environ, "PYTHONPATH": str(ROOT / "src"),
           "JAX_PLATFORMS": "cpu"}
    if chips > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    r = subprocess.run(
        [sys.executable, "-c", TEACHER_FORCED,
         str(ROOT / "chipbench" / "reference" / "qwen2.py"), str(chips)],
        capture_output=True, text=True, timeout=900, env=env, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-3000:]
    line, = [ln for ln in r.stdout.splitlines() if ln.startswith("WORST")]
    _, worst, positions = line.split()
    assert int(positions) == 2 * 20
    return float(worst)


@pytest.mark.parametrize("chips", [1, 4])
def test_engine_logits_match_the_reference(chips):
    """Prefill through the engine's program into its slot cache, then
    teacher-forced decode steps over that cache: the logits at every
    served position match the reference's full forward pass."""
    assert _teacher_forced(chips) <= TOLERANCE


def test_attention_counters():
    """The engine's per-tick records of what attention adds, and the
    counters beside them: ``engine.kv_tokens`` sums the cache positions
    each decode tick attended over (prompt plus the tokens fed so far),
    ``engine.prefill_tokens`` the real prompt tokens prefilled, and the
    gauge ``engine.kv_cache_bytes`` the dense cache's K, V and position
    rings."""
    from repro.plan import ServingPlan
    from repro.serving import ServingEngine
    from repro.testing import reduced_config

    cfg = reduced_config("qwen2.5-14b", head_dim=32, n_heads=4, n_kv_heads=2,
                         d_model=128, d_ff=256, vocab_size=512)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    plan = ServingPlan(arch=cfg.name, max_batch=2, max_len=64)
    eng = ServingEngine.from_plan(plan, params, model=model)
    prompts, new = (5, 9), 4
    for n in prompts:
        eng.submit(list(range(1, n + 1)), max_new_tokens=new)
    eng.run()
    snap = eng.metrics.snapshot()
    # the first token comes from the prefill; each of the other new - 1
    # ticks feeds the last token and attends over every position before it
    kv = sum(sum(n + j for j in range(1, new)) for n in prompts)
    assert sum(eng.kv_history) == snap["engine.kv_tokens"] == kv
    assert len(eng.kv_history) == len(eng.util_history)
    assert sorted(n for tick in eng.prefill_history for n in tick) == \
        list(prompts)
    assert snap["engine.prefill_tokens"] == sum(prompts)
    ring = cfg.n_layers * 2 * 64 * (2 * 2 * 32 * 2 + 4)  # k, v bf16; pos i32
    assert snap["engine.kv_cache_bytes"] == ring


def test_a_bucket_admits_in_calls_of_prefill_rows():
    """Four 300-token prompts admitted together fall in the 512 bucket,
    whose calls hold two rows: two prefill calls in one tick, and every
    request is served in full."""
    from repro.plan import ServingPlan
    from repro.serving import ServingEngine
    from repro.testing import reduced_config

    cfg = reduced_config("qwen2.5-14b", head_dim=32, n_heads=4, n_kv_heads=2,
                         d_model=128, d_ff=256, vocab_size=512)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    plan = ServingPlan(arch=cfg.name, max_batch=4, max_len=1024)
    eng = ServingEngine.from_plan(plan, params, model=model)
    assert eng.bucket(300) == 512 and plan.prefill_rows(512) == 2
    reqs = [eng.submit(list(range(i, i + 300)), max_new_tokens=3)
            for i in range(4)]
    eng.run()
    assert eng.prefill_shapes == {(2, 512)}
    assert eng.metrics.snapshot()["engine.prefill_calls"] == 2
    assert [t for t in eng.prefill_history if t] == [(300,) * 4]
    assert all(len(r.output) == 3 for r in reqs)
