"""The engine's serving copy of the weights (``LM.serving_params``).

The engine rounds every ``compute_only`` weight to the compute dtype once,
at construction, so the decode and prefill programs read bf16 weights
instead of converting the f32 masters on every call.  The rounding is the
one the programs made, so what is served does not change by a bit."""

import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quant import quantize_tree
from repro.dist.sharding import Sharder
from repro.models import params as pspec
from repro.models.layers import COMPUTE_DTYPE
from repro.models.lm import build_model
from repro.plan import ServingPlan
from repro.serving import ServingEngine
from repro.serving.sampler import SamplerConfig, split_and_sample
from repro.testing import compute_weight_shapes, reduced_config

NOSH = Sharder(None, {})
ARCHS = ["rwkv6-1.6b", "qwen2.5-14b", "qwen3-moe-30b-a3b"]


@pytest.fixture(scope="module", params=ARCHS)
def built(request):
    model = build_model(reduced_config(request.param))
    return model, model.init(jax.random.PRNGKey(0))


def _marked(model):
    """{path: spec} of every leaf the specs mark ``compute_only``."""
    flat = jax.tree_util.tree_flatten_with_path(
        model.param_specs(), is_leaf=pspec.is_spec)[0]
    return {jax.tree_util.keystr(p): s for p, s in flat if s.compute_only}


def _by_path(tree):
    return {jax.tree_util.keystr(p): x
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _prefill_batch(model, prompt, S):
    tokens = np.zeros((1, S), np.int32)
    tokens[0, :len(prompt)] = prompt
    batch = {"tokens": jnp.asarray(tokens),
             "lengths": jnp.asarray([len(prompt)], jnp.int32)}
    if model.cfg.m_rope_sections:
        batch["positions"] = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32), (1, 3, S))
    return batch


def test_copy_gives_bit_identical_logits(built):
    model, params = built
    copy, _ = model.serving_params(params)
    prefill = jax.jit(lambda p, b: model.prefill(p, b, NOSH, max_len=16))
    decode = jax.jit(lambda p, c, t: model.decode_step(p, c, t, NOSH))
    batch = {"tokens": jnp.asarray([[5, 9, 3, 7], [2, 4, 6, 8]], jnp.int32)}
    c32, l32 = prefill(params, batch)
    c16, l16 = prefill(copy, batch)
    np.testing.assert_array_equal(np.asarray(l32), np.asarray(l16))
    toks = jnp.argmax(l32, -1).astype(jnp.int32)
    for _ in range(3):
        c32, l32 = decode(params, c32, toks)
        c16, l16 = decode(copy, c16, toks)
        np.testing.assert_array_equal(np.asarray(l32), np.asarray(l16))
        toks = jnp.argmax(l32, -1).astype(jnp.int32)


def test_engine_serves_the_f32_models_tokens(built):
    """An engine built from the f32 tree serves, under one seed and a
    sampling sampler, the tokens of a hand-run prefill + decode loop on
    that f32 tree with the engine's key convention."""
    model, params = built
    sampler, seed, prompt, n = SamplerConfig(temperature=0.8), 3, \
        [5, 9, 3, 7, 11], 6
    eng = ServingEngine.from_plan(
        ServingPlan(arch=model.cfg.name, max_batch=1, max_len=32,
                    temperature=sampler.temperature),
        params, model=model, sharder=NOSH, seed=seed)
    req = eng.submit(list(prompt), max_new_tokens=n)
    eng.run()

    batch = _prefill_batch(model, prompt, eng.bucket(len(prompt)))
    cache, logits = jax.jit(
        lambda p, b: model.prefill(p, b, NOSH, max_len=32))(params, batch)
    decode = jax.jit(lambda p, c, t: model.decode_step(p, c, t, NOSH))
    key, tok = split_and_sample(jax.random.PRNGKey(seed), logits, sampler)
    served = [int(tok[0])]
    while len(served) < n:
        cache, logits = decode(params, cache, tok)
        key, tok = split_and_sample(key, logits, sampler)
        served.append(int(tok[0]))
    assert req.output == served


def test_cast_twice_is_cast_once(built):
    """The crash-restart path rebuilds an engine from ``dead.params``, the
    copy itself: casting it again must hand back the very same leaves."""
    model, params = built
    once, n1 = model.serving_params(params)
    twice, n2 = model.serving_params(once)
    assert jax.tree.structure(once) == jax.tree.structure(twice)
    assert all(a is b for a, b in zip(jax.tree.leaves(once),
                                      jax.tree.leaves(twice)))
    assert n1 == n2 > 0


def _weight_converts(stablehlo: str, shapes) -> list:
    """The shapes of the ``convert``s in lowered program text that round an
    f32 array of one of ``shapes`` to bf16."""
    found = re.findall(r"stablehlo\.convert %\S+ : \(tensor<([\dx]+)xf32>\)"
                       r" -> tensor<[\dx]+xbf16>", stablehlo)
    return [d for d in found
            if tuple(int(n) for n in d.split("x")) in shapes]


def test_decode_program_converts_no_weight():
    """Mechanism: the decode program the engine launches asks for no
    rounding of a weight-shaped f32 array (the whole stack or one layer's
    slice), where the same program over the f32 tree asks for one per
    weight.  (The program as lowered: XLA's CPU backend widens bf16 dot
    operands to f32 itself and rounds each layer's slice back, losslessly;
    ``test_tpu_compile`` checks the program compiled for a v5e.)  The gauge
    reads the copy's bf16 bytes; leaves read at f32 keep their dtype."""
    # d_model 128: no weight then has the shape of a leaf read at f32
    model = build_model(reduced_config("rwkv6-1.6b", d_model=128))
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine.from_plan(
        ServingPlan(arch=model.cfg.name, max_batch=2, max_len=32), params,
        model=model, sharder=NOSH)
    marked = _marked(model)
    weights = compute_weight_shapes(model)
    assert not weights & {s.shape for s in jax.tree.leaves(
        model.param_specs(), is_leaf=pspec.is_spec) if not s.compute_only}

    assert _weight_converts(eng.lower_decode().as_text(), weights) == []
    f32_program = jax.jit(
        lambda p, c, t: model.decode_step(p, c, t, NOSH)).lower(
        params, eng.sm.cache, eng.sm.next_token).as_text()
    assert len(_weight_converts(f32_program, weights)) >= len(marked)

    served = _by_path(eng.params)
    assert eng.metrics.snapshot()["engine.params_cast_bytes"] == sum(
        served[p].nbytes for p in marked)
    for path, x in _by_path(params).items():
        assert served[path].dtype == (COMPUTE_DTYPE if path in marked
                                      else x.dtype), path
    assert {p for p, x in served.items() if x.dtype == jnp.float32} >= {
        "['blocks']['p0']['lora_b']", "['blocks']['p0']['decay_b']",
        "['blocks']['p0']['decay_base']", "['blocks']['p0']['bonus']",
        "['final_norm']"}


def test_int8_dicts_pass_through_unwidened():
    """An int8 ``{q, scale}`` weight stays as stored: widening it ahead of
    time would double its reads.  Leaves already at the compute dtype are
    counted but not copied."""
    model = build_model(reduced_config("rwkv6-1.6b"))
    qparams = quantize_tree(model.init(jax.random.PRNGKey(0)))
    assert isinstance(qparams["lm_head"], dict)
    copy, nbytes = model.serving_params(qparams)
    assert all(a is b for a, b in zip(jax.tree.leaves(qparams),
                                      jax.tree.leaves(copy)))
    assert copy["lm_head"]["q"].dtype == jnp.int8
    served = _by_path(copy)
    assert nbytes == sum(served[p].nbytes for p in _marked(model)
                         if p in served)


SHARDED_CAST = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
from repro.dist.sharding import make_sharder
from repro.launch.mesh import make_test_mesh
from repro.models.lm import build_model
from repro.testing import compute_weight_shapes, reduced_config

cfg = reduced_config("qwen2.5-14b")
model = build_model(cfg)
sharder = make_sharder(cfg, make_test_mesh((1, 4), ("data", "model")),
                       "decode")
shardings = sharder.param_shardings(model.param_specs())
params = jax.device_put(model.init(jax.random.PRNGKey(0)), shardings)
copy, nbytes = model.serving_params(params)
split = 0
for x, y in zip(jax.tree.leaves(params), jax.tree.leaves(copy)):
    assert y.sharding.is_equivalent_to(x.sharding, x.ndim), (x.sharding,
                                                             y.sharding)
    split += len(x.sharding.device_set) == 4 and not x.sharding.is_fully_replicated
assert nbytes > 0 and split > 0, (nbytes, split)
print("OK")
"""


@pytest.mark.slow
def test_copy_keeps_each_leafs_sharding():
    """On a (1, 4) mesh every leaf of the copy lies where its master lies
    (the four-chip qwen2.5-14b path shards the parameters)."""
    r = subprocess.run([sys.executable, "-c", SHARDED_CAST],
                       capture_output=True, text=True, timeout=600,
                       env={**os.environ, "PYTHONPATH": "src",
                            "JAX_PLATFORMS": "cpu"}, cwd=".")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
