"""The Pallas kernels compile for a TPU v5e at published widths.

Interpret mode (the other kernel tests) cannot see Mosaic's tiling rules
or its VMEM limit; the TPU compiler installed with jax can, for a chip
that is described rather than attached.  Each test compiles one kernel at
the tile ``core.dse`` / ``plan.planner`` picks for that shape and checks
the program holds the kernel as a ``tpu_custom_call``.  Nothing runs.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library at a time, and every
test worker imports this file.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import hw
from repro.configs import DEEPBENCH_TASKS
from repro.core import dse
from repro.core.cells import RNNCellConfig
from repro.plan import planner

BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A v5e device sharding, with the persistent compile cache off: a
    compile for a described chip is written to the cache but cannot be
    read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def test_described_chip_is_v5e(topo):
    assert topo.devices[0].device_kind in hw.DEVICE_KINDS
    assert hw.spec_for_device(topo.devices[0]) is hw.TPU_V5E


def test_rwkv6_step_rwkv6_1_6b(one_chip):
    """rwkv6-1.6b decode: 32 heads of 64x64 f32 state at the smoke's
    max_batch 8, head tile from the planner."""
    from repro.kernels.rwkv_step.rwkv_step import rwkv6_step

    B, H, K = 8, 32, 64
    tp = planner.tile_plans_for("rwkv6-1.6b", B, hw.TPU_V5E)["rwkv"]
    assert tp["resident"]
    heads = tp["bh"] // K
    step = ((1, B, H, K), BF16)
    _compile(one_chip,
             lambda r, k, v, w, u, s: rwkv6_step(r, k, v, w, u, s, bh=heads),
             step, step, step, ((1, B, H, K), F32), ((H, K), F32),
             ((B, H, K, K), F32))


def _rnn(one_chip, cell, H, T, bh, persistent):
    from repro.kernels.fused_rnn.fused_rnn import fused_gru, fused_lstm

    G = 4 if cell == "lstm" else 3
    w = [((T, 1, H), BF16), ((H, G, H), I8), ((H, G, H), I8),
         ((G, H), F32), ((G, H), F32), ((G, H), F32)]
    if cell == "lstm":
        fn = lambda *a: fused_lstm(*a, bh=bh, persistent=persistent)
        return _compile(one_chip, fn, *w, ((1, H), F32), ((1, H), F32))
    fn = lambda *a: fused_gru(*a, bh=bh, persistent=persistent)
    return _compile(one_chip, fn, *w, ((G, H), F32), ((1, H), F32))


@pytest.mark.parametrize("task", DEEPBENCH_TASKS, ids=lambda t: t.name)
def test_fused_rnn_deepbench_streaming(one_chip, task):
    """Every DeepBench task, batch 1, int8 weights, at its own timesteps
    and the DSE's tile (lstm-h2048 and gru-h2560 stream several tiles)."""
    cfg = RNNCellConfig(task.cell, task.hidden, timesteps=task.timesteps,
                        precision="int8")
    plan = dse.best_plan(cfg, max_batch=1)
    assert plan.resident
    _rnn(one_chip, task.cell, task.hidden, task.timesteps, plan.bh, False)


@pytest.mark.parametrize("cell", ["lstm", "gru"])
def test_fused_rnn_persistent(one_chip, cell):
    """The weights-resident variant at a width the DSE keeps in one tile."""
    H = 1024
    plan = dse.best_plan(RNNCellConfig(cell, H, precision="int8"),
                         max_batch=1)
    assert plan.resident and plan.n_tiles == 1
    _rnn(one_chip, cell, H, 25, H, True)


# qwen2.5-14b: 40 query heads, 8 kv heads, head_dim 128, 2048 positions
QB, QS, QH, QK, QD = 4, 2048, 40, 8, 128


def _attn_plan():
    return planner.tile_plans_for("qwen2.5-14b", QB, hw.TPU_V5E,
                                  max_len=QS)["attn"]


def test_flash_decode_qwen2_5_14b(one_chip):
    from repro.kernels.flash_attention import ops

    plan = _attn_plan()
    cache = ((QB, QS, QK, QD), BF16)
    _compile(one_chip,
             lambda q, k, v, kp, qp: ops.decode(q, k, v, kp, qp, plan=plan,
                                                interpret=False),
             ((QB, QH, QD), BF16), cache, cache, ((QB, QS), I32),
             ((QB,), I32))


@pytest.mark.parametrize("positions", [True, False])
def test_flash_attention_qwen2_5_14b(one_chip, positions):
    from repro.kernels.flash_attention import ops

    plan = _attn_plan()
    q, kv, pos = ((1, QS, QH, QD), BF16), ((1, QS, QK, QD), BF16), \
        ((1, QS), I32)
    if positions:
        _compile(one_chip,
                 lambda q, k, v, qp, kp: ops.attention(
                     q, k, v, q_pos=qp, kv_pos=kp, plan=plan,
                     interpret=False),
                 q, kv, kv, pos, pos)
    else:
        _compile(one_chip,
                 lambda q, k, v: ops.attention(q, k, v, plan=plan,
                                               interpret=False),
                 q, kv, kv)


def test_matmul_w8a16_qwen2_5_14b_mlp(one_chip):
    from repro.kernels.matmul_int8.matmul_int8 import matmul_w8a16

    M, K, N = QB, 5120, 13824
    p = dse.best_matmul_plan(M, N, K)
    _compile(one_chip,
             lambda x, w, s: matmul_w8a16(x, w, s, bm=p.bm, bn=p.bn,
                                          bk=p.bk),
             ((M, K), BF16), ((K, N), I8), ((N,), F32))


def _weight_converts(hlo: str, shapes) -> set:
    """The shapes among ``shapes`` of the bf16 arrays that ``convert``s of
    compiled HLO text round from an f32 operand."""
    types = dict(re.findall(r"%([\w.\-]+) = (\w+\[[\d,]*\])", hlo))
    out = set()
    for dims, operand in re.findall(
            r"= bf16\[([\d,]*)\]\S* convert\(%([\w.\-]+)\)", hlo):
        shape = tuple(int(d) for d in dims.split(",") if d)
        if types.get(operand, "").startswith("f32[") and shape in shapes:
            out.add(shape)
    return out


def test_rwkv6_decode_reads_the_serving_copy(one_chip):
    """Compiled for a v5e, a decode step over the serving copy converts no
    weight, where over the f32 masters XLA writes a bf16 copy of every
    weight stack on every call (a reduced rwkv6: the mechanism, not the
    size)."""
    from repro.dist.sharding import Sharder
    from repro.models.lm import build_model
    from repro.models.params import is_spec, tree_abstract
    from repro.testing import compute_weight_shapes, reduced_config

    model = build_model(reduced_config("rwkv6-1.6b", d_model=128))
    params = model.init(jax.random.PRNGKey(0))
    copy, _ = model.serving_params(params)
    at = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    cache = at(tree_abstract(model.cache_specs(8, 64)))
    tokens = jax.ShapeDtypeStruct((8,), I32, sharding=one_chip)
    step = jax.jit(lambda p, c, t: model.decode_step(p, c, t,
                                                     Sharder(None, {})))
    weights = compute_weight_shapes(model)
    served = step.lower(at(copy), cache, tokens).compile().as_text()
    masters = step.lower(at(params), cache, tokens).compile().as_text()
    assert _weight_converts(served, weights) == set()
    assert _weight_converts(masters, weights) == {
        s.shape for s in jax.tree.leaves(model.param_specs(), is_leaf=is_spec)
        if s.compute_only}
