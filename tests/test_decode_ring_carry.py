"""The decode layer scan carries the KV rings in place.

``LM._scan`` keeps each cache entry's ring leaves (k, v, pos and the int8
scales) in its loop state and writes back only the row a decode tick
wrote per slot; every other leaf is sliced and restacked.  That must
serve exactly what writing each layer's updated entry back whole serves:
the same logits and the same cache, bit for bit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist.sharding import Sharder
from repro.models.lm import build_model
from repro.testing import reduced_config

NOSH = Sharder(None, {})
B, MAX_LEN, TICKS = 3, 32, 3
# slot 2 reaches MAX_LEN - 1 at the second tick and passes it at the
# third, so a full ring clamps its slot; slot 1 wraps a 16-slot window
LENGTHS = (3, 15, 30)

CASES = {
    "qwen-gqa": ("qwen2.5-14b", {}),
    "gemma2-local-ring": ("gemma2-9b", {}),
    "qwen-int8-kv": ("qwen2.5-14b", {"kv_cache_dtype": "int8"}),
    "hymba-ring-and-ssm": ("hymba-1.5b", {}),
    "whisper-cross-keys": ("whisper-tiny", {}),
    "qwen2-vl-mrope": ("qwen2-vl-2b", {}),
    "rwkv6-no-ring": ("rwkv6-1.6b", {}),
}


def _filled_cache(model, key):
    """A decode cache with every leaf random (positions valid below each
    slot's length, -1 above), so each written row and each kept one
    shows in the comparison."""
    cache = model.init_cache(B, MAX_LEN)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(cache["blocks"])
    out = []
    for i, (path, a) in enumerate(leaves):
        k = jax.random.fold_in(key, i)
        if path[-1].key == "pos":
            s = jnp.arange(a.shape[-1], dtype=jnp.int32)
            ln = jnp.asarray(LENGTHS, jnp.int32)[None, :, None]
            out.append(jnp.broadcast_to(jnp.where(s < ln, s, -1), a.shape))
        elif a.dtype == jnp.int8:
            out.append(jax.random.randint(k, a.shape, -127, 128, jnp.int8))
        else:
            out.append(jax.random.normal(k, a.shape, a.dtype))
    return {"blocks": jax.tree_util.tree_unflatten(treedef, out),
            "lengths": jnp.asarray(LENGTHS, jnp.int32)}


def _unscanned_step(model, params, cache, tokens):
    """The decode step as a Python loop over the periods, each period one
    program whose returned entries are written back into the stacks
    whole.  (Programs of their own, since XLA rounds differently where
    it fuses across layers of an unrolled loop.)"""
    cfg = model.cfg
    lengths = cache["lengths"]
    positions = (jnp.broadcast_to(lengths[:, None, None], (B, 3, 1))
                 if cfg.m_rope_sections else lengths[:, None])
    period = jax.jit(lambda p, x, c: model.period_apply(
        p, x, positions=positions, lengths=lengths, mode="decode",
        sharder=NOSH, p_cache=c)[:2])
    x = jax.jit(lambda p, t: model.embed_tokens(p, t[:, None], NOSH))(
        params, tokens)
    blocks = cache["blocks"]
    for i in range(cfg.n_periods):
        layer = lambda tree: jax.tree.map(lambda a: a[i], tree)
        x, new_c = period(layer(params["blocks"]), x, layer(blocks))
        blocks = jax.tree.map(lambda s, n: s.at[i].set(n), blocks, new_c)
    logits = jax.jit(lambda p, x: model.final_hidden_to_logits(
        p, x, NOSH))(params, x)
    return {"blocks": blocks, "lengths": lengths + 1}, logits[:, 0]


@pytest.mark.parametrize("case", list(CASES))
def test_scanned_decode_matches_unscanned_bit_for_bit(case):
    arch, overrides = CASES[case]
    model = build_model(reduced_config(arch, **overrides))
    params = model.init(jax.random.PRNGKey(0))
    cache = _filled_cache(model, jax.random.PRNGKey(1))
    scanned = jax.jit(lambda p, c, t: model.decode_step(p, c, t, NOSH))
    ours, ref = cache, cache
    for tick in range(TICKS):
        tokens = jax.random.randint(jax.random.PRNGKey(10 + tick), (B,), 0,
                                    model.cfg.vocab_size)
        ours, logits = scanned(params, ours, tokens)
        ref, ref_logits = _unscanned_step(model, params, ref, tokens)
        np.testing.assert_array_equal(np.asarray(logits),
                                      np.asarray(ref_logits))
        for (path, a), b in zip(
                jax.tree_util.tree_flatten_with_path(ours)[0],
                jax.tree.leaves(ref)):
            assert a.dtype == b.dtype, path
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"tick {tick}: {jax.tree_util.keystr(path)}")
    # the tick wrote rows: the cache moved
    assert not np.array_equal(
        np.asarray(jax.tree.leaves(ours["blocks"])[0]),
        np.asarray(jax.tree.leaves(cache["blocks"])[0]))
