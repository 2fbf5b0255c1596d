"""Jit'd wrappers: model-layout adapters for the flash attention kernels.

Accept the model's (B, S, H, hd) layout with separate KV heads and
dispatch to the Pallas kernels (TPU) or interpret mode (CPU tests).
Tile geometry (bq/bk) comes from a ``tile_plans`` entry when one is
passed, snapped to the actual sequence lengths; the hardcoded values
are the documented defaults.

A Mosaic kernel cannot be partitioned by GSPMD, so under a mesh
``sharder`` each adapter runs its kernel inside ``jax.shard_map``:
prefill attention per head shard (heads replicated where the rules
leave them unmapped), decode per slice of the cache's sequence axis,
with the flash-decoding partials merged across devices.
"""

from __future__ import annotations

import functools
from typing import Mapping, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.dispatch import interpret_mode, tile_arg
from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.flash_decode import combine, decode_partials

DEFAULT_BQ = 256
DEFAULT_BK = 512


def _expand_kv(k, v, H: int):
    K = k.shape[2]
    if K != H:
        rep = H // K
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return k, v


def _mesh(sharder):
    return None if sharder is None else sharder.mesh


def _axes(entry):
    """Mesh axis names of one PartitionSpec entry, as a tuple."""
    if entry is None:
        return ()
    return (entry,) if isinstance(entry, str) else tuple(entry)


def attention(q, k, v, *, causal: bool = True, window: int = 0,
              softcap: float = 0.0, q_pos=None, kv_pos=None,
              bq: int = 0, bk: int = 0,
              interpret: Optional[bool] = None,
              plan: Optional[Mapping[str, object]] = None,
              sharder=None):
    """q (B, S, H, hd); k/v (B, S, K, hd) -> (B, S, H, hd).

    ``q_pos``/``kv_pos`` (B, S) enable position-array masking (padded
    prefill buckets); ``plan`` supplies bq/bk tile geometry."""
    from repro.core.dse import snap_tile

    if interpret is None:
        interpret = interpret_mode()

    def local(q, k, v, q_pos, kv_pos):
        S, H = q.shape[1], q.shape[2]
        Skv = k.shape[1]
        k, v = _expand_kv(k, v, H)
        t = lambda x: x.transpose(0, 2, 1, 3)
        tq = snap_tile(S, min(tile_arg(plan, "bq", bq or DEFAULT_BQ), S))
        tk = snap_tile(Skv, min(tile_arg(plan, "bk", bk or DEFAULT_BK), Skv))
        out = flash_attention(t(q), t(k), t(v), q_pos, kv_pos,
                              causal=causal, window=window, softcap=softcap,
                              bq=tq, bk=tk, interpret=interpret)
        return t(out)

    mesh = _mesh(sharder)
    if mesh is None:
        return local(q, k, v, q_pos, kv_pos)
    B, S, H, _ = q.shape
    K = k.shape[2]
    iota = lambda n: jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (B, n))
    q_pos = iota(S) if q_pos is None else q_pos
    kv_pos = iota(k.shape[1]) if kv_pos is None else kv_pos
    heads = sharder.resolve("heads", H)
    if heads != sharder.resolve("kv_heads", K):
        heads = None          # q and kv heads must split into the same groups
    batch = sharder.spec(("batch",), (B,))[0]
    spec = P(batch, None, heads, None)
    pos = P(batch, None)
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(spec, spec, spec, pos, pos),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v, q_pos, kv_pos)


def decode(q, k_cache, v_cache, kv_pos, q_pos, *, causal: bool = True,
           window: int = 0, softcap: float = 0.0, bk: int = 0,
           interpret: Optional[bool] = None,
           plan: Optional[Mapping[str, object]] = None,
           sharder=None):
    """Split-KV flash-decoding adapter, mirroring the contract of
    ``repro.models.attention.decode_attention``: q (B, H, hd), caches
    (B, S, K, hd), kv_pos (B, S) with -1 holes, q_pos (B,).
    Returns (B, H, hd) bf16."""
    from repro.core.dse import snap_tile

    if interpret is None:
        interpret = interpret_mode()

    def local(q, k_cache, v_cache, kv_pos, q_pos, seq_axes=()):
        S = k_cache.shape[1]
        t = lambda x: x.transpose(0, 2, 1, 3)             # (B, K, S, hd)
        tk = snap_tile(S, min(tile_arg(plan, "bk", bk or DEFAULT_BK), S))
        parts = decode_partials(q, t(k_cache), t(v_cache), kv_pos, q_pos,
                                causal=causal, window=window,
                                softcap=softcap, bk=tk, interpret=interpret)
        return combine(*parts, seq_axes).astype(jnp.bfloat16)

    mesh = _mesh(sharder)
    if mesh is None:
        return local(q, k_cache, v_cache, kv_pos, q_pos)
    kv = sharder.spec(("batch", "cache_seq", None, None), k_cache.shape)
    batch, seq = kv[0], kv[1]
    fn = jax.shard_map(
        functools.partial(local, seq_axes=_axes(seq)), mesh=mesh,
        in_specs=(P(batch, None, None), kv, kv, P(batch, seq), P(batch)),
        out_specs=P(batch, None, None), check_vma=False)
    return fn(q, k_cache, v_cache, kv_pos, q_pos)
