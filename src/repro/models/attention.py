"""Attention: projections, blockwise flash attention, decode attention.

Design notes (see DESIGN.md §Attention):

* **Blockwise flash, loop-free.**  Train/prefill attention is computed with
  an online-softmax over KV blocks using a *python-unrolled* block loop —
  no ``lax.scan`` — for two reasons: XLA's ``cost_analysis`` counts a while
  body only once (which would wreck the roofline accounting), and the
  unrolled chain lets XLA reuse one block-sized buffer instead of ever
  materializing the (S, S) score matrix.  On real TPUs the Pallas kernel in
  ``repro.kernels.flash_attention`` replaces this path.

* **GQA grouped form.**  q is viewed as (B, K, G, S, d) over K kv-heads and
  G = H/K query groups.  In "heads" sharding mode the kv heads are first
  repeated to H (K=H, G=1) so the head dim shards over the model axis; in
  "qseq" mode the grouped form avoids materializing repeated KV and the
  query *sequence* dim shards instead.  One code path serves both; the
  logical-axis rules make the same ``constrain`` calls resolve differently.

* **Decode.**  One query token against a cache whose sequence dim shards
  over the model axis (flash-decode style): the softmax over the sharded
  dim lowers to partial max/sum + all-reduce, and the A·V contraction to a
  partial-sum all-reduce.  This works for every head count, so decode needs
  no head-divisibility at all.  The cache is stored (layers, B, S, K, hd);
  the decode layer scan (``LM._scan``) carries those stacks and writes one
  row per slot a tick into them, never a whole layer.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import apply_m_rope, apply_rope, dot, groupnorm_heads
from repro.models.params import ParamSpec

F32 = jnp.float32
NEG_INF = -1e30

# Maximum number of unrolled KV blocks; the block size grows with sequence
# length so the unrolled HLO stays bounded.
MAX_KV_BLOCKS = 8
MIN_KV_BLOCK = 512


def kv_block_size(skv: int) -> int:
    block = max(MIN_KV_BLOCK, -(-skv // MAX_KV_BLOCKS))
    return -(-block // 128) * 128  # multiple of the MXU edge


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------


def attention_specs(cfg: ModelConfig, prefix: str = "") -> Dict[str, ParamSpec]:
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    w = lambda shape, axes: ParamSpec(shape, jnp.float32, axes,
                                      compute_only=True)
    specs = {
        "wq": w((d, qd), ("embed", "q_flat")),
        "wk": w((d, kvd), ("embed", "kv_flat")),
        "wv": w((d, kvd), ("embed", "kv_flat")),
        "wo": w((qd, d), ("q_flat", "embed")),
    }
    if cfg.qkv_bias:
        specs["bq"] = ParamSpec((qd,), jnp.float32, ("q_flat",), init="zeros")
        specs["bk"] = ParamSpec((kvd,), jnp.float32, ("kv_flat",), init="zeros")
        specs["bv"] = ParamSpec((kvd,), jnp.float32, ("kv_flat",), init="zeros")
    if cfg.qk_norm:
        specs["q_norm"] = ParamSpec((cfg.head_dim_,), jnp.float32, (None,),
                                    init="zeros")
        specs["k_norm"] = ParamSpec((cfg.head_dim_,), jnp.float32, (None,),
                                    init="zeros")
    return specs


# ---------------------------------------------------------------------------
# Projections
# ---------------------------------------------------------------------------


def _headnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(F32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(F32))).astype(dtype)


def project_qkv(params, x: jax.Array, cfg: ModelConfig, sharder,
                positions: jax.Array, rope: bool = True
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, S, d) -> q (B, S, H, hd), k/v (B, S, K, hd), rope applied."""
    B, S, _ = x.shape
    hd, H, K = cfg.head_dim_, cfg.n_heads, cfg.n_kv_heads
    q = dot(x, params["wq"])
    k = dot(x, params["wk"])
    v = dot(x, params["wv"])
    if cfg.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, K, hd)
    v = v.reshape(B, S, K, hd)
    if cfg.qk_norm:
        q = _headnorm(q, params["q_norm"], cfg.norm_eps)
        k = _headnorm(k, params["k_norm"], cfg.norm_eps)
    if rope:
        if cfg.m_rope_sections and positions.ndim == 3:
            q = apply_m_rope(q, positions, cfg.rope_theta, cfg.m_rope_sections)
            k = apply_m_rope(k, positions, cfg.rope_theta, cfg.m_rope_sections)
        else:
            pos2d = positions if positions.ndim == 2 else positions[:, 0]
            q = apply_rope(q, pos2d, cfg.rope_theta)
            k = apply_rope(k, pos2d, cfg.rope_theta)
    q = sharder.constrain(q, "batch", "qseq", "heads", None)
    # kv is gathered whole-sequence here (one gather per layer under
    # sequence parallelism; free otherwise) for the blockwise flash loop
    k = sharder.constrain(k, "batch", "kv_full_seq", "kv_heads", None)
    v = sharder.constrain(v, "batch", "kv_full_seq", "kv_heads", None)
    return q, k, v


# ---------------------------------------------------------------------------
# Blockwise flash attention (train / prefill)
# ---------------------------------------------------------------------------


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    q_pos: jax.Array, kv_pos: jax.Array, *,
                    cfg: ModelConfig, sharder, causal: bool = True,
                    window: int = 0, block: int = 0,
                    tile_plan=None) -> jax.Array:
    """Online-softmax attention over unrolled KV blocks.

    q: (B, Sq, H, hd); k, v: (B, Skv, K, hd); positions are (B, S) int32.
    Returns (B, Sq, H, hd).  An active ``tile_plan`` routes to the Pallas
    flash kernel with the plan's bq/bk BlockSpec geometry (under a mesh,
    one kernel per head shard via ``shard_map``).
    """
    from repro.kernels.dispatch import pallas_active

    if pallas_active(tile_plan):
        from repro.kernels.flash_attention import ops as flash_ops

        out = flash_ops.attention(
            q, k, v, causal=causal, window=window,
            softcap=cfg.attn_softcap, q_pos=q_pos, kv_pos=kv_pos,
            plan=tile_plan, sharder=sharder)
        return sharder.constrain(
            out, "batch", "qseq", "heads", None).astype(q.dtype)
    B, Sq, H, hd = q.shape
    Skv, K = k.shape[1], k.shape[2]
    scale = 1.0 / math.sqrt(hd)
    softcap = cfg.attn_softcap

    heads_mode = cfg.attention_sharding != "qseq"
    if heads_mode and K != H:
        # repeat kv to full heads so the head dim shards over the model axis
        rep = H // K
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
        K = H
    G = H // K

    # grouped views: q (B, K, G, Sq, hd); kv (B, K, Skv, hd)
    qg = q.reshape(B, Sq, K, G, hd).transpose(0, 2, 3, 1, 4)
    qg = sharder.constrain(qg, "batch", "heads", None, "qseq", None)
    kg = k.transpose(0, 2, 1, 3)   # (B, K, Skv, hd)
    vg = v.transpose(0, 2, 1, 3)

    block = block or (cfg.attn_block or kv_block_size(Skv))
    n_blocks = -(-Skv // block)

    m = jnp.full((B, K, G, Sq), NEG_INF, F32)
    l = jnp.zeros((B, K, G, Sq), F32)
    acc = jnp.zeros((B, K, G, Sq, hd), F32)
    qf = qg.astype(jnp.bfloat16)

    for i in range(n_blocks):
        s0, s1 = i * block, min((i + 1) * block, Skv)
        kb = jax.lax.slice_in_dim(kg, s0, s1, axis=2).astype(jnp.bfloat16)
        vb = jax.lax.slice_in_dim(vg, s0, s1, axis=2).astype(jnp.bfloat16)
        pb = jax.lax.slice_in_dim(kv_pos, s0, s1, axis=1)      # (B, bk)

        logits = jnp.einsum("bkgqd,bksd->bkgqs", qf, kb,
                            preferred_element_type=F32) * scale
        if softcap > 0.0:
            logits = softcap * jnp.tanh(logits / softcap)
        mask = jnp.ones((B, 1, 1, Sq, s1 - s0), bool)
        if causal:
            mask &= (pb[:, None, None, None, :] <=
                     q_pos[:, None, None, :, None])
        if window > 0:
            mask &= (q_pos[:, None, None, :, None] -
                     pb[:, None, None, None, :]) < window
        mask &= (pb >= 0)[:, None, None, None, :]              # cache validity
        logits = jnp.where(mask, logits, NEG_INF)
        logits = sharder.constrain(
            logits, "batch", "heads", None, "qseq", None)

        m_new = jnp.maximum(m, logits.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgqs,bksd->bkgqd", p.astype(jnp.bfloat16), vb,
            preferred_element_type=F32)
        m = m_new

    out = acc / jnp.maximum(l[..., None], 1e-30)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd)
    return sharder.constrain(out, "batch", "qseq", "heads", None).astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode attention (one token against a cache)
# ---------------------------------------------------------------------------


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     kv_pos: jax.Array, q_pos: jax.Array, *,
                     cfg: ModelConfig, sharder, causal: bool = True,
                     window: int = 0, tile_plan=None) -> jax.Array:
    """q: (B, H, hd); caches: (B, S, K, hd); kv_pos: (B, S) absolute
    positions (-1 = empty slot); q_pos: (B,).  Returns (B, H, hd).
    An active ``tile_plan`` routes to the split-KV flash-decoding kernel
    with the plan's bk chunk size (under a mesh, per cache-sequence shard
    with a cross-device log-sum-exp merge)."""
    from repro.kernels.dispatch import pallas_active

    if pallas_active(tile_plan):
        from repro.kernels.flash_attention import ops as flash_ops

        return flash_ops.decode(
            q, k_cache, v_cache, kv_pos, q_pos, causal=causal,
            window=window, softcap=cfg.attn_softcap, plan=tile_plan,
            sharder=sharder)
    B, H, hd = q.shape
    S, K = k_cache.shape[1], k_cache.shape[2]
    G = H // K
    scale = 1.0 / math.sqrt(hd)

    qg = q.reshape(B, K, G, hd).astype(jnp.bfloat16)
    kc = k_cache.astype(jnp.bfloat16)
    logits = jnp.einsum("bkgd,bskd->bkgs", qg, kc,
                        preferred_element_type=F32) * scale
    if cfg.attn_softcap > 0.0:
        c = cfg.attn_softcap
        logits = c * jnp.tanh(logits / c)

    mask = kv_pos >= 0
    if causal:
        mask &= kv_pos <= q_pos[:, None]
    if window > 0:
        mask &= (q_pos[:, None] - kv_pos) < window
    logits = jnp.where(mask[:, None, None, :], logits, NEG_INF)
    logits = sharder.constrain(logits, "batch", "kv_heads", None, "cache_seq")

    # softmax over the (possibly model-axis-sharded) cache dim
    m = logits.max(axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    p = p / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bkgs,bskd->bkgd", p.astype(jnp.bfloat16),
                     v_cache.astype(jnp.bfloat16),
                     preferred_element_type=F32)
    return out.reshape(B, H, hd).astype(jnp.bfloat16)


# ---------------------------------------------------------------------------
# KV-cache helpers
# ---------------------------------------------------------------------------


def cache_slot_count(cfg: ModelConfig, kind: str, max_len: int) -> int:
    if kind == "local" or (kind == "swa_ssm" and cfg.local_window):
        return min(cfg.local_window, max_len)
    return max_len


def fill_cache_from_prefill(k, v, positions, n_slots: int):
    """Build (cache, cache_pos) from prefill-computed k/v (B, S, K, hd).

    ``positions`` (B, S) carries each token's absolute position, -1 for
    padding (right-padded bucketed prefill), so examples in one batch may
    have different true lengths.  Per example, the last ``n_slots`` *valid*
    tokens are kept at their ring slots (slot = pos % n_slots); unfilled
    slots get pos -1 — decode attention masks them, and a decode step
    (``blocks._attn_step``: slot = lengths % n_slots in a ring) writes
    its row into them in the same layout.
    """
    B, S, K, hd = k.shape
    lengths = jnp.sum((positions >= 0).astype(jnp.int32), axis=1)  # (B,)
    s = jnp.arange(n_slots, dtype=jnp.int32)[None, :]              # (1, n)
    last = lengths[:, None] - 1                                    # (B, 1)
    # token position landing in slot s: the largest valid p with
    # p % n_slots == s (>= lengths - n_slots by construction of the mod)
    p = last - ((last - s) % n_slots)                              # (B, n)
    idx = jnp.maximum(p, 0)[:, :, None, None]
    kc = jnp.take_along_axis(k, idx, axis=1)
    vc = jnp.take_along_axis(v, idx, axis=1)
    pos = jnp.where(p >= 0, p, -1).astype(jnp.int32)
    return kc, vc, pos
