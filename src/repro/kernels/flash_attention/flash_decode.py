"""Pallas TPU split-KV flash-decoding: one query token per sequence
against a (possibly ring-layout) KV cache.

The single-pass jnp decode path (``repro.models.attention.decode_attention``)
serializes the whole softmax over one KV stretch; for long contexts that
leaves the chip idle behind one block.  Flash-decoding instead grids over
KV *chunks* — every (batch, head, chunk) cell computes an independent
partial softmax (running max ``m``, normalizer ``l``, unnormalized
accumulator ``acc``) and a cheap log-sum-exp combine over the chunk axis
merges them outside the kernel.  All three grid axes are "parallel": no
cross-chunk carry exists, which is exactly what lets long-context decode
stop serializing.

Grid: (B, K, S/bk) over batch rows, KV heads and KV chunks.  Each cell
serves the G = H/K query heads that share one KV head (GQA), so the KV
cache is never repeated to H heads, and every block keeps its two minor
dimensions whole or 128-lane aligned: q (G, d), k/v (bk, d), the
per-chunk partials (G, 1) and (G, d).

Masking follows ``decode_attention``: slots with ``kv_pos < 0`` are empty
(ring cache holes / unfilled prefill slots), ``causal`` compares against
the query's absolute position, ``window`` bounds the lookback.  A chunk
whose every slot is masked yields ``m = NEG_INF`` and is annihilated by
the ``exp(m - M)`` combine weight.  :func:`combine` merges the partials;
given mesh axis names it also merges across devices that each hold a
slice of the cache's sequence axis (the sharded decode path).
"""

from __future__ import annotations

import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import hw

F32 = jnp.float32
NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, kvp_ref, qp_ref,
                   m_ref, l_ref, acc_ref, *,
                   scale: float, causal: bool, window: int, softcap: float):
    q = q_ref[0, 0].astype(jnp.bfloat16)                  # (G, d)
    k = k_ref[0, 0].astype(jnp.bfloat16)                  # (bk, d)
    v = v_ref[0, 0].astype(jnp.bfloat16)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=F32) * scale  # (G, bk)
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)

    kvp = kvp_ref[0]                                      # (1, bk)
    qp = qp_ref[0]                                        # (1, 1)
    mask = kvp >= 0
    if causal:
        mask &= kvp <= qp
    if window > 0:
        mask &= (qp - kvp) < window
    s = jnp.where(mask, s, NEG_INF)

    m = jnp.max(s, axis=1, keepdims=True)                 # (G, 1)
    p = jnp.exp(s - m)                                    # (G, bk)
    l = jnp.sum(p, axis=1, keepdims=True)                 # (G, 1)
    acc = jax.lax.dot_general(p.astype(jnp.bfloat16), v,
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=F32)  # (G, d)
    m_ref[0, 0, 0] = m
    l_ref[0, 0, 0] = l
    acc_ref[0, 0, 0] = acc


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "bk", "interpret"))
def decode_partials(q, k, v, kv_pos, q_pos, *, causal: bool = True,
                    window: int = 0, softcap: float = 0.0, bk: int = 512,
                    interpret: bool = False):
    """q (B, H, d); k/v (B, K, S, d) with K dividing H; kv_pos (B, S)
    absolute positions (-1 = empty slot); q_pos (B,).  Returns the
    unnormalized per-chunk partials m, l (B, K, nk, G) and acc
    (B, K, nk, G, d), G = H/K."""
    B, H, d = q.shape
    K, S = k.shape[1], k.shape[2]
    G = H // K
    bk = min(bk, S)
    assert S % bk == 0 and H % K == 0, (S, bk, H, K)
    nk = S // bk
    kvp = kv_pos.astype(jnp.int32)[:, None, :]            # (B, 1, S)
    qp = q_pos.astype(jnp.int32)[:, None, None]           # (B, 1, 1)
    part = lambda b, h, ik: (b, h, ik, 0, 0)
    m_p, l_p, acc_p = pl.pallas_call(
        functools.partial(_decode_kernel, scale=1.0 / math.sqrt(d),
                          causal=causal, window=window, softcap=softcap),
        grid=(B, K, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, d), lambda b, h, ik: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, h, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, h, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, h, ik: (b, 0, ik)),
            pl.BlockSpec((1, 1, 1), lambda b, h, ik: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, G, 1), part),
            pl.BlockSpec((1, 1, 1, G, 1), part),
            pl.BlockSpec((1, 1, 1, G, d), part),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, K, nk, G, 1), F32),
            jax.ShapeDtypeStruct((B, K, nk, G, 1), F32),
            jax.ShapeDtypeStruct((B, K, nk, G, d), F32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=hw.vmem_budget()),
        interpret=interpret,
        name="flash_decode",
    )(q.reshape(B, K, G, d), k, v, kvp, qp)
    return m_p[..., 0], l_p[..., 0], acc_p


def combine(m_p, l_p, acc_p, axis_names: Sequence[str] = ()):
    """Log-sum-exp merge of the chunk partials (axis 2) into the
    normalized output (B, H, d) f32.  ``axis_names``: mesh axes over
    which the chunks are further split (inside ``shard_map``)."""
    B, K, _, G, d = acc_p.shape
    m_g = jnp.max(m_p, axis=2)                            # (B, K, G)
    if axis_names:
        m_g = jax.lax.pmax(m_g, tuple(axis_names))
    alpha = jnp.exp(m_p - m_g[:, :, None])                # (B, K, nk, G)
    l_g = jnp.sum(alpha * l_p, axis=2)                    # (B, K, G)
    out = jnp.sum(alpha[..., None] * acc_p, axis=2)       # (B, K, G, d)
    if axis_names:
        l_g = jax.lax.psum(l_g, tuple(axis_names))
        out = jax.lax.psum(out, tuple(axis_names))
    out = out / jnp.maximum(l_g, 1e-30)[..., None]
    return out.reshape(B, K * G, d)


__all__ = ["decode_partials", "combine"]
