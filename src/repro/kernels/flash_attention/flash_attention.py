"""Pallas TPU flash attention (forward) with causal / sliding-window
masking and gemma-style logit soft-capping.

Cross-kernel fusion in the paper's sense applied to attention: the QK^T
matmul, soft-cap, mask, online softmax, and the AV matmul live in one
kernel, so the (Sq, Skv) score matrix never exists in HBM — only a
(bq, bk) tile in VMEM.  This is the TPU-side replacement for the unrolled
jnp flash path in repro.models.attention (which the CPU-backend dry-run
lowers).

Grid: (B, H, Sq/bq, Skv/bk); the KV dim is the innermost ("arbitrary")
axis, with m/l/acc accumulators in VMEM scratch reinitialized at ik == 0
and the output written at the last KV block.  GQA is handled by the
caller (kv head index = h // group).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import hw

F32 = jnp.float32
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref,
            o_ref,
            m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: int, softcap: float,
            bq: int, bk: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]                                       # (bq, d)
    k = k_ref[0, 0]                                       # (bk, d)
    v = v_ref[0, 0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=F32) * scale
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)

    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=F32)
    m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def _kernel_pos(q_ref, k_ref, v_ref, qp_ref, kvp_ref,
                o_ref,
                m_scr, l_scr, acc_scr, *,
                scale: float, causal: bool, window: int, softcap: float):
    """Position-array masking variant: instead of assuming positions are
    the row/col iota, read per-token absolute positions (-1 = padding /
    empty cache slot) — what the model's right-padded bucketed prefill
    needs before the Pallas kernel can replace the unrolled jnp path."""
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]                                       # (bq, d)
    k = k_ref[0, 0]                                       # (bk, d)
    v = v_ref[0, 0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=F32) * scale
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)

    qp = qp_ref[0]                                        # (bq, 1)
    kvp = kvp_ref[0]                                      # (1, bk)
    mask = kvp >= 0
    if causal:
        mask &= kvp <= qp
    if window > 0:
        mask &= (qp - kvp) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=F32)
    m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "bq", "bk", "interpret"))
def flash_attention(q, k, v, q_pos=None, kv_pos=None, *,
                    causal: bool = True, window: int = 0,
                    softcap: float = 0.0, bq: int = 256, bk: int = 512,
                    interpret: bool = False):
    """q (B, H, Sq, d); k/v (B, H, Skv, d) — kv already head-expanded.
    Returns (B, H, Sq, d) in q.dtype.

    ``bq``/``bk`` are the BlockSpec tile rows — defaults are the pre-DSE
    hardcoded geometry; a ``tile_plans["attn"]`` entry overrides them via
    the ops wrapper.  When ``q_pos``/``kv_pos`` (B, S) int32 arrays are
    given, masking uses the per-token absolute positions (-1 masks the
    slot) instead of the tile iota."""
    B, H, Sq, d = q.shape
    Skv = k.shape[2]
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, bq, Skv, bk)
    scale = 1.0 / math.sqrt(d)
    grid = (B, H, Sq // bq, Skv // bk)
    qkv_specs = [
        pl.BlockSpec((1, 1, bq, d), lambda b, h, iq, ik: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, bk, d), lambda b, h, iq, ik: (b, h, ik, 0)),
        pl.BlockSpec((1, 1, bk, d), lambda b, h, iq, ik: (b, h, ik, 0)),
    ]
    common = dict(
        grid=grid,
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), F32),
            pltpu.VMEM((bq,), F32),
            pltpu.VMEM((bq, d), F32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=hw.vmem_budget()),
        interpret=interpret,
    )
    if q_pos is None and kv_pos is None:
        return pl.pallas_call(
            functools.partial(_kernel, scale=scale, causal=causal,
                              window=window, softcap=softcap, bq=bq, bk=bk),
            in_specs=qkv_specs,
            name="flash_attention",
            **common,
        )(q, k, v)
    if q_pos is None:
        q_pos = jnp.broadcast_to(jnp.arange(Sq, dtype=jnp.int32), (B, Sq))
    if kv_pos is None:
        kv_pos = jnp.broadcast_to(jnp.arange(Skv, dtype=jnp.int32), (B, Skv))
    qp = q_pos.astype(jnp.int32)[:, :, None]              # (B, Sq, 1)
    kvp = kv_pos.astype(jnp.int32)[:, None, :]            # (B, 1, Skv)
    return pl.pallas_call(
        functools.partial(_kernel_pos, scale=scale, causal=causal,
                          window=window, softcap=softcap),
        in_specs=qkv_specs + [
            pl.BlockSpec((1, bq, 1), lambda b, h, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, h, iq, ik: (b, 0, ik)),
        ],
        name="flash_attention_pos",
        **common,
    )(q, k, v, qp, kvp)
