"""Pallas TPU W8A16 matmul: int8-stored weights, bf16 activations, f32 MXU
accumulation, fused per-channel dequant + bias + activation epilogue.

This is the serving-path workhorse the paper's precision scheme implies for
transformer decode: decode is HBM-bandwidth-bound on weight reads, so int8
storage halves the dominant roofline term while the multiply runs wide.
The epilogue fusion (scale, bias, silu/gelu) is the cross-kernel
optimization: no (M, N) intermediate round-trips HBM.

Grid (M/bm, N/bn, K/bk), f32 accumulator in VMEM scratch, epilogue at the
last K block.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import hw

F32 = jnp.float32

_EPILOGUES = {
    "none": lambda x: x,
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "relu": jax.nn.relu,
}


def _kernel(x_ref, w_ref, s_ref, b_ref, o_ref, acc_scr, *, act: str,
            has_bias: bool):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[...]                                        # (bm, bk) bf16
    w = w_ref[...].astype(jnp.bfloat16)                   # (bk, bn) int8->bf16
    acc_scr[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=F32)

    @pl.when(ik == nk - 1)
    def _epilogue():
        out = acc_scr[...] * s_ref[...]                   # per-channel scale
        if has_bias:
            out = out + b_ref[...]
        out = _EPILOGUES[act](out)
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "act", "bm", "bn", "bk", "interpret"))
def matmul_w8a16(x, w_q, scale, bias=None, *, act: str = "none",
                 bm: int = 256, bn: int = 256, bk: int = 512,
                 interpret: bool = False):
    """x (M, K) bf16; w_q (K, N) int8; scale (N,) f32; bias (N,) f32 or None.
    Returns act(x @ (w_q * scale) + bias) as (M, N) bf16."""
    M, K = x.shape
    N = w_q.shape[1]
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    has_bias = bias is not None
    if bias is None:
        bias = jnp.zeros((N,), F32)
    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        functools.partial(_kernel, act=act, has_bias=has_bias),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda im, jn, ik: (im, ik)),
            pl.BlockSpec((bk, bn), lambda im, jn, ik: (ik, jn)),
            pl.BlockSpec((1, bn), lambda im, jn, ik: (0, jn)),
            pl.BlockSpec((1, bn), lambda im, jn, ik: (0, jn)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda im, jn, ik: (im, jn)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.bfloat16),
        scratch_shapes=[pltpu.VMEM((bm, bn), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=hw.vmem_budget()),
        interpret=interpret,
        name="matmul_w8a16",
    )(x, w_q, scale.reshape(1, N), bias.reshape(1, N))
