"""Pallas TPU kernel: fused RWKV6 serving step (the paper's LSTM-1
pattern on the modern recurrent cell).

One kernel evaluates, per (batch, head) tile, the whole wkv recurrence for
a token:

    y   = r . (S + (u * k) v^T)
    S' <- diag(w) S + k v^T

with the state S resident in VMEM and every intermediate (outer product,
bonus read) on chip — no (K, V)-sized tensor round-trips HBM, which is
exactly the paper's cross-kernel-fusion claim applied to RWKV serving.

Grid: (H/bh, T), head-tile-major.  Heads are independent, so each head
tile runs all T tokens with its state block resident in the output
buffer (its index map is constant in t): the state is accumulated in
place, with no scratch copy of the whole (B, H, K, V) state.

Layouts: r/k/w (T, B, H, K); v (T, B, H, V); u (H, K); state (B, H, K, V).
On a TPU the head tile ``bh`` must be a multiple of 8 or all H heads
(``core.dse.best_rwkv_plan`` only proposes such tiles); K = V = 64 pads
each state row to the 128-lane tile, which the DSE counts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import hw

F32 = jnp.float32


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, s_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_ref[...] = s0_ref[...].astype(F32)        # (B, bh, K, V)

    r = r_ref[0].astype(F32)                        # (B, bh, K)
    k = k_ref[0].astype(F32)
    w = w_ref[0].astype(F32)                        # log-decay, <= 0
    v = v_ref[0].astype(F32)                        # (B, bh, V)
    u = u_ref[...].astype(F32)                      # (bh, K)

    S = s_ref[...]
    kv = k[..., None] * v[:, :, None, :]            # (B, bh, K, V)
    read = S + u[None, :, :, None] * kv
    y = jnp.sum(r[..., None] * read, axis=2)        # (B, bh, V)
    s_ref[...] = jnp.exp(w)[..., None] * S + kv
    y_ref[0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bh", "interpret"))
def rwkv6_step(r, k, v, w_log, u, state, *, bh: int = 0,
               interpret: bool = False):
    """Serve T tokens through the fused recurrence.

    r/k/w_log: (T, B, H, K); v: (T, B, H, V); u: (H, K);
    state: (B, H, K, V) f32.  Returns (y (T, B, H, V) bf16, state').

    ``bh`` tiles the head axis (grid (H/bh, T)): heads are independent,
    so any head split is bit-exact; 0 = all heads in one tile."""
    T, B, H, K = r.shape
    V = v.shape[-1]
    bh = bh or H
    assert H % bh == 0, (H, bh)
    step = pl.BlockSpec((1, B, bh, K), lambda h, t: (t, 0, h, 0))
    stepv = pl.BlockSpec((1, B, bh, V), lambda h, t: (t, 0, h, 0))
    tile = pl.BlockSpec((B, bh, K, V), lambda h, t: (0, h, 0, 0))
    return pl.pallas_call(
        _kernel,
        grid=(H // bh, T),
        in_specs=[step, step, stepv, step,
                  pl.BlockSpec((bh, K), lambda h, t: (h, 0)), tile],
        out_specs=[stepv, tile],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, H, V), jnp.bfloat16),
            jax.ShapeDtypeStruct((B, H, K, V), F32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=hw.vmem_budget()),
        interpret=interpret,
        name="rwkv6_step",
    )(r, k, v, w_log, u, state)
