"""Gradient-communication compression.

Two pieces:

  * :func:`compressed_allreduce` — a shard_map collective that implements
    mean-all-reduce as f32 ``psum_scatter`` + **int8 all-gather**: each
    device averages its 1/n shard at full precision, quantizes it once,
    and the replication traffic (the (n-1)/n·bytes all-gather leg) moves
    int8 — a ~1.6x wire-byte reduction vs f32 ring all-reduce, visible in
    the lowered HLO (``all-gather ... s8``).  Deployment point: the
    cross-pod (DCN) gradient sync, where bandwidth is scarcest.
  * :func:`make_error_feedback` — error-feedback quantization wrapper
    (residual carried in f32) so repeated compression does not bias the
    optimizer; composes with the train step's ``grad_transform`` hook.
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.quant import quantize_int8

F32 = jnp.float32


def _compressed_mean_1d(x: jax.Array, axis_name: str, n: int) -> jax.Array:
    """Per-device body: f32 psum_scatter -> int8 quantize -> all_gather."""
    shard = jax.lax.psum_scatter(x, axis_name, scatter_dimension=0,
                                 tiled=True) / n
    q, scale = quantize_int8(shard.reshape(1, -1), axis=-1)
    q = jax.lax.all_gather(q[0], axis_name, tiled=True)
    scales = jax.lax.all_gather(scale.reshape(1), axis_name).reshape(n)
    # undo the scatter layout: segment i was quantized with scales[i]
    seg = q.reshape(n, -1).astype(F32) * scales[:, None]
    return seg.reshape(x.shape)


def compressed_allreduce(grads: Any, mesh: Mesh, axis: str = "data") -> Any:
    """Mean-all-reduce every leaf over ``axis`` with int8 replication
    traffic.  Leaves must be replicated over ``axis`` on entry (the usual
    DP layout) and divisible by the axis size when flattened."""
    n = mesh.shape[axis]

    def one(g):
        flat = g.astype(F32).reshape(-1)
        pad = (-flat.size) % n
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), F32)])
        out = _compressed_mean_1d(flat, axis, n)
        if pad:
            out = out[:-pad]
        return out.reshape(g.shape).astype(g.dtype)

    fn = jax.shard_map(lambda t: jax.tree.map(one, t), mesh=mesh,
                       in_specs=P(), out_specs=P(), check_vma=False)
    return fn(grads)


def make_error_feedback():
    """Returns (init_fn, apply_fn) for error-feedback int8 compression:
    apply(grads, residual) -> (compressed_grads, new_residual)."""

    def init(grads):
        return jax.tree.map(lambda g: jnp.zeros(g.shape, F32), grads)

    def apply(grads, residual):
        def one(g, r):
            x = g.astype(F32) + r
            q, scale = quantize_int8(x.reshape(1, -1), axis=-1)
            deq = (q.astype(F32) * scale).reshape(g.shape)
            return deq.astype(g.dtype), x - deq

        pairs = jax.tree.map(one, grads, residual)
        comp = jax.tree.map(lambda p: p[0], pairs,
                            is_leaf=lambda x: isinstance(x, tuple))
        res = jax.tree.map(lambda p: p[1], pairs,
                           is_leaf=lambda x: isinstance(x, tuple))
        return comp, res

    return init, apply
