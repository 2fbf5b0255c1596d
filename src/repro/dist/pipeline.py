"""GPipe pipeline parallelism over a mesh axis via shard_map + ppermute.

Each device along the ``pipe`` axis owns one stage's parameters (the
stacked stage tree shards on its leading dim).  The batch splits into
microbatches; device 0 feeds one in per step, every device applies its
stage to whatever it holds, and a ``ppermute`` shifts activations one hop
down the pipe — the classic GPipe fill/steady/drain schedule, S + M - 1
steps for S stages and M microbatches.  The last device's outputs are
collected per microbatch and replicated with a ``psum`` (only the owning
device contributes), so the whole schedule is a pure differentiable
function: ``jax.grad`` through it yields the backward pipeline for free,
and the lowered HLO moves activations with ``collective-permute`` (asserted
by tests/test_pipeline.py).
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def stack_stage_params(stages: Sequence) -> jax.Array:
    """Stack a list of per-stage param trees along a new leading (stage)
    dim, giving the pipeline-sharded layout ``pipeline_apply`` expects."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *stages)


def pipeline_apply(stage_fn: Callable, params, x: jax.Array, mesh: Mesh,
                   axis: str = "pipe",
                   n_microbatches: Optional[int] = None) -> jax.Array:
    """Apply ``stage_fn`` S times through an S-deep pipeline.

    ``params``: stage-stacked tree (leaves lead with the stage dim, which
    shards over ``axis``); ``x``: (B, ...) batch, replicated.  Equals the
    sequential composition of the stages exactly.
    """
    n_stages = mesh.shape[axis]
    batch = x.shape[0]
    n_micro = n_microbatches or (n_stages if batch % n_stages == 0 else 1)
    assert batch % n_micro == 0, (batch, n_micro)

    def schedule(p_block, xs):
        # p_block: this device's (1, ...) stage slice; xs: (M, mb, ...)
        p = jax.tree.map(lambda a: a[0], p_block)
        idx = jax.lax.axis_index(axis)
        fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        zero = jnp.zeros(xs.shape[1:], xs.dtype)
        h = zero
        out = jnp.zeros_like(xs)
        for t in range(n_stages + n_micro - 1):
            feed = xs[t] if t < n_micro else zero
            y = stage_fn(p, jnp.where(idx == 0, feed, h))
            j = t - (n_stages - 1)       # microbatch draining this step
            if 0 <= j < n_micro:
                out = out.at[j].set(jnp.where(idx == n_stages - 1, y, 0.0))
            h = jax.lax.ppermute(y, axis, fwd)
        # only the last stage wrote non-zeros -> psum replicates its rows
        return jax.lax.psum(out, axis)

    fn = jax.shard_map(schedule, mesh=mesh, in_specs=(P(axis), P()),
                       out_specs=P(), check_vma=False)
    xs = x.reshape(n_micro, batch // n_micro, *x.shape[1:])
    return fn(params, xs).reshape(batch, *x.shape[1:])
