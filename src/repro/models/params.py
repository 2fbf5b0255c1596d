"""Parameter-spec system.

A model describes its parameters as a pytree of :class:`ParamSpec` — shape,
dtype, *logical axis names*, and an initializer.  From the same spec tree we
derive:

  * real initialized parameters (smoke tests / examples),
  * ``jax.ShapeDtypeStruct`` stand-ins (the multi-pod dry-run: no allocation),
  * ``NamedSharding`` trees via the logical-axis rules in
    :mod:`repro.dist.sharding`.

This mirrors what flax/maxtext do with ``nn.with_logical_partitioning`` but
stays dependency-free and explicit.
"""

from __future__ import annotations

import dataclasses
import math
import zlib
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    dtype: Any = jnp.float32
    axes: Tuple[Optional[str], ...] = ()
    init: str = "normal"        # normal | zeros | ones | uniform | custom
    scale: Optional[float] = None
    custom_init: Optional[Callable[[jax.Array, "ParamSpec"], jax.Array]] = None
    # the model reads this leaf only at the compute dtype (a matmul weight
    # through layers.dot/wcast/embed/unembed or an MoE expert einsum), so a
    # serving copy may hold it there: LM.serving_params
    compute_only: bool = False

    def __post_init__(self):
        if self.axes and len(self.axes) != len(self.shape):
            raise ValueError(
                f"axes {self.axes} rank != shape {self.shape} rank")

    @property
    def size(self) -> int:
        return int(np.prod(self.shape)) if self.shape else 1

    @property
    def nbytes(self) -> int:
        return self.size * jnp.dtype(self.dtype).itemsize

    def abstract(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(self.shape, self.dtype)

    def initialize(self, key: jax.Array) -> jax.Array:
        if self.custom_init is not None:
            return self.custom_init(key, self)
        if self.init == "zeros":
            return jnp.zeros(self.shape, self.dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, self.dtype)
        if self.init == "uniform":
            s = self.scale if self.scale is not None else 1.0
            return jax.random.uniform(
                key, self.shape, jnp.float32, -s, s).astype(self.dtype)
        # default: truncated-normal, fan-in scaled unless overridden
        if self.scale is not None:
            std = self.scale
        else:
            fan_in = self.shape[-2] if len(self.shape) >= 2 else self.shape[-1]
            std = 1.0 / math.sqrt(max(1, fan_in))
        x = jax.random.truncated_normal(key, -3.0, 3.0, self.shape, jnp.float32)
        return (x * std).astype(self.dtype)


def is_spec(x: Any) -> bool:
    return isinstance(x, ParamSpec)


def tree_abstract(specs) -> Any:
    """ShapeDtypeStruct tree for dry-run lowering (no device allocation)."""
    return jax.tree.map(lambda s: s.abstract(), specs, is_leaf=is_spec)


def tree_axes(specs) -> Any:
    return jax.tree.map(lambda s: s.axes, specs, is_leaf=is_spec)


def tree_init(specs, key: jax.Array) -> Any:
    """Initialize every leaf with an independent, path-derived key.

    Keys are derived by folding a stable hash of the tree path into `key`,
    so adding/removing parameters does not reshuffle unrelated leaves —
    useful for checkpoint-compat tests.
    """
    leaves_with_paths = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=is_spec)[0]
    treedef = jax.tree.structure(specs, is_leaf=is_spec)
    out = []
    for path, spec in leaves_with_paths:
        path_str = jax.tree_util.keystr(path)
        # crc32, not hash(): str hashing is randomized per process, which
        # would reshuffle every init between runs (and break the promise
        # this docstring makes)
        sub = jax.random.fold_in(key, zlib.crc32(path_str.encode()) % (2**31))
        out.append(spec.initialize(sub))
    return jax.tree.unflatten(treedef, out)


def tree_size(specs) -> int:
    return sum(s.size for s in jax.tree.leaves(specs, is_leaf=is_spec))


def tree_bytes(specs) -> int:
    return sum(s.nbytes for s in jax.tree.leaves(specs, is_leaf=is_spec))


def stack_specs(spec: ParamSpec, n: int, axis_name: Optional[str] = "layers") -> ParamSpec:
    """Prepend a stacking dimension (for scan-over-layers parameters)."""
    return dataclasses.replace(
        spec,
        shape=(n,) + tuple(spec.shape),
        axes=(axis_name,) + tuple(spec.axes) if spec.axes else (),
    )


def tree_stack_specs(specs, n: int) -> Any:
    return jax.tree.map(lambda s: stack_specs(s, n), specs, is_leaf=is_spec)
