"""Plan-driven kernel dispatch: one place that reads a ``tile_plans``
entry and decides which implementation a model call site runs.

A ``ServingPlan.tile_plans`` entry (one dict per kernel kind, produced
by ``planner.tile_plans_for`` / ``core.dse``) may carry an ``impl``
field:

  * ``"auto"`` (default) — the Pallas kernel on a TPU backend, the
    pure-jnp reference path everywhere else.  CPU runs (tests, the
    committed BENCH trajectories, the virtual-clock scheduler) stay
    byte-identical to a plan with no tile_plans at all.
  * ``"jnp"`` — force the reference path.
  * ``"pallas"`` — force the Pallas kernel: compiled by Mosaic on a TPU,
    run in interpret mode on the CPU (the mode the parity tests use).
    Any other backend is an error.

On a TPU, ``launch.serve.resolve_plan`` gives a plan with no tile_plans
the planner's plans for the attached chip, so the plain CLI runs the
kernels; :func:`kernel_impls` reports which ones a plan runs.

The tile fields themselves (``bh``/``bq``/``bk``/``bm``/``bn``,
``persistent``) are read by each kernel's ops wrapper via
:func:`tile_arg`; geometry is snapped to the actual shapes with
``core.dse.snap_tile`` at the call site.
"""

from __future__ import annotations

from typing import Dict, Mapping, Optional

import jax

VALID_IMPLS = ("auto", "jnp", "pallas")


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_impl(entry: Optional[Mapping[str, object]]) -> str:
    """Collapse a tile-plan entry's ``impl`` field to "jnp" | "pallas"."""
    impl = str((entry or {}).get("impl", "auto"))
    if impl not in VALID_IMPLS:
        raise ValueError(f"tile plan impl {impl!r} not in {VALID_IMPLS}")
    if impl == "auto":
        return "pallas" if on_tpu() else "jnp"
    return impl


def pallas_active(entry: Optional[Mapping[str, object]]) -> bool:
    """True when this call site should run its Pallas kernel."""
    return entry is not None and resolve_impl(entry) == "pallas"


def kernel_impls(tile_plans: Optional[Mapping[str, Mapping[str, object]]]
                 ) -> Dict[str, str]:
    """``{kind: "pallas" | "jnp"}`` for every entry of a plan's
    ``tile_plans`` on the current backend (the serve banner prints it)."""
    return {kind: resolve_impl(entry)
            for kind, entry in sorted((tile_plans or {}).items())}


def interpret_mode() -> bool:
    """Pallas interpret flag: True on the CPU, False on a TPU.  The
    kernels are Mosaic (TPU) kernels, so any other backend raises."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"the Pallas kernels compile for TPU and "
                           f"interpret on CPU; backend {backend!r} is "
                           f"neither")
    return backend == "cpu"


def tile_arg(entry: Optional[Mapping[str, object]], name: str,
             default: int) -> int:
    """Read one tile field from a plan entry, falling back to the
    kernel's documented default when absent or zero."""
    val = int((entry or {}).get(name, 0) or 0)
    return val if val > 0 else default


__all__ = ["VALID_IMPLS", "on_tpu", "resolve_impl", "pallas_active",
           "kernel_impls", "interpret_mode", "tile_arg"]
