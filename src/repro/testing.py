"""Reduced configurations and helpers for smoke tests and examples.

``reduced_config(arch_id)`` shrinks each assigned architecture to a
CPU-friendly size while preserving its *family structure* (layer pattern,
GQA ratios, MoE routing, SSM/RWKV state shapes, softcaps, M-RoPE splits),
so the smoke tests exercise the same code paths the full configs lower.
"""

from __future__ import annotations

import dataclasses
from typing import Set, Tuple

import jax

from repro.configs import get_config
from repro.configs.base import ModelConfig, MoEConfig, RWKVConfig, SSMConfig, ShapeSpec
from repro.models.params import is_spec


def reduced_config(arch: str, **overrides) -> ModelConfig:
    cfg = get_config(arch)
    r: dict = dict(
        d_model=64,
        n_heads=4,
        n_kv_heads=2,
        head_dim=16,
        d_ff=128,
        vocab_size=503,          # deliberately unaligned: exercises padding
        vocab_pad_to=64,
        n_microbatches=1,
        remat="full",
        fsdp=False,
    )
    if cfg.local_window:
        r["local_window"] = 16
    if cfg.moe is not None:
        r["moe"] = MoEConfig(n_experts=8, top_k=2, capacity_factor=1.5,
                             group_size=16)
        r["d_ff"] = 32
    if cfg.rwkv is not None:
        r["rwkv"] = RWKVConfig(head_dim=16, chunk=8)
        r["n_heads"] = 4
        r["n_kv_heads"] = 4
    if cfg.ssm is not None:
        r["ssm"] = SSMConfig(d_state=4, expand=2, head_dim=16, conv_width=4,
                             chunk=8)
    # shrink the stack to two periods of a (possibly shortened) pattern
    pattern = cfg.layer_pattern
    if len(pattern) > 4:
        kinds = list(dict.fromkeys(pattern))  # unique, order-preserving
        pattern = tuple(kinds) * (4 // max(1, len(kinds)))
        pattern = pattern or cfg.layer_pattern[:4]
    r["layer_pattern"] = pattern
    r["n_layers"] = 2 * len(pattern)
    if cfg.is_encoder_decoder:
        r["n_encoder_layers"] = 2
    if cfg.m_rope_sections:
        r["m_rope_sections"] = (4, 2, 2)  # sums to head_dim // 2
    r.update(overrides)
    return dataclasses.replace(cfg, **r)


def smoke_shape(mode: str = "train", seq: int = 16, batch: int = 2) -> ShapeSpec:
    return ShapeSpec(f"smoke_{mode}", seq, batch, mode)


def compute_weight_shapes(model) -> Set[Tuple[int, ...]]:
    """The shapes a ``compute_only`` weight of ``model`` takes in a program:
    the whole leaf and, for a layer-stacked leaf, one scan step's slice."""
    out: Set[Tuple[int, ...]] = set()
    for s in jax.tree.leaves(model.param_specs(), is_leaf=is_spec):
        if s.compute_only:
            out.add(s.shape)
            if s.axes[:1] == ("layers",):
                out |= {s.shape[1:], (1,) + s.shape[1:]}
    return out
