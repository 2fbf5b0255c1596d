"""Mixture-of-experts layer: top-k routing, GShard dispatch/combine einsums.

Experts shard over the model axis (expert parallelism); the dispatch einsum
contracts tokens against a (group, token, expert, capacity) one-hot, which
GSPMD partitions into the canonical all-to-all exchange.  Capacity is
computed per token *group* so the dispatch tensor stays bounded; overflow
tokens are dropped (their combine weight is zero) as in GShard/Switch, and
the auxiliary load-balance loss keeps the router near-uniform.

The dispatch-einsum overhead relative to useful expert FLOPs is
2*E*C/(k*d_ff)-ish and is reported by the roofline's useful-flops ratio;
replacing it with sort-based ragged dispatch is a recorded hillclimb
candidate (EXPERIMENTS.md §Perf).
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.params import ParamSpec

F32 = jnp.float32


def moe_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, f, m = cfg.d_model, cfg.d_ff, cfg.moe
    specs = {
        "router": ParamSpec((d, m.n_experts), jnp.float32, ("embed", None),
                            scale=0.02),
        "w_up": ParamSpec((m.n_experts, d, f), jnp.float32,
                          ("experts", "embed", "mlp"), compute_only=True),
        "w_down": ParamSpec((m.n_experts, f, d), jnp.float32,
                            ("experts", "mlp", "embed"), compute_only=True),
    }
    if cfg.mlp_gated:
        specs["w_gate"] = ParamSpec((m.n_experts, d, f), jnp.float32,
                                    ("experts", "embed", "mlp"),
                                    compute_only=True)
    return specs


def _group_size(cfg: ModelConfig, n_tokens: int, sharder) -> int:
    """Groups must (a) bound the dispatch tensor, (b) outnumber the data
    shards so the group dim shards."""
    n_data = 1
    if sharder.mesh is not None:
        for a in ("pod", "data"):
            if a in sharder.mesh.shape:
                n_data *= sharder.mesh.shape[a]
    gs = min(cfg.moe.group_size, max(1, n_tokens // max(1, n_data)))
    while n_tokens % gs:
        gs -= 1
    return gs


def moe_mlp(params, x: jax.Array, cfg: ModelConfig, sharder
            ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (y, aux_loss)."""
    m = cfg.moe
    B, S, d = x.shape
    n_tokens = B * S
    gs = _group_size(cfg, n_tokens, sharder)
    G = n_tokens // gs
    E, K = m.n_experts, m.top_k
    C = max(1, int(math.ceil(gs * K * m.capacity_factor / E)))

    xg = x.reshape(G, gs, d)
    xg = sharder.constrain(xg, "expert_group", None, None)

    # ---- routing (f32) ------------------------------------------------------
    logits = jax.lax.dot_general(
        xg.astype(F32), params["router"].astype(F32),
        (((2,), (0,)), ((), ())))                          # (G, gs, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_idx = jax.lax.top_k(probs, K)               # (G, gs, K)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # ---- position-in-expert, slot by slot -----------------------------------
    dispatch = jnp.zeros((G, gs, E, C), jnp.bfloat16)
    combine = jnp.zeros((G, gs, E, C), F32)
    counts = jnp.zeros((G, E), F32)
    for j in range(K):
        oh = jax.nn.one_hot(top_idx[..., j], E, dtype=F32)  # (G, gs, E)
        pos = counts[:, None, :] + jnp.cumsum(oh, axis=1) - oh
        keep = (pos < C) * oh
        slot = jax.nn.one_hot(pos.astype(jnp.int32), C, dtype=F32)  # (G,gs,E,C)
        dj = keep[..., None] * slot
        dispatch = dispatch + dj.astype(jnp.bfloat16)
        combine = combine + dj * top_p[..., j][..., None, None]
        counts = counts + oh.sum(axis=1)

    # ---- dispatch -> expert compute -> combine ------------------------------
    expert_in = jnp.einsum("gsec,gsd->egcd", dispatch, xg.astype(jnp.bfloat16),
                           preferred_element_type=jnp.bfloat16)
    expert_in = sharder.constrain(expert_in, "experts", "expert_group",
                                  None, None)
    up = jnp.einsum("egcd,edf->egcf", expert_in,
                    params["w_up"].astype(jnp.bfloat16),
                    preferred_element_type=F32)
    if cfg.mlp_gated:
        gate = jnp.einsum("egcd,edf->egcf", expert_in,
                          params["w_gate"].astype(jnp.bfloat16),
                          preferred_element_type=F32)
        h = jax.nn.silu(gate) * up
    else:
        h = jax.nn.gelu(up)
    h = sharder.constrain(h.astype(jnp.bfloat16), "experts", "expert_group",
                          None, "mlp")
    out_e = jnp.einsum("egcf,efd->egcd", h,
                       params["w_down"].astype(jnp.bfloat16),
                       preferred_element_type=F32)
    y = jnp.einsum("gsec,egcd->gsd", combine.astype(jnp.bfloat16),
                   out_e.astype(jnp.bfloat16), preferred_element_type=F32)
    y = y.reshape(B, S, d).astype(x.dtype)

    # ---- aux losses ----------------------------------------------------------
    # load balance: E * sum_e f_e * P_e  (f from top-1 assignment)
    f_e = jax.nn.one_hot(top_idx[..., 0], E, dtype=F32).mean(axis=(0, 1))
    p_e = probs.mean(axis=(0, 1))
    balance = E * jnp.sum(f_e * p_e)
    router_z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    aux = m.router_aux_coef * balance + 1e-3 * router_z
    return y, aux
