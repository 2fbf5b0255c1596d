"""Block assembly: one function per layer *kind*.

Kinds (ModelConfig.layer_pattern entries):
  "attn"     — global attention + MLP (or MoE when cfg.moe is set)
  "local"    — sliding-window attention + MLP/MoE; ring-buffer KV cache
  "swa_ssm"  — hymba hybrid: parallel sliding-window attention + SSD heads,
               outputs mean-fused after per-path norm, then MLP
  "rwkv"     — rwkv6 time-mix + channel-mix (handles its own norms)

Every block is a pure function (params, x, cache) -> (x, cache, aux,
written) so the layer-stack scan, the per-period cost piece of the
roofline analyzer, and the smoke tests all share one implementation;
``written`` is the rows a decode step wrote into its KV ring.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.quant import dequantize_kv, quantize_kv
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import rwkv as rwkv_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import dot, mlp, mlp_specs, rmsnorm
from repro.models.params import ParamSpec

F32 = jnp.float32


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------


def block_specs(cfg: ModelConfig, kind: str, cross: bool = False
                ) -> Dict[str, ParamSpec]:
    if kind == "rwkv":
        return rwkv_lib.rwkv_specs(cfg)
    d = cfg.d_model
    norm = lambda: ParamSpec((d,), jnp.float32, (None,), init="zeros")
    specs: Dict[str, object] = {
        "norm1": norm(),
        "norm2": norm(),
        "attn": attn.attention_specs(cfg),
    }
    if cfg.moe is not None:
        specs["moe"] = moe_lib.moe_specs(cfg)
    else:
        specs["mlp"] = mlp_specs(cfg)
    if kind == "swa_ssm":
        specs["ssm"] = ssm_lib.ssm_specs(cfg)
        specs["attn_out_norm"] = norm()
        specs["ssm_out_norm"] = norm()
    if cross:
        specs["norm_cross"] = norm()
        specs["cross"] = attn.attention_specs(cfg)
    return specs


# ---------------------------------------------------------------------------
# KV-cache entry helpers (bf16 or int8 storage)
# ---------------------------------------------------------------------------


def _kv_store_dtype(cfg: ModelConfig):
    return jnp.int8 if cfg.kv_cache_dtype == "int8" else jnp.bfloat16


def _encode_kv(cfg: ModelConfig, k, v):
    """(B,S,K,hd) -> cache arrays (+ scales when int8)."""
    if cfg.kv_cache_dtype == "int8":
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        return {"k": kq, "v": vq, "k_scale": ks[..., 0], "v_scale": vs[..., 0]}
    return {"k": k.astype(jnp.bfloat16), "v": v.astype(jnp.bfloat16)}


def _decode_kv(cfg: ModelConfig, entry):
    if cfg.kv_cache_dtype == "int8":
        k = dequantize_kv(entry["k"], entry["k_scale"][..., None])
        v = dequantize_kv(entry["v"], entry["v_scale"][..., None])
        return k, v
    return entry["k"], entry["v"]


def attn_cache_entry(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                     as_specs: bool = True) -> Dict[str, ParamSpec]:
    """ParamSpec tree for one attention cache entry (pre-stacking)."""
    n = attn.cache_slot_count(cfg, kind, max_len)
    K, hd = cfg.n_kv_heads, cfg.head_dim_
    seq_ax = "window" if n < max_len else "cache_seq"
    dt = _kv_store_dtype(cfg)
    entry = {
        "k": ParamSpec((batch, n, K, hd), dt,
                       ("batch", seq_ax, "kv_heads", None), init="zeros"),
        "v": ParamSpec((batch, n, K, hd), dt,
                       ("batch", seq_ax, "kv_heads", None), init="zeros"),
        "pos": ParamSpec((batch, n), jnp.int32, ("batch", seq_ax),
                         init="custom",
                         custom_init=lambda k, s: -jnp.ones(s.shape, s.dtype)),
    }
    if cfg.kv_cache_dtype == "int8":
        entry["k_scale"] = ParamSpec((batch, n, K), jnp.float32,
                                     ("batch", seq_ax, "kv_heads"), init="ones")
        entry["v_scale"] = ParamSpec((batch, n, K), jnp.float32,
                                     ("batch", seq_ax, "kv_heads"), init="ones")
    return entry


# ---------------------------------------------------------------------------
# Attention sub-block (shared by attn / local / swa_ssm kinds)
# ---------------------------------------------------------------------------


def _attn_seq(params, x, cfg: ModelConfig, sharder, positions, *,
              window: int, mode: str, causal: bool = True, max_len: int = 0,
              tile_plan=None):
    """Full-sequence attention.  Returns (out, cache_entry_or_None)."""
    B, S, _ = x.shape
    q, k, v = attn.project_qkv(params, x, cfg, sharder, positions)
    pos2d = positions if positions.ndim == 2 else positions[:, 0]
    out = attn.flash_attention(
        q, k, v, pos2d, pos2d, cfg=cfg, sharder=sharder, causal=causal,
        window=window, tile_plan=tile_plan)
    out = out.reshape(B, S, cfg.q_dim)
    out = dot(out, params["wo"])
    entry = None
    if mode == "prefill":
        n_slots = min(window, max_len or S) if window else (max_len or S)
        kc, vc, pc = attn.fill_cache_from_prefill(k, v, pos2d, n_slots)
        # the cache leaves the program laid out as decode reads it (see
        # attn_cache_entry), not replicated on every device
        seq_ax = "window" if n_slots < (max_len or S) else "cache_seq"
        kc = sharder.constrain(kc, "batch", seq_ax, "kv_heads", None)
        vc = sharder.constrain(vc, "batch", seq_ax, "kv_heads", None)
        pc = sharder.constrain(pc, "batch", seq_ax)
        entry = _encode_kv(cfg, kc, vc)
        entry["pos"] = pc.astype(jnp.int32)
    return out, entry


def write_rows(leaves, slot, rows, layer=None):
    """``leaves`` with one row a slot written: ``rows[name][b]`` at
    ``[b, slot[b]]`` of ``leaves[name]``, or at ``[layer, b, slot[b]]``
    of a layer-stacked leaf.  The decode step writes its token with it
    into the layer it attends over, and the layer scan the same rows into
    the stack it carries."""
    lead = () if layer is None else (layer,)
    b = jnp.arange(slot.shape[0])
    out = dict(leaves)
    for name, row in rows.items():
        out[name] = out[name].at[lead + (b, slot)].set(row)
    return out


def _attn_step(params, x, cfg: ModelConfig, sharder, lengths, cache, *,
               window: int, positions=None, tile_plan=None):
    """One-token attention over the cache.  x: (B, 1, d).

    Returns (out, entry, (slot, rows)): ``entry`` is ``cache`` with this
    token's K, V, position (and int8 scales) written at each slot's
    ``slot`` (``lengths % n_slots`` in a ring, else ``lengths`` clamped to
    the last slot), the operand attention reads; ``rows`` holds those
    values by leaf name.  The decode layer scan (``LM._scan``) carries
    the ring stacks as loop state and writes these rows into them
    (:func:`write_rows`), without choosing the slot again; the cache's
    other leaves it slices per layer and restacks whole."""
    B = x.shape[0]
    pos = positions if positions is not None else lengths[:, None]
    q, k, v = attn.project_qkv(params, x, cfg, sharder, pos)
    n_slots = cache["k"].shape[1]
    ring = window > 0 and n_slots <= window
    slot = lengths % n_slots if ring else jnp.minimum(lengths, n_slots - 1)
    rows = {name: a[:, 0] for name, a in _encode_kv(cfg, k, v).items()}
    rows["pos"] = lengths.astype(jnp.int32)
    entry = write_rows(cache, slot, rows)
    kc, vc = _decode_kv(cfg, entry)
    out = attn.decode_attention(
        q[:, 0], kc, vc, entry["pos"], lengths, cfg=cfg, sharder=sharder,
        causal=True, window=window, tile_plan=tile_plan)
    out = out.reshape(B, 1, cfg.q_dim)
    out = dot(out.astype(x.dtype), params["wo"])
    return out, entry, (slot, rows)


def _cross_attn(params, x, cfg: ModelConfig, sharder, *, enc_out=None,
                cache=None, mode: str):
    """Encoder-decoder cross attention.  Caches projected enc k/v."""
    B, S, _ = x.shape
    if cache is not None and "xk" in cache:
        k, v = cache["xk"], cache["xv"]
    else:
        Se = enc_out.shape[1]
        kf = dot(enc_out, params["wk"])
        vf = dot(enc_out, params["wv"])
        k = kf.reshape(B, Se, cfg.n_kv_heads, cfg.head_dim_)
        v = vf.reshape(B, Se, cfg.n_kv_heads, cfg.head_dim_)
    qf = dot(x, params["wq"])
    q = qf.reshape(B, S, cfg.n_heads, cfg.head_dim_)
    Se = k.shape[1]
    kv_pos = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32), (B, Se))
    if mode == "decode":
        out = attn.decode_attention(
            q[:, 0], k.astype(jnp.bfloat16), v.astype(jnp.bfloat16), kv_pos,
            jnp.full((B,), Se, jnp.int32), cfg=cfg, sharder=sharder,
            causal=False, window=0)
        out = out.reshape(B, 1, cfg.q_dim)
    else:
        q_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        out = attn.flash_attention(
            q, k, v, q_pos, kv_pos, cfg=cfg, sharder=sharder, causal=False,
            window=0)
        out = out.reshape(B, S, cfg.q_dim)
    out = dot(out.astype(x.dtype), params["wo"])
    entry = {"xk": k.astype(jnp.bfloat16), "xv": v.astype(jnp.bfloat16)} \
        if mode == "prefill" else None
    return out, entry


# ---------------------------------------------------------------------------
# Full blocks
# ---------------------------------------------------------------------------


def _ffn(params, h, cfg: ModelConfig, sharder):
    if cfg.moe is not None:
        return moe_lib.moe_mlp(params["moe"], h, cfg, sharder)
    return mlp(params["mlp"], h, cfg, sharder), jnp.zeros((), F32)


def apply_block(params, x, cfg: ModelConfig, kind: str, sharder, *,
                positions=None, lengths=None, mode: str = "train",
                cache: Optional[Dict] = None, enc_out=None,
                causal: bool = True, max_len: int = 0, tile_plan=None):
    """Returns (x, new_cache_entry, aux_loss, written).

    In decode mode ``written`` is what the attention step wrote into the
    entry's ring leaves, ``(slot, rows)`` (see :func:`_attn_step`), and
    None for a block without a KV ring; it is None in the other modes.

    In prefill mode ``lengths`` (when not None) marks each example's true
    prompt length within a right-padded batch: recurrent state updates are
    masked to the identity on padded steps (bucketed batched prefill);
    attention masks padding through the -1 entries of ``positions``.

    ``tile_plan`` is this kind's ``tile_plans`` entry (or None): an active
    pallas entry routes the hot-path math to the Pallas kernels with the
    DSE-chosen BlockSpec geometry.  The swa_ssm attention half stays on
    the jnp path — its plan entry models the SSD recurrence, for which no
    Pallas kernel exists yet."""
    if kind == "rwkv":
        x, new_cache = rwkv_lib.rwkv_block(
            params, x, cfg, sharder, mode=mode, cache=cache,
            lengths=lengths if mode == "prefill" else None,
            tile_plan=tile_plan)
        if mode == "train":
            new_cache = None
        return x, new_cache, jnp.zeros((), F32), None

    window = cfg.local_window if kind in ("local", "swa_ssm") else 0
    new_cache: Dict = {}
    written = None
    h = rmsnorm(x, params["norm1"], cfg.norm_eps)

    if kind == "swa_ssm":
        sub_attn = {k2: cache[k2] for k2 in ("k", "v", "pos", "k_scale",
                                             "v_scale") if cache and k2 in cache} \
            if cache else None
        sub_ssm = {k2: cache[k2] for k2 in ("conv_state", "ssd_state")} \
            if cache else None
        if mode == "decode":
            a_out, a_cache, written = _attn_step(
                params["attn"], h, cfg, sharder, lengths, sub_attn,
                window=window)
        else:
            a_out, a_cache = _attn_seq(params["attn"], h, cfg, sharder,
                                       positions, window=window, mode=mode,
                                       causal=causal, max_len=max_len)
        s_out, s_cache = ssm_lib.ssm_mixer(
            params["ssm"], h, cfg, sharder, mode=mode, cache=sub_ssm,
            lengths=lengths if mode == "prefill" else None)
        fused = 0.5 * (rmsnorm(a_out, params["attn_out_norm"], cfg.norm_eps)
                       + rmsnorm(s_out, params["ssm_out_norm"], cfg.norm_eps))
        x = x + fused
        if a_cache:
            new_cache.update(a_cache)
        if s_cache and mode != "train":
            new_cache.update(s_cache)
    else:
        if mode == "decode":
            a_out, a_cache, written = _attn_step(
                params["attn"], h, cfg, sharder, lengths, cache,
                window=window, positions=positions, tile_plan=tile_plan)
        else:
            a_out, a_cache = _attn_seq(params["attn"], h, cfg, sharder,
                                       positions, window=window, mode=mode,
                                       causal=causal, max_len=max_len,
                                       tile_plan=tile_plan)
        x = x + a_out
        if a_cache:
            new_cache.update(a_cache)

    if "cross" in params:
        hc = rmsnorm(x, params["norm_cross"], cfg.norm_eps)
        c_out, c_cache = _cross_attn(params["cross"], hc, cfg, sharder,
                                     enc_out=enc_out, cache=cache, mode=mode)
        x = x + c_out
        if c_cache:
            new_cache.update(c_cache)
        elif cache is not None and "xk" in cache:
            new_cache["xk"], new_cache["xv"] = cache["xk"], cache["xv"]

    h = rmsnorm(x, params["norm2"], cfg.norm_eps)
    f_out, aux = _ffn(params, h, cfg, sharder)
    x = x + f_out
    return x, (new_cache if mode != "train" else None), aux, written
