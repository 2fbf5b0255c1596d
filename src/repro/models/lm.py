"""The unified LM wrapper: parameters, train loss, prefill, decode.

One class serves all ten assigned architectures; family differences are
entirely expressed through ``ModelConfig.layer_pattern`` and the block
library.  The layer stack is scanned at *period* granularity (stacked
parameters, one period = one iteration) which keeps HLO size and compile
time independent of depth — and the class exposes ``period_apply`` /
``stem_train`` / ``stem_serve`` so the roofline analyzer can lower the
scanned body separately and scale its cost by the trip count
(EXPERIMENTS.md §Methodology).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeSpec
from repro.dist.sharding import Sharder
from repro.models import params as pspec
from repro.models.attention import cache_slot_count
from repro.models.blocks import (apply_block, attn_cache_entry,
                                 block_specs, write_rows)
from repro.models.layers import COMPUTE_DTYPE, embed, embed_specs, unembed
from repro.models.params import ParamSpec
from repro.models.ssm import _d_inner, _n_ssm_heads

F32 = jnp.float32


def build_model(cfg: ModelConfig, tile_plans=None) -> "LM":
    return LM(cfg, tile_plans=tile_plans)


@functools.partial(jax.jit, static_argnums=1)
def _serving_cast(xs, dtype):
    """``xs`` cast to ``dtype`` in one program.  An elementwise convert
    keeps its operand's sharding, and an uncommitted operand gives an
    uncommitted result, free to follow the engine's programs."""
    return [x.astype(dtype) for x in xs]


def _store_compute_only(specs, dtype):
    """``specs`` with every ``compute_only`` f32 leaf stored at ``dtype``
    (``ModelConfig.weight_dtype``); the model reads such a leaf only at
    the compute dtype, so a bf16 store loses nothing the programs use."""
    if dtype == F32:
        return specs
    return jax.tree.map(
        lambda s: dataclasses.replace(s, dtype=dtype)
        if s.compute_only and s.dtype == F32 else s,
        specs, is_leaf=pspec.is_spec)


def _split_ring(cache):
    """(ring, rest) of a period cache: each entry's ring leaves
    (``LM.PAGEABLE_LEAVES``), for the entries that have any, and its
    other leaves; ``({}, None)`` without a cache."""
    if cache is None:
        return {}, None
    ring = {key: {n: a for n, a in e.items() if n in LM.PAGEABLE_LEAVES}
            for key, e in cache.items()}
    rest = {key: {n: a for n, a in e.items() if n not in LM.PAGEABLE_LEAVES}
            for key, e in cache.items()}
    return {key: r for key, r in ring.items() if r}, rest


def _join(rest, ring):
    """A period cache from its entries' other leaves (``rest``) and their
    ring leaves (``ring``, keyed like ``rest``, entries without any left
    out)."""
    return {key: {**e, **ring.get(key, {})} for key, e in rest.items()}


def _wider_float(x, dtype) -> bool:
    """``x`` is a plain floating array wider than ``dtype`` (an int8
    ``{q, scale}`` dict is not)."""
    return (hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)
            and jnp.dtype(x.dtype).itemsize > jnp.dtype(dtype).itemsize)


class LM:
    def __init__(self, cfg: ModelConfig, tile_plans=None):
        self.cfg = cfg
        # per-kind kernel tile geometry (ServingPlan.tile_plans); entries
        # reach every apply_block call so an autotuned plan provably
        # changes the compiled hot path.
        self.tile_plans = dict(tile_plans or {})

    def with_tile_plans(self, tile_plans) -> "LM":
        """A copy of this model whose blocks run under ``tile_plans``."""
        return type(self)(self.cfg, tile_plans=tile_plans)

    # ------------------------------------------------------------------ specs
    def param_specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        specs: Dict[str, Any] = {}
        specs.update(embed_specs(cfg))
        specs["final_norm"] = ParamSpec((cfg.d_model,), F32, (None,),
                                        init="zeros")
        period = {
            f"p{i}": block_specs(cfg, kind, cross=cfg.is_encoder_decoder)
            for i, kind in enumerate(cfg.layer_pattern)
        }
        specs["blocks"] = pspec.tree_stack_specs(period, cfg.n_periods)
        if cfg.is_encoder_decoder:
            enc_period = {"p0": block_specs(cfg, "attn")}
            specs["enc_blocks"] = pspec.tree_stack_specs(
                enc_period, cfg.n_encoder_layers)
            specs["enc_final_norm"] = ParamSpec((cfg.d_model,), F32, (None,),
                                                init="zeros")
        return _store_compute_only(specs, jnp.dtype(cfg.weight_dtype))

    def init(self, key: jax.Array):
        return pspec.tree_init(self.param_specs(), key)

    def abstract_params(self):
        return pspec.tree_abstract(self.param_specs())

    def n_params(self) -> int:
        return pspec.tree_size(self.param_specs())

    def serving_params(self, params) -> Tuple[Any, int]:
        """The copy of ``params`` that the serving programs read, and the
        bytes of its ``compute_only`` leaves held at the compute dtype.

        A leaf whose spec is ``compute_only`` and which is a plain floating
        array wider than the compute dtype is rounded to it here, once, by
        one jitted cast that keeps each leaf's sharding, instead of inside
        every program call.  It is the rounding ``layers.wcast`` makes, so
        the programs compute the same bf16 operands bit for bit.  Every
        other leaf (read at f32, or an int8 ``{q, scale}`` dict, which
        stays narrow) is passed through as stored.  Casting a copy again
        changes nothing."""
        flags = jax.tree.map(lambda s: s.compute_only, self.param_specs(),
                             is_leaf=pspec.is_spec)
        treedef = jax.tree.structure(flags)
        subs = treedef.flatten_up_to(params)
        marked = [i for i, f in enumerate(jax.tree.leaves(flags)) if f]
        wide = [i for i in marked if _wider_float(subs[i], COMPUTE_DTYPE)]
        if wide:
            cast = _serving_cast([subs[i] for i in wide], COMPUTE_DTYPE)
            for i, y in zip(wide, cast):
                subs[i] = y
        nbytes = sum(subs[i].nbytes for i in marked
                     if getattr(subs[i], "dtype", None) == COMPUTE_DTYPE)
        return treedef.unflatten(subs), int(nbytes)

    # ------------------------------------------------------------- period body
    def period_apply(self, p_params, x, *, positions=None, lengths=None,
                     mode: str, sharder: Sharder, p_cache=None, enc_out=None,
                     causal: bool = True, max_len: int = 0):
        """Apply one scan period (all layers of the pattern).

        Returns (x, new_period_cache_or_None, aux, written): each cache
        entry comes back whole, and ``written`` maps each entry whose KV
        ring a decode step wrote to the rows it wrote, ``(slot, rows)``."""
        cfg = self.cfg
        aux = jnp.zeros((), F32)
        new_cache: Dict[str, Any] = {}
        written: Dict[str, Any] = {}
        for i, kind in enumerate(cfg.layer_pattern):
            key = f"p{i}"
            x, c, a, w = apply_block(
                p_params[key], x, cfg, kind, sharder, positions=positions,
                lengths=lengths, mode=mode, enc_out=enc_out, causal=causal,
                cache=(p_cache or {}).get(key) if p_cache else None,
                max_len=max_len, tile_plan=self.tile_plans.get(kind))
            aux = aux + a
            if c is not None:
                new_cache[key] = c
            if w is not None:
                written[key] = w
        return x, (new_cache or None), aux, written

    def _scan(self, blocks, x, *, positions=None, lengths=None, mode: str,
              sharder: Sharder, cache=None, enc_out=None, causal=True,
              max_len: int = 0, remat: Optional[bool] = None):
        """Run the period stack as one ``lax.scan``; returns (x, cache or
        None, aux).

        The cache's ring leaves (``PAGEABLE_LEAVES``: KV, positions, int8
        scales) ride in the loop state: step ``i`` hands its period layer
        ``i`` of each stack, and writes back into the stack only the rows
        the decode step wrote (``blocks.write_rows`` at ``[i, b, slot]``),
        so a tick moves one row a slot, not the whole layer.  Every other
        leaf (rwkv and SSM state, cross-attention keys) is sliced out of
        ``xs`` and restacked from ``ys``; a cache without ring leaves has
        an empty loop state.  Prefill builds its cache from ``ys``."""
        cfg = self.cfg
        collect = mode in ("prefill", "decode")
        remat = (cfg.remat != "none" and mode == "train") \
            if remat is None else remat
        ring, rest = _split_ring(cache)

        def body(carry, xs):
            x, aux, ring = carry
            i, p_params, p_rest = xs
            layer = jax.tree.map(lambda stack: jax.lax.dynamic_index_in_dim(
                stack, i, keepdims=False), ring)
            p_cache = None if p_rest is None else _join(p_rest, layer)
            x, new_c, a, written = self.period_apply(
                p_params, x, positions=positions, lengths=lengths, mode=mode,
                sharder=sharder, p_cache=p_cache, enc_out=enc_out,
                causal=causal, max_len=max_len)
            if mode == "train":
                # the scan carry is what remat saves; under
                # cfg.shard_residual_seq its seq dim shards over the model
                # axis (re-gathered on recompute) — §Perf lever
                x = sharder.constrain(x, "batch", "res_seq", None)
            ring = {key: write_rows(r, *written[key], layer=i)
                    for key, r in ring.items()}
            ys = {key: {n: leaf for n, leaf in e.items()
                        if n not in ring.get(key, ())}
                  for key, e in new_c.items()} if collect else 0
            return (x, aux + a, ring), ys

        if remat:
            policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                      if cfg.remat == "dots" else None)
            body = jax.checkpoint(body, policy=policy)
        xs = (jnp.arange(cfg.n_periods), blocks, rest)
        (x, aux, ring), caches = jax.lax.scan(
            body, (x, jnp.zeros((), F32), ring), xs)
        return x, (_join(caches, ring) if collect else None), aux

    # ------------------------------------------------------------------ stems
    def embed_tokens(self, params, tokens, sharder) -> jax.Array:
        return embed(params, tokens, self.cfg, sharder)

    def final_hidden_to_logits(self, params, x, sharder,
                               norm_name="final_norm") -> jax.Array:
        from repro.models.layers import rmsnorm
        x = rmsnorm(x, params[norm_name], self.cfg.norm_eps)
        return unembed(params, x, self.cfg, sharder)

    # --------------------------------------------------------------- encoder
    def encode(self, params, frames, sharder, mode="train"):
        """Whisper encoder over precomputed frame embeddings (stub
        frontend).  frames: (B, S_enc, d_model)."""
        from repro.models.layers import rmsnorm
        B, Se, _ = frames.shape
        pos = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32), (B, Se))
        x = frames.astype(jnp.bfloat16)
        x, _, _ = self._scan(params["enc_blocks"], x, positions=pos,
                             mode="train", sharder=sharder, causal=False,
                             remat=(mode == "train" and self.cfg.remat != "none"))
        return rmsnorm(x, params["enc_final_norm"], self.cfg.norm_eps)

    # ------------------------------------------------------------------ train
    def loss(self, params, batch, sharder: Sharder
             ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        cfg = self.cfg
        tokens = batch["tokens"]
        x_tok, targets = tokens[:, :-1], tokens[:, 1:]
        B, S = x_tok.shape
        positions = batch.get("positions")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                         (B, S))
        enc_out = None
        if cfg.is_encoder_decoder:
            enc_out = self.encode(params, batch["frames"], sharder)
        x = self.embed_tokens(params, x_tok, sharder)
        x, _, aux = self._scan(params["blocks"], x, positions=positions,
                               mode="train", sharder=sharder, enc_out=enc_out)
        logits = self.final_hidden_to_logits(params, x, sharder)
        return self.ce_loss(logits, targets, aux)

    def ce_loss(self, logits, targets, aux=None):
        cfg = self.cfg
        logits = logits.astype(F32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, targets[..., None].astype(jnp.int32), axis=-1)[..., 0]
        ce = jnp.mean(lse - gold)
        z_loss = 1e-4 * jnp.mean(jnp.square(lse))
        total = ce + z_loss + (aux if aux is not None else 0.0)
        metrics = {"loss": total, "ce": ce, "z_loss": z_loss,
                   "aux": aux if aux is not None else jnp.zeros((), F32)}
        return total, metrics

    # ------------------------------------------------------------------ cache
    def cache_specs(self, batch: int, max_len: int) -> Dict[str, Any]:
        """ParamSpec tree for the serving cache (decode input)."""
        cfg = self.cfg
        period = self.period_cache_specs(batch, max_len)
        blocks = pspec.tree_stack_specs(period, cfg.n_periods)
        return {"blocks": blocks,
                "lengths": ParamSpec((batch,), jnp.int32, ("batch",),
                                     init="zeros")}

    def period_cache_specs(self, batch: int, max_len: int) -> Dict[str, Any]:
        """Cache specs for ONE scan period (pre-stacking); also used by the
        roofline analyzer's per-period decode cost piece."""
        cfg = self.cfg
        period: Dict[str, Any] = {}
        for i, kind in enumerate(cfg.layer_pattern):
            key = f"p{i}"
            if kind == "rwkv":
                H, hd = cfg.d_model // cfg.rwkv.head_dim, cfg.rwkv.head_dim
                period[key] = {
                    "wkv_state": ParamSpec((batch, H, hd, hd), F32,
                                           ("batch", "rwkv_heads", None, None),
                                           init="zeros"),
                    "tm_shift": ParamSpec((batch, cfg.d_model), jnp.bfloat16,
                                          ("batch", None), init="zeros"),
                    "cm_shift": ParamSpec((batch, cfg.d_model), jnp.bfloat16,
                                          ("batch", None), init="zeros"),
                }
                continue
            entry = attn_cache_entry(cfg, kind, batch, max_len)
            if kind == "swa_ssm":
                s = cfg.ssm
                di, nh = _d_inner(cfg), _n_ssm_heads(cfg)
                entry["conv_state"] = ParamSpec(
                    (batch, s.conv_width - 1, di), jnp.bfloat16,
                    ("batch", None, "ssm_inner"), init="zeros")
                entry["ssd_state"] = ParamSpec(
                    (batch, nh, s.d_state, s.head_dim), F32,
                    ("batch", None, None, None), init="zeros")
            if cfg.is_encoder_decoder:
                se = max_len // cfg.encoder_downsample
                entry["xk"] = ParamSpec(
                    (batch, se, cfg.n_kv_heads, cfg.head_dim_), jnp.bfloat16,
                    ("batch", None, "kv_heads", None), init="zeros")
                entry["xv"] = ParamSpec(
                    (batch, se, cfg.n_kv_heads, cfg.head_dim_), jnp.bfloat16,
                    ("batch", None, "kv_heads", None), init="zeros")
            period[key] = entry
        return period

    def init_cache(self, batch: int, max_len: int, shardings=None):
        """The empty serving cache; with ``shardings`` (a tree from
        ``Sharder.param_shardings`` of :meth:`cache_specs`) it is made
        in place, each device writing only its own shard."""
        specs = self.cache_specs(batch, max_len)
        if shardings is None:
            return pspec.tree_init(specs, jax.random.PRNGKey(0))
        return jax.jit(functools.partial(pspec.tree_init, specs),
                       out_shardings=shardings)(jax.random.PRNGKey(0))

    def cache_batch_axes(self, cache) -> Dict[str, Any]:
        """Batch(=slot)-axis index for every cache leaf — the cache pytree
        contract the serving layer's slot-state manager keys on.

        Every leaf under ``blocks`` is period-stacked (axis 0 = scan
        period), so its slot axis is 1; the top-level ``lengths`` vector
        carries slots on axis 0.  Gathering a slot's column across this
        axes tree captures the request's *entire* decode state — KV ring
        (k/v/pos and int8 scales), rwkv wkv/shift, ssd/conv, cross-attn
        keys, and its length counter — which is what makes preempt-to-
        host / resume (repro.serving.slotstate) architecture-agnostic."""
        return {"blocks": jax.tree.map(lambda _: 1, cache["blocks"]),
                "lengths": 0}

    # KV-ring leaves: paged along their length(-ring) axis by the paged
    # slot-state manager, and carried through the decode layer scan, which
    # writes one row of each a slot a tick (``_scan``).  Everything else — rwkv wkv/shift, ssd/conv,
    # cross-attn keys, lengths — is per-slot state with no length axis
    # (or, for xk/xv, written whole at prefill), i.e. "one block per
    # slot": the cheap recurrent case.
    PAGEABLE_LEAVES = frozenset({"k", "v", "pos", "k_scale", "v_scale"})

    def cache_page_axes(self, cache) -> Dict[str, Any]:
        """Length(-ring)-axis index for every *pageable* cache leaf, None
        for per-slot state — the companion contract to
        :meth:`cache_batch_axes` that lets the paged slot-state manager
        (repro.serving.paged) split the cache into a block pool (KV rings,
        paged along axis 2 after period stacking) and dense per-slot
        leaves.  Accepts either a live cache pytree or a ``cache_specs``
        spec tree (classification is by leaf name, not by value)."""
        def classify(path, _leaf):
            name = path[-1].key if hasattr(path[-1], "key") else None
            return 2 if name in self.PAGEABLE_LEAVES else None

        blocks = jax.tree_util.tree_map_with_path(
            classify, cache["blocks"],
            is_leaf=lambda x: isinstance(x, ParamSpec))
        return {"blocks": blocks, "lengths": None}

    # ---------------------------------------------------------------- prefill
    def prefill(self, params, batch, sharder: Sharder, max_len: int = 0):
        """Full-sequence prefill.  Returns (cache, last_token_logits).

        ``batch["lengths"]`` (B,) int32, when present, marks each example's
        true prompt length within a right-padded batch (bucketed batched
        prefill): padding positions are masked out of attention (position
        -1), recurrent-state updates on padded steps are forced to the
        identity, the returned logits are read at each example's last
        *valid* token, and the cache records the true lengths — so one
        padded batched call is equivalent to per-example exact-length
        prefills.  (MoE routing is the one approximate spot: padded tokens
        still compete for expert capacity.)"""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        max_len = max_len or S
        lengths = batch.get("lengths")
        positions = batch.get("positions")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                         (B, S))
        if lengths is not None:
            lengths = lengths.astype(jnp.int32)
            valid = (jnp.arange(S, dtype=jnp.int32)[None, :]
                     < lengths[:, None])                          # (B, S)
            vmask = valid if positions.ndim == 2 else valid[:, None, :]
            positions = jnp.where(vmask, positions, -1)
        enc_out = None
        if cfg.is_encoder_decoder:
            enc_out = self.encode(params, batch["frames"], sharder,
                                  mode="prefill")
        x = self.embed_tokens(params, tokens, sharder)
        x, caches, _ = self._scan(params["blocks"], x, positions=positions,
                                  lengths=lengths, mode="prefill",
                                  sharder=sharder, enc_out=enc_out,
                                  max_len=max_len)
        if lengths is None:
            h_last = x[:, -1:, :]
            cache_lengths = jnp.full((B,), S, jnp.int32)
        else:
            idx = jnp.maximum(lengths - 1, 0)[:, None, None]
            h_last = jnp.take_along_axis(x, idx, axis=1)
            cache_lengths = lengths
        logits = self.final_hidden_to_logits(params, h_last, sharder)
        cache = {"blocks": caches, "lengths": cache_lengths}
        return cache, logits[:, 0]

    # ----------------------------------------------------------------- decode
    def decode_step(self, params, cache, tokens, sharder: Sharder):
        """One decode step.  tokens: (B,) int32.  Returns (cache, logits)."""
        cfg = self.cfg
        B = tokens.shape[0]
        lengths = cache["lengths"]
        if cfg.m_rope_sections:
            positions = jnp.broadcast_to(lengths[:, None, None], (B, 3, 1))
        else:
            positions = lengths[:, None]
        x = self.embed_tokens(params, tokens[:, None], sharder)
        x, new_blocks, _ = self._scan(
            params["blocks"], x, positions=positions, lengths=lengths,
            mode="decode", sharder=sharder, cache=cache["blocks"])
        logits = self.final_hidden_to_logits(params, x, sharder)
        new_cache = {"blocks": new_blocks, "lengths": lengths + 1}
        return new_cache, logits[:, 0]

    # ------------------------------------------------ cost pieces (roofline)
    def stem_train(self, params, tokens, h_final, sharder):
        """Embedding + head + loss (the non-scanned part of a train step)."""
        x_tok, targets = tokens[:, :-1], tokens[:, 1:]
        x0 = self.embed_tokens(params, x_tok, sharder)
        logits = self.final_hidden_to_logits(
            params, h_final + 0.0 * x0, sharder)
        total, _ = self.ce_loss(logits, targets)
        return total

    def stem_serve(self, params, tokens, h_final, sharder, last_only=True):
        x0 = self.embed_tokens(params, tokens, sharder)
        h = h_final + 0.0 * x0
        if last_only:
            h = h[:, -1:, :]
        return self.final_hidden_to_logits(params, h, sharder)
