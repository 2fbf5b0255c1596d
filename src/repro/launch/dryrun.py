import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (architecture x input shape x
mesh) cell with ShapeDtypeStruct stand-ins (no allocation), then derive the
three-term roofline from the compiled artifacts.

The two lines above MUST stay the first statements of this module: jax
locks the device count at first initialization, and the production meshes
need 512 placeholder host devices.

Per cell this produces:
  * the FULL artifact — the real train/serve step with scan-over-layers:
    its successful ``.lower().compile()`` is the pass/fail gate, and its
    ``memory_analysis()`` proves per-chip fit;
  * COST PIECES — the scanned period body (fwd+bwd for training), the
    embed/head stem, and the optimizer update, each compiled separately and
    scaled by its trip count, because XLA's cost model counts a while body
    exactly once (EXPERIMENTS.md §Methodology);
  * the collective inventory parsed from post-SPMD HLO (launch/hlo.py).

Usage:
  python -m repro.launch.dryrun --arch qwen2.5-14b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod-only|--single-pod-only]
  python -m repro.launch.dryrun --all --out results/dryrun
"""

import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import hw
from repro.configs import get_config, get_shape, grid
from repro.configs.base import ModelConfig, ShapeSpec
from repro.core.quant import serving_specs
from repro.dist.sharding import Sharder, make_sharder
from repro.launch.hlo import collective_summary, parse_collectives
from repro.launch.mesh import make_production_mesh, mesh_chips
from repro.launch.roofline import RooflineResult, model_flops
from repro.models import params as pspec
from repro.models.blocks import block_specs
from repro.models.inputs import input_specs
from repro.models.lm import LM, build_model
from repro.optim import AdamW, cosine_schedule
from repro.optim.adamw import TrainState, abstract_state
from repro.train.step import make_train_step

F32 = jnp.float32


# ---------------------------------------------------------------------------
# Artifact helpers
# ---------------------------------------------------------------------------


def _analyze(compiled) -> Dict[str, Any]:
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):  # jax < 0.5 wraps the dict in a list
        cost = cost[0] if cost else {}
    colls = parse_collectives(compiled.as_text())
    mem = compiled.memory_analysis()
    out = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "collectives": collective_summary(colls),
    }
    if mem is not None:
        out["memory"] = {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            "peak_est_bytes": int(mem.argument_size_in_bytes
                                  + mem.output_size_in_bytes
                                  + mem.temp_size_in_bytes
                                  - mem.alias_size_in_bytes),
        }
    return out


def _lower_compile(fn, args, in_shardings=None, out_shardings=None,
                   donate=(), mesh=None) -> Tuple[Any, Dict[str, Any]]:
    kwargs = {}
    if in_shardings is not None:
        kwargs["in_shardings"] = in_shardings
    if out_shardings is not None:
        kwargs["out_shardings"] = out_shardings
    if donate:
        kwargs["donate_argnums"] = donate
    jitted = jax.jit(fn, **kwargs)
    t0 = time.time()
    with mesh:
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    info = _analyze(compiled)
    info["compile_s"] = time.time() - t0
    return compiled, info


def _batch_shardings(sharder: Sharder, specs: Dict, axes: Dict):
    return {k: sharder.sharding(axes[k], specs[k].shape) for k in specs}


# ---------------------------------------------------------------------------
# Cell construction: full artifact + cost pieces per mode
# ---------------------------------------------------------------------------


def make_optimizer() -> AdamW:
    return AdamW(lr=cosine_schedule(3e-4, 200, 50_000))


def _abstract_x(cfg: ModelConfig, batch: int, seq: int):
    return jax.ShapeDtypeStruct((batch, seq, cfg.d_model), jnp.bfloat16)


def _positions(cfg: ModelConfig, batch: int, seq: int):
    if cfg.m_rope_sections:
        return jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32),
                                (batch, 3, seq))
    return jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), (batch, seq))


def build_train_cell(model: LM, shape: ShapeSpec, mesh, sharder: Sharder,
                     pieces: bool):
    cfg = model.cfg
    specs = model.param_specs()
    opt = make_optimizer()
    step_fn = make_train_step(model, opt, sharder)

    state_abs = abstract_state(specs)
    psh = sharder.param_shardings(specs)
    rep = sharder.sharding((), ())
    mvsh = psh
    if cfg.zero1:
        # ZeRO-1: only the optimizer state shards over the data axis; the
        # update step re-gathers params (GSPMD inserts the all-gather).
        from repro.dist.sharding import make_rules
        zrules = dict(make_rules(cfg, "train"))
        zrules["embed"] = ("data",)
        mvsh = Sharder(mesh, zrules).param_shardings(specs)
    state_sh = TrainState(params=psh, m=mvsh, v=mvsh, step=rep)
    b_specs, b_axes = input_specs(cfg, shape)
    b_sh = _batch_shardings(sharder, b_specs, b_axes)

    _, full = _lower_compile(
        step_fn, (state_abs, b_specs), in_shardings=(state_sh, b_sh),
        out_shardings=(state_sh, None), donate=(0,), mesh=mesh)
    result = {"full": full}
    if not pieces:
        return result

    # ---- piece 1: one scanned period, fwd+bwd, x (n_periods * n_micro) ----
    B_micro = shape.global_batch // cfg.n_microbatches
    S = shape.seq_len
    period_specs = {f"p{i}": block_specs(cfg, kind, cross=cfg.is_encoder_decoder)
                    for i, kind in enumerate(cfg.layer_pattern)}
    pp_abs = pspec.tree_abstract(period_specs)
    pp_sh = sharder.param_shardings(period_specs)
    positions = _positions(cfg, B_micro, S)
    enc_abs = None
    if cfg.is_encoder_decoder:
        enc_abs = _abstract_x(cfg, B_micro, S // cfg.encoder_downsample)

    def period_loss(p_params, x, enc_out=None):
        y, _, aux, _ = model.period_apply(
            p_params, x, positions=positions, mode="train", sharder=sharder,
            enc_out=enc_out)
        if cfg.shard_residual_seq:
            y = sharder.constrain(y, "batch", "res_seq", None)
        return jnp.sum(y.astype(F32)) * 1e-6 + aux

    if cfg.remat != "none":  # match the real scan body: bwd re-gathers
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if cfg.remat == "dots" else None)
        period_loss = jax.checkpoint(period_loss, policy=policy)

    grad_args = (0, 1) if enc_abs is None else (0, 1, 2)
    period_fn = jax.value_and_grad(period_loss, argnums=grad_args)
    x_abs = _abstract_x(cfg, B_micro, S)
    x_sh = sharder.sharding(("batch", "seq", None), x_abs.shape)
    args = (pp_abs, x_abs) + ((enc_abs,) if enc_abs is not None else ())
    in_sh = (pp_sh, x_sh) + ((x_sh,) if enc_abs is not None else ())
    # grads carry the params' (FSDP) sharding -> reduce-scatter, not
    # all-reduce, exactly as the real scan accumulates them
    grad_sh = (pp_sh, x_sh) + ((x_sh,) if enc_abs is not None else ())
    _, piece = _lower_compile(period_fn, args, in_shardings=in_sh,
                              out_shardings=(rep, grad_sh), mesh=mesh)
    result["pieces"] = {"period": dict(
        piece, mult=cfg.n_periods * cfg.n_microbatches)}

    # ---- piece 2: stem (embed + head + loss) fwd+bwd, x n_micro ------------
    stem_names = [k for k in specs if k not in
                  ("blocks", "enc_blocks", "enc_final_norm")]
    stem_specs = {k: specs[k] for k in stem_names}
    tok_abs = jax.ShapeDtypeStruct((B_micro, S + 1), jnp.int32)
    tok_sh = sharder.sharding(("batch", "seq"), tok_abs.shape)

    def stem_loss(s_params, tokens, h_final):
        return model.stem_train(s_params, tokens, h_final, sharder)

    stem_fn = jax.value_and_grad(stem_loss, argnums=(0, 2))
    stem_sh = sharder.param_shardings(stem_specs)
    _, piece = _lower_compile(
        stem_fn, (pspec.tree_abstract(stem_specs), tok_abs, x_abs),
        in_shardings=(stem_sh, tok_sh, x_sh),
        out_shardings=(rep, (stem_sh, x_sh)), mesh=mesh)
    result["pieces"]["stem"] = dict(piece, mult=cfg.n_microbatches)

    # ---- piece 3: optimizer update, x 1 ------------------------------------
    def opt_fn(state, grads):
        from repro.optim.adamw import adamw_update
        new_state, _ = adamw_update(opt, state, grads)
        return new_state

    _, piece = _lower_compile(
        opt_fn, (state_abs, state_abs["params"]),
        in_shardings=(state_sh, psh), out_shardings=state_sh, mesh=mesh)
    result["pieces"]["optimizer"] = dict(piece, mult=1)

    # ---- encoder piece (whisper) -------------------------------------------
    if cfg.is_encoder_decoder:
        eb = {"p0": block_specs(cfg, "attn")}
        Se = S // cfg.encoder_downsample
        pos_e = _positions(cfg, B_micro, Se)

        def enc_loss(p_params, x):
            y, _, aux, _ = model.period_apply(
                p_params, x, positions=pos_e, mode="train", sharder=sharder,
                causal=False)
            return jnp.sum(y.astype(F32)) * 1e-6 + aux

        enc_fn = jax.value_and_grad(enc_loss, argnums=(0, 1))
        xe_abs = _abstract_x(cfg, B_micro, Se)
        _, piece = _lower_compile(
            enc_fn, (pspec.tree_abstract(eb), xe_abs),
            in_shardings=(sharder.param_shardings(eb),
                          sharder.sharding(("batch", "seq", None),
                                           xe_abs.shape)),
            mesh=mesh)
        result["pieces"]["encoder"] = dict(
            piece, mult=cfg.n_encoder_layers * cfg.n_microbatches)
    return result


def build_serve_cell(model: LM, shape: ShapeSpec, mesh, sharder: Sharder,
                     pieces: bool, int8: bool = False):
    cfg = model.cfg
    specs = serving_specs(model.param_specs(), int8=int8)
    p_abs = pspec.tree_abstract(specs)
    psh = sharder.param_shardings(specs)
    B, S = shape.global_batch, shape.seq_len
    result: Dict[str, Any] = {}

    if shape.mode == "prefill":
        b_specs, b_axes = input_specs(cfg, shape)
        b_sh = _batch_shardings(sharder, b_specs, b_axes)

        def prefill_fn(params, batch):
            return model.prefill(params, batch, sharder, max_len=S)

        _, full = _lower_compile(prefill_fn, (p_abs, b_specs),
                                 in_shardings=(psh, b_sh), mesh=mesh)
        result["full"] = full
        if pieces:
            positions = _positions(cfg, B, S)
            period_specs = {f"p{i}": block_specs(cfg, kind, cross=cfg.is_encoder_decoder)
                            for i, kind in enumerate(cfg.layer_pattern)}
            enc_abs = (_abstract_x(cfg, B, S // cfg.encoder_downsample)
                       if cfg.is_encoder_decoder else None)

            def period_fwd(p_params, x, enc_out=None):
                y, cache, _, _ = model.period_apply(
                    p_params, x, positions=positions, mode="prefill",
                    sharder=sharder, enc_out=enc_out, max_len=S)
                return y, cache

            x_abs = _abstract_x(cfg, B, S)
            x_sh = sharder.sharding(("batch", "seq", None), x_abs.shape)
            args = (pspec.tree_abstract(period_specs), x_abs) + (
                (enc_abs,) if enc_abs is not None else ())
            in_sh = (sharder.param_shardings(period_specs), x_sh) + (
                (x_sh,) if enc_abs is not None else ())
            _, piece = _lower_compile(period_fwd, args, in_shardings=in_sh,
                                      mesh=mesh)
            result["pieces"] = {"period": dict(piece, mult=cfg.n_periods)}

            tok_abs = jax.ShapeDtypeStruct((B, S), jnp.int32)

            def stem_fwd(s_params, tokens, h_final):
                return model.stem_serve(s_params, tokens, h_final, sharder)

            stem_names = [k for k in specs if k not in
                          ("blocks", "enc_blocks", "enc_final_norm")]
            stem_specs = {k: specs[k] for k in stem_names}
            _, piece = _lower_compile(
                stem_fwd, (pspec.tree_abstract(stem_specs), tok_abs, x_abs),
                in_shardings=(sharder.param_shardings(stem_specs),
                              sharder.sharding(("batch", "seq"), (B, S)),
                              x_sh),
                mesh=mesh)
            result["pieces"]["stem"] = dict(piece, mult=1)
        return result

    # ---- decode -------------------------------------------------------------
    cache_specs = model.cache_specs(B, S)
    cache_abs = pspec.tree_abstract(cache_specs)
    cache_sh = sharder.param_shardings(cache_specs)
    tok_abs = jax.ShapeDtypeStruct((B,), jnp.int32)
    tok_sh = sharder.sharding(("batch",), (B,))

    def decode_fn(params, cache, tokens):
        return model.decode_step(params, cache, tokens, sharder)

    _, full = _lower_compile(
        decode_fn, (p_abs, cache_abs, tok_abs),
        in_shardings=(psh, cache_sh, tok_sh),
        out_shardings=(cache_sh, None), donate=(1,), mesh=mesh)
    result["full"] = full
    if pieces:
        period_specs = {f"p{i}": block_specs(cfg, kind, cross=cfg.is_encoder_decoder)
                        for i, kind in enumerate(cfg.layer_pattern)}
        period_specs = serving_specs(period_specs, int8=int8)
        pc_specs = model.period_cache_specs(B, S)
        lengths = jnp.full((B,), S - 1, jnp.int32)
        positions = (lengths[:, None] if not cfg.m_rope_sections
                     else jnp.broadcast_to(lengths[:, None, None], (B, 3, 1)))

        def period_step(p_params, x, p_cache):
            y, new_c, _, _ = model.period_apply(
                p_params, x, positions=positions, lengths=lengths,
                mode="decode", sharder=sharder, p_cache=p_cache)
            return y, new_c

        x_abs = _abstract_x(cfg, B, 1)
        x_sh = sharder.sharding(("batch", None, None), x_abs.shape)
        _, piece = _lower_compile(
            period_step,
            (pspec.tree_abstract(period_specs), x_abs,
             pspec.tree_abstract(pc_specs)),
            in_shardings=(sharder.param_shardings(period_specs), x_sh,
                          sharder.param_shardings(pc_specs)),
            donate=(2,), mesh=mesh)
        result["pieces"] = {"period": dict(piece, mult=cfg.n_periods)}

        def stem_step(s_params, tokens, h_final):
            return model.stem_serve(s_params, tokens, h_final, sharder,
                                    last_only=True)

        stem_names = [k for k in specs if k not in
                      ("blocks", "enc_blocks", "enc_final_norm")]
        stem_specs = {k: specs[k] for k in stem_names}
        tok2 = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        _, piece = _lower_compile(
            stem_step, (pspec.tree_abstract(stem_specs), tok2, x_abs),
            in_shardings=(sharder.param_shardings(stem_specs),
                          sharder.sharding(("batch", None), (B, 1)), x_sh),
            mesh=mesh)
        result["pieces"]["stem"] = dict(piece, mult=1)
    return result


# ---------------------------------------------------------------------------
# Cell driver
# ---------------------------------------------------------------------------


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             pieces: bool = True, int8: bool = False,
             kv_int8: bool = False,
             overrides: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    cfg = get_config(arch)
    if kv_int8:
        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = get_shape(shape_name)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cell: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "int8": int8, "kv_int8": kv_int8, "overrides": overrides or {},
    }
    runs, reason = cfg.runs_shape(shape)
    if not runs:
        cell.update(ok=None, skip=reason)
        return cell
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        model = build_model(cfg)
        sharder = make_sharder(cfg, mesh, shape.mode)
        t0 = time.time()
        if shape.mode == "train":
            result = build_train_cell(model, shape, mesh, sharder, pieces)
        else:
            result = build_serve_cell(model, shape, mesh, sharder, pieces,
                                      int8=int8)
        cell.update(result)
        cell["ok"] = True
        cell["wall_s"] = time.time() - t0
        cell["chips"] = mesh_chips(mesh)
        if pieces and "pieces" in result:
            cell["roofline"] = summarize_roofline(model, shape, cell)
    except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
        cell["ok"] = False
        cell["error"] = f"{type(e).__name__}: {e}"
        cell["traceback"] = traceback.format_exc()[-4000:]
    return cell


def summarize_roofline(model: LM, shape: ShapeSpec, cell: Dict) -> Dict:
    chips = cell["chips"]
    flops = bytes_ = coll = coll_op = 0.0
    for name, piece in cell["pieces"].items():
        m = piece["mult"]
        flops += piece["flops"] * m
        bytes_ += piece["bytes"] * m
        coll += piece["collectives"]["ici_bytes"] * m
        coll_op += piece["collectives"]["operand_bytes"] * m
    mf = model_flops(model, shape)
    rr = RooflineResult(
        arch=cell["arch"], shape=shape.name, mesh=cell["mesh"], chips=chips,
        flops_device=flops, bytes_device=bytes_,
        coll_ici_bytes_device=coll, coll_operand_bytes_device=coll_op,
        model_flops_total=mf).finalize()
    return rr.row()


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true",
                    help="use the 2x16x16 multi-pod mesh")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-pieces", action="store_true")
    ap.add_argument("--int8", action="store_true",
                    help="int8 weight storage for serve cells")
    ap.add_argument("--kv-int8", action="store_true",
                    help="int8 kv-cache storage")
    # §Perf levers
    ap.add_argument("--micro", type=int, default=0,
                    help="override n_microbatches")
    ap.add_argument("--no-tp", action="store_true",
                    help="replicate weights at train (pure DP)")
    ap.add_argument("--zero1", action="store_true",
                    help="shard only optimizer state over data")
    ap.add_argument("--shard-res", action="store_true",
                    help="shard the residual scan carry's seq dim")
    ap.add_argument("--sp", action="store_true",
                    help="Megatron-style sequence parallelism at train")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--tag", default="",
                    help="suffix for the result file name")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    overrides: Dict[str, Any] = {}
    if args.micro:
        overrides["n_microbatches"] = args.micro
    if args.no_tp:
        overrides["train_tp"] = False
    if args.zero1:
        overrides["zero1"] = True
    if args.shard_res:
        overrides["shard_residual_seq"] = True
    if args.sp:
        overrides["seq_parallel"] = True
    if args.no_fsdp:
        overrides["fsdp"] = False

    os.makedirs(args.out, exist_ok=True)
    todo = []
    if args.all:
        for cfg, shape, _, _ in grid():
            todo.append((cfg.name, shape.name))
    else:
        todo.append((args.arch, args.shape))

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    for arch, shape_name in todo:
        for multi_pod in meshes:
            tag = f"{arch}_{shape_name}_{'multi' if multi_pod else 'single'}"
            if args.int8:
                tag += "_int8"
            if args.kv_int8:
                tag += "_kv8"
            if args.tag:
                tag += "_" + args.tag
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path):
                print(f"[skip existing] {tag}")
                continue
            print(f"[dryrun] {tag} ...", flush=True)
            cell = run_cell(arch, shape_name, multi_pod,
                            pieces=not args.no_pieces and not multi_pod,
                            int8=args.int8, kv_int8=args.kv_int8,
                            overrides=overrides or None)
            # strip unserializable / huge fields
            with open(path, "w") as f:
                json.dump(cell, f, indent=1, default=str)
            status = cell.get("ok")
            extra = cell.get("error", "") or cell.get("skip", "")
            print(f"[dryrun] {tag}: ok={status} "
                  f"wall={cell.get('wall_s', 0):.1f}s {extra}", flush=True)


if __name__ == "__main__":
    main()
