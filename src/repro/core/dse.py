"""Design-space exploration for the fused RNN kernel.

The paper's central systems claim (§3.3, Table 7): exposing the loop
tiling/unrolling parameters (hv, hu, rv, ru) and searching them per problem
size yields consistent utilization across DeepBench, unlike a
fixed-geometry MVM engine (Brainwave's hv=400, rv=40, ru=6) that fragments
2-D.  On TPU the parameter space collapses to:

  rv  — lane vectorization: fixed at 128 by the MXU/VPU geometry,
  bh  — the H-tile (hv x hu analogue): the kernel's BlockSpec row count,
  ru  — reduction unrolling: subsumed by the MXU's internal systolic
         reduction over the contraction dim,

so the search is over ``bh`` under a VMEM-residency constraint, with an
analytic latency model built from the hardware constants in repro.hw.
VMEM is counted in padded (sublane, 128-lane) tiles against
``hw.vmem_budget``, the ``vmem_limit_bytes`` every kernel requests, and
only tiles Mosaic accepts are proposed (128-lane multiples, or a whole
axis).  ``fragmentation`` reproduces Fig. 4's utilization comparison.

PR 9 widens the same :class:`Plan` record to the other three Pallas
kernels so ``ServingPlan.tile_plans`` can carry every kernel's BlockSpec
geometry: ``bq``/``bk`` for flash_attention (query/KV tile rows, searched
by :func:`best_attn_plan`) and ``bm``/``bn``/``bk`` for matmul_int8
(output/contraction tiles, :func:`best_matmul_plan`); ``rwkv6_step``
gets a head-tile search (:func:`best_rwkv_plan`).  Fields a given
kernel does not use stay at their zero default and are stripped from the
serialized form by :func:`plan_dict`, so recurrent-cell plan dicts keep
the exact key set the committed BENCH trajectories embed.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from repro import hw
from repro.core.cells import RNNCellConfig

MXU = 128
SUBLANE = 8

# pipeline overhead per grid step (issue + reduction drain): the
# 2 + log2(lanes) + 1 cycles of paper §4.1, at ~1 GHz
_STEP_OVERHEAD_S = (2 + 7 + 1) / 0.94e9


@dataclasses.dataclass(frozen=True)
class Plan:
    bh: int                   # H-tile rows per grid step
    n_tiles: int              # grid steps (H / bh for the RNN kernels)
    vmem_bytes: int           # working set claimed by the BlockSpecs
    resident: bool            # working set fits the VMEM budget
    step_latency_s: float     # modeled per-timestep latency
    util: float               # useful MACs / padded MACs
    bound: str                # "compute" | "vmem" | "hbm" | "latency"
    # --- per-kernel tile fields (zero = unused by this kernel) ----------
    bq: int = 0               # flash_attention: query rows per grid step
    bk: int = 0               # flash_attention KV tile / matmul K tile
    bm: int = 0               # matmul_int8: output rows per grid step
    bn: int = 0               # matmul_int8: output cols per grid step
    persistent: bool = False  # fused decode keeps weights VMEM-resident
    #                           across the device loop (requires n_tiles=1)


# Plan fields stripped by plan_dict() when at their unused default, so a
# recurrent-cell plan serializes to the same key set as before PR 9.
_OPTIONAL_PLAN_FIELDS = ("bq", "bk", "bm", "bn", "persistent")


def plan_dict(plan: Plan) -> Dict[str, object]:
    """Compact JSON form of a Plan: optional tile fields at their unused
    defaults are dropped (``tile_plans`` entries embedded in committed
    BENCH cells predate them), and ``bh: 0`` likewise vanishes for the
    attention/matmul plans that have no H tile."""
    d = dataclasses.asdict(plan)
    for name in _OPTIONAL_PLAN_FIELDS:
        if not d[name]:
            del d[name]
    if not d["bh"]:
        del d["bh"]
    return d


def snap_tile(dim: int, tile: int) -> int:
    """Largest divisor of ``dim`` that is <= ``tile`` (always >= 1).

    The ops wrappers snap a requested tile to the nearest feasible
    BlockSpec geometry instead of asserting, so a plan autotuned for one
    shape degrades gracefully on a non-divisible one."""
    dim, tile = int(dim), int(tile)
    tile = max(1, min(tile, dim))
    while dim % tile:
        tile -= 1
    return tile


def _pad(n: int, m: int) -> int:
    return -(-n // m) * m


# sublane rows of one (rows, 128) VMEM tile, by element size in bytes
_SUBLANES = {4: SUBLANE, 2: 2 * SUBLANE, 1: 4 * SUBLANE}


def _tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of a (rows, cols) block as Mosaic lays it out: rows
    padded to the dtype's sublane count, cols to the 128-lane width."""
    return _pad(rows, _SUBLANES[itemsize]) * _pad(cols, MXU) * itemsize


def tile_vmem_bytes(cfg: RNNCellConfig, bh: int, *,
                    max_batch: Optional[int] = None) -> int:
    """VMEM bytes the fused RNN kernel claims per grid step, in padded
    tiles: the per-gate weight blocks (single-buffered when one tile
    covers H, double-buffered when streamed), their bf16 widening when
    stored int8, the h/c state and its blocks, the x/y blocks and the
    per-gate scale/bias rows.

    ``max_batch`` overrides ``cfg.batch``: the serving engine decodes
    ``max_batch`` slots per step, so the h/c state and io buffers scale
    with it even though the DeepBench cell configs say batch 1."""
    g, H, D = cfg.n_gates, cfg.hidden, cfg.d
    B = cfg.batch if max_batch is None else max_batch
    wbytes = 1 if cfg.precision in ("int8", "blocked_fp") else 2
    n_tiles = H // bh
    w_block = g * (_tile_bytes(D, bh, wbytes) + _tile_bytes(H, bh, wbytes))
    weights = w_block * (1 if n_tiles == 1 else 2)
    widened = (g * (_tile_bytes(D, bh, 2) + _tile_bytes(H, bh, 2))
               if wbytes == 1 else 0)
    # h parity double buffer + c scratch, h0/c0 blocks, hT/cT blocks
    state = (3 * _tile_bytes(B, H, 4) + 4 * _tile_bytes(B, H, 4)
             + 4 * _tile_bytes(B, bh, 4))
    io = 2 * _tile_bytes(B, D, 2) + 2 * _tile_bytes(B, bh, 2)
    n_vec = 3 if cfg.cell == "lstm" else 4           # s_x, s_h, biases
    scales = 2 * n_vec * _tile_bytes(g, bh, 4)
    return weights + widened + state + io + scales


def plan_metrics(cfg: RNNCellConfig, bh: int,
                 spec: hw.HardwareSpec = hw.DEFAULT, *,
                 max_batch: Optional[int] = None) -> Plan:
    """Score one tile choice.  ``max_batch`` threads the *serving* batch
    dimension through the model: the engine runs a batched decode over
    ``max_batch`` slots, so both the compute bound (sublane-padded batch)
    and the VMEM working set (h/c state, io) must be scored at the batch
    the engine actually runs — a tile that is VMEM-resident at batch 1
    can spill at batch 64, flipping the best plan to smaller tiles."""
    g, H, D = cfg.n_gates, cfg.hidden, cfg.d
    B = cfg.batch if max_batch is None else max_batch
    R = D + H
    n_tiles = H // bh
    vmem = tile_vmem_bytes(cfg, bh, max_batch=max_batch)
    resident = vmem <= hw.vmem_budget(spec)

    # --- utilization: 1-D fragmentation on R only (Fig. 4b).  The batch-
    # padding penalty of the MXU is a *latency* effect (modeled below),
    # not a fragmentation effect — the paper's Fig. 4 compares tiling
    # geometries at fixed batch.
    true_macs = g * H * R
    padded_macs = g * _pad(H, MXU) * _pad(R, MXU)
    util = true_macs / padded_macs

    # --- per-step time: three bounds
    # (1) MXU compute with sublane-padded batch,
    # (2) VMEM weight streaming — a matvec reads every resident weight
    #     byte per step, so small-batch serving is VMEM-bandwidth-bound
    #     (the paper's §4.2 compute:memory-read ratio argument),
    # (3) HBM streaming when the weights don't fit VMEM.
    mul_peak = (spec.peak_int8_ops if cfg.precision in ("int8", "blocked_fp")
                else spec.peak_bf16_flops)
    compute_s = 2.0 * padded_macs * max(B, SUBLANE) / mul_peak
    vmem_s = cfg.weight_bytes() / spec.vmem_bw
    hbm_s = 0.0 if resident else cfg.weight_bytes() / spec.hbm_bw
    # fixed pipeline overhead per tile (grid step issue + reduction drain)
    overhead_s = n_tiles * _STEP_OVERHEAD_S
    slowest = max(compute_s, vmem_s, hbm_s)
    lat = slowest + overhead_s
    # explicit comparison (a dict keyed by the times would merge entries
    # whenever two bounds are numerically equal); ties break toward the
    # earlier term in compute > vmem > hbm order
    if slowest == compute_s:
        bound = "compute"
    elif slowest == vmem_s:
        bound = "vmem"
    else:
        bound = "hbm"
    if overhead_s > slowest:
        bound = "latency"
    return Plan(bh=bh, n_tiles=n_tiles, vmem_bytes=vmem, resident=resident,
                step_latency_s=lat, util=util, bound=bound)


def candidate_tiles(H: int) -> List[int]:
    """H tiles the TPU lays out natively: power-of-two multiples of the
    128-lane width that divide H, plus H itself (always legal: a block
    spanning a whole axis needs no alignment)."""
    c = []
    bh = MXU
    while bh < H:
        if H % bh == 0:
            c.append(bh)
        bh *= 2
    return c + [H]


def search(cfg: RNNCellConfig, spec: hw.HardwareSpec = hw.DEFAULT, *,
           max_batch: Optional[int] = None) -> List[Plan]:
    return [plan_metrics(cfg, bh, spec, max_batch=max_batch)
            for bh in candidate_tiles(cfg.hidden)]


def best_plan(cfg: RNNCellConfig, spec: hw.HardwareSpec = hw.DEFAULT, *,
              max_batch: Optional[int] = None) -> Plan:
    plans = [p for p in search(cfg, spec, max_batch=max_batch)
             if p.vmem_bytes <= hw.vmem_budget(spec)]
    if not plans:  # weights can never be resident; stream with big tiles
        plans = search(cfg, spec, max_batch=max_batch)
    return min(plans, key=lambda p: p.step_latency_s)


# ---------------------------------------------------------------------------
# rwkv6_step head-tile search
# ---------------------------------------------------------------------------


def candidate_head_tiles(n_heads: int) -> List[int]:
    """Head tiles the rwkv6_step BlockSpecs allow on a TPU: multiples of
    the 8-row sublane count that divide the head count, plus all heads."""
    return [t for t in range(SUBLANE, n_heads, SUBLANE)
            if n_heads % t == 0] + [n_heads]


def rwkv_tile_vmem_bytes(head_tile: int, head_dim: int, batch: int) -> int:
    """VMEM per rwkv6_step grid step: the (B, bh, K, V) f32 state block in
    and out (double-buffered) plus four state-sized temporaries (k v^T,
    the bonus read, r * read, the decayed state), with V padded to 128
    lanes; and the per-token r/k/w/v/y and bonus blocks."""
    state = batch * head_tile * _tile_bytes(head_dim, head_dim, 4)
    step = batch * _tile_bytes(head_tile, head_dim, 4)
    return 8 * state + 2 * 5 * step + 2 * _tile_bytes(head_tile, head_dim, 4)


def rwkv_plan_metrics(n_heads: int, head_dim: int, head_tile: int,
                      batch: int, spec: hw.HardwareSpec = hw.DEFAULT
                      ) -> Plan:
    """Score one head tile.  ``bh`` is in hidden units (head_tile x
    head_dim), the convention ``kernels.rwkv_step.ops.head_tile`` reads."""
    n_tiles = n_heads // head_tile
    vmem = rwkv_tile_vmem_bytes(head_tile, head_dim, batch)
    state_bytes = 2 * batch * n_heads * head_dim * head_dim * 4
    hbm_s = state_bytes / spec.hbm_bw
    overhead_s = n_tiles * _STEP_OVERHEAD_S
    return Plan(bh=head_tile * head_dim, n_tiles=n_tiles, vmem_bytes=vmem,
                resident=vmem <= hw.vmem_budget(spec),
                step_latency_s=hbm_s + overhead_s, util=1.0,
                bound="latency" if overhead_s > hbm_s else "hbm")


def best_rwkv_plan(n_heads: int, head_dim: int, batch: int,
                   spec: hw.HardwareSpec = hw.DEFAULT) -> Plan:
    plans = [rwkv_plan_metrics(n_heads, head_dim, t, batch, spec)
             for t in candidate_head_tiles(n_heads)]
    feasible = [p for p in plans if p.resident] or plans[:1]
    return min(feasible, key=lambda p: p.step_latency_s)


# ---------------------------------------------------------------------------
# flash_attention tile search (bq x bk)
# ---------------------------------------------------------------------------


def candidate_attn_tiles(seq_q: int, seq_kv: int) -> List[Tuple[int, int]]:
    """(bq, bk) grid: power-of-two divisors, bq from the sublane count up,
    bk from one lane row (128) up — the shapes the TPU tiles natively."""
    bqs = [t for t in (8, 16, 32, 64, 128, 256)
           if t <= seq_q and seq_q % t == 0] or [snap_tile(seq_q, 256)]
    bks = [t for t in (128, 256, 512, 1024)
           if t <= seq_kv and seq_kv % t == 0] or [snap_tile(seq_kv, 512)]
    return [(bq, bk) for bq in bqs for bk in bks]


def attn_tile_vmem_bytes(bq: int, bk: int, head_dim: int) -> int:
    """VMEM per flash grid step: q tile + double-buffered k/v tiles +
    f32 score block + f32 accumulator/softmax-state scratch + out tile."""
    q = bq * head_dim * 2
    kv = 2 * (2 * bk * head_dim * 2)      # k and v, double-buffered
    scores = bq * bk * 4
    acc = bq * head_dim * 4 + 2 * bq * 4  # acc + (m, l)
    out = bq * head_dim * 2
    return q + kv + scores + acc + out


def attn_plan_metrics(seq_q: int, seq_kv: int, head_dim: int,
                      bq: int, bk: int,
                      spec: hw.HardwareSpec = hw.DEFAULT, *,
                      n_heads: int = 1, batch: int = 1) -> Plan:
    """Score one flash_attention tile choice (QK^T + AV roofline)."""
    ntq, ntk = seq_q // bq, seq_kv // bk
    n_steps = batch * n_heads * ntq * ntk
    vmem = attn_tile_vmem_bytes(bq, bk, head_dim)
    resident = vmem <= hw.vmem_budget(spec)

    true_macs = 2 * seq_q * seq_kv * head_dim          # QK^T and AV
    padded_macs = (2 * ntq * ntk * _pad(bq, SUBLANE)
                   * _pad(bk, MXU) * _pad(head_dim, MXU))
    util = true_macs / padded_macs

    compute_s = 2.0 * padded_macs * batch * n_heads / spec.peak_bf16_flops
    # K/V stream once per query tile; q and out stream once
    kv_bytes = batch * n_heads * ntq * seq_kv * head_dim * 2 * 2
    qo_bytes = batch * n_heads * seq_q * head_dim * 2 * 2
    hbm_s = (kv_bytes + qo_bytes) / spec.hbm_bw
    overhead_s = n_steps * _STEP_OVERHEAD_S
    slowest = max(compute_s, hbm_s)
    bound = "compute" if slowest == compute_s else "hbm"
    if overhead_s > slowest:
        bound = "latency"
    return Plan(bh=0, n_tiles=n_steps, vmem_bytes=vmem, resident=resident,
                step_latency_s=slowest + overhead_s, util=util, bound=bound,
                bq=bq, bk=bk)


def best_attn_plan(seq_q: int, seq_kv: int, head_dim: int,
                   spec: hw.HardwareSpec = hw.DEFAULT, *,
                   n_heads: int = 1, batch: int = 1) -> Plan:
    plans = [attn_plan_metrics(seq_q, seq_kv, head_dim, bq, bk, spec,
                               n_heads=n_heads, batch=batch)
             for bq, bk in candidate_attn_tiles(seq_q, seq_kv)]
    feasible = [p for p in plans if p.resident] or plans
    return min(feasible, key=lambda p: p.step_latency_s)


# ---------------------------------------------------------------------------
# matmul_int8 tile search (bm x bn x bk)
# ---------------------------------------------------------------------------


def candidate_mm_tiles(M: int, N: int, K: int) -> List[Tuple[int, int, int]]:
    bms = [t for t in (8, 32, 64, 128, 256)
           if t <= M and M % t == 0] or [snap_tile(M, 256)]
    bns = [t for t in (128, 256, 512)
           if t <= N and N % t == 0] or [snap_tile(N, 256)]
    bks = [t for t in (128, 256, 512)
           if t <= K and K % t == 0] or [snap_tile(K, 512)]
    return [(bm, bn, bk) for bm in bms for bn in bns for bk in bks]


def matmul_tile_vmem_bytes(bm: int, bn: int, bk: int) -> int:
    """VMEM per matmul grid step: double-buffered x/w tiles + f32
    accumulator + out tile + per-column scale/bias row."""
    x = 2 * bm * bk * 2
    w = 2 * bk * bn * 1
    acc = bm * bn * 4
    out = bm * bn * 2
    scale = 2 * bn * 4
    return x + w + acc + out + scale


def matmul_plan_metrics(M: int, N: int, K: int,
                        bm: int, bn: int, bk: int,
                        spec: hw.HardwareSpec = hw.DEFAULT) -> Plan:
    """Score one W8A16 matmul tile choice.  The kernel widens int8
    weights to bf16 before the MXU dot, so compute runs at bf16 peak;
    the win from int8 is the halved weight stream."""
    ntm, ntn, ntk = M // bm, N // bn, K // bk
    n_steps = ntm * ntn * ntk
    vmem = matmul_tile_vmem_bytes(bm, bn, bk)
    resident = vmem <= hw.vmem_budget(spec)

    true_macs = M * N * K
    padded_macs = (n_steps * _pad(bm, SUBLANE)
                   * _pad(bn, MXU) * _pad(bk, MXU))
    util = true_macs / padded_macs

    compute_s = 2.0 * padded_macs / spec.peak_bf16_flops
    # weights stream once per m-tile, activations once per n-tile
    hbm_bytes = ntm * K * N * 1 + ntn * M * K * 2 + M * N * 2
    hbm_s = hbm_bytes / spec.hbm_bw
    overhead_s = n_steps * _STEP_OVERHEAD_S
    slowest = max(compute_s, hbm_s)
    bound = "compute" if slowest == compute_s else "hbm"
    if overhead_s > slowest:
        bound = "latency"
    return Plan(bh=0, n_tiles=n_steps, vmem_bytes=vmem, resident=resident,
                step_latency_s=slowest + overhead_s, util=util, bound=bound,
                bk=bk, bm=bm, bn=bn)


def best_matmul_plan(M: int, N: int, K: int,
                     spec: hw.HardwareSpec = hw.DEFAULT) -> Plan:
    plans = [matmul_plan_metrics(M, N, K, bm, bn, bk, spec)
             for bm, bn, bk in candidate_mm_tiles(M, N, K)]
    feasible = [p for p in plans if p.resident] or plans
    return min(feasible, key=lambda p: p.step_latency_s)


# ---------------------------------------------------------------------------
# Fig. 4: fragmentation of MVM-tiled vs loop-based designs
# ---------------------------------------------------------------------------


def utilization_loop(H: int, R: int, rv: int = MXU, ru: int = 1) -> float:
    """Loop-based design: 1-D fragmentation on the reduction dim only."""
    return R / _pad(R, rv * ru)


def utilization_mvm(H: int, R: int, hv: int = 400, rv: int = 40,
                    ru: int = 6) -> float:
    """Brainwave-style tiled MVM: 2-D fragmentation on H and R
    (hv/rv/ru defaults = BW's Stratix-10 configuration, Table 7)."""
    return (H / _pad(H, hv)) * (R / _pad(R, rv * ru))


def fragmentation(H: int, D: Optional[int] = None) -> dict:
    R = H + (D if D is not None else H)
    return {
        "H": H, "R": R,
        "util_loop": utilization_loop(H, R),
        "util_mvm_bw": utilization_mvm(H, R),
    }
