"""Configuration dataclasses for models, input shapes, and runs.

Every assigned architecture is a :class:`ModelConfig` in its own module under
``repro.configs``; the registry in ``repro.configs.__init__`` maps the public
``--arch`` ids onto them.  Shapes (the four assigned input-shape cells) are
:class:`ShapeSpec` instances shared by all LM-family architectures.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple

# ---------------------------------------------------------------------------
# Input shapes
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    """One input-shape cell from the assignment grid."""

    name: str
    seq_len: int
    global_batch: int
    mode: str  # "train" | "prefill" | "decode"

    @property
    def tokens(self) -> int:
        return self.seq_len * self.global_batch


TRAIN_4K = ShapeSpec("train_4k", 4_096, 256, "train")
PREFILL_32K = ShapeSpec("prefill_32k", 32_768, 32, "prefill")
DECODE_32K = ShapeSpec("decode_32k", 32_768, 128, "decode")
LONG_500K = ShapeSpec("long_500k", 524_288, 1, "decode")

SHAPES = {s.name: s for s in (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)}


# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25
    # Token group size for GShard-style dispatch; capacity is computed per
    # group so the one-hot dispatch tensors stay bounded.
    group_size: int = 512
    router_aux_coef: float = 0.01


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """Mamba2/SSD-style selective state space head block (see DESIGN.md for
    the adaptation from Mamba1's per-(channel, state) decay to SSD's
    per-head scalar decay, which admits a TPU-friendly chunked form)."""

    d_state: int = 16
    expand: int = 2
    head_dim: int = 64
    conv_width: int = 4
    chunk: int = 128


@dataclasses.dataclass(frozen=True)
class RWKVConfig:
    head_dim: int = 64          # key/value dim per wkv head
    chunk: int = 128            # chunked-recurrence block length
    ffn_mult: float = 3.5


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """A single architecture.

    ``layer_pattern`` gives one *period* of the layer stack; the stack is
    ``layer_pattern * (n_layers // len(layer_pattern))``.  Scanning over the
    layer stack happens at period granularity so heterogeneous stacks
    (gemma2 local/global alternation, gemma3 5:1, hymba) still admit stacked
    parameters.
    Entries: "attn" (global), "local" (sliding window), "swa_ssm"
    (parallel sliding-window attention + SSM heads, hymba), "rwkv".
    """

    name: str
    family: str                 # dense | moe | rwkv | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0           # 0 -> d_model // n_heads
    layer_pattern: Tuple[str, ...] = ("attn",)

    # --- attention flavour -------------------------------------------------
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    local_window: int = 0               # sliding-window size for "local"
    attn_softcap: float = 0.0           # gemma2 logit soft-capping
    final_softcap: float = 0.0          # gemma2 final-logit soft-capping
    qk_norm: bool = False               # gemma3 / qwen3 style
    m_rope_sections: Tuple[int, ...] = ()  # qwen2-vl M-RoPE (t, h, w) split

    # --- mlp flavour ---------------------------------------------------------
    mlp_gated: bool = True              # 3-matrix gated (llama-style) vs 2-matrix
    mlp_act: str = "silu"               # silu | gelu | relu_sq

    # --- mixture of experts -------------------------------------------------
    moe: Optional[MoEConfig] = None

    # --- recurrent families --------------------------------------------------
    ssm: Optional[SSMConfig] = None
    rwkv: Optional[RWKVConfig] = None

    # --- encoder/decoder (whisper) -------------------------------------------
    n_encoder_layers: int = 0
    encoder_downsample: int = 1         # conv-frontend stub stride

    # --- embedding / head ----------------------------------------------------
    tie_embeddings: bool = False
    scale_embeddings: bool = False      # gemma multiplies embeds by sqrt(d)
    vocab_pad_to: int = 256             # pad vocab so it shards over the mesh
    norm_eps: float = 1e-6

    # --- execution policy -----------------------------------------------------
    fsdp: bool = False                  # shard params over the data axis too
    remat: str = "full"                 # "none" | "full" | "dots"
    n_microbatches: int = 1             # grad-accumulation steps at train_4k
    attention_sharding: str = "auto"    # "heads" | "qseq" | "auto"
    # FLOPs-efficient attention block size chosen by the DSE when 0.
    attn_block: int = 0
    # --- perf-iteration levers (EXPERIMENTS.md §Perf) -------------------------
    train_tp: bool = True               # False: replicate weights; batch then
                                        # shards over (pod, data, model)
    zero1: bool = False                 # shard ONLY optimizer state over data
    shard_residual_seq: bool = False    # shard the scan carry's seq dim over
                                        # the model axis (sharded remat saves)
    seq_parallel: bool = False          # Megatron-SP: activations stay seq-
                                        # sharded over model through the whole
                                        # layer; attention gathers kv once

    # --- paper-technique hooks --------------------------------------------------
    # int8 weight storage for serving (the paper's mixed-precision scheme:
    # 8-bit storage/multiply, wider accumulate).
    serve_int8: bool = False
    kv_cache_dtype: str = "bf16"        # "bf16" | "int8"
    # storage dtype of the weights the model reads only at the compute
    # dtype (``ParamSpec.compute_only``: matmul weights, embedding, head);
    # norms, biases and other f32-read leaves stay f32 whatever it says
    weight_dtype: str = "float32"       # "float32" | "bfloat16"

    # ------------------------------------------------------------------ derived
    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim_

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim_

    @property
    def padded_vocab(self) -> int:
        p = self.vocab_pad_to
        return ((self.vocab_size + p - 1) // p) * p

    @property
    def period(self) -> int:
        return len(self.layer_pattern)

    @property
    def n_periods(self) -> int:
        assert self.n_layers % self.period == 0, (
            f"{self.name}: n_layers={self.n_layers} not divisible by "
            f"layer pattern period {self.period}")
        return self.n_layers // self.period

    @property
    def is_encoder_decoder(self) -> bool:
        return self.n_encoder_layers > 0

    @property
    def sub_quadratic(self) -> bool:
        """True when no layer needs a full-length KV cache *or* the config
        is explicitly long-context by construction.  Used by the shape-grid
        skip rule for ``long_500k`` (see DESIGN.md §Arch-applicability)."""
        kinds = set(self.layer_pattern)
        if kinds <= {"rwkv", "swa_ssm", "local"}:
            return True
        # Mostly-local stacks (gemma2/gemma3) are long-context by design:
        # global layers are a bounded fraction and the local layers cache
        # only their window.
        n_global = sum(1 for k in self.layer_pattern if k == "attn")
        return n_global < len(self.layer_pattern) and self.local_window > 0

    # ---------------------------------------------------------------- counting
    def param_count(self) -> int:
        """Exact parameter count (embedding included once if tied)."""
        d, L = self.d_model, self.n_layers
        total = self.padded_vocab * d  # embedding
        if not self.tie_embeddings:
            total += self.padded_vocab * d  # lm head
        for kind in self.layer_pattern * self.n_periods:
            total += self._block_params(kind)
        if self.is_encoder_decoder:
            # encoder self-attn blocks + decoder cross-attn additions
            total += self.n_encoder_layers * self._block_params("attn")
            total += L * self._attn_params()  # cross attention
        total += d  # final norm
        return total

    def _attn_params(self) -> int:
        d = self.d_model
        p = d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d
        if self.qkv_bias:
            p += self.q_dim + 2 * self.kv_dim
        return p

    def _mlp_params(self) -> int:
        n_mats = 3 if self.mlp_gated else 2
        return n_mats * self.d_model * self.d_ff

    def _block_params(self, kind: str) -> int:
        d = self.d_model
        norms = 2 * d
        if kind == "rwkv":
            a = self.rwkv or RWKVConfig()
            wkv = d * d * 4 + d * d  # r,k,v,g(+output) projections approx
            wkv += d * d             # w (decay) lora-ish projections
            ffn = 2 * d * int(d * a.ffn_mult)
            return wkv + ffn + norms
        if kind == "swa_ssm":
            s = self.ssm or SSMConfig()
            d_in = d * s.expand
            ssm = d * d_in * 2 + d_in * d  # in/out projections (x, z)
            ssm += d_in * (2 * s.d_state) + d_in  # B,C,dt projections-ish
            return self._attn_params() + ssm + self._mlp_params() + norms
        if self.moe is not None:
            router = d * self.moe.n_experts
            experts = self.moe.n_experts * 3 * d * self.d_ff
            return self._attn_params() + router + experts + norms
        return self._attn_params() + self._mlp_params() + norms

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top_k experts only)."""
        if self.moe is None:
            return self.param_count()
        total = self.param_count()
        experts_all = self.n_layers * self.moe.n_experts * 3 * self.d_model * self.d_ff
        experts_active = self.n_layers * self.moe.top_k * 3 * self.d_model * self.d_ff
        return total - experts_all + experts_active

    def model_flops(self, shape: ShapeSpec) -> float:
        """MODEL_FLOPS per step: 6*N*D for training, 2*N*D for inference
        (N = active params, D = tokens processed in the step)."""
        n_active = self.active_param_count()
        if shape.mode == "train":
            return 6.0 * n_active * shape.tokens
        if shape.mode == "prefill":
            return 2.0 * n_active * shape.tokens
        # decode: one token per sequence in the batch
        return 2.0 * n_active * shape.global_batch

    def runs_shape(self, shape: ShapeSpec) -> Tuple[bool, str]:
        """Shape-grid applicability rule.  Returns (runs, reason)."""
        if shape.name == "long_500k" and not self.sub_quadratic:
            return False, ("skip: pure full-attention stack; 500k-token decode "
                           "needs sub-quadratic attention (DESIGN.md)")
        return True, ""


def mxu_pad(n: int, align: int = 128) -> int:
    return ((n + align - 1) // align) * align
