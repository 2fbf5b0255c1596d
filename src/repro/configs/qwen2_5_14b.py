"""Qwen2.5-14B  [dense]  48L d_model=5120 40H (GQA kv=8) d_ff=13824
vocab=152064 — GQA, QKV bias, RoPE theta 1e6, RMSNorm eps 1e-5, untied head.
[arXiv:2412.15115; hf:Qwen/Qwen2.5-14B config.json]

The matmul weights are stored at bf16, the checkpoint's ``torch_dtype``
(29.5 GB in all, 7.4 GB a chip over four); norms and biases stay f32.
40 query heads do not divide the 16-way model axis, so attention activations
are sequence-sharded ("qseq") while the projection weights stay flat-sharded
(5120 / 1024 both divide 16).  14.8B params require FSDP at train_4k.
"""

from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="qwen2.5-14b",
    family="dense",
    n_layers=48,
    d_model=5120,
    n_heads=40,
    n_kv_heads=8,
    head_dim=128,
    d_ff=13824,
    vocab_size=152064,
    qkv_bias=True,
    rope_theta=1e6,
    norm_eps=1e-5,
    weight_dtype="bfloat16",
    layer_pattern=("attn",),
    fsdp=True,
    remat="full",
    n_microbatches=8,
    attention_sharding="qseq",
)
