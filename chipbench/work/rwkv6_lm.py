"""Model FLOPs per token of an RWKV-6 language model (one forward token,
prefill or decode), from the configuration's sizes.

Per layer, matrix products (2 FLOPs a multiply-add): time-mix r, k, v, g,
o (5 d^2), channel-mix receptance (d^2), key and value (2 d d_ff), the
token-shift LoRA (d x 5*32 and 5*32 x d) and the decay LoRA (d x 64 and
64 x d); the wkv recurrence takes 7 K V per head (``rwkv6_step``).  The
head is d x vocab; the embedding is a gather.
"""

LORA = 5 * 32
DECAY = 64


def flops_per_token(m) -> float:
    d, ff, layers = m["d_model"], m["d_ff"], m["n_layers"]
    k = m["head_dim"]
    mats = 6 * d * d + 2 * d * ff + 2 * d * LORA + 2 * d * DECAY
    wkv = (d // k) * 7 * k * k
    return layers * (2.0 * mats + wkv) + 2.0 * d * m["vocab_size"]
