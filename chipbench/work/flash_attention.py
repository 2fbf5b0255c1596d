"""Useful operations and bytes of ``flash_attention`` over one prompt:
causal self-attention of its ``length`` real tokens, in every layer.

Per layer and query head, the n(n+1)/2 causal (query, key) pairs each take
a q.k product and a p.v product (4 * head_dim operations).  Bytes: q, k,
v read once and the output written once, at ``act_bytes`` an element.
Padding tokens, padding rows and the masked half of the score matrix do
not count.
"""


def call(*, length: int, layers: int, heads: int, kv_heads: int,
         head_dim: int, act_bytes: int = 2):
    pairs = length * (length + 1) // 2
    return {"ops": 4 * layers * heads * head_dim * pairs,
            "bytes": layers * length * head_dim * (2 * heads + 2 * kv_heads)
            * act_bytes}


def least_seconds(work, peaks) -> float:
    """The larger of operations over the bf16 peak and bytes over HBM
    bandwidth."""
    return max(work["ops"] / peaks["bf16_flops"],
               work["bytes"] / peaks["hbm_bytes_per_s"])
