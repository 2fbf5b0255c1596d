"""Useful operations and bytes of ``flash_decode``: one query token per
slot against the live part of its KV cache, in every layer.

Per live cache position, layer and KV head: its key and value are read
once (2 * head_dim elements at ``kv_bytes`` each), and each of the
H / K query heads that share the KV head takes a q.k product and a p.v
product (4 * head_dim operations).  Positions the kernel visits that hold
no token (the empty part of a ring, padding) do not count, nor do the
query, the partials or the position array.
"""


def call(*, tokens: int, layers: int, heads: int, kv_heads: int,
         head_dim: int, kv_bytes: int = 2):
    """``tokens``: live cache positions attended over, summed over the
    slots and ticks counted (the engine's ``engine.kv_tokens``)."""
    per = tokens * layers
    return {"ops": 4 * per * heads * head_dim,
            "bytes": 2 * per * kv_heads * head_dim * kv_bytes}


def least_seconds(work, peaks) -> float:
    """The larger of operations over the bf16 peak and bytes over HBM
    bandwidth."""
    return max(work["ops"] / peaks["bf16_flops"],
               work["bytes"] / peaks["hbm_bytes_per_s"])
