"""Model FLOPs per token of a Qwen2 language model (one forward token,
prefill or decode), from the configuration's sizes.

Per layer, matrix products (2 FLOPs a multiply-add): the query and output
projections (d x H*hd each), the key and value projections (d x K*hd
each) and the SwiGLU MLP's gate, up and down (3 d d_ff).  The head is
d x vocab; the embedding is a gather.  Attention's products with the
cache grow with the context and are counted by ``work/flash_decode.py``
and ``work/flash_attention.py``, not here.
"""


def flops_per_token(m) -> float:
    d, ff, layers = m["d_model"], m["d_ff"], m["n_layers"]
    q = m["n_heads"] * m["head_dim"]
    kv = m["n_kv_heads"] * m["head_dim"]
    mats = 2 * d * q + 2 * d * kv + 3 * d * ff
    return layers * 2.0 * mats + 2.0 * d * m["vocab_size"]
