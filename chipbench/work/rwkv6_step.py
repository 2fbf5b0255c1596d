"""Operations and algorithmic bytes of one ``rwkv6_step`` kernel call.

Per token, batch row and head, with a K x V state S:
  kv = k v^T (K*V mul), read = S + u*kv (2*K*V), y = r . read (2*K*V),
  S' = w*S + kv (2*K*V), exp(w) (K): 7*K*V + K operations.
Bytes: the state read and written once in f32, r/k/w/v read once in f32,
u once, y written once in bf16.  Padding (64-lane rows held in 128-lane
tiles) does not count.
"""


def call(*, batch: int, heads: int, key: int, value: int, tokens: int):
    bh = batch * heads
    ops = tokens * bh * (7 * key * value + key)
    state = bh * key * value * 4
    nbytes = (2 * state + tokens * bh * (3 * key + value) * 4
              + heads * key * 4 + tokens * bh * value * 2)
    return {"ops": ops, "bytes": nbytes}


def least_seconds(work, peaks) -> float:
    """The larger of operations over the bf16 peak and bytes over HBM
    bandwidth."""
    return max(work["ops"] / peaks["bf16_flops"],
               work["bytes"] / peaks["hbm_bytes_per_s"])
