"""Operations and algorithmic bytes of one ``fused_lstm`` / ``fused_gru``
call at batch B over T steps (G = 4 gates for an LSTM, 3 for a GRU).

Operations: the gate products, 2 G H (D + H) per step and row (the
elementwise tail is not counted).  Bytes: the int8 weights and their f32
per-(gate, unit) scales and biases once per call, the f32 state in and
out, the bf16 inputs and outputs once.  Re-fetching weight tiles on every step (a streamed plan)
does not count: it is what the roofline measures against.
"""

GATES = {"lstm": 4, "gru": 3}


def call(*, cell: str, hidden: int, features: int, timesteps: int,
         batch: int = 1, weight_bytes: int = 1):
    g, h, d, t = GATES[cell], hidden, features, timesteps
    ops = 2 * g * h * (d + h) * t * batch
    n_vec = 3 if cell == "lstm" else 4      # two scales, one or two biases
    n_state = 4 if cell == "lstm" else 2    # h (and c) in and out, f32
    nbytes = (g * h * (d + h) * weight_bytes + n_vec * g * h * 4
              + n_state * batch * h * 4 + t * batch * (d + h) * 2)
    return {"ops": ops, "bytes": nbytes}


def least_seconds(work, peaks) -> float:
    """The larger of operations over the int8 peak (the weights are int8)
    and bytes over HBM bandwidth."""
    return max(work["ops"] / peaks["int8_ops"],
               work["bytes"] / peaks["hbm_bytes_per_s"])
