"""A tiny copy of the ``qwen2.5-14b.chat-tp4`` cell, for CPU tests: the
same harness, system, driver, readers and reference as the chip cell, at
a size a test run holds, added to the tiny root of ``tiny.py``.

``CHIPS`` devices serve it: 1 in the test process, 4 in a subprocess that
fakes four CPU devices, where the model is sharded over a 1 x 4 mesh as
on the chip.  The plan carries the v5e's tile plans (``--hw-spec``), so
a test can put the flash kernels in the path (interpret mode) by
resolving every tile-plan entry to "pallas".
"""

from __future__ import annotations

import json
import math
from pathlib import Path

from chipbench.tests import tiny

CONFIG = "tiny-qwen"
TRAFFIC = "tiny-chat"
CELL = f"{CONFIG}.{TRAFFIC}"

TINY_QWEN = {
    "name": CONFIG,
    "source": "https://huggingface.co/Qwen/Qwen2.5-14B/blob/main/config.json",
    "system": "lm", "reference": "qwen2", "work": "qwen2_lm",
    "arch": "qwen2.5-14b",
    "model_overrides": {"d_model": 128, "n_layers": 2, "n_heads": 4,
                        "n_kv_heads": 2, "head_dim": 32, "d_ff": 256,
                        "vocab_size": 512},
    "model": {"n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv_heads": 2,
              "head_dim": 32, "d_ff": 256, "vocab_size": 512,
              "qkv_bias": True, "rope_theta": 1000000.0,
              "tie_embeddings": False, "mlp_gated": True, "mlp_act": "silu",
              "norm_eps": 1e-05, "weight_dtype": "bfloat16"},
    "plan": {"max_batch": 4, "max_len": 128, "cache_layout": "dense",
             "hw_spec": "tpu-v5e"},
}
CHAT = {"loop": "closed", "clients": 6, "pool": 64,
        "prompt": {"dist": "lognormal", "median": 24, "min": 8, "max": 40},
        "output": {"dist": "uniform", "min": 24, "max": 48},
        "trace_seconds": 1}
# six requests of 24-48 served tokens: two layers and a 512-token vocab
# move few tokens, so the mean needs about 200 of them.  Over seeds 7-9
# the program read 2e-5-1.4e-4, a RoPE one position off 8e-4-1.5e-3 and
# the control 4.0e-3-5.9e-3
CHECK = {"sample": {"requests": 6, "block": 3}, "control": "float8_e4m3fn",
         "limits": {"mean_logit_gap": 5e-4}}


def make_root(tmp: Path, chips: int = 1) -> Path:
    """``tiny.make_root`` plus the tiny qwen configuration, traffic and
    cell on ``chips`` devices, listed wherever the chip cell is."""
    root = tiny.make_root(tmp)
    here = root / "chipbench"
    init = json.loads((here / "configs" / "qwen2.5-14b.json").read_text()
                      )["init"]
    # the chip config states wo's std for 40 x 128 query dims: the same
    # share of 1/sqrt(fan-in) at the tiny widths
    dims = TINY_QWEN["model"]
    init["wo"] = {"normal": init["wo"]["normal"] * math.sqrt(
        40 * 128 / (dims["n_heads"] * dims["head_dim"]))}
    (here / "configs" / f"{CONFIG}.json").write_text(
        json.dumps(dict(TINY_QWEN, init=init, reduced=[])))
    (here / "traffic" / f"{TRAFFIC}.json").write_text(json.dumps(CHAT))
    (here / "cells" / f"{CELL}.json").write_text(json.dumps(CHECK))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": CONFIG, "source": TINY_QWEN["source"],
                             "file": f"chipbench/configs/{CONFIG}.json",
                             "reduced": [], "why": "CPU test"})
    bench["workloads"].append({"name": CELL, "config": CONFIG,
                               "traffic": TRAFFIC, "chips": chips,
                               "why": "CPU test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "qwen2.5-14b.chat-tp4" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root
