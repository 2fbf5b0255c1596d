"""A copy of the benchmark with tiny cells, for CPU tests: the same
harness, systems, drivers, readers and references, at sizes a test run
holds."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parent

TINY_RWKV = {
    "name": "tiny-rwkv", "source": "https://arxiv.org/abs/2404.05892",
    "system": "lm", "reference": "rwkv6", "work": "rwkv6_lm",
    "arch": "rwkv6-1.6b",
    "model_overrides": {"d_model": 128, "n_layers": 2, "n_heads": 2,
                        "n_kv_heads": 2, "d_ff": 448, "vocab_size": 512},
    "model": {"n_layers": 2, "d_model": 128, "n_heads": 2, "head_dim": 64,
              "d_ff": 448, "vocab_size": 512, "norm_eps": 1e-06},
    "plan": {"max_batch": 4, "max_len": 128},
}
TINY_RNN = {
    "name": "tiny-rnn", "source": "https://github.com/baidu-research/DeepBench",
    "system": "rnn", "reference": "rnn",
    "tasks": [{"cell": "lstm", "hidden": 128, "timesteps": 3},
              {"cell": "gru", "hidden": 128, "timesteps": 2}],
    "precision": {"weights": "int8", "compute": "bfloat16",
                  "control": "int4"},
    "init": {"bias_std": 0.1, "input_std": 1.0},
}
TRAFFIC = {
    "tiny-closed": {"loop": "closed", "clients": 6, "pool": 64,
                    "prompt": {"dist": "lognormal", "median": 12, "min": 8,
                               "max": 24},
                    "output": {"dist": "uniform", "min": 6, "max": 12},
                    "trace_seconds": 1},
    "tiny-b1": {"loop": "rnn", "trace_seconds": 1},
}
CELLS = {
    "tiny-rwkv.tiny-closed": ("tiny-rwkv", "tiny-closed",
                              {"sample": {"requests": 6, "block": 4},
                               "control": "float8_e4m3fn",
                               "limits": {"mean_logit_gap": 0.002}}),
    "tiny-rnn.tiny-b1": ("tiny-rnn", "tiny-b1",
                         {"sample": {"per_task": 2}, "control_bits": 4,
                          "limits": {"rnn_max_abs_gap": 0.02}}),
}


def make_root(tmp: Path) -> Path:
    """A checkout-like directory: the benchmark's files, plus the tiny
    configurations, traffic, cells and a BENCHMARK.json that lists the
    repository's entries and the tiny ones."""
    root = Path(tmp) / "root"
    shutil.copytree(BENCH, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for cfg in (TINY_RWKV, TINY_RNN):
        init = json.loads((BENCH / "configs" / (
            "rwkv6-1.6b.json" if cfg["system"] == "lm"
            else "deepbench-rnn.json")).read_text())["init"]
        body = dict(cfg, init=init, reduced=[])
        (root / "chipbench" / "configs" / f"{cfg['name']}.json").write_text(
            json.dumps(body))
        bench["configs"].append({"name": cfg["name"], "source": cfg["source"],
                                 "file": f"chipbench/configs/{cfg['name']}.json",
                                 "reduced": [], "why": "CPU test"})
    for name, body in TRAFFIC.items():
        (root / "chipbench" / "traffic" / f"{name}.json").write_text(
            json.dumps(body))
    for name, (config, mix, check) in CELLS.items():
        (root / "chipbench" / "cells" / f"{name}.json").write_text(
            json.dumps(check))
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": mix, "chips": 1,
                                   "why": "CPU test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" not in m:
            continue
        loops = {_loop(w) for w in m["workloads"]}
        m["workloads"] += [name for name, (_, mix, _) in CELLS.items()
                           if TRAFFIC[mix]["loop"] in loops]
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root


def _loop(workload: str) -> str:
    """The loop of one of the repository's own cells."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}[workload]
    return json.loads((BENCH / "traffic" / f"{cell['traffic']}.json"
                       ).read_text())["loop"]


def run(root: Path, workload: str, *, seed: int = 7, seconds: float = 3.0,
        trace: bool = False, control: bool = False):
    """One run of a tiny cell on the CPU (the chip check skipped)."""
    import time

    import jax

    from chipbench.core import harness

    ctx = harness.make_ctx(root, workload, seed, seconds, trace,
                           jax.devices(), control=control)
    return harness.run_cell(ctx, time.perf_counter())
