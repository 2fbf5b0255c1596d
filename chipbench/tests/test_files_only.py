"""A configuration, a traffic mix and a per-layer metric are picked up
from new files and new BENCHMARK.json entries alone: no file of the
benchmark is edited."""

import hashlib
import json

from chipbench.tests import tiny

NEW_METRIC = '''"""Decode tokens per engine tick in the traced part."""

from chipbench.core.readers import tokens_in, traced


def read(run, system, ctx):
    info = traced(run)
    if info is None:
        return None
    ticks = info["c1"]["ticks"] - info["c0"]["ticks"]
    return tokens_in(run, info["t0"], info["t1"]) / ticks if ticks else None
'''


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "chipbench").rglob("*") if p.is_file()}


def test_new_config_traffic_and_metric_as_files(tmp_path):
    root = tiny.make_root(tmp_path)
    before = _digests(root)
    cfg = dict(tiny.TINY_RWKV, name="tiny-rwkv-b2",
               plan={"max_batch": 2, "max_len": 64})
    cfg["init"] = json.loads((root / "chipbench" / "configs" /
                              "tiny-rwkv.json").read_text())["init"]
    (root / "chipbench" / "configs" / "tiny-rwkv-b2.json").write_text(
        json.dumps(cfg))
    (root / "chipbench" / "traffic" / "three-clients.json").write_text(
        json.dumps(dict(tiny.TRAFFIC["tiny-closed"], clients=3)))
    (root / "chipbench" / "cells" / "tiny-rwkv-b2.three-clients.json"
     ).write_text(json.dumps(tiny.CELLS["tiny-rwkv.tiny-closed"][2]))
    (root / "chipbench" / "layers" / "tokens_per_tick.b2.py").write_text(
        NEW_METRIC)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-rwkv-b2", "source": cfg["source"],
                             "file": "chipbench/configs/tiny-rwkv-b2.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-rwkv-b2.three-clients",
                               "config": "tiny-rwkv-b2",
                               "traffic": "three-clients", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "tokens_per_tick.b2", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "model step",
                               "moves": "output_tokens_per_s",
                               "workloads": ["tiny-rwkv-b2.three-clients"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    line = tiny.run(root, "tiny-rwkv-b2.three-clients", seconds=2.0,
                    trace=True)
    after = _digests(root)
    assert {k: v for k, v in after.items() if k in before} == before
    assert line["correct"]
    assert line["metrics"]["tokens_per_tick.b2"]["value"] > 0
    assert "batch_occupancy.decode" not in line["metrics"]
