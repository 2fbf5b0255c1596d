"""One run of one benchmark cell: set up, measure, check, print.

Everything that belongs to one configuration, traffic mix, cell or metric
is a file found by its name:

* ``chipbench/configs/<config>.json``: the configuration as it is run;
  its ``system`` names ``chipbench/systems/<system>.py`` (what builds the
  program under test, warms it up and checks it) and its ``reference``
  names ``chipbench/reference/<reference>.py`` (the plain reference);
* ``chipbench/traffic/<traffic>.json``: the mix; its ``loop`` names
  ``chipbench/drivers/<loop>.py``;
* ``chipbench/cells/<workload>.json``: the cell's comparison, its sample
  and its limits;
* ``chipbench/metrics/<name>.py``: an end-to-end metric, read from the
  run's host-clock records;
* ``chipbench/layers/<name>.py``: a per-layer metric, read from the
  traced run (trace reduction, program counters, host stamps).

A reader returns a number, or None where it finds nothing to read; the
metric is then left out of the line.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

from chipbench.core import device as device_mod
from chipbench.core import profile as profile_mod

ROOT = Path(__file__).resolve().parents[2]     # the checkout


@dataclasses.dataclass
class Ctx:
    """What a system, a driver and a reader are given."""

    root: Path
    bench: Dict[str, Any]
    cell: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    checks: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    devices: List[Any]
    peaks: Dict[str, float]
    control: bool = False
    profiler: Optional[profile_mod.Profiler] = None

    @property
    def name(self) -> str:
        return self.cell["name"]

    def log(self, msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Run:
    """What a driver hands back: host-clock records of the window."""

    window_start: float              # perf_counter at the window's start
    window_end: float
    requests: List[Any]              # driver-specific request records
    attempted: int
    failed: int
    trace: Optional[Dict[str, Any]] = None      # profile.reduce(...)
    traced: Dict[str, Any] = dataclasses.field(default_factory=dict)
    extra: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.window_end - self.window_start


def load_module(path: Path):
    """Import one file of the benchmark by its path (its name may hold
    dots, as metric names do)."""
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file {path} is missing")
    name = "_".join(("chipbench", path.parent.name, path.stem))
    name = name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def make_ctx(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, devices, control: bool = False) -> Ctx:
    bench = _read_json(root / "BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read_json(root / configs[cell["config"]]["file"])
    here = root / "chipbench"
    traffic = _read_json(here / "traffic" / f"{cell['traffic']}.json")
    checks = _read_json(here / "cells" / f"{workload}.json")
    kind = devices[0].device_kind
    peaks = (device_mod.peaks(kind) if devices[0].platform == "tpu"
             else dict(device_mod.PEAKS["TPU v5 lite"]))
    return Ctx(root=root, bench=bench, cell=cell, config=config,
               traffic=traffic, checks=checks, seed=seed, seconds=seconds,
               trace=trace, devices=list(devices), peaks=peaks,
               control=control,
               profiler=profile_mod.Profiler(
                   str(root / ".chipbench_out" / "trace"))
               if trace else None)


class CompileCounter:
    """Counts XLA compiles and compile-cache loads between ``open`` and
    ``close`` (JAX's monitoring events)."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax

        self.counting = False
        self.count = 0
        self.seen: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kwargs) -> None:
        if self.counting and event in self.EVENTS:
            self.count += 1
            self.seen.append(f"{event.rsplit('/', 1)[-1]} {duration:.3f}s")

    def open(self) -> None:
        self.count, self.counting, self.seen = 0, True, []

    def close(self) -> int:
        self.counting = False
        return self.count


def run_cell(ctx: Ctx, t_start: float) -> Dict[str, Any]:
    """Set up, measure, read the metrics, check; return the result line
    (the check's numbers under the last key)."""
    here = ctx.root / "chipbench"
    system = load_module(here / "systems" / f"{ctx.config['system']}.py"
                         ).build(ctx)
    driver = load_module(here / "drivers" / f"{ctx.traffic['loop']}.py")
    compiles = CompileCounter()
    run = driver.run(system, ctx, compiles)
    ctx.log(f"compiles in the window: {run.extra.get('compiles', 0)} "
            f"{compiles.seen}")
    setup_s = run.window_start - t_start
    metrics: Dict[str, Dict[str, Any]] = {}
    if ctx.trace:
        for m in ctx.bench["per_layer"]:
            if not applies(m, ctx.name):
                continue
            value = load_module(here / "layers" / f"{m['name']}.py").read(
                run, system, ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in ctx.bench["end_to_end"]:
            if not applies(m, ctx.name):
                continue
            if m["name"] == "setup_s":
                value = setup_s
            else:
                value = load_module(here / "metrics" / f"{m['name']}.py"
                                    ).value(run, ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = device_mod.describe(ctx.devices)
    dev["memory_peak_bytes"] = device_mod.memory_peak_bytes(ctx.devices)
    if run.trace is not None:
        dev["busy_s"] = run.trace["busy_s"]
        dev["window_s"] = run.trace["window_s"]
    system.release()
    gc.collect()
    numbers = system.check(run, ctx)
    correct = all(n["value"] <= n["limit"] for n in numbers.values()
                  if n.get("limit") is not None)
    line: Dict[str, Any] = {"correct": bool(correct and numbers),
                            "attempted": run.attempted,
                            "failed": run.failed, "metrics": metrics,
                            "device": dev}
    if run.trace is not None:
        line["breakdown"] = {"device_ops": run.trace["device_ops"],
                             "idle_gaps": run.trace["idle_gaps"]}
    line["check"] = numbers
    return line


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache: the directory the environment
    names, else ``<checkout>/.jax_cache`` (a fixed path: a cache whose
    directory moves never hits).  Every compile is kept, small ones too,
    so that a second run of a cell compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def emit(line: Dict[str, Any]) -> None:
    """The check's numbers as the last lines of stderr, then the result
    as the last line of stdout."""
    for name, n in line["check"].items():
        print(f"check {name}: {n['value']!r} limit {n.get('limit')!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(
        description="Run one cell of BENCHMARK.json on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = _read_json(ROOT / "BENCHMARK.json")
    cell = {c["name"]: c for c in bench["workloads"]}.get(args.workload)
    if cell is None:
        print(f"no workload {args.workload!r}", file=sys.stderr)
        return 2
    # libtpu's logs go inside the checkout, not to a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR",
                          str(ROOT / ".chipbench_out" / "tpu_logs"))
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"the program under test is missing ({src / 'repro'})",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    cache = enable_compile_cache(ROOT)
    try:
        devices = device_mod.require_tpu(int(cell["chips"]))
    except device_mod.NoChip as e:
        print(f"chipbench: {e}; nothing was run", file=sys.stderr)
        return 3
    n_cached = sum(1 for _ in Path(cache).iterdir()) \
        if Path(cache).is_dir() else 0
    print(f"compile cache: {cache} ({n_cached} entries at start)",
          file=sys.stderr, flush=True)
    ctx = make_ctx(ROOT, args.workload, args.seed, args.seconds,
                   bool(args.trace), devices)
    emit(run_cell(ctx, t_start))
    return 0
