"""One run of one cell, driven by ``BENCHMARK.json`` and the files it
names: the configuration file, ``traffic/<mix>.json``, the driver
``drivers/<kind>.py`` that the traffic file names, ``limits/<cell>.json``
and one reader ``metrics/<metric>.py`` per per-layer metric.  A new cell,
mix, driver kind or metric is new files and new entries; nothing here
changes.

The last line of standard output is the run's JSON result; the numbers
compared against the reference, each beside its limit, are the last lines
of standard error.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import pathlib
import sys
import time
from dataclasses import dataclass, field

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


def process_age_s() -> float:
    """Seconds since this process started, from the kernel's record."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclass
class Cell:
    """What a driver gets: the cell's files, read, and the run's
    arguments."""
    workload: str
    spec: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    limits: dict = field(default_factory=dict)
    peak: dict = field(default_factory=dict)
    started: float = 0.0     # time.perf_counter() at process start

    def since_start(self) -> float:
        return time.perf_counter() - self.started


def load_cell(workload: str):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    config = {c["name"]: c for c in bench["configs"]}[w["config"]]
    spec = json.loads((ROOT / config["file"]).read_text())
    tr = json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text())
    return bench, w, spec, tr


def applies(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def main(argv=None) -> int:
    started = time.perf_counter() - process_age_s()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench, w, spec, tr = load_cell(args.workload)

    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from chipbench import chip
    chip.use_compile_cache()
    devices = chip.require_chips(w["chips"])
    dev = devices[0]
    from chipbench.check import limits
    cell = Cell(args.workload, spec, tr, args.seed, args.seconds,
                bool(args.trace), limits(args.workload),
                chip.peaks(dev.device_kind), started)
    driver = importlib.import_module(f"chipbench.drivers.{tr['kind']}")
    res = driver.run(cell)

    unit = {m["name"]: m["unit"]
            for m in bench["end_to_end"] + bench["per_layer"]}
    metrics = {}
    if args.trace:
        for m in bench["per_layer"]:
            if applies(m, args.workload):
                v = reader(m["name"])(res["ctx"])
                if v is not None:
                    metrics[m["name"]] = v
    else:
        for m in bench["end_to_end"]:
            if applies(m, args.workload):
                metrics[m["name"]] = res["e2e"][m["name"]]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(res["memory_peak_bytes"])}
    line = {"correct": bool(res["correct"]), "attempted": res["attempted"],
            "failed": res["failed"],
            "metrics": {k: {"value": v, "unit": unit[k]}
                        for k, v in metrics.items()},
            "device": device}
    if args.trace:
        device["busy_s"] = res["busy_s"]
        device["window_s"] = res["window_s"]
        line["breakdown"] = res["breakdown"]
    line["checks"] = res["checks"]
    for name, c in res["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(line), flush=True)
    return 0
