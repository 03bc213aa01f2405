"""Find a cell's pieces by name: BENCHMARK.json, configuration, traffic,
per-layer metric readers and kernel costs, each a file of its own."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict

BENCH_DIR = Path(__file__).resolve().parents[1]      # chipbench/
ROOT = BENCH_DIR.parent                              # the checkout

_MODULES: Dict[str, ModuleType] = {}


def load_module(path: Path) -> ModuleType:
    """Import a file by path (names may hold '.' and '-')."""
    path = Path(path).resolve()
    key = str(path)
    if key not in _MODULES:
        spec = importlib.util.spec_from_file_location(
            "chipbench_" + path.stem.replace(".", "_").replace("-", "_"),
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[key] = mod
    return _MODULES[key]


def benchmark(root: Path = ROOT) -> Dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def cell(name: str, root: Path = ROOT) -> Dict:
    """Everything one cell needs: its entry, configuration, traffic and
    the end-to-end and per-layer metrics it reports."""
    bench = benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    config = json.loads((Path(root) / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (BENCH_DIR / "traffic" / f"{entry['traffic']}.json").read_text())

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    return {
        "name": name,
        "chips": int(entry["chips"]),
        "config_name": cfg_entry["name"],
        "config": config,
        "traffic_name": entry["traffic"],
        "traffic": traffic,
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def reference(config: Dict) -> ModuleType:
    return load_module(BENCH_DIR / "configs" / f"{config['reference']}.py")


def metric_reader(name: str) -> ModuleType:
    return load_module(BENCH_DIR / "metrics" / f"{name}.py")


def cost(name: str) -> ModuleType:
    return load_module(BENCH_DIR / "costs" / f"{name}.py")


def peaks(device_kind: str) -> Dict:
    table = json.loads((BENCH_DIR / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise SystemExit(f"device kind {device_kind!r} is not in "
                         "chipbench/peaks.json: add its published peaks")
    return table["devices"][device_kind]
