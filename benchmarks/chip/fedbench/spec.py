"""Find a cell's files by the names ``BENCHMARK.json`` gives them."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List

BENCH_DIR = Path(__file__).resolve().parents[1]       # benchmarks/chip
REPO_ROOT = BENCH_DIR.parents[1]


def load_module(path: Path) -> ModuleType:
    name = "fedbench_" + "_".join(path.relative_to(BENCH_DIR).with_suffix(
        "").parts)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark(root: Path = REPO_ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


class Cell:
    """One entry of ``workloads`` with everything it names."""

    def __init__(self, name: str, root: Path = REPO_ROOT):
        bench = benchmark(root)
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; known: "
                             f"{sorted(cells)}")
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in bench["configs"]}
        self.config_entry = configs[self.entry["config"]]
        self.config = load_module(root / self.config_entry["file"])
        self.traffic = json.loads(
            (BENCH_DIR / "traffic" / f"{self.entry['traffic']}.json")
            .read_text())
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"] if self._reports(m,
                                                                        bench)]
        limits = BENCH_DIR / "limits" / f"{name}.json"
        self.limits: Dict[str, float] = (json.loads(limits.read_text())
                                         if limits.exists() else {})

    def _reports(self, metric: dict, bench: dict) -> bool:
        if "workloads" in metric:
            return self.name in metric["workloads"]
        moved = {m["name"]: m for m in bench["end_to_end"]}[metric["moves"]]
        return self.name in moved.get("workloads", [self.name])

    def readers(self) -> Dict[str, ModuleType]:
        return {m["name"]: load_module(BENCH_DIR / "metrics"
                                       / f"{m['name']}.py")
                for m in self.per_layer}


def kernel_counters() -> List[ModuleType]:
    """Every kernel's operation and byte counts (``kernels/*.py``)."""
    return [load_module(p) for p in sorted((BENCH_DIR / "kernels")
                                           .glob("*.py"))
            if not p.name.startswith("_")]
