"""Find a cell's files by name.

`BENCHMARK.json` at the checkout's root names each cell's configuration
and traffic mix and lists the metrics. A configuration's file is the one
its entry names; a traffic mix is `traffic/<name>.json`; a per-layer
metric is `metrics/<name>.py`; a plain reference is
`references/<name>.py`. Adding any of them is adding a file and an entry:
nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH_DIR.parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def load_benchmark(root: Path = CHECKOUT) -> Dict[str, Any]:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise FileNotFoundError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def _for_cell(metrics: List[Dict[str, Any]], cell: str):
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(name: str, root: Path = CHECKOUT,
              bench_dir: Optional[Path] = None) -> Cell:
    """The cell `name` of `root/BENCHMARK.json`, with its files read."""
    bench_dir = bench_dir or BENCH_DIR
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       + ", ".join(sorted(cells)))
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    c = configs[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]), config_name=c["name"],
        config=json.loads((root / c["file"]).read_text()),
        traffic_name=w["traffic"],
        traffic=load_traffic(w["traffic"], bench_dir),
        end_to_end=_for_cell(bench["end_to_end"], name),
        per_layer=_for_cell(bench["per_layer"], name))


def load_traffic(name: str, bench_dir: Path = BENCH_DIR) -> Dict[str, Any]:
    return json.loads((bench_dir / "traffic" / f"{name}.json").read_text())


def _load_module(path: Path, label: str):
    if not path.is_file():
        raise FileNotFoundError(f"no {label} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{label}_{path.stem}".replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: Path = BENCH_DIR) -> Callable:
    """`read(record) -> float | None` of the per-layer metric `name`."""
    return _load_module(bench_dir / "metrics" / f"{name}.py", "metric").read


def reference(name: str, bench_dir: Path = BENCH_DIR):
    """The plain reference module `name` (`references/<name>.py`)."""
    return _load_module(bench_dir / "references" / f"{name}.py",
                        "reference")
