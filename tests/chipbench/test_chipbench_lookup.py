"""A configuration, a traffic mix and a per-layer metric are added by
adding files and BENCHMARK.json entries, with no edit to a file that is
already there."""
import hashlib
import json
import shutil
from pathlib import Path

import pytest

from chipbench import spec as S

ROOT = Path(__file__).resolve().parents[2]


def _digests(d: Path):
    return {p.relative_to(d): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in d.rglob("*") if p.is_file() and "__pycache__" not in
            p.parts}


def test_every_declared_cell_resolves():
    bench = S.load_benchmark(ROOT)
    for w in bench["workloads"]:
        cell = S.load_cell(w["name"], ROOT)
        assert cell.chips in (1, 4)
        assert cell.traffic["kind"]
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        for m in cell.per_layer:
            assert callable(S.metric_reader(m["name"]))
        S.reference(cell.config["check"]["reference"])


def test_new_files_are_found_without_edits(tmp_path):
    root = tmp_path / "checkout"
    bench_dir = root / "benchmarks" / "chip"
    shutil.copytree(ROOT / "benchmarks" / "chip", bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    before = _digests(bench_dir)

    # the new files
    tiny = json.loads((Path(__file__).parent / "data"
                       / "tiny-config.json").read_text())
    (bench_dir / "configs" / "dummy-model.json").write_text(
        json.dumps(tiny))
    mix = json.loads((bench_dir / "traffic" / "paper-chat.json").read_text())
    mix["rate_rps"] = 1.5
    (bench_dir / "traffic" / "dummy-mix.json").write_text(json.dumps(mix))
    (bench_dir / "metrics" / "dummy_metric.x.py").write_text(
        "def read(rec):\n    return 42.0 if rec else None\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "dummy-model", "source": "test",
        "file": "benchmarks/chip/configs/dummy-model.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({"name": "dummy-cell", "config": "dummy-model",
                               "traffic": "dummy-mix", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({
        "name": "dummy_metric.x", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "device",
        "moves": "ttft_p95_ms", "workloads": ["dummy-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = S.load_cell("dummy-cell", root, bench_dir)
    assert cell.config["models"].keys() == tiny["models"].keys()
    assert cell.traffic["rate_rps"] == 1.5
    names = [m["name"] for m in cell.per_layer]
    assert "dummy_metric.x" in names
    assert S.metric_reader("dummy_metric.x", bench_dir)({"x": 1}) == 42.0
    # a metric restricted to the new cell is not reported elsewhere
    other = S.load_cell(bench["workloads"][0]["name"], root, bench_dir)
    assert "dummy_metric.x" not in [m["name"] for m in other.per_layer]

    after = _digests(bench_dir)
    assert {k: v for k, v in after.items() if k in before} == before


def test_unknown_names_fail_loudly(tmp_path):
    with pytest.raises(KeyError):
        S.load_cell("no-such-cell", ROOT)
    with pytest.raises(FileNotFoundError):
        S.metric_reader("no_such_metric")
