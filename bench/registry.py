"""Finds everything by the names in ``BENCHMARK.json``.

* a cell: an entry of ``workloads``;
* its configuration: the ``file`` of the entry of ``configs`` it names;
* its traffic mix: ``bench/traffic/<traffic>.json``, whose ``kind`` names
  the general runner in ``bench/mixes/<kind>.py``;
* a per-layer metric: its reader, ``bench/metrics/<name>.py``.

Adding a cell, a configuration, a mix of an existing kind or a metric is
adding files and entries; nothing here changes.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
BENCH = Path(__file__).resolve().parent


@dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: tuple[dict, ...]  # the metrics this cell reports at --trace 0
    per_layer: tuple[dict, ...]  # and at --trace 1


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT, bench_dir: Path = BENCH) -> Cell:
    """The cell named ``name``, with its configuration and traffic loaded."""
    root, bench_dir = Path(root), Path(bench_dir)
    spec = load_benchmark(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = tuple(m for m in spec["end_to_end"] if _applies(m, name))
    reported = {m["name"] for m in e2e}
    per_layer = tuple(m for m in spec["per_layer"]
                      if _applies(m, name) and m["moves"] in reported)
    return Cell(name=name, config=config, traffic=traffic, chips=int(w["chips"]),
                end_to_end=e2e, per_layer=per_layer)


def mix_module(kind: str):
    """The general runner of a traffic kind."""
    return importlib.import_module(f"bench.mixes.{kind}")


def metric_reader(name: str, bench_dir: Path = BENCH):
    """``read(ctx)`` of ``bench/metrics/<name>.py`` (names may hold dots, so
    the file is loaded by path, not imported by module name)."""
    path = Path(bench_dir) / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
