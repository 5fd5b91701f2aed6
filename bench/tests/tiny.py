"""A tiny checkout for CPU runs of the harness."""
import json
import shutil
from pathlib import Path


def make_root(tmp: Path, *, side: int = 32, tile: int = 16, config: dict | None = None,
              limits: dict | None = None) -> Path:
    """A checkout-like tree: ``BENCHMARK.json`` and a copy of ``bench/`` whose
    configurations are cut to ``side``^3 fields over ``tile``^3 tiles."""
    from bench import registry

    spec = registry.load_benchmark()
    shutil.copytree(registry.BENCH, tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for c in spec["configs"]:
        p = tmp / c["file"]
        cfg = json.loads(p.read_text())
        cfg.update(side=side, tile=tile, **(config or {}))
        p.write_text(json.dumps(cfg))
    if limits:
        for p in (tmp / "bench" / "limits").glob("*.json"):
            p.write_text(json.dumps({**json.loads(p.read_text()), **limits}))
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def run(root: Path, cell: str, *, seconds: float = 0.5, traced: bool = False,
        seed: int = 2**33 + 5, **kw) -> dict:
    from bench import harness

    return harness.run_cell(cell, seed, seconds, traced, root=root,
                            bench_dir=root / "bench", **kw)
