"""``bench/run.py`` as a benchmark run calls it: without a TPU, and in a
directory that holds only the benchmark, it exits non-zero with no result."""
import json
import os
import shutil
import subprocess
import sys

from bench import registry


def _run(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_ENABLE_COMPILATION_CACHE": "false"}
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "nyx-dmd-512-eb1e-4.ingest-plain", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "metrics" in json.loads(line):
                return False
        except (ValueError, TypeError):
            continue
    return True


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(registry.ROOT)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "TPU" in p.stderr


def test_benchmark_alone_exits_nonzero_without_a_result(tmp_path):
    shutil.copy(registry.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(registry.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)
