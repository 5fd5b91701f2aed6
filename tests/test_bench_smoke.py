"""Benchmark harness smoke test: ``benchmarks/run.py --fast`` must execute
end-to-end so the scripts can't silently rot (imports all benchmark modules;
runs the throughput module at smoke settings)."""
import os
import subprocess
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def test_run_fast_smoke():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT, env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    env.setdefault("JAX_PLATFORMS", "cpu")
    # the entry point turns on the persistent compile cache; tests keep it off
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--fast", "--only", "throughput"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=840,
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    lines = [l for l in proc.stdout.splitlines() if "," in l]
    assert lines and lines[0].startswith("name,"), proc.stdout
    assert not any(",0,ERROR" in l for l in lines), proc.stdout
    names = {l.split(",")[0] for l in lines[1:]}
    # the entropy-stage rows must be present (perf trajectory anchor)
    assert any(n.startswith("throughput/entropy/hcz_decode") for n in names), names
    assert any(n.startswith("throughput/entropy/decode_speedup") for n in names), names
    # device entropy rows (ISSUE 8): kernel encode/decode vs host plus the
    # executor's host-stage shrink, each reporting its speedup column
    for row in ("throughput/entropy/device/encode",
                "throughput/entropy/device/decode"):
        dev_rows = [l for l in lines[1:] if l.split(",")[0] == row]
        assert dev_rows and "speedup_vs_host=" in dev_rows[0], lines
    stage_rows = [l for l in lines[1:]
                  if l.split(",")[0] == "throughput/entropy/device/stream_host_stage"]
    assert stage_rows and "stage_reduction=" in stage_rows[0], lines
    assert any(n.startswith("throughput/compress/interp/huffman+zlib") for n in names), names
    # the tiled-engine rows must be present for BOTH registered predictors
    # (random-access decode anchor; the tiled path is predictor-pluggable)
    for pred in ("lorenzo", "interp"):
        assert f"throughput/tiled/compress/{pred}" in names, names
        tiled_rows = [l for l in lines[1:]
                      if l.split(",")[0] == f"throughput/tiled/region_decode/{pred}"]
        assert tiled_rows and "speedup_vs_full=" in tiled_rows[0], lines
    # batched tile enhancement must report its measured speedup over the
    # per-tile loop (bit-identity is asserted inside the benchmark itself)
    enh_rows = [l for l in lines[1:]
                if l.split(",")[0] == "throughput/tiled/enhance_batched"]
    assert enh_rows and "speedup_vs_loop=" in enh_rows[0], lines
    # bucketed decode must report its compile-cache hit rate (ISSUE 10;
    # bit-identity vs the unbucketed path is asserted inside the benchmark)
    bk_rows = [l for l in lines[1:]
               if l.split(",")[0] == "throughput/tiled/decode_bucketed"]
    assert bk_rows and "compile_hit_rate=" in bk_rows[0], lines
    # serving-layer warm re-read must report its speedup over the cold path
    wc_rows = [l for l in lines[1:]
               if l.split(",")[0] == "throughput/serve/region_warm_vs_cold"]
    assert wc_rows and "speedup=" in wc_rows[0], lines


def test_run_rejects_unknown_module():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT, env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--fast", "--only", "nope"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
