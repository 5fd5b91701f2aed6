"""BENCHMARK.json and the files it names: every cell, configuration, mix,
limit and per-layer reader loads by name and keeps to the allowed names,
units and keys; a cell added as data alone runs."""
import json
import re

import pytest

from bench import registry
from bench.tests.tiny import run

SPEC = registry.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_paths():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_loads(cell):
    c = registry.load_cell(cell)
    assert c.chips == 1
    assert registry.mix_module(c.traffic["kind"]).Mix
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    limits = json.loads((registry.BENCH / "limits" / f"{cell}.json").read_text())
    assert "mismatch_share" in limits


def test_names_units_and_sources():
    everything = SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"] + SPEC["per_layer"]
    names = [x["name"] for x in everything]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    layers = {m["layer"] for m in SPEC["per_layer"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            assert cell in e2e[m["moves"]].get("workloads", [cell])
    assert all("\n" not in layer for layer in layers)


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_every_reader_loads_and_finds_nothing_in_another_kind(metric):
    read = registry.metric_reader(metric)
    ctx = {"counters": {"kind": "none", "ops": 0}, "work": {},
           "trace": {"window_s": 1.0, "busy_s": 0.0, "devices": 0, "op_s": {},
                     "module_s": {}},
           "device": {"kind": "TPU v5 lite"}}
    assert read(ctx) is None


@pytest.mark.parametrize("config", SPEC["configs"], ids=lambda c: c["name"])
def test_configuration_files(config):
    cfg = json.loads((registry.ROOT / config["file"]).read_text())
    assert cfg["name"] == config["name"]
    assert cfg["reduced"] == config["reduced"]
    assert cfg["side"] % cfg["tile"] == 0
    e = cfg["enhancer"]
    assert (e["n_groups"], e["channels"], e["convs"]) == (20, 9, 2)


def test_a_cell_added_as_data_alone_runs(tiny_root):
    """A new traffic file, limits file and workload entry; no code."""
    bench = tiny_root / "bench"
    (bench / "traffic" / "ingest-plain-again.json").write_text(json.dumps(
        {"kind": "ingest", "enhance": False, "why": "a second plain ingest mix"}))
    name = "nyx-temperature-512-eb1e-3.ingest-plain-again"
    (bench / "limits" / f"{name}.json").write_text(json.dumps(
        {"mismatch_share": 1e-4, "over_bound_ulp": 2.0}))
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": name, "config": "nyx-temperature-512-eb1e-3",
                              "traffic": "ingest-plain-again", "chips": 1,
                              "why": "data only"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "nyx-dmd-512-eb1e-4.ingest-plain" in m.get("workloads", []):
            m["workloads"].append(name)
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    r = run(tiny_root, name)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"ingest_MBps", "ratio", "setup_s"}
