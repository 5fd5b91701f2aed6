"""The four-chip enhanced ingest mix on four host CPU devices, in a
subprocess (the device count is fixed when JAX starts): its check holds the
container to the one-device container, byte for byte, and sees a fault that
only the multi-device path makes; its readers find the training spans."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import registry

CELL = "nyx-temperature-512-eb1e-3.ingest-4chip"
REPO = Path(__file__).resolve().parents[2]

_SCRIPT = """
import json, sys
from pathlib import Path
import jax
import numpy as np
from bench.tests.tiny import make_root, run
from repro.core import trainer

assert len(jax.devices()) == 4
root = make_root(Path(sys.argv[1]), side=64, tile=16,
                 config={"mem_budget": 4 << 20}, limits={"enh_gain_db": 1.0})
out = {"sound": run(root, %(cell)r), "traced": run(root, %(cell)r, traced=True)}

split = trainer._split_groups
def nudged(tree, placement):  # the multi-device path alone trains otherwise
    blocks = split(tree, placement)
    if len(set(placement)) > 1:
        blocks[1][0] = dict(blocks[1][0], b2=blocks[1][0]["b2"] + 1e-3)
    return blocks
trainer._split_groups = nudged
out["mesh_fault"] = run(root, %(cell)r)
print(json.dumps(out))
""" % {"cell": CELL}


@pytest.fixture(scope="module")
def four_devices(tmp_path_factory):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(REPO / "src"), str(REPO), env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4").strip()
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT, str(tmp_path_factory.mktemp("mesh"))],
        env=env, capture_output=True, timeout=900, cwd=REPO)
    assert proc.returncode == 0, proc.stderr.decode()[-4000:]
    return json.loads(proc.stdout.decode().strip().splitlines()[-1])


def test_mesh_ingest_is_correct_and_byte_identical_to_one_device(four_devices):
    r = four_devices["sound"]
    assert r["correct"], r["checks"]
    assert r["checks"]["mesh_bytes_differ"] == {"value": 0, "max": 0}
    assert r["checks"]["enh_gain_db"]["value"] > 1.0
    assert set(r["metrics"]) == {"ingest_MBps", "ratio", "setup_s"}
    assert r["device"]["count"] == 4


def test_mesh_only_training_fault_is_not_correct(four_devices):
    r = four_devices["mesh_fault"]
    assert not r["correct"], r["checks"]
    assert r["checks"]["mesh_bytes_differ"]["value"] > 0


def test_traced_mesh_ingest_reads_the_training_spans(four_devices):
    m = four_devices["traced"]["metrics"]
    assert m["train_step_ms.ingest-4chip"]["value"] > 0
    assert 0 < m["train_mesh_share.ingest-4chip"]["value"] < 100


def test_the_cell_loads_on_four_chips():
    c = registry.load_cell(CELL)
    assert c.chips == 4
    assert registry.mix_module(c.traffic["kind"]).Mix
    assert {m["name"] for m in c.end_to_end} == {"ingest_MBps", "ratio", "setup_s"}
    assert {m["name"] for m in c.per_layer} >= {
        "train_step_ms.ingest-4chip", "train_mesh_share.ingest-4chip", "mfu.ingest-4chip"}
    assert "mfu.ingest" not in {m["name"] for m in c.per_layer}
    limits = json.loads((registry.BENCH / "limits" / f"{CELL}.json").read_text())
    assert limits == {"mismatch_share": 1e-4, "enh_err": 0.02, "enh_gain_db": 1.0,
                      "mesh_bytes_differ": 0}


def test_at_most_half_the_cells_take_four_chips():
    chips = [w["chips"] for w in registry.load_benchmark()["workloads"]]
    assert set(chips) <= {1, 4}
    assert chips.count(4) <= max(1, len(chips) // 2)


def _ctx(**counters):
    return {"counters": {"kind": "ingest", "ops": 2, **counters}, "work": {},
            "trace": {"window_s": 1.0, "busy_s": 0.0, "devices": 0, "op_s": {},
                      "module_s": {}},
            "device": {"kind": "TPU v5 lite", "count": 4}}


def test_training_readers():
    step = registry.metric_reader("train_step_ms.ingest-4chip")
    share = registry.metric_reader("train_mesh_share.ingest-4chip")
    ctx = _ctx(train_s=8.0, train_step_s=4.0, train_steps=400, train_mesh_s=0.2)
    assert step(ctx) == pytest.approx(10.0)
    assert share(ctx) == pytest.approx(2.5)
    # one device (the parent's path) records neither mesh span
    assert share(_ctx(train_s=8.0, train_step_s=4.0, train_steps=400,
                      train_mesh_s=None)) is None
    assert step(_ctx(train_s=None, train_step_s=None, train_steps=None,
                     train_mesh_s=None)) is None


def test_host_mfu_is_one_chip_mfu_over_the_chips():
    from bench import readers

    ctx = _ctx()
    ctx["trace"]["devices"] = 4
    ctx["work"] = {"whole": (197e12, 0.0)}  # one second of one v5e's peak
    one_chip = readers.mfu(ctx, "ingest")
    assert one_chip == pytest.approx(100.0)
    assert registry.metric_reader("mfu.ingest-4chip")(ctx) == pytest.approx(25.0)
