"""The trace reduction, on hand-made events and on a small recorded trace
(the first 0.3 s of a traced decode window on one TPU v5e)."""
import json
from pathlib import Path

import pytest

from bench import readers, trace

RECORDED = Path(__file__).parent / "data" / "decode_trace_v5e.json"


def _ex(ops, modules=(), host=()):
    return {"ops": {"/device:TPU:0": list(ops)},
            "modules": {"/device:TPU:0": list(modules)},
            "host": [(trace.WINDOW, 100, 1000)] + list(host)}


def test_busy_is_the_union_of_op_intervals_clipped_to_the_window():
    ex = _ex([("p/a", 50, 100), ("p/b", 120, 100), ("p/c", 500, 100),
              ("p/d", 1050, 200)])
    r = trace.reduce(ex)
    assert r["window_s"] == pytest.approx(1000e-9)
    # [100,220) + [500,600) + [1050,1100) = 120 + 100 + 50 ns
    assert r["busy_s"] == pytest.approx(270e-9)
    assert r["op_s"]["p/a"] == pytest.approx(50e-9)  # clipped at the window start
    assert r["op_s"]["p/d"] == pytest.approx(50e-9)  # and at its end
    gaps = sorted(g for _, g in r["idle_gaps"])
    assert gaps == pytest.approx([280e-9, 450e-9])


def test_idle_gaps_are_named_by_the_most_specific_host_event():
    ex = _ex([("p/a", 100, 100)],
             host=[("outer", 0, 2000), ("np.asarray", 250, 700), ("tiny", 300, 5)])
    r = trace.reduce(ex)
    assert r["idle_gaps"][0][0] == "np.asarray"
    assert r["idle_gaps"][0][1] == pytest.approx(900e-9)


def test_ops_are_labelled_by_the_program_run_that_holds_them():
    ex = trace.label(_ex(
        [("%fusion.1 = f32[2] fusion(...)", 110, 10), ("%fusion.1 = f32[4] add()", 400, 5),
         ("%x = f32[1] copy()", 900, 5)],
        modules=[("jit_train_step(123)", 100, 100), ("jit__gate_groups(9)", 390, 50)]))
    names = [n for n, _, _ in ex["ops"]["/device:TPU:0"]]
    assert names == ["train_step/fusion.1", "_gate_groups/fusion.1", "?/x"]


def test_no_window_annotation_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce({"ops": {}, "modules": {}, "host": []})


def test_recorded_trace():
    r = trace.reduce(json.loads(RECORDED.read_text()))
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.3)
    assert r["busy_s"] == pytest.approx(0.21218371)
    probe = trace.seconds_matching(r["op_s"], readers.KERNELS["huffman_probe"])
    assert probe == pytest.approx(0.212168065)
    assert r["device_ops"][0][0] == "huffman_decode_probe/huffman_decode_probe.1"
    assert r["idle_gaps"][0] == ["shard_args", pytest.approx(0.087799907)]
    assert set(r["module_s"]) == {"huffman_decode_probe"}
