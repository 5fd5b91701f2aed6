"""Device idle put down to the program's host spans (``bench/stages.py``),
on hand-made events and on a small recorded trace (the first 0.5 s of a
traced plain-ingest window on one TPU v5e)."""
import json
from pathlib import Path

import pytest

from bench import stages, trace

RECORDED = Path(__file__).parent / "data" / "ingest_plain_spans_v5e.json"
DEV = "/device:TPU:0"


def _ex(ops, spans):
    # the window is [100, 1100) ns
    return {"ops": {DEV: list(ops)}, "modules": {DEV: []},
            "host": [(trace.WINDOW, 100, 1000)], "spans": list(spans)}


def test_idle_goes_to_the_innermost_open_span():
    ex = _ex([("p/a", 100, 100), ("p/b", 500, 100)],
             [["gwlz.ingest", "T0", 0, 2000, 0],
              ["gwlz.ingest.lanes", "T0", 200, 600, 0],
              ["gwlz.entropy.fit", "T0", 300, 100, 0]])
    r = stages.reduce(ex)
    # idle [200, 500) and [600, 1100)
    assert r["idle_by_span"] == pytest.approx(
        {"gwlz.ingest.lanes": 400e-9, "gwlz.entropy.fit": 100e-9,
         "gwlz.ingest": 300e-9})
    assert r["idle_pct"]["executor"] == pytest.approx(70.0)
    assert r["idle_pct"]["entropy"] == pytest.approx(10.0)
    assert r["idle_pct"]["-"] == 0.0
    assert r["span_s"]["gwlz.ingest"] == pytest.approx(1000e-9)  # clipped


def test_a_span_nested_from_the_same_instant_is_the_inner_one():
    ex = _ex([("p/a", 1000, 100)],
             [["gwlz.ingest", "T0", 100, 1000, 0],
              ["gwlz.ingest.read", "T0", 100, 300, 0]])
    r = stages.reduce(ex)
    assert r["idle_by_span"] == pytest.approx(
        {"gwlz.ingest.read": 300e-9, "gwlz.ingest": 600e-9})


def test_only_the_operation_thread_names_idle():
    ex = _ex([("p/a", 1000, 50)],
             [["gwlz.ingest", "main", 400, 500, 0],
              ["gwlz.ingest.append", "writer", 100, 1000, 64]])
    r = stages.reduce(ex)
    assert r["idle_by_span"] == pytest.approx({"-": 450e-9, "gwlz.ingest": 500e-9})
    assert r["span_s"]["gwlz.ingest.append"] == pytest.approx(1000e-9)
    assert r["span_bytes"]["gwlz.ingest.append"] == 64


@pytest.mark.parametrize("n_ops", [1, 3])
def test_idle_by_span_sums_to_the_window_idle(n_ops):
    ops = [("p/a", 150 + 300 * k, 120) for k in range(n_ops)]
    ex = _ex(ops, [["gwlz.decode", "T0", 50, 700, 0],
                   ["gwlz.decode.lanes", "T0", 120, 200, 0],
                   ["gwlz.entropy.inflate", "T1", 130, 50, 0]])
    r = stages.reduce(ex)
    idle = 1000e-9 - trace.reduce(ex)["busy_s"]
    assert sum(r["idle_by_span"].values()) == pytest.approx(idle)
    assert sum(r["idle_pct"].values()) == pytest.approx(100.0 * idle / 1000e-9)


def test_metadata_after_the_name_is_ignored():
    ex = _ex([("p/a", 1000, 100)],
             [["gwlz.ingest", "T0", 100, 1000, 0],
              ["gwlz.entropy.deflate#nbytes=10#", "T0", 200, 100, 10]])
    r = stages.reduce(ex)
    assert r["span_bytes"]["gwlz.entropy.deflate"] == 10
    assert r["idle_by_span"]["gwlz.entropy.deflate"] == pytest.approx(100e-9)
    assert r["idle_pct"]["entropy"] == pytest.approx(10.0)


def test_lane_decode_share_only_where_the_window_decodes():
    dec = stages.reduce(_ex([("p/a", 1000, 100)],
                            [["gwlz.decode", "T0", 100, 1000, 0],
                             ["gwlz.decode.lanes", "T0", 100, 250, 0]]))
    assert dec["lane_decode_share"] == pytest.approx(25.0)
    ing = stages.reduce(_ex([("p/a", 1000, 100)],
                            [["gwlz.ingest", "T0", 100, 1000, 0]]))
    assert "lane_decode_share" not in ing


def test_a_program_without_spans_leaves_all_idle_unattributed():
    r = stages.reduce(_ex([("p/a", 100, 400)], []))
    assert r["idle_by_span"] == pytest.approx({"-": 600e-9})
    assert r["idle_pct"]["-"] == pytest.approx(60.0)
    assert r["idle_pct"]["entropy"] == r["idle_pct"]["executor"] == 0.0


def test_head_cuts_the_window():
    ex = _ex([("p/a", 150, 10), ("p/b", 900, 10)],
             [["gwlz.ingest", "T0", 100, 1000, 0],
              ["gwlz.ingest.read", "T0", 800, 50, 0]])
    h = stages.head(ex, 500e-9)
    assert trace.window_bounds(h) == (100, 600)
    assert [o[0] for o in h["ops"][DEV]] == ["p/a"]
    assert [s[0] for s in h["spans"]] == ["gwlz.ingest"]


def test_a_traced_cpu_run_reports_its_stages(tiny_root):
    # no device plane on the CPU: the spans are read, no idle is put down
    result, ex = stages.run_traced("nyx-dmd-512-eb1e-4.ingest-plain", 2**33 + 7,
                                   0.5, root=tiny_root,
                                   bench_dir=tiny_root / "bench")
    st = result["stages"]
    n = st["span_n"]["gwlz.ingest"]
    assert n >= 1 and st["span_n"]["gwlz.ingest.read"] >= n
    assert st["span_bytes"]["gwlz.entropy.deflate"] > 0
    assert st["span_n"]["gwlz.entropy.deflate_out"] == st["span_n"]["gwlz.entropy.deflate"]
    assert st["idle_by_span"] == {}
    assert {s[0] for s in ex["spans"]} >= {"gwlz.ingest", "gwlz.entropy.fit"}
    assert trace.extract.__module__ == "bench.trace"  # the harness's again


def test_recorded_plain_ingest():
    ex = json.loads(RECORDED.read_text())
    r = stages.reduce(ex)
    t = trace.reduce(ex)
    idle = t["window_s"] - t["busy_s"]
    assert t["devices"] == 1
    assert r["window_s"] == pytest.approx(0.5)
    assert sum(r["idle_by_span"].values()) == pytest.approx(idle)
    assert t["busy_s"] == pytest.approx(0.022252591)
    named = sum(r["idle_pct"][k] for k in ("entropy", "executor", "training"))
    assert named >= 0.9 * 100.0 * idle / t["window_s"]
    assert r["idle_pct"]["entropy"] == pytest.approx(74.1625714)
    assert r["idle_pct"]["executor"] == pytest.approx(21.3628966)
    assert r["idle_pct"]["-"] == pytest.approx(0.0240138)
    assert max(r["idle_by_span"], key=r["idle_by_span"].get) == "gwlz.entropy.hist"
    assert r["span_n"]["gwlz.entropy.deflate"] == 25
