"""Host spans and counters (``repro.obs``) and what a streamed compression
reports through them (``StreamReport.stages``, ``programs_compiled``)."""
import threading

import numpy as np
import pytest

from repro import api, obs
from repro.core.trainer import GWLZTrainConfig
from repro.exec import stream_compress


@pytest.fixture(scope="module")
def field():
    from repro.data import nyx_like_field

    x = np.asarray(nyx_like_field((32, 32, 32), "temperature", seed=11), np.float32)
    return x / np.float32(np.abs(x).max())


def test_spans_nest_and_add_up_in_the_bound_collector():
    with obs.collect() as col:
        with obs.span("gwlz.t", 10):
            with obs.span("gwlz.t.inner", 3):
                pass
            with obs.span("gwlz.t.inner", 4):
                pass
        obs.count("gwlz.t.out", 7)
    st = col.stages()
    assert st["gwlz.t"][0] == 1 and st["gwlz.t"][2] == 10
    assert st["gwlz.t.inner"][0] == 2 and st["gwlz.t.inner"][2] == 7
    assert st["gwlz.t"][1] >= st["gwlz.t.inner"][1] >= 0.0
    assert st["gwlz.t.out"][0] == 1 and st["gwlz.t.out"][2] == 7


def test_a_span_outside_a_collector_records_nothing_and_raises_through():
    with obs.collect() as col:
        pass
    with pytest.raises(KeyError):
        with obs.span("gwlz.t"):
            raise KeyError("x")
    obs.count("gwlz.t.out", 1)
    assert col.stages() == {}


def test_a_thread_reports_only_through_a_copied_context():
    import contextvars

    def work():
        with obs.span("gwlz.t.worker"):
            pass

    with obs.collect() as col:
        plain = threading.Thread(target=work)
        copied = threading.Thread(target=contextvars.copy_context().run,
                                  args=(work,))
        for t in (plain, copied):
            t.start()
            t.join(timeout=30)
            assert not t.is_alive()
    assert col.stages()["gwlz.t.worker"][0] == 1


def test_stream_stages_come_from_both_threads(tmp_path, field):
    rep = stream_compress(field, tmp_path / "a.gwtc", abs_eb=1e-3,
                          tile=(16, 16, 16), mem_budget=200_000, use_pallas=False)
    st = rep.stages
    assert st["gwlz.ingest"][0] == 1
    # the caller's thread
    for name in ("gwlz.ingest.read", "gwlz.ingest.encode", "gwlz.ingest.fetch"):
        assert st[name][0] == rep.n_batches, name
    assert st["gwlz.ingest.fetch"][2] == field.nbytes  # int32 codes, 8 tiles
    assert st["gwlz.ingest.finalize"][0] == 1
    # the writer thread: the append, and with host entropy the lane coding
    assert st["gwlz.ingest.append"][0] == rep.n_batches
    assert st["gwlz.entropy.fit"][0] == rep.n_tiles
    assert st["gwlz.entropy.deflate"][0] == rep.n_tiles
    assert st["gwlz.entropy.deflate_out"][0] == rep.n_tiles
    assert 0 < st["gwlz.entropy.deflate_out"][2] < rep.nbytes
    assert rep.host_stage_s == st["gwlz.ingest.append"][1] > 0.0
    assert st["gwlz.ingest"][1] >= st["gwlz.ingest.read"][1]


def test_stream_stages_with_device_entropy_and_training(tmp_path, field):
    cfg = GWLZTrainConfig(n_groups=2, epochs=2, batch_size=8, min_group_pixels=16)
    rep = stream_compress(field, tmp_path / "e.gwtc", abs_eb=1e-3,
                          tile=(16, 16, 16), mem_budget=400_000, enhance=cfg,
                          use_pallas=True)
    st = rep.stages
    assert rep.entropy_device and rep.enhanced
    # lane coding runs on the caller's thread, inside gwlz.ingest.lanes
    assert st["gwlz.ingest.lanes"][0] == rep.n_batches
    for name in ("gwlz.entropy.fit", "gwlz.entropy.pack",
                 "gwlz.entropy.splice", "gwlz.entropy.deflate"):
        assert st[name][0] == rep.n_tiles, name
    assert st["gwlz.ingest.lanes"][1] >= st["gwlz.entropy.pack"][1]
    assert st["gwlz.ingest.reservoir"][0] == rep.n_batches
    slices = rep.reservoir_tiles * 16
    assert st["gwlz.train.step"][0] == cfg.epochs * (slices // cfg.batch_size)
    for name in ("gwlz.train", "gwlz.train.stage", "gwlz.train.groups",
                 "gwlz.train.calibrate", "gwlz.train.gate",
                 "gwlz.train.serialize"):
        assert st[name][0] == 1, name
    assert st["gwlz.train"][1] >= st["gwlz.train.step"][1]
    # the writer thread only appends and commits
    assert rep.host_stage_s == st["gwlz.ingest.append"][1]


def test_no_collector_outlives_its_stream(tmp_path, field):
    kw = dict(abs_eb=1e-3, tile=(16, 16, 16), mem_budget=200_000,
              use_pallas=False)
    a = stream_compress(field, tmp_path / "a.gwtc", **kw)
    b = stream_compress(field[:16], tmp_path / "b.gwtc", **kw)
    assert obs._COLLECTOR.get() is None
    assert a.stages["gwlz.ingest"][0] == b.stages["gwlz.ingest"][0] == 1
    assert a.stages["gwlz.ingest.read"][0] == a.n_batches
    assert b.stages["gwlz.ingest.read"][0] == b.n_batches < a.n_batches
    assert b.stages["gwlz.entropy.fit"][0] == b.n_tiles == a.n_tiles // 2


def test_programs_compiled_counts_backend_compiles(tmp_path):
    # a tile no other test uses, so the first stream compiles its programs
    x = np.linspace(0, 1, 12 * 10 * 6, dtype=np.float32).reshape(12, 10, 6)
    kw = dict(abs_eb=1e-3, tile=(6, 5, 3), mem_budget=100_000, use_pallas=False)
    first = stream_compress(x, tmp_path / "a.gwtc", **kw)
    again = stream_compress(x, tmp_path / "b.gwtc", **kw)
    assert first.programs_compiled > 0
    assert again.programs_compiled == 0


def test_full_decode_spans(tmp_path, field):
    path = tmp_path / "d.gwtc"
    stream_compress(field, path, abs_eb=1e-3, tile=(16, 16, 16),
                    mem_budget=200_000, use_pallas=False)
    with api.open(path) as vol, obs.collect() as col:
        np.asarray(vol)
    st = col.stages()
    for name in ("gwlz.decode", "gwlz.decode.lanes", "gwlz.decode.upload",
                 "gwlz.decode.reconstruct", "gwlz.decode.stitch",
                 "gwlz.decode.fetch"):
        assert st[name][0] == 1, name
    assert st["gwlz.decode"][1] >= st["gwlz.decode.lanes"][1]
