"""Concurrent region-serving daemon + read-path concurrency fixes.

Covers the ISSUE 7 tentpole and bugfix satellites: exact lock-guarded
``DecodeStats`` under a thread hammer; ``TileCache`` counter/lock fixes and
single-flight claim coalescing; the shared-cache injection path through
``api.open``; the ``repro.serve`` pool + HTTP daemon (bit-equal regions
under concurrency, including quarantined volumes); admission control; and
the CLI's normalized exit codes (0 ok / 1 integrity / 2 usage).
"""
from __future__ import annotations

import threading

import numpy as np
import pytest

from repro import api, cli
from repro.data import nyx_like_field
from repro.exec.cache import TileCache
from repro.exec.plan import max_inflight_tiles, tile_working_bytes
from repro.serve import (
    AdmissionController,
    RegionServer,
    RequestRejected,
    VolumePool,
    fetch_json,
    fetch_region,
)
from repro.sz import tiled


@pytest.fixture(scope="module")
def field():
    return np.asarray(nyx_like_field((24, 24, 24), "temperature", seed=5),
                      np.float32)


@pytest.fixture(scope="module")
def tiled_vol(field):
    return api.compress(field, abs_eb=float(np.ptp(field)) * 1e-3, tiled=True,
                        tile=(8, 8, 8), predictor="lorenzo")


@pytest.fixture(scope="module")
def full(tiled_vol):
    return np.asarray(api.CompressedVolume(tiled_vol.artifact))


def _gwtc_path(tmp_path, vol, name="v.gwtc"):
    out = tmp_path / name
    api.save(out, vol)
    return out


# ---------------------------------------------------------------------------
# TileCache: lock fixes, counters, single-flight
# ---------------------------------------------------------------------------


def test_cache_counters_and_hit_rate():
    cache = TileCache(1 << 20)
    a = np.zeros(16, np.float32)
    cache.put("k", a)
    assert cache.get_many(["k", "missing"]).keys() == {"k"}
    info = cache.info()
    assert (info["hits"], info["misses"]) == (1, 1)
    assert info["hit_rate"] == 0.5
    assert cache.hits == 1 and cache.misses == 1
    # nbytes/__len__ are lock-guarded snapshots, still correct values
    assert cache.nbytes == a.nbytes and len(cache) == 1


def test_cache_claim_partitions_atomically():
    cache = TileCache(1 << 20)
    cache.put(1, np.zeros(4, np.float32))
    found, mine, theirs = cache.claim([1, 2, 3])
    assert set(found) == {1} and mine == [2, 3] and theirs == {}
    # a second claimant sees the first one's in-flight keys, owns nothing
    found2, mine2, theirs2 = cache.claim([2, 3])
    assert found2 == {} and mine2 == [] and set(theirs2) == {2, 3}
    v = np.ones(4, np.float32)
    cache.fulfill(2, v)
    got = cache.wait(theirs2[2], timeout=5)
    np.testing.assert_array_equal(got, v)
    # abandon wakes waiters empty-handed; the key is claimable again
    cache.abandon([3])
    assert cache.wait(theirs2[3], timeout=5) is None
    _f, mine3, theirs3 = cache.claim([3])
    assert mine3 == [3] and theirs3 == {}
    cache.abandon([3])


@pytest.mark.parametrize("capacity", [1 << 20, 0])
def test_cache_single_flight_under_contention(capacity):
    """Threads racing for one missing key: owners are elected through the
    in-flight registry and every non-owner receives the decoded value via
    the flight hand-off — even with a ZERO-capacity cache that can never
    retain the tile (there, a claim arriving after a fulfill legitimately
    elects a new owner, but no claim is ever left hanging)."""
    cache = TileCache(capacity)
    owners: list[int] = []
    values: list[np.ndarray] = []
    lock = threading.Lock()
    gate = threading.Barrier(16)

    def worker(seed: int) -> None:
        gate.wait()
        found, mine, theirs = cache.claim(["tile"])
        if mine:
            with lock:
                owners.append(seed)
            cache.fulfill("tile", np.full(8, seed, np.float32))
        elif theirs:
            v = cache.wait(theirs["tile"], timeout=10)
            with lock:
                values.append(v)
        else:
            with lock:
                values.append(found["tile"])

    ts = [threading.Thread(target=worker, args=(s,)) for s in range(16)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert len(owners) + len(values) == 16
    assert len(owners) >= 1
    assert all(v is not None and v[0] in owners for v in values), \
        "every waiter must receive some owner's decoded value"
    if capacity:  # retained tile: later claims hit the cache, one owner ever
        assert len(owners) == 1
        assert all(v[0] == owners[0] for v in values)
    assert cache.info()["inflight"] == 0


def test_cache_namespace_drop():
    cache = TileCache(1 << 20)
    for ns in ("a", "b"):
        for i in range(3):
            cache.put((ns, i), np.zeros(8, np.float32))
    assert cache.drop_namespace("a") == 3
    assert len(cache) == 3
    assert set(cache.get_many([("b", i) for i in range(3)])) \
        == {("b", i) for i in range(3)}


# ---------------------------------------------------------------------------
# DecodeStats: exact counters under a thread hammer (bugfix satellite)
# ---------------------------------------------------------------------------


def test_decode_stats_exact_under_hammer(field, full):
    """N threads hammer overlapping ROIs on ONE handle: with lock-guarded
    stats and single-flight decode the counters are EXACT — every lane
    decodes once, and decoded + hits equals the total lane touches."""
    vol = api.compress(field, abs_eb=float(np.ptp(field)) * 1e-3, tiled=True,
                       tile=(8, 8, 8), predictor="lorenzo")
    rois = [(slice(0, 12), slice(0, 24), slice(4, 20)),
            (slice(8, 24), slice(8, 16), slice(0, 8)),
            (slice(0, 8), slice(0, 8), slice(0, 24))]
    touches_per_pass = sum(api.region_lane_count(vol, r)[0] for r in rois)
    union = set()
    for r in rois:
        ids, _ = tiled.region_tiles(vol.artifact, r)
        union.update(ids.tolist())
    n_threads, errors = 12, []
    gate = threading.Barrier(n_threads)

    def worker() -> None:
        gate.wait()
        try:
            for r in rois:
                np.testing.assert_array_equal(vol[r], np.asarray(full)[r])
        except Exception as e:  # pragma: no cover - surfaced below
            errors.append(e)

    ts = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errors, errors[0]
    assert vol.stats.tiles_decoded == len(union), \
        "single-flight must decode each lane exactly once"
    assert vol.stats.tiles_decoded + vol.stats.cache_hits \
        == n_threads * touches_per_pass, "no lost counter updates"


def test_shared_cache_injection_and_close(tmp_path, tiled_vol, full):
    """Two handles share one injected cache under distinct namespaces;
    closing one evicts only its own tiles."""
    p1 = _gwtc_path(tmp_path, tiled_vol, "a.gwtc")
    p2 = _gwtc_path(tmp_path, tiled_vol, "b.gwtc")
    shared = TileCache(8 << 20)
    v1 = api.open(p1, tile_cache=shared, cache_ns="a")
    v2 = api.open(p2, tile_cache=shared, cache_ns="b")
    roi = (slice(0, 8),) * 3
    np.testing.assert_array_equal(v1[roi], full[roi])
    np.testing.assert_array_equal(v2[roi], full[roi])
    assert len(shared) == 2  # one tile each, namespaced apart
    v1.close()
    assert len(shared) == 1, "closing a pooled handle keeps its neighbors"
    np.testing.assert_array_equal(v2[roi], full[roi])
    assert v2.stats.cache_hits >= 1
    v2.close()
    assert len(shared) == 0


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------


def test_admission_budget_and_oversize():
    adm = AdmissionController(100, max_queue=8, timeout=5.0)
    adm.admit(60)
    done = threading.Event()

    def second() -> None:
        adm.admit(60)  # must wait: 120 > 100
        done.set()

    t = threading.Thread(target=second)
    t.start()
    assert not done.wait(0.15), "over-budget request must queue"
    adm.release(60)
    assert done.wait(5), "release must wake the waiter"
    adm.release(60)
    t.join()
    # oversize: admitted alone rather than deadlocking
    adm.admit(10_000)
    adm.release(10_000)
    assert adm.info()["inflight_bytes"] == 0


def test_admission_queue_full_rejects():
    adm = AdmissionController(10, max_queue=1, timeout=5.0)
    adm.admit(10)
    blocked = threading.Thread(target=lambda: (adm.admit(5), adm.release(5)))
    blocked.start()
    for _ in range(100):
        if adm.info()["queue_depth"] == 1:
            break
        threading.Event().wait(0.01)
    with pytest.raises(RequestRejected):
        adm.admit(5)  # queue already holds max_queue waiters
    assert adm.info()["rejected"] == 1
    adm.release(10)
    blocked.join()


def test_admission_cost_uses_plan_estimate(tmp_path, tiled_vol):
    pool = VolumePool({"v": _gwtc_path(tmp_path, tiled_vol)},
                      cache_bytes=1 << 20, mem_budget=32 << 20)
    with pool:
        vol = pool.volume("v")
        art = vol.artifact
        per = tile_working_bytes(art.tile, art.predictor, art.levels)
        _block, meta = pool.region("v", "0:8,0:8,0:8")
        assert meta["cost_bytes"] == meta["lanes"] * per
        assert max_inflight_tiles(32 << 20, art.tile) == (32 << 20) // per


# ---------------------------------------------------------------------------
# the pool + daemon
# ---------------------------------------------------------------------------


def test_pool_region_info_metrics(tmp_path, tiled_vol, full):
    pool = VolumePool({"nyx": _gwtc_path(tmp_path, tiled_vol)},
                      cache_bytes=8 << 20, mem_budget=8 << 20)
    with pool:
        block, meta = pool.region("nyx", "0:12,:,4:20")
        np.testing.assert_array_equal(block, full[0:12, :, 4:20])
        lanes = api.region_lane_count(pool.volume("nyx"),
                                      (slice(0, 12), slice(None),
                                       slice(4, 20)))[0]
        assert meta["lanes"] == lanes and meta["lanes_total"] == 27
        pool.region("nyx", "0:12,:,4:20")  # repeat: all hits
        info = pool.info("nyx")
        assert info["stats"]["cache_hits"] >= lanes
        m = pool.metrics_snapshot()
        assert m["requests"] == 2 and m["cache"]["hit_rate"] > 0
        assert m["latency_ms"]["count"] == 2
        assert m["volumes"]["nyx"]["tiles_decoded"] == lanes
        with pytest.raises(KeyError, match="no volume"):
            pool.region("nope", "0:4")
        with pytest.raises(ValueError):
            pool.add_volume("nyx", _gwtc_path(tmp_path, tiled_vol))


def test_daemon_concurrent_http_bit_equal(tmp_path, tiled_vol, field, full):
    """Tentpole acceptance (scaled for tier-1): concurrent clients fetching
    overlapping ROIs over real HTTP get bytes bit-equal to ``full[roi]``,
    including from an ``on_corrupt="quarantine"`` volume in the same pool,
    while the shared cache reports a true hit rate."""
    good = _gwtc_path(tmp_path, tiled_vol, "good.gwtc")
    blob = bytearray(good.read_bytes())
    blob[tiled._HDR_V3.size + 16 * 3 + 7] ^= 0x10  # flip a bit in lane 0
    bad = tmp_path / "bad.gwtc"
    bad.write_bytes(bytes(blob))

    pool = VolumePool(cache_bytes=16 << 20, mem_budget=16 << 20,
                      on_corrupt="quarantine", fill_value=-7.0)
    pool.add_volume("good", good)
    pool.add_volume("quar", bad)
    # reference decodes through independent handles with the same policy
    with api.open(bad, on_corrupt="quarantine", fill_value=-7.0) as ref:
        quar_full = np.asarray(ref).copy()
    assert np.all(quar_full[:8, :8, :8] == -7.0)

    errors: list[Exception] = []
    gate = threading.Barrier(8)

    def client(seed: int) -> None:
        rng = np.random.default_rng(seed)
        try:
            gate.wait()
            for _ in range(6):
                lo = rng.integers(0, 16, 3)
                hi = lo + rng.integers(4, 12, 3)
                roi = ",".join(f"{a}:{min(int(b), 24)}"
                               for a, b in zip(lo, hi))
                sl = tuple(slice(*map(int, t.split(":")))
                           for t in roi.split(","))
                name, want = (("good", full) if seed % 2 else
                              ("quar", quar_full))
                arr, meta = fetch_region(server.url, name, roi)
                np.testing.assert_array_equal(arr, want[sl])
                assert meta["lanes_total"] == 27
        except Exception as e:  # pragma: no cover - surfaced below
            errors.append(e)

    with RegionServer(pool) as server:
        ts = [threading.Thread(target=client, args=(s,)) for s in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errors, errors[0]

        health = fetch_json(server.url, "/healthz")
        assert health == {"status": "ok", "volumes": ["good", "quar"]}
        m = fetch_json(server.url, "/metrics")
        assert m["requests"] == 48 and m["errors"] == 0
        assert m["cache"]["hit_rate"] > 0, "overlapping ROIs must share"
        assert m["volumes"]["quar"]["quarantined"] == 1
        assert m["latency_ms"]["p99"] >= m["latency_ms"]["p50"]
        # error surface: unknown volume 404, bad roi 400, bad route 404
        with pytest.raises(RuntimeError, match="404"):
            fetch_region(server.url, "nope", "0:4")
        with pytest.raises(RuntimeError, match="400"):
            fetch_region(server.url, "good", "banana")
        info = fetch_json(server.url, "/v/good/info")
        assert info["tiled"] and info["n_lanes"] == 27
    assert len(pool.names) == 0, "server close must close the pool"


# ---------------------------------------------------------------------------
# ETag revalidation: 304 without decode (ISSUE 8 satellite)
# ---------------------------------------------------------------------------


def test_pool_region_etag_canonical(tmp_path, tiled_vol):
    """ETags hash the CANONICAL ROI: equivalent spellings revalidate each
    other, different regions never collide, and the tag is a strong quoted
    token stable across calls."""
    pool = VolumePool({"nyx": _gwtc_path(tmp_path, tiled_vol)},
                      cache_bytes=1 << 20, mem_budget=8 << 20)
    with pool:
        e1, _ = pool.region_etag("nyx", "0:8,0:8,0:8")
        e2, _ = pool.region_etag("nyx", ":8,:8,:8")
        e3, _ = pool.region_etag("nyx", "0:8,0:8,0:8")
        assert e1 == e2 == e3
        assert e1.startswith('"') and e1.endswith('"')
        e4, _ = pool.region_etag("nyx", "8:16,0:8,0:8")
        assert e4 != e1
        with pytest.raises(KeyError):
            pool.region_etag("nope", "0:4")


def test_daemon_etag_304_skips_decode(tmp_path, tiled_vol, full):
    """Revalidating with the returned ETag answers 304 with an empty body:
    no decode work runs (tiles_decoded frozen, no latency sample), yet the
    request and the not_modified counter both advance."""
    pool = VolumePool({"nyx": _gwtc_path(tmp_path, tiled_vol)},
                      cache_bytes=8 << 20, mem_budget=8 << 20)
    with RegionServer(pool) as srv:
        arr1, meta1 = fetch_region(srv.url, "nyx", "0:8,8:16,0:8")
        np.testing.assert_array_equal(arr1, full[0:8, 8:16, 0:8])
        assert meta1["etag"]
        m1 = fetch_json(srv.url, "/metrics")

        # exact and canonical-equivalent ROI spellings both revalidate
        arr2, meta2 = fetch_region(srv.url, "nyx", "0:8,8:16,0:8",
                                   etag=meta1["etag"])
        assert arr2 is None and meta2["etag"] == meta1["etag"]
        arr3, _ = fetch_region(srv.url, "nyx", ":8,8:16,:8",
                               etag=meta1["etag"])
        assert arr3 is None

        m2 = fetch_json(srv.url, "/metrics")
        assert m2["not_modified"] == 2
        assert m2["requests"] == m1["requests"] + 2
        assert m2["volumes"]["nyx"]["tiles_decoded"] \
            == m1["volumes"]["nyx"]["tiles_decoded"], "304 must not decode"
        assert m2["latency_ms"]["count"] == m1["latency_ms"]["count"], \
            "304s take no latency sample"

        # a stale tag for a DIFFERENT region is a miss: full 200 + new tag
        arr4, meta4 = fetch_region(srv.url, "nyx", "8:16,8:16,0:8",
                                   etag=meta1["etag"])
        np.testing.assert_array_equal(arr4, full[8:16, 8:16, 0:8])
        assert meta4["etag"] != meta1["etag"]
        m3 = fetch_json(srv.url, "/metrics")
        assert m3["not_modified"] == 2 and m3["errors"] == 0


def test_daemon_serves_integer_index_rois(tmp_path, tiled_vol, full):
    """An integer index in the ROI drops that axis, like numpy: the daemon
    serves it (the ETag path used to crash on it), and its tag differs from
    the one-wide slice that reads the same voxels but keeps the axis."""
    pool = VolumePool({"nyx": _gwtc_path(tmp_path, tiled_vol)},
                      cache_bytes=8 << 20, mem_budget=8 << 20)
    with RegionServer(pool) as srv:
        arr, meta = fetch_region(srv.url, "nyx", "12,:,3:9")
        np.testing.assert_array_equal(arr, full[12, :, 3:9])
        neg, _ = fetch_region(srv.url, "nyx", "-1,5,:")
        np.testing.assert_array_equal(neg, full[-1, 5, :])
        wide, meta_wide = fetch_region(srv.url, "nyx", "12:13,:,3:9")
        np.testing.assert_array_equal(wide, full[12:13, :, 3:9])
        assert meta["etag"] != meta_wide["etag"]
        assert fetch_json(srv.url, "/metrics")["errors"] == 0


# ---------------------------------------------------------------------------
# CLI: normalized exit codes (0 ok / 1 integrity / 2 usage) + serve
# ---------------------------------------------------------------------------


def _exit_code(argv) -> int:
    try:
        rc = cli.main(argv)
    except SystemExit as e:
        return int(e.code or 0)
    return int(rc or 0)


def test_cli_usage_errors_exit_2(tmp_path, tiled_vol):
    out = _gwtc_path(tmp_path, tiled_vol)
    assert _exit_code(["region", str(tmp_path / "missing.gwtc"),
                       "--roi", "0:4"]) == 2
    assert _exit_code(["region", str(out), "--roi", "banana"]) == 2
    assert _exit_code(["region", str(out), "--roi", "0:4", "--field", "t"]) == 2
    assert _exit_code(["verify", str(out), "--field", "t"]) == 2
    assert _exit_code(["decompress", str(tmp_path / "missing.gwtc"),
                       str(tmp_path / "o.npy")]) == 2
    assert _exit_code(["compress", str(tmp_path / "missing.npy"),
                       str(tmp_path / "o.gwtc"), "--eb", "1e-3"]) == 2
    assert _exit_code(["compress", "synthetic:temperature:8",
                       str(tmp_path / "o.gwtc"), "--eb", "1e-3",
                       "--resume"]) == 2


def test_cli_integrity_errors_exit_1(tmp_path, tiled_vol):
    out = _gwtc_path(tmp_path, tiled_vol)
    blob = bytearray(out.read_bytes())
    blob[tiled._HDR_V3.size + 16 * 3 + 5] ^= 0x10
    bad = tmp_path / "bad.gwtc"
    bad.write_bytes(bytes(blob))
    assert _exit_code(["verify", str(bad)]) == 1
    assert _exit_code(["region", str(bad), "--roi", "0:8,0:8,0:8"]) == 1
    assert _exit_code(["verify", str(out)]) == 0
    assert _exit_code(["region", str(out), "--roi", "0:8,0:8,0:8"]) == 0


def test_cli_serve_smoke_and_usage(tmp_path, tiled_vol, capsys):
    out = _gwtc_path(tmp_path, tiled_vol, "nyx.gwtc")
    assert _exit_code(["serve", f"v={out}", "--port", "0", "--smoke"]) == 0
    text = capsys.readouterr().out
    assert "smoke ok" in text and "hit_rate" in text
    assert _exit_code(["serve", f"a={out}", f"a={out}", "--port", "0"]) == 2
    assert _exit_code(["serve", str(tmp_path / "missing.gwtc"),
                       "--port", "0"]) == 2
    assert _exit_code(["serve", f"v={out}", "--port", "0",
                       "--cache-bytes", "banana"]) == 2


# ---------------------------------------------------------------------------
# DecodeBatcher: cross-request micro-batched dispatch (ISSUE 10)
# ---------------------------------------------------------------------------


def test_decode_batcher_coalesces_across_threads():
    """N concurrent single-lane submits to one volume must collapse into ONE
    decode call: with ``max_batch_tiles == N`` the leader cannot drain until
    every submitter has arrived, so the round is deterministic."""
    from repro.exec.cache import DecodeBatcher

    calls: list[list[int]] = []
    lock = threading.Lock()

    def decode(ids):
        with lock:
            calls.append(list(ids))
        return {i: i * 10 for i in ids}

    n = 8
    b = DecodeBatcher(max_wait_ms=5000.0, max_batch_tiles=n)
    gate = threading.Barrier(n)
    out: dict[int, dict] = {}

    def worker(i):
        gate.wait()
        got = b.submit("vol", [i], decode)
        with lock:
            out[i] = got

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(calls) == 1 and sorted(calls[0]) == list(range(n))
    assert out == {i: {i: i * 10} for i in range(n)}
    assert b.dispatches == 1 and b.submits == n
    assert b.coalesced_submits == n - 1
    assert b.pending_tiles == 0 and b.peak_pending_tiles == n
    info = b.info()
    assert info["batch_hist"] == {str(n): 1}


def test_decode_batcher_propagates_leader_error():
    """A decode failure in the leader must surface in EVERY submitter of the
    round — a follower silently getting an empty dict would serve garbage."""
    from repro.exec.cache import DecodeBatcher

    def boom(ids):
        raise RuntimeError("lane decode failed")

    n = 4
    b = DecodeBatcher(max_wait_ms=5000.0, max_batch_tiles=n)
    gate = threading.Barrier(n)
    errs: list[str] = []
    lock = threading.Lock()

    def worker(i):
        gate.wait()
        try:
            b.submit("vol", [i], boom)
        except RuntimeError as e:
            with lock:
                errs.append(str(e))

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(errs) == n and all("lane decode failed" in e for e in errs)
    assert b.pending_tiles == 0, "a failed round must not leak queue depth"


def test_decode_batcher_dedups_and_empty():
    from repro.exec.cache import DecodeBatcher

    seen: list[list[int]] = []

    def decode(ids):
        seen.append(list(ids))
        return {i: -i for i in ids}

    b = DecodeBatcher(max_wait_ms=0.0, max_batch_tiles=64)
    assert b.submit("v", [], decode) == {}
    got = b.submit("v", [3, 3, 5], decode)
    assert seen == [[3, 5]], "duplicate lane ids must decode once"
    assert got == {3: -3, 5: -5}


def test_pool_batcher_metrics_and_bucketed_cost(tmp_path, tiled_vol, full):
    """The pool prices admission on the PADDED batch (6 lanes bucket to 8),
    routes decodes through its batcher, and exposes both the batcher and the
    process-wide compile/dispatch counters in /metrics."""
    from repro.exec.plan import bucketed_batch_tiles

    pool = VolumePool({"v": _gwtc_path(tmp_path, tiled_vol)},
                      cache_bytes=1 << 20, mem_budget=32 << 20,
                      batch_wait_ms=1.0)
    with pool:
        art = pool.volume("v").artifact
        per = tile_working_bytes(art.tile, art.predictor, art.levels)
        roi = "0:17,0:9,0:8"  # 3*2*1 = 6 lanes -> one width-8 bucket
        block, meta = pool.region("v", roi)
        np.testing.assert_array_equal(block, full[0:17, 0:9, 0:8])
        assert meta["lanes"] == 6
        assert bucketed_batch_tiles(6) == 8
        assert meta["cost_bytes"] == 8 * per, \
            "admission must price the padded batch, not the raw lane count"
        m = pool.metrics_snapshot()
        assert m["batcher"]["dispatches"] >= 1
        assert m["batcher"]["submits"] >= 1
        assert m["decode"]["programs"] >= 1
        assert m["decode"]["dispatches"] >= 1
        assert all(isinstance(k, str) for k in m["decode"]["batch_hist"])


def test_pool_no_batcher_mode(tmp_path, tiled_vol, full):
    """``batch_wait_ms=None`` (the CLI's ``--no-batcher``) must serve the
    same bytes with no batcher block in /metrics."""
    pool = VolumePool({"v": _gwtc_path(tmp_path, tiled_vol)},
                      cache_bytes=1 << 20, mem_budget=32 << 20,
                      batch_wait_ms=None)
    with pool:
        assert pool.batcher is None
        block, _ = pool.region("v", "0:12,:,4:20")
        np.testing.assert_array_equal(block, full[0:12, :, 4:20])
        assert "batcher" not in pool.metrics_snapshot()
