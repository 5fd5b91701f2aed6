"""Serving-daemon load test: hundreds of concurrent readers, one shared
budgeted tile cache — thresholds ASSERTED, not just printed.

    PYTHONPATH=src:. python -m benchmarks.serve_load [--fast] [--json PATH]

Drives the real HTTP daemon (``repro.serve.RegionServer`` on an ephemeral
port) with ``--readers`` concurrent client threads issuing overlapping
ROI requests drawn from a shared pool against one volume, then asserts the
three properties the tentpole promises (docs/SERVING.md):

* **correctness** — every served region is byte-compared against
  ``full[roi]`` from an independent eager decode; one mismatch fails,
* **cache sharing** — the aggregate hit rate over the shared cache must
  clear ``--min-hit-rate`` (overlapping ROIs + single-flight mean each
  lane entropy-decodes roughly once no matter how many clients want it),
* **latency** — p99 region latency (client-observed, queueing included)
  must stay under ``--p99-ms``,
* **compile stability** — after a warmup pass that touches every decode
  bucket, the storm must trigger **zero** new decode programs
  (``recompiles_after_warmup == 0``); bucketed padding bounds the set of
  compiled executables, and this assertion is what keeps it bounded,
* **dispatch reduction** — a serialized in-process phase hammers one
  volume with ``--readers`` concurrent single-lane region reads, batcher
  off then on, and asserts the cross-request micro-batcher cuts device
  dispatches by at least 2x.

``--batcher off`` disables the pool's cross-request decode batcher (CI
runs both modes and uploads both reports); ``--max-wait-ms`` sets the
batcher's coalescing window.

Emits ``serve_load/...`` rows in the harness CSV schema and, with
``--json``, a machine-readable report CI uploads next to the throughput
artifact.  ``--fast`` shrinks the volume, not the concurrency: the
100-reader floor is the acceptance criterion and always holds.
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
import time

import numpy as np


def _warm_decode_buckets(handle) -> None:
    """Compile every decode program the storm can reach: one decode per
    power-of-two bucket width up to the cap (27 lanes under the cap also
    touches the cap-width bucket via padding).  Goes through the pipeline
    directly so the tile cache stays cold for the hit-rate assertion."""
    from repro.sz import tiled

    n_lanes = handle.artifact.n_tiles
    b = 1
    while b <= tiled.DEFAULT_BUCKET_CAP:
        handle.pipeline.decode_tiles(handle.artifact,
                                     list(range(min(b, n_lanes))))
        if b >= n_lanes:
            break
        b *= 2


def _dispatch_compare(args, artifact, full) -> dict:
    """Serialized in-process phase: ``--readers`` threads each decode one
    tile-aligned lane through a fresh shared-cache handle, batcher off then
    on; the device-dispatch delta (process-global ``tiled`` counters) must
    drop by >= 2x with the batcher coalescing cross-request work."""
    import itertools

    from repro import api
    from repro.exec.cache import DecodeBatcher, TileCache
    from repro.sz import tiled

    t, shp = artifact.tile, artifact.shape
    rois = [tuple(slice(a, min(a + t[d], shp[d])) for d, a in enumerate(pos))
            for pos in itertools.product(
                *[range(0, shp[d], t[d]) for d in range(len(shp))])]

    out = {}
    for mode in ("off", "on"):
        batcher = None if mode == "off" else DecodeBatcher(
            max_wait_ms=max(args.max_wait_ms, 20.0), max_batch_tiles=4096)
        handle = api.CompressedVolume(
            artifact, tile_cache=TileCache(args.cache_bytes),
            cache_ns="cmp", decode_batcher=batcher)
        bad: list[int] = []
        lock = threading.Lock()
        gate = threading.Barrier(args.readers)

        def worker(i: int) -> None:
            roi = rois[i % len(rois)]
            gate.wait()
            arr = handle[roi]
            if not np.array_equal(arr, full[roi]):
                with lock:
                    bad.append(i)

        before = tiled.dispatch_stats()["dispatches"]
        threads = [threading.Thread(target=worker, args=(i,), daemon=True)
                   for i in range(args.readers)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        out[mode] = {
            "dispatches": tiled.dispatch_stats()["dispatches"] - before,
            "mismatches": len(bad),
        }
        if batcher is not None:
            out[mode]["batcher"] = batcher.info()
    off, on = out["off"]["dispatches"], out["on"]["dispatches"]
    out["reduction"] = off / on if on else float("inf")
    return out


def build_report(args) -> dict:
    from repro import api
    from repro.data import nyx_like_field
    from repro.serve import RegionServer, fetch_json, fetch_region
    from repro.sz import tiled

    from benchmarks.common import emit

    side, tile = args.side, args.tile
    x = np.asarray(nyx_like_field((side,) * 3, "temperature", seed=11),
                   np.float32)
    vol = api.compress(x, abs_eb=float(np.ptp(x)) * 1e-3, tiled=True,
                       tile=(tile,) * 3, predictor="lorenzo")
    full = np.asarray(api.CompressedVolume(vol.artifact))  # independent decode

    # the served handle shares the daemon pool's budgeted cache
    server = RegionServer(cache_bytes=args.cache_bytes,
                          mem_budget=args.mem_budget,
                          batch_wait_ms=(None if args.batcher == "off"
                                         else args.max_wait_ms))
    shared = api.CompressedVolume(vol.artifact, tile_cache=server.pool.cache,
                                  cache_ns="nyx")
    server.pool.add_volume("nyx", shared)

    # compile every reachable bucket program, then snapshot: the storm must
    # not mint a single new one (zero warm-path recompiles, asserted below)
    _warm_decode_buckets(shared)
    warm_programs = tiled.dispatch_stats()["programs"]

    # shared ROI pool: overlapping windows so readers contend for the same
    # lanes — the regime the single-flight + shared-cache design targets
    rng = np.random.default_rng(7)
    rois = []
    for _ in range(args.roi_pool):
        lo = rng.integers(0, max(1, side - tile), 3)
        hi = [int(min(side, a + rng.integers(tile // 2, 2 * tile)))
              for a in lo]
        rois.append(",".join(f"{int(a)}:{b}" for a, b in zip(lo, hi)))

    latencies: list[float] = []
    mismatches: list[str] = []
    failures: list[str] = []
    lock = threading.Lock()
    gate = threading.Barrier(args.readers + 1)

    def reader(seed: int) -> None:
        r = np.random.default_rng(seed)
        picks = [rois[int(i)] for i in r.integers(0, len(rois),
                                                  args.requests_per_reader)]
        gate.wait()
        for roi in picks:
            t0 = time.perf_counter()
            try:
                arr, _meta = fetch_region(server.url, "nyx", roi,
                                          timeout=args.p99_ms / 250)
            except Exception as e:  # noqa: BLE001 - reported, asserted below
                with lock:
                    failures.append(f"{roi}: {e}")
                continue
            ms = (time.perf_counter() - t0) * 1e3
            sl = tuple(slice(*map(int, t.split(":"))) for t in roi.split(","))
            ok = np.array_equal(arr, full[sl])
            with lock:
                latencies.append(ms)
                if not ok:
                    mismatches.append(roi)

    threads = [threading.Thread(target=reader, args=(1000 + s,), daemon=True)
               for s in range(args.readers)]
    with server:
        for t in threads:
            t.start()
        gate.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        wall_s = time.perf_counter() - t0
        metrics = fetch_json(server.url, "/metrics")

    recompiles = tiled.dispatch_stats()["programs"] - warm_programs
    compare = _dispatch_compare(args, vol.artifact, full)

    lat = np.asarray(latencies, np.float64)
    total = args.readers * args.requests_per_reader
    p50, p90, p99 = (np.percentile(lat, [50, 90, 99]) if lat.size
                     else (float("nan"),) * 3)
    cache = metrics["cache"]
    report = {
        "readers": args.readers,
        "requests": total,
        "completed": int(lat.size),
        "failures": failures[:10],
        "mismatches": mismatches[:10],
        "wall_s": wall_s,
        "rps": lat.size / wall_s if wall_s else 0.0,
        "latency_ms": {"p50": float(p50), "p90": float(p90), "p99": float(p99),
                       "mean": float(lat.mean()) if lat.size else float("nan")},
        "cache": cache,
        "admission": metrics["admission"],
        "batcher_mode": args.batcher,
        "batcher": metrics.get("batcher"),
        "decode_programs": tiled.dispatch_stats(),
        "recompiles_after_warmup": int(recompiles),
        "dispatch_compare": compare,
        "volume": {"side": side, "tile": tile,
                   "n_lanes": vol.stats.tiles_total},
        "thresholds": {"p99_ms": args.p99_ms,
                       "min_hit_rate": args.min_hit_rate},
    }
    report["decode_programs"]["batch_hist"] = {
        str(k): v for k, v in report["decode_programs"]["batch_hist"].items()}

    emit("serve_load/region_p99", p99 * 1e3,
         f"p99_ms={p99:.1f} over {lat.size} requests from {args.readers} readers")
    emit("serve_load/region_p50", p50 * 1e3, f"p50_ms={p50:.1f}")
    emit("serve_load/hit_rate", 0.0,
         f"hit_rate={cache['hit_rate']:.3f} hits={cache['hits']} "
         f"misses={cache['misses']} coalesced={cache['coalesced']}")
    emit("serve_load/throughput", 0.0, f"rps={report['rps']:.1f} "
         f"peak_queue={metrics['admission']['peak_queue_depth']}")
    emit("serve_load/recompiles", 0.0,
         f"recompiles_after_warmup={recompiles} "
         f"programs={report['decode_programs']['programs']} "
         f"batcher={args.batcher}")
    emit("serve_load/dispatch_reduction", 0.0,
         f"off={compare['off']['dispatches']} on={compare['on']['dispatches']} "
         f"reduction={compare['reduction']:.1f}x readers={args.readers}")

    # -- asserted acceptance thresholds ------------------------------------
    errors = []
    if failures:
        errors.append(f"{len(failures)} requests failed (first: {failures[0]})")
    if mismatches:
        errors.append(f"{len(mismatches)} regions != full[roi] "
                      f"(first: {mismatches[0]})")
    if lat.size < total:
        errors.append(f"only {lat.size}/{total} requests completed")
    if not (p99 < args.p99_ms):
        errors.append(f"p99 {p99:.1f} ms exceeds the {args.p99_ms:.0f} ms bound")
    if not (cache["hit_rate"] > args.min_hit_rate):
        errors.append(f"hit rate {cache['hit_rate']:.3f} below "
                      f"{args.min_hit_rate} — the shared cache is not sharing")
    if recompiles != 0:
        errors.append(f"{recompiles} decode programs compiled AFTER warmup — "
                      f"the bucket set is not bounding compilation")
    if compare["off"]["mismatches"] or compare["on"]["mismatches"]:
        errors.append("dispatch-compare phase served bytes != full[roi]")
    if compare["on"]["dispatches"] * 2 > compare["off"]["dispatches"]:
        errors.append(
            f"batcher cut dispatches only {compare['reduction']:.2f}x "
            f"({compare['off']['dispatches']} -> "
            f"{compare['on']['dispatches']}); need >= 2x")
    report["passed"] = not errors
    report["errors"] = errors
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke: smaller volume, same 100-reader floor")
    ap.add_argument("--readers", type=int, default=None,
                    help="concurrent client threads (default 200, fast 100; "
                         "the acceptance floor is 100)")
    ap.add_argument("--requests-per-reader", type=int, default=None)
    ap.add_argument("--roi-pool", type=int, default=32,
                    help="distinct (overlapping) ROIs shared by all readers")
    ap.add_argument("--side", type=int, default=None, help="volume side")
    ap.add_argument("--tile", type=int, default=None, help="tile side")
    ap.add_argument("--cache-bytes", type=int, default=64 << 20)
    ap.add_argument("--mem-budget", type=int, default=64 << 20)
    ap.add_argument("--p99-ms", type=float, default=None,
                    help="asserted p99 latency bound (default 5000 ms; "
                         "client-observed, queueing included)")
    ap.add_argument("--min-hit-rate", type=float, default=0.5,
                    help="asserted shared-cache hit-rate floor")
    ap.add_argument("--batcher", choices=("on", "off"), default="on",
                    help="cross-request decode micro-batcher in the served "
                         "pool (CI runs both and uploads both reports)")
    ap.add_argument("--max-wait-ms", type=float, default=2.0,
                    help="batcher coalescing window (pool batch_wait_ms)")
    ap.add_argument("--json", default=None, metavar="PATH")
    args = ap.parse_args(argv)
    if args.readers is None:
        args.readers = 100 if args.fast else 200
    if args.readers < 100:
        ap.error("the acceptance criterion needs >= 100 concurrent readers")
    if args.requests_per_reader is None:
        args.requests_per_reader = 3 if args.fast else 5
    if args.side is None:
        args.side = 24 if args.fast else 48
    if args.tile is None:
        args.tile = 8 if args.fast else 16
    if args.p99_ms is None:
        # single-core CI shares one GIL between 100 readers and the decode
        # pool; the bound is about catching collapse (serialized decodes,
        # admission deadlock), not micro-latency
        args.p99_ms = 5000.0 if args.fast else 10000.0

    report = build_report(args)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2)
        print(f"wrote {args.json}")
    for e in report["errors"]:
        print(f"FAIL: {e}", file=sys.stderr)
    if report["passed"]:
        print(f"serve_load ok: {report['completed']} requests, "
              f"p99 {report['latency_ms']['p99']:.1f} ms, "
              f"hit_rate {report['cache']['hit_rate']:.3f}, "
              f"{report['rps']:.1f} req/s, "
              f"recompiles {report['recompiles_after_warmup']}, "
              f"dispatch x{report['dispatch_compare']['reduction']:.1f}")
    return 0 if report["passed"] else 1


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
