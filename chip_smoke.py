#!/usr/bin/env python3
"""Bring-up smoke run of the GWLZ compressor on a TPU.

    python chip_smoke.py [--seed 0]            # one chip, every phase, 512^3
    python chip_smoke.py --chips 4 [--seed 0]  # four chips: sharded ingest only

One process runs every phase; the region server answers on threads of it.
The default run drives the normal path on exactly one device (even on a host
with more), at the paper's scale: a 512^3 f32 Nyx-like temperature field from
``--seed``, Lorenzo at relative error bound 1e-3 over 64^3 tiles, and the
group-wise enhancer at its published width (G=20 groups, C=9 channels,
2 convs) trained for 2 epochs on the streaming reservoir:

1. ``setup``: generate the field (set-up time, not ingest time);
2. ``ingest_enhanced``: ``api.compress_stream`` with the enhancer;
3. ``ingest_plain``: the same stream without it, whose full decode must
   hold the pointwise bound ``|x - x'| <= eb_abs * (1 + 1e-6)``;
4. ``decode_enhanced``: ``api.open`` + full decode, all values finite, with
   PSNR with and without the enhancer, the ratio and the enhancer overhead;
5. ``roi``: region reads, one crossing tile boundaries, each bit-equal to
   the same crop of the full decode;
6. ``serve``: ``repro.serve.RegionServer`` over HTTP, bodies bit-equal to
   the direct reads;
7. ``interp``: ``api.compress(tiled=True, predictor="interp")`` at 128^3 under
   the same pointwise bound;
8. ``entropy_identity``: on a sample of lanes, the device (Pallas) entropy
   pack's bytes == the host pack's == the lane bytes the ingest wrote, and
   the device decode probe's symbols == the host walk's.

``--chips 4`` runs only the phase that needs the mesh: the same ingest over a
4-device tile mesh (tile batches and the enhancer's groups split over the
devices) and pinned to one of those devices, whose containers must be
byte-identical and read the same ROI.  Each ingest's line gives its
enhancer training seconds (``gwlz.train``).

Earlier lines report each phase; the last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Any failure, or a platform other than TPU, exits non-zero without it.
Numbers printed here are smoke readings, not benchmark results.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

TILE = (64, 64, 64)
REL_EB = 1e-3
SIDE = 512
INTERP_SIDE = 128
EPOCHS = 2


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class Phases:
    """Wall time per phase, printed as each one ends."""

    def __init__(self):
        self.seconds: dict[str, float] = {}

    def run(self, name: str, fn, *args, **kw):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        self.seconds[name] = time.perf_counter() - t0
        print(f"phase {name}: ok in {self.seconds[name]:.3f} s", flush=True)
        return out

    def report(self) -> None:
        print("phase seconds " + json.dumps(
            {k: round(v, 3) for k, v in self.seconds.items()}), flush=True)


def make_field(side: int, seed: int):
    import numpy as np

    from repro.data import nyx_like_field

    return np.ascontiguousarray(
        nyx_like_field((side,) * 3, "temperature", seed=seed), np.float32)


def ingest(x, path, *, enhance: bool):
    from repro import api
    from repro.core.trainer import GWLZTrainConfig

    rep = api.compress_stream(
        x, path, eb=REL_EB, tile=TILE, predictor="lorenzo",
        enhance=GWLZTrainConfig(epochs=EPOCHS) if enhance else False)
    check(rep.enhanced == enhance, f"enhanced={rep.enhanced}, wanted {enhance}")
    train_s = rep.stages.get("gwlz.train", (0, 0.0, 0))[1]
    print(f"  {Path(path).name}: {rep.nbytes} bytes, {rep.n_batches} batches "
          f"of {rep.batch_tiles} tiles, reservoir {rep.reservoir_tiles} tiles, "
          f"programs_compiled {rep.programs_compiled}, host_stage "
          f"{rep.host_stage_s:.3f} s, train {train_s:.3f} s, entropy_device "
          f"{rep.entropy_device}", flush=True)
    return rep


def decode_plain(x, path):
    import numpy as np

    from repro import api

    with api.open(path) as vol:
        full = np.asarray(vol)
        eb = vol.eb_abs
    err = float(np.max(np.abs(full.astype(np.float64) - x)))
    print(f"  max |x - x'| {err!r} <= eb_abs {eb!r} * (1 + 1e-6)", flush=True)
    check(err <= eb * (1 + 1e-6), f"pointwise bound broken: {err} > {eb}")
    return full


def decode_enhanced(x, path, plain, rep_plain):
    import numpy as np

    from repro import api
    from repro.core.metrics import psnr

    with api.open(path) as vol:
        check(vol.enhanced, "enhanced container carries no model")
        full = np.array(vol)
        nbytes = vol.nbytes
    check(bool(np.isfinite(full).all()), "non-finite values in the enhanced decode")
    p_plain, p_enh = float(psnr(x, plain)), float(psnr(x, full))
    overhead = (nbytes - rep_plain.nbytes) / rep_plain.nbytes
    print(f"  PSNR without enhancer {p_plain:.4f} dB, with {p_enh:.4f} dB; "
          f"ratio {x.nbytes / nbytes:.4f}; enhancer overhead {overhead:.6f}",
          flush=True)
    return full


def rois(side: int):
    """A few ROIs: inside one tile, across tile boundaries, a slab, a point."""
    t = TILE[0]
    return [
        (slice(3, t - 5), slice(t + 2, 2 * t - 9), slice(7, 40)),
        (slice(t - 13, 3 * t + 11), slice(2 * t - 1, 2 * t + 1),
         slice(t // 2, side - t // 2)),
        (side // 2, slice(None), slice(None)),
        (side - 1, 5, slice(t - 1, t + 1)),
    ]


def read_rois(path, full, side):
    import numpy as np

    from repro import api

    with api.open(path) as vol:
        for roi in rois(side):
            got = vol[roi]
            want = full[roi]
            check(got.dtype == want.dtype and np.array_equal(got, want),
                  f"region read {roi} differs from the full decode")
            print(f"  roi {roi}: shape {got.shape} bit-equal", flush=True)


def roi_text(roi) -> str:
    parts = []
    for r in roi:
        if isinstance(r, slice):
            parts.append(f"{'' if r.start is None else r.start}:"
                         f"{'' if r.stop is None else r.stop}")
        else:
            parts.append(str(r))
    return ",".join(parts)


def serve(path, full, side):
    import numpy as np

    from repro.serve import RegionServer, fetch_json, fetch_region

    with RegionServer({"nyx": str(path)}, port=0) as server:
        url = server.url
        check(fetch_json(url, "/healthz")["status"] == "ok", "healthz")
        info = fetch_json(url, "/v/nyx/info")
        check(tuple(info["shape"]) == full.shape, f"info shape {info['shape']}")
        reqs = rois(side) + [rois(side)[1]]  # the repeat must hit the cache
        for roi in reqs:
            body, meta = fetch_region(url, "nyx", roi_text(roi))
            want = full[roi]
            check(np.array_equal(body, want) and body.dtype == want.dtype,
                  f"served region {roi_text(roi)} differs from the direct read")
            print(f"  GET {roi_text(roi)}: {meta['lanes']}/{meta['lanes_total']}"
                  f" lanes, {meta['latency_ms']:.3f} ms", flush=True)
        m = fetch_json(url, "/metrics")
        check(m["cache"]["hit_rate"] > 0, f"no cache hit on a repeat: {m['cache']}")
        print(f"  {m['requests']} requests, hit_rate {m['cache']['hit_rate']:.3f}, "
              f"p99 {m['latency_ms'].get('p99', 0):.3f} ms", flush=True)


def interp(x):
    import numpy as np

    from repro import api

    sub = np.ascontiguousarray(x[:INTERP_SIDE, :INTERP_SIDE, :INTERP_SIDE])
    vol = api.compress(sub, eb=REL_EB, tiled=True, tile=TILE, predictor="interp")
    back = api.from_bytes(vol.to_bytes())
    full = np.asarray(back)
    err = float(np.max(np.abs(full.astype(np.float64) - sub)))
    print(f"  interp {sub.shape}: {back.nbytes} bytes, max |x - x'| {err!r} "
          f"<= eb_abs {back.eb_abs!r} * (1 + 1e-6)", flush=True)
    check(err <= back.eb_abs * (1 + 1e-6), f"interp bound broken: {err}")


def entropy_identity(path, n_lanes: int = 6):
    """On sampled lanes: the device pack's bytes equal the host pack's and
    the lane the ingest wrote; the device probe decodes what the host walk
    decodes.  Prints the probe's and the walk's wall time over the sample."""
    import numpy as np

    from repro import api
    from repro.sz import entropy

    probe_s = walk_s = 0.0
    with api.open(path) as vol:
        art = vol.artifact
        lanes = np.linspace(0, art.n_tiles - 1, n_lanes).astype(int)
        for i in lanes:
            blob = bytes(art.tile_blobs[int(i)])
            t0 = time.perf_counter()
            codes = entropy.decode_codes(blob, art.tile, use_pallas=False)
            t1 = time.perf_counter()
            probed = entropy.decode_codes(blob, art.tile, use_pallas=True)
            t2 = time.perf_counter()
            walk_s, probe_s = walk_s + t1 - t0, probe_s + t2 - t1
            check(np.array_equal(probed, codes),
                  f"lane {i}: device probe != host walk")
            dev = entropy.encode_codes(codes, art.backend, use_pallas=True)
            host = entropy.encode_codes(codes, art.backend, use_pallas=False,
                                        use_accel=False)
            check(dev == host, f"lane {i}: device pack != host pack")
            check(dev == blob, f"lane {i}: repacked bytes != the ingested lane")
    print(f"  lanes {lanes.tolist()}: device pack == host pack == ingested "
          f"bytes; device probe == host walk (probe {probe_s:.3f} s, walk "
          f"{walk_s:.3f} s over {n_lanes} lanes, compiles included)",
          flush=True)


def report_counters(phases: Phases):
    import jax

    from repro.sz import entropy, tiled

    d = tiled.dispatch_stats()
    paths = entropy.entropy_path_stats()
    mem = jax.devices()[0].memory_stats() or {}
    print(f"compile: {d['programs']} programs registered, {d['dispatches']} "
          f"bucketed dispatches", flush=True)
    print(f"entropy lanes: pack device {paths['pack_device']}, pack host "
          f"{paths['pack_host']} (fallback {paths['pack_fallback']}); "
          f"probe device {paths['probe_device']}, probe host "
          f"{paths['probe_host']} (fallback {paths['probe_fallback']})",
          flush=True)
    print(f"peak_bytes_in_use {mem.get('peak_bytes_in_use', 'not reported')}",
          flush=True)
    phases.report()
    check(paths["pack_fallback"] == 0,
          f"{paths['pack_fallback']} fresh lanes fell back to the host pack")
    check(paths["pack_device"] > 0, "no lane went through the device pack")


def one_chip(seed: int, side: int, work: Path):
    import jax

    from repro.launch.sharding import pin_tile_devices

    phases = Phases()
    with pin_tile_devices(jax.devices()[:1]):
        x = phases.run("setup", make_field, side, seed)
        enh, plain = work / "enhanced.gwtc", work / "plain.gwtc"
        phases.run("ingest_enhanced", ingest, x, enh, enhance=True)
        rep_plain = phases.run("ingest_plain", ingest, x, plain, enhance=False)
        full_plain = phases.run("decode_plain", decode_plain, x, plain)
        full = phases.run("decode_enhanced", decode_enhanced, x, enh,
                          full_plain, rep_plain)
        del full_plain
        phases.run("roi", read_rois, enh, full, side)
        phases.run("serve", serve, enh, full, side)
        phases.run("interp", interp, x)
        phases.run("entropy_identity", entropy_identity, plain)
    report_counters(phases)


def four_chips(seed: int, side: int, work: Path):
    import jax
    import numpy as np

    from repro import api
    from repro.launch.sharding import pin_tile_devices

    devs = jax.devices()
    check(len(devs) >= 4, f"--chips 4 needs 4 devices, JAX sees {len(devs)}")
    phases = Phases()
    x = phases.run("setup", make_field, side, seed)
    mesh_path, one_path = work / "mesh4.gwtc", work / "one.gwtc"
    with pin_tile_devices(devs[:4]):
        phases.run("ingest_4_devices", ingest, x, mesh_path, enhance=True)
    with pin_tile_devices(devs[:1]):
        phases.run("ingest_1_device", ingest, x, one_path, enhance=True)
    same = mesh_path.read_bytes() == one_path.read_bytes()
    print(f"  containers byte-identical: {same}", flush=True)
    check(same, "4-device and 1-device containers differ")
    roi = rois(side)[1]
    with pin_tile_devices(devs[:4]), api.open(mesh_path) as vol:
        a = vol[roi]
    with pin_tile_devices(devs[:1]), api.open(one_path) as vol:
        b = vol[roi]
    check(np.array_equal(a, b), "ROI reads differ between the containers")
    print(f"  roi {roi_text(roi)} equal from both", flush=True)
    phases.report()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0, help="field seed")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-ingest phase on a 4-chip host")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r})",
              file=sys.stderr)
        return 1
    print(f"devices: {len(devs)} x {devs[0].device_kind}; compile cache {cache}",
          flush=True)
    with tempfile.TemporaryDirectory(prefix=".chip_smoke-", dir=ROOT) as tmp:
        run = four_chips if args.chips == 4 else one_chip
        try:
            run(args.seed, SIDE, Path(tmp))
        except SmokeFailure as e:
            print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
            return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
