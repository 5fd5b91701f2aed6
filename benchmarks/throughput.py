"""Compression/decompression + kernel throughput (host CPU; the TPU path is
characterized by the dry-run roofline, EXPERIMENTS.md §Roofline).

Times every entropy backend (zlib / huffman / huffman+zlib) end-to-end and
per-stage, plus the entropy-stage isolation benchmark: chunked vectorized
Huffman decode vs the seed per-symbol walk on a 64^3 code tensor (the
acceptance target for the chunked codec is >= 20x)."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.common import (
    ENTROPY_VOLUME,
    TILED_TILE,
    TILED_VOLUME,
    VOLUME,
    emit,
    peak_rss_mb,
    timed,
)
from repro import api
from repro.core import enhancer as E
from repro.data import nyx_like_field
from repro.kernels import ops
from repro.sz.entropy import decode_codes, encode_codes, encode_codes_legacy

BACKENDS = ("zlib", "huffman", "huffman+zlib")


def _entropy_stage_bench() -> None:
    """Isolated entropy-stage decode: new chunked format vs seed format."""
    x = jnp.asarray(nyx_like_field(ENTROPY_VOLUME, "temperature", seed=3))
    eb = 1e-3 * float(jnp.max(x) - jnp.min(x))
    codes = np.asarray(ops.lorenzo_quant_op(x, eb, use_pallas=False))
    raw_mb = codes.size * 4

    blob_new = encode_codes(codes, "huffman+zlib")
    blob_old = encode_codes_legacy(codes, "huffman+zlib")
    out_new, us_new = timed(lambda: decode_codes(blob_new, codes.shape), repeats=3)
    out_old, us_old = timed(lambda: decode_codes(blob_old, codes.shape), repeats=1)
    assert np.array_equal(out_new, codes), "chunked decode must be byte-identical"
    assert np.array_equal(out_old, codes), "legacy decode must be byte-identical"
    side = ENTROPY_VOLUME[0]
    emit(f"throughput/entropy/hcz_decode_{side}c", us_new, f"MBps={raw_mb/us_new:.1f}")
    emit(f"throughput/entropy/hz_seed_decode_{side}c", us_old, f"MBps={raw_mb/us_old:.1f}")
    emit(f"throughput/entropy/decode_speedup_{side}c", us_new,
         f"speedup_vs_seed={us_old/us_new:.1f}x;overhead={(len(blob_new)/len(blob_old)-1)*100:.2f}%")


def _entropy_device_bench() -> None:
    """Device (Pallas) Huffman encode/decode vs the host codec on the same
    code tensor, byte-identity asserted (the ISSUE 8 acceptance rows).

    Off-TPU the kernels run in interpret mode, so the speedup column
    characterizes the dispatch path, not silicon; on TPU the same rows
    report the compiled device throughput.  The stream rows compare the
    executor's per-batch host-stage time with lane packing on the device
    stage vs on the host stage."""
    import os
    import tempfile

    from repro.exec import stream_compress

    x = jnp.asarray(nyx_like_field(ENTROPY_VOLUME, "temperature", seed=3))
    eb = 1e-3 * float(jnp.max(x) - jnp.min(x))
    codes = np.asarray(ops.lorenzo_quant_op(x, eb, use_pallas=False))
    raw_mb = codes.size * 4

    blob_host, us_he = timed(
        lambda: encode_codes(codes, "huffman", use_pallas=False), repeats=3)
    blob_dev, us_de = timed(
        lambda: encode_codes(codes, "huffman", use_pallas=True), repeats=3)
    assert blob_dev == blob_host, "device blob must be bit-identical to host"
    emit("throughput/entropy/device/encode", us_de,
         f"MBps={raw_mb/us_de:.1f};host_MBps={raw_mb/us_he:.1f};"
         f"speedup_vs_host={us_he/us_de:.2f}x")

    out_host, us_hd = timed(
        lambda: decode_codes(blob_host, codes.shape, use_pallas=False), repeats=3)
    out_dev, us_dd = timed(
        lambda: decode_codes(blob_host, codes.shape, use_pallas=True), repeats=3)
    assert np.array_equal(out_dev, codes) and np.array_equal(out_host, codes)
    emit("throughput/entropy/device/decode", us_dd,
         f"MBps={raw_mb/us_dd:.1f};host_MBps={raw_mb/us_hd:.1f};"
         f"speedup_vs_host={us_hd/us_dd:.2f}x")

    # streaming executor: host-stage time with device vs host lane packing
    xs = np.asarray(nyx_like_field(TILED_VOLUME, "temperature", seed=11),
                    np.float32)
    src = tempfile.mktemp(suffix=".npy")
    np.save(src, xs)
    try:
        outs = {}
        for label, dev in (("host", False), ("device", True)):
            out = tempfile.mktemp(suffix=".gwtc")
            rep, us = timed(lambda: stream_compress(
                src, out, tile=TILED_TILE, rel_eb=1e-3, predictor="lorenzo",
                mem_budget=max(xs.nbytes // 4, 1 << 20), use_pallas=dev),
                repeats=1)
            outs[label] = (out, rep, us)
        (out_h, rep_h, us_h), (out_d, rep_d, us_d) = outs["host"], outs["device"]
        assert rep_d.entropy_device and not rep_h.entropy_device
        assert open(out_h, "rb").read() == open(out_d, "rb").read(), \
            "device-packed container must be bit-identical to the host one"
        emit("throughput/entropy/device/stream_host_stage",
             rep_d.host_stage_s * 1e6,
             f"host_path_stage_s={rep_h.host_stage_s:.4f};"
             f"device_path_stage_s={rep_d.host_stage_s:.4f};"
             f"stage_reduction={rep_h.host_stage_s/max(rep_d.host_stage_s, 1e-9):.1f}x;"
             f"batches={rep_d.n_batches}")
        os.unlink(out_h)
        os.unlink(out_d)
    finally:
        os.unlink(src)


def _tiled_bench() -> None:
    """Tiled engine THROUGH THE FAÇADE (`api.compress` + handle slicing):
    compress, full decode, and single-tile region decode per registered
    predictor — the benchmarked hot path is the public path.

    The region row reports the speedup over full decode — random-access
    reads must only pay for intersecting entropy lanes (target >= 4x at the
    full-size 128^3/64^3 setting, where 1 of 8 lanes intersects)."""
    from repro.sz import tiled

    x = jnp.asarray(nyx_like_field(TILED_VOLUME, "temperature", seed=7))
    nbytes = x.size * 4
    for pred in ("lorenzo", "interp"):
        vol, us = timed(
            lambda: api.compress(x, eb=1e-3, tiled=True, tile=TILED_TILE,
                                 predictor=pred), repeats=1)
        art = vol.artifact
        emit(f"throughput/tiled/compress/{pred}", us,
             f"MBps={nbytes/us:.1f};cr={nbytes/vol.nbytes:.1f};tiles={art.n_tiles}")

        # fresh handle per call: full decode is cached once per volume
        full, us_full = timed(
            lambda: np.asarray(api.CompressedVolume(art)), repeats=3)
        emit(f"throughput/tiled/decompress_full/{pred}", us_full,
             f"MBps={nbytes/us_full:.1f}")

        roi = tuple(slice(0, t) for t in art.tile)  # exactly one tile
        reg, us_reg = timed(lambda: vol[roi], repeats=3)
        assert np.array_equal(reg, full[roi]), \
            "façade slicing must equal the full decode's crop"
        lanes = tiled.DECODE_STATS["tiles_decoded"]
        emit(f"throughput/tiled/region_decode/{pred}", us_reg,
             f"MBps={reg.size*4/us_reg:.1f};speedup_vs_full={us_full/us_reg:.1f}x;"
             f"lanes={lanes}/{art.n_tiles}")


def _stream_bench() -> None:
    """Streaming (out-of-core) vs eager compress, with peak-RSS columns.

    The streamed run compresses off an ``.npy`` memmap against a budget of
    a quarter of the volume, so multiple batches are exercised; its row
    reports the executor-tracked peak (the bounded working set) next to
    process peak RSS, and the eager row reports the same RSS column for the
    whole-volume path.  Decodes are asserted identical (lorenzo's integer
    transform makes streamed and eager artifacts byte-equal)."""
    import os
    import tempfile

    from repro.exec import stream_compress

    x = np.asarray(nyx_like_field(TILED_VOLUME, "temperature", seed=11), np.float32)
    nbytes = x.size * 4
    budget = max(nbytes // 4, 1 << 20)
    src = tempfile.mktemp(suffix=".npy")
    np.save(src, x)
    try:
        out = tempfile.mktemp(suffix=".gwtc")
        rep, us_s = timed(lambda: stream_compress(
            src, out, tile=TILED_TILE, rel_eb=1e-3, predictor="lorenzo",
            mem_budget=budget), repeats=1)
        emit("throughput/stream/compress/lorenzo", us_s,
             f"MBps={nbytes/us_s:.1f};peak_trackedMB={rep.peak_tracked_bytes/2**20:.1f};"
             f"budgetMB={budget/2**20:.1f};rssMB={peak_rss_mb():.0f};"
             f"batches={rep.n_batches}")

        vol, us_e = timed(lambda: api.compress(
            x, eb=1e-3, tiled=True, tile=TILED_TILE, predictor="lorenzo"),
            repeats=1)
        emit("throughput/stream/eager_compress/lorenzo", us_e,
             f"MBps={nbytes/us_e:.1f};rssMB={peak_rss_mb():.0f};"
             f"stream_vs_eager={us_e/us_s:.2f}x")

        with api.open(out) as vs:
            assert np.array_equal(np.asarray(vs), np.asarray(vol)), \
                "streamed artifact must decode identically to the eager path"
        os.unlink(out)
    finally:
        os.unlink(src)


def _cached_region_bench() -> None:
    """Repeated region reads through the handle's decoded-tile LRU cache:
    the second read of the same ROI must skip entropy decode entirely."""
    import time

    x = jnp.asarray(nyx_like_field(TILED_VOLUME, "temperature", seed=13))
    vol = api.compress(x, eb=1e-3, tiled=True, tile=TILED_TILE, predictor="lorenzo")
    roi = tuple(slice(0, t) for t in vol.artifact.tile)
    vol[tuple(slice(0, 1) for _ in vol.shape)]  # compile warmup off one tile
    vol.tile_cache.clear()
    t0 = time.perf_counter()  # timed() warms up first, which would fill the cache
    cold = vol[roi]
    us_cold = (time.perf_counter() - t0) * 1e6
    warm, us_warm = timed(lambda: vol[roi], repeats=3)
    assert np.array_equal(cold, warm)
    assert vol.stats.cache_hits > 0, "warm reads must hit the tile cache"
    emit("throughput/tiled/region_cached/lorenzo", us_warm,
         f"MBps={warm.size*4/us_warm:.1f};speedup_vs_cold={us_cold/us_warm:.1f}x;"
         f"hits={vol.stats.cache_hits}")


def _verify_overhead_bench() -> None:
    """Integrity-check overhead (docs/ROBUSTNESS.md): open + full decode of
    an on-disk container with lane CRCs checked up front (``verify="full"``)
    vs skipped entirely (``verify="none"``).  The overhead column is the
    price of checksumming every lane with the stdlib's C crc32."""
    import os
    import tempfile

    x = jnp.asarray(nyx_like_field(TILED_VOLUME, "temperature", seed=17))
    nbytes = x.size * 4
    vol = api.compress(x, eb=1e-3, tiled=True, tile=TILED_TILE,
                       predictor="lorenzo")
    path = tempfile.mktemp(suffix=".gwtc")
    api.save(path, vol)
    try:
        def run(policy: str) -> np.ndarray:
            with api.open(path, verify=policy) as v:
                return np.asarray(v)

        off, us_off = timed(lambda: run("none"), repeats=3)
        on, us_on = timed(lambda: run("full"), repeats=3)
        assert np.array_equal(off, on), \
            "verification must not change a clean decode"
        emit("throughput/verify/off", us_off, f"MBps={nbytes/us_off:.1f}")
        emit("throughput/verify/full", us_on,
             f"MBps={nbytes/us_on:.1f};overhead_vs_off={(us_on/us_off-1)*100:.1f}%")
    finally:
        os.unlink(path)


def _tile_enhance_bench() -> None:
    """Batched (lax.map) tile enhancement vs the per-tile Python loop.

    Both paths are bit-identical (asserted); the batched row reports the
    measured speedup from collapsing ~n_tiles jit dispatches into one."""
    from repro.core.pipeline import GWLZ, deserialize_model
    from repro.core.trainer import GWLZTrainConfig, enhance_tiles, enhance_tiles_looped
    from repro.sz import tiled

    x = jnp.asarray(nyx_like_field(TILED_VOLUME, "temperature", seed=9))
    tile = tuple(t // 2 for t in TILED_TILE)  # more tiles -> dispatch-bound loop
    gw = GWLZ(train_cfg=GWLZTrainConfig(
        n_groups=4, epochs=2, batch_size=8, min_group_pixels=64))
    art, _ = gw.compress_tiled(x, tile, rel_eb=1e-3)
    model = deserialize_model(art.extras["gwlz"])
    recon_tiles, _ = tiled.decode_lanes(art, range(art.n_tiles))

    batched, us_b = timed(
        lambda: enhance_tiles(recon_tiles, model).block_until_ready(), repeats=3)
    looped, us_l = timed(
        lambda: enhance_tiles_looped(recon_tiles, model).block_until_ready(), repeats=3)
    assert np.array_equal(np.asarray(batched), np.asarray(looped)), \
        "batched tile enhancement must be bit-identical to the looped path"
    emit("throughput/tiled/enhance_batched", us_b,
         f"MBps={batched.size*4/us_b:.1f};speedup_vs_loop={us_l/us_b:.2f}x;"
         f"tiles={art.n_tiles}")


def _bucketed_decode_bench() -> None:
    """Bucketed (compile-cached) lane decode vs the unbucketed path over
    assorted ragged lane counts, bit-identity asserted.

    Bucket padding rounds each batch up to a power-of-two width so every
    decode reuses one of a bounded set of compiled programs; the info
    column reports the compile-cache hit rate over the timed window
    (1 - programs/dispatches) and the padded-tile overhead."""
    from repro.sz import tiled

    x = jnp.asarray(nyx_like_field(TILED_VOLUME, "temperature", seed=7))
    vol = api.compress(x, eb=1e-3, tiled=True, tile=TILED_TILE,
                       predictor="lorenzo")
    art = vol.artifact
    # ragged lane counts: full batch plus off-bucket subsets that need padding
    counts = sorted({art.n_tiles, max(1, art.n_tiles - 1), 3,
                     min(5, art.n_tiles)})

    def run(cap):
        return [np.asarray(tiled.decode_lanes(art, range(n),
                                              bucket_cap=cap)[0])
                for n in counts]

    before = tiled.dispatch_stats()
    bucketed, us_b = timed(lambda: run(None), repeats=3)
    after = tiled.dispatch_stats()
    plain, us_u = timed(lambda: run(0), repeats=3)
    for a, b in zip(bucketed, plain):
        assert np.array_equal(a, b), \
            "bucketed decode must be bit-identical to the unbucketed path"
    dispatches = after["dispatches"] - before["dispatches"]
    programs = after["programs"] - before["programs"]
    padded = after["padded_tiles"] - before["padded_tiles"]
    hit = 1.0 - programs / max(dispatches, 1)
    emit("throughput/tiled/decode_bucketed", us_b,
         f"vs_unbucketed={us_u/us_b:.2f}x;compile_hit_rate={hit:.3f};"
         f"dispatches={dispatches};programs={programs};padded_tiles={padded}")


def _serve_warm_cold_bench() -> None:
    """Region read through an in-process ``VolumePool`` (admission + shared
    tile cache + bucketed decode): first touch pays entropy decode and
    device dispatch, the warm re-read must come out of the shared cache."""
    import time

    from repro.serve import VolumePool

    x = jnp.asarray(nyx_like_field(TILED_VOLUME, "temperature", seed=19))
    vol = api.compress(x, eb=1e-3, tiled=True, tile=TILED_TILE,
                       predictor="lorenzo")
    pool = VolumePool(cache_bytes=64 << 20)
    pool.add_volume("bench", api.CompressedVolume(
        vol.artifact, tile_cache=pool.cache, cache_ns="bench"))
    roi = ",".join(f"0:{t}" for t in vol.artifact.tile)  # one lane

    t0 = time.perf_counter()  # timed() warms up first, which would fill the cache
    cold, _ = pool.region("bench", roi)
    us_cold = (time.perf_counter() - t0) * 1e6
    (warm, _meta), us_warm = timed(lambda: pool.region("bench", roi), repeats=3)
    assert np.array_equal(cold, warm), \
        "warm region read must be byte-equal to the cold decode"
    info = pool.cache.info()
    assert info["hits"] > 0, "warm reads must hit the pool's shared cache"
    emit("throughput/serve/region_warm_vs_cold", us_warm,
         f"cold_us={us_cold:.0f};speedup={us_cold/us_warm:.1f}x;"
         f"hits={info['hits']};misses={info['misses']}")


def _lint_gate_bench() -> None:
    """The RA001–RA005 static-analysis gate (docs/ANALYSIS.md) runs on
    every CI push; this row guards that a full-tree lint stays interactive
    — one shared parse + walk per file must keep it in the single-digit
    seconds, or the gate starts costing more than it saves."""
    from repro.analysis import run_analysis
    from repro.analysis.engine import default_root

    findings, us = timed(run_analysis, repeats=1)
    assert not findings, "lint gate must be clean on the benchmarked tree"
    assert us < 10e6, f"full-tree lint took {us / 1e6:.1f}s (budget: a few seconds)"
    files = sum(1 for p in default_root().rglob("*.py")
                if "__pycache__" not in p.parts)
    emit("throughput/analysis/lint_full_tree", us,
         f"files={files};findings=0;files_per_s={files / (us / 1e6):.0f}")


def main() -> None:
    x = jnp.asarray(nyx_like_field(VOLUME, "temperature", seed=1))
    nbytes = x.size * 4

    for pred in ("lorenzo", "interp"):
        for backend in BACKENDS:
            # monolithic rows go through the façade too (public == hot path)
            vol, us = timed(
                lambda: api.compress(x, eb=1e-3, predictor=pred, backend=backend),
                repeats=2)
            art = vol.artifact
            emit(f"throughput/compress/{pred}/{backend}", us,
                 f"MBps={nbytes/us:.1f};cr={nbytes/vol.nbytes:.1f}")
            _, us = timed(lambda: np.asarray(api.CompressedVolume(art)), repeats=2)
            emit(f"throughput/decompress/{pred}/{backend}", us, f"MBps={nbytes/us:.1f}")
            # per-stage: entropy decode alone (the former Python-loop bottleneck)
            shape = art.padded_shape if pred == "interp" else art.shape
            codes_mb = int(np.prod(shape)) * 4
            _, us = timed(lambda: decode_codes(art.code_blob, shape), repeats=3)
            emit(f"throughput/entropy_decode/{pred}/{backend}", us, f"MBps={codes_mb/us:.1f}")

    _entropy_stage_bench()
    _entropy_device_bench()
    _tiled_bench()
    _stream_bench()
    _verify_overhead_bench()
    _cached_region_bench()
    _tile_enhance_bench()
    _bucketed_decode_bench()
    _serve_warm_cold_bench()
    _lint_gate_bench()

    # kernels (interpret mode on CPU: correctness-path timing only)
    _, us = timed(lambda: ops.lorenzo_quant_op(x, 1.0, use_pallas=False).block_until_ready(), repeats=3)
    emit("throughput/kernel/lorenzo_ref", us, f"MBps={nbytes/us:.1f}")

    import jax

    edges = jnp.linspace(float(x.min()), float(x.max()) + 1, 21)
    p = jax.vmap(E.init_params)(jax.random.split(jax.random.PRNGKey(0), 20))
    s = jax.vmap(lambda _: E.init_state())(jnp.arange(20))
    slices = x[:16]
    _, us = timed(lambda: ops.enhancer_fused_op(
        slices, p, s, edges, jnp.ones(20), jnp.float32(0.0), n_groups=20,
        residual_learning=True, use_clamp=False, use_pallas=False).block_until_ready(),
        repeats=3)
    emit("throughput/kernel/enhancer_ref", us, f"MBps={slices.size*4/us:.1f}")

    n = (x.size // 128) * 128
    xf = x.ravel()[:n]
    _, us = timed(lambda: ops.group_hist_op(xf.reshape(-1, 128), edges, n_groups=20, use_pallas=False)[0].block_until_ready(), repeats=3)
    emit("throughput/kernel/group_hist_ref", us, f"MBps={n*4/us:.1f}")

    codes_i32 = jnp.asarray(np.asarray(ops.lorenzo_quant_op(x, 1.0, use_pallas=False)))
    span = int(codes_i32.max() - codes_i32.min()) + 1
    shifted = codes_i32 - codes_i32.min()
    _, us = timed(lambda: ops.symbol_hist_op(shifted, n_bins=span, use_pallas=False).block_until_ready(), repeats=3)
    emit("throughput/kernel/symbol_hist_ref", us, f"MBps={n*4/us:.1f}")


if __name__ == "__main__":
    main()
