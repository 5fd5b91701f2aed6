"""Tiled engine (GWTC): tiled-vs-untiled parity, container round trip,
random-access region decode (structural: only intersecting lanes are
entropy-decoded), sharded dispatch, and the GWLZ tiled path."""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pipeline import GWLZ
from repro.core.trainer import GWLZTrainConfig
from repro.data import nyx_like_field
from repro.sz import SZCompressor, tiled

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture(scope="module")
def vol():
    return jnp.asarray(nyx_like_field((20, 33, 17), "temperature", seed=2))


# -- parity vs the untiled path ------------------------------------------------


def test_tiled_recon_matches_untiled_lorenzo(vol):
    """The Lorenzo transform is lossless, so tiling changes the codes but not
    the reconstruction: tiled recon == untiled lorenzo recon bit-for-bit."""
    c = SZCompressor(predictor="lorenzo")
    art_t, recon_t = c.compress_tiled(vol, (8, 16, 8), rel_eb=1e-3)
    art_u, recon_u = c.compress(vol, abs_eb=art_t.eb_abs)
    np.testing.assert_array_equal(np.asarray(recon_t), np.asarray(recon_u))
    assert float(jnp.max(jnp.abs(recon_t - vol))) <= art_t.eb_abs * (1 + 1e-6)
    # and both decompress to the same volume
    full_t = c.decompress_tiled(art_t)
    full_u = c.decompress(art_u)
    np.testing.assert_array_equal(np.asarray(full_t), np.asarray(full_u))


def test_tiled_codes_bitexact_off_carry_planes(vol):
    """Quant codes agree exactly wherever the Lorenzo stencil does not cross
    a tile boundary (the cut prediction carry only touches the planes at
    multiples of the tile pitch)."""
    tile = (8, 16, 8)
    from repro.kernels import ref

    c = SZCompressor(predictor="lorenzo")
    art_t, _ = c.compress_tiled(vol, tile, rel_eb=1e-3)
    eb = art_t.eb_abs
    from repro.sz.entropy import decode_codes

    codes_t = np.stack([decode_codes(b, tile) for b in art_t.tile_blobs])
    stitched = np.asarray(tiled.stitch_tiles(jnp.asarray(codes_t), art_t.grid))
    cropped = stitched[tuple(slice(0, d) for d in vol.shape)]
    codes_u = np.asarray(ref.lorenzo_quant_ref(vol, eb))
    interior = np.ones(vol.shape, bool)
    for ax, t in enumerate(tile):
        coord = np.arange(vol.shape[ax])
        on_carry = (coord % t == 0) & (coord > 0)
        sl = [None] * vol.ndim
        sl[ax] = slice(None)
        interior &= ~on_carry[tuple(sl)]
    assert interior.any() and not interior.all()
    np.testing.assert_array_equal(cropped[interior], codes_u[interior])


@pytest.mark.parametrize("backend", ["zlib", "huffman", "huffman+zlib"])
@pytest.mark.parametrize("pred", ["lorenzo", "interp"])
def test_container_roundtrip_all_backends(vol, backend, pred):
    art, recon = tiled.compress_tiled(vol, (8, 16, 8), rel_eb=1e-3,
                                      backend=backend, predictor=pred)
    art.extras["meta"] = b"\x01\x02"
    art2 = tiled.TiledCompressed.from_bytes(art.to_bytes())
    assert art2.shape == art.shape and art2.tile == art.tile
    assert art2.backend == backend and art2.extras == {"meta": b"\x01\x02"}
    assert art2.eb_abs == art.eb_abs
    assert (art2.predictor, art2.order, art2.levels) == \
        (pred, art.order, art.levels)
    out = tiled.decompress_tiled(art2)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(recon))


# -- predictor-pluggable tiled path --------------------------------------------


def test_tiled_interp_roundtrip_error_bounded(vol):
    """compress_tiled(predictor="interp") holds the bound end to end through
    the container byte round trip."""
    for order in ("linear", "cubic"):
        art, recon = tiled.compress_tiled(vol, (8, 16, 8), rel_eb=1e-3,
                                          predictor="interp", order=order)
        assert art.predictor == "interp" and art.levels >= 1
        full = tiled.decompress_tiled(tiled.TiledCompressed.from_bytes(art.to_bytes()))
        assert float(jnp.max(jnp.abs(full - vol))) <= art.eb_abs * (1 + 1e-6)
        np.testing.assert_array_equal(np.asarray(full), np.asarray(recon))


def test_tiled_interp_region_matches_full_crop(vol):
    """Interp tiles are independent prediction domains: a region decode
    (different batch size through the vmapped decode) must reproduce the full
    decode's crop bit-for-bit."""
    art, _ = tiled.compress_tiled(vol, (8, 16, 8), rel_eb=1e-3, predictor="interp")
    full = np.asarray(tiled.decompress_tiled(art))
    for roi in [(slice(0, 8), slice(16, 32), slice(8, 16)),
                (slice(3, 19), slice(2, 33), slice(4, 13))]:
        reg = tiled.decompress_region(art, roi)
        np.testing.assert_array_equal(np.asarray(reg), full[roi])


def test_tiled_interp_beats_lorenzo_ratio(nyx_small):
    """The point of the predictor layer: tiled interp should compress a
    smooth field tighter than tiled Lorenzo (the SZ3-lineage advantage the
    tiled path previously gave up).  Needs production-ish tile sizes — at
    tiny tiles the interp padded-grid overhead (+~20% symbols) dominates."""
    x = jnp.asarray(nyx_small)
    art_l, _ = tiled.compress_tiled(x, (16, 16, 16), rel_eb=1e-3, predictor="lorenzo")
    art_i, _ = tiled.compress_tiled(x, (16, 16, 16), rel_eb=1e-3, predictor="interp")
    assert art_i.nbytes < art_l.nbytes


def test_unknown_predictor_rejected(vol):
    with pytest.raises(ValueError, match="unknown predictor"):
        tiled.compress_tiled(vol, (8, 16, 8), rel_eb=1e-3, predictor="nope")


def test_szcompressor_routes_predictor(vol):
    """SZCompressor.compress_tiled honors self.predictor (unified stack) and
    the per-call override."""
    art, _ = SZCompressor(predictor="interp").compress_tiled(vol, (8, 16, 8), rel_eb=1e-3)
    assert art.predictor == "interp"
    art, _ = SZCompressor(predictor="interp").compress_tiled(
        vol, (8, 16, 8), rel_eb=1e-3, predictor="lorenzo")
    assert art.predictor == "lorenzo"


def test_decode_lanes_returns_lane_count(vol):
    art, _ = tiled.compress_tiled(vol, (8, 16, 8), rel_eb=1e-3)
    recon, lanes = tiled.decode_lanes(art, [0, 5, 7])
    assert lanes == 3 and recon.shape == (3, 8, 16, 8)


@pytest.mark.parametrize("shape,tile", [((100,), (32,)), ((40, 52), (16, 24))])
def test_tiled_low_rank_volumes(shape, tile):
    rng = np.random.default_rng(0)
    x = jnp.asarray((rng.normal(size=shape) * 10).astype(np.float32))
    art, recon = tiled.compress_tiled(x, tile, abs_eb=0.01)
    full = tiled.decompress_tiled(tiled.TiledCompressed.from_bytes(art.to_bytes()))
    assert float(jnp.max(jnp.abs(full - x))) <= 0.01 * (1 + 1e-6)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(recon))


def test_decode_workers_param(vol):
    art, _ = tiled.compress_tiled(vol, (8, 8, 8), rel_eb=1e-3)
    serial = tiled.decompress_tiled(art, workers=1)
    threaded = tiled.decompress_tiled(art, workers=4)
    np.testing.assert_array_equal(np.asarray(serial), np.asarray(threaded))


def test_roi_validation(vol):
    art, _ = tiled.compress_tiled(vol, (8, 16, 8), rel_eb=1e-3)
    with pytest.raises(ValueError):
        tiled.decompress_region(art, (slice(0, 5), slice(0, 5)))  # rank mismatch
    with pytest.raises(ValueError):
        tiled.decompress_region(art, (slice(5, 5), slice(0, 5), slice(0, 5)))
    with pytest.raises(ValueError):
        tiled.decompress_region(art, (slice(0, 5, 2), slice(0, 5), slice(0, 5)))


# -- random-access decode ------------------------------------------------------


def test_region_decode_touches_only_intersecting_lanes(vol, monkeypatch):
    """decompress_region must entropy-decode ONLY the intersecting tiles —
    counted at the decode_codes call site, not inferred from timings."""
    import repro.sz.entropy as entropy

    art, _ = tiled.compress_tiled(vol, (8, 16, 8), rel_eb=1e-3)  # 3x3x3 grid
    calls = []
    orig = entropy.decode_codes

    def counting(blob, shape, **kw):
        calls.append(int(np.prod(shape)))
        return orig(blob, shape, **kw)

    monkeypatch.setattr(entropy, "decode_codes", counting)
    full = tiled.decompress_tiled(art)
    assert len(calls) == art.n_tiles
    calls.clear()
    roi = (slice(0, 8), slice(16, 32), slice(8, 16))  # exactly one tile
    reg = tiled.decompress_region(art, roi)
    assert len(calls) == 1 and sum(calls) == int(np.prod(art.tile))
    assert tiled.DECODE_STATS == {"tiles_decoded": 1, "tiles_total": 27}
    np.testing.assert_array_equal(np.asarray(reg), np.asarray(full)[roi])


@pytest.mark.slow
def test_single_tile_region_decode_128cube():
    """Acceptance: one tile of a 128^3 volume decodes without the full-volume
    entropy decode (1 of 8 lanes; 64^3 of 128^3 symbols touched)."""
    import repro.sz.entropy as entropy

    x = jnp.asarray(nyx_like_field((128, 128, 128), "temperature", seed=11))
    art, _ = tiled.compress_tiled(x, (64, 64, 64), rel_eb=1e-3)
    assert art.n_tiles == 8

    counted = {"symbols": 0, "lanes": 0}
    orig = entropy.decode_codes

    def counting(blob, shape, **kw):
        counted["symbols"] += int(np.prod(shape))
        counted["lanes"] += 1
        return orig(blob, shape, **kw)

    entropy.decode_codes, prev = counting, entropy.decode_codes
    try:
        reg = tiled.decompress_region(art, (slice(64, 128), slice(0, 64), slice(64, 128)))
    finally:
        entropy.decode_codes = prev
    assert counted == {"symbols": 64**3, "lanes": 1}  # not 128^3, not 8 lanes
    assert reg.shape == (64, 64, 64)
    assert float(jnp.max(jnp.abs(reg - x[64:128, 0:64, 64:128]))) <= art.eb_abs * (1 + 1e-6)


@pytest.mark.slow
def test_sharded_dispatch_multi_device_parity(vol):
    """Artifact bytes and reconstruction must not depend on the device count:
    re-run compress on 4 forced host devices and compare."""
    art, recon = tiled.compress_tiled(vol, (8, 16, 8), rel_eb=1e-3)
    code = (
        "import numpy as np, jax, jax.numpy as jnp\n"
        "assert len(jax.devices()) == 4\n"
        "from repro.data import nyx_like_field\n"
        "from repro.sz import tiled\n"
        "x = jnp.asarray(nyx_like_field((20, 33, 17), 'temperature', seed=2))\n"
        "art, recon = tiled.compress_tiled(x, (8, 16, 8), rel_eb=1e-3)\n"
        "full = tiled.decompress_tiled(art)\n"
        "np.testing.assert_array_equal(np.asarray(full), np.asarray(recon))\n"
        "import sys; sys.stdout.buffer.write(art.to_bytes())\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4").strip()
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, timeout=300)
    assert proc.returncode == 0, proc.stderr.decode()
    assert proc.stdout == art.to_bytes()


def test_stream_ingest_pinned_device_matches_mesh(tmp_path):
    """One process, 4 host devices: a streamed ingest over the 4-device tile
    mesh and one pinned to a single device write the same container (the
    CPU rehearsal of ``chip_smoke.py --chips 4``); pins do not nest."""
    code = (
        "import sys, numpy as np, jax\n"
        "from repro import api\n"
        "from repro.data import nyx_like_field\n"
        "from repro.launch import sharding\n"
        "assert len(sharding.tile_devices()) == 4\n"
        "x = np.asarray(nyx_like_field((32, 32, 32), 'temperature', seed=4))\n"
        "out = sys.argv[1]\n"
        "r4 = api.compress_stream(x, out + '/a.gwtc', eb=1e-3, tile=(8, 8, 8),\n"
        "                         mem_budget=256 << 10)\n"
        "with sharding.pin_tile_devices(jax.devices()[:1]) as devs:\n"
        "    assert sharding.tile_devices() == devs and len(devs) == 1\n"
        "    assert sharding.tile_mesh().devices.size == 1\n"
        "    try:\n"
        "        with sharding.pin_tile_devices(jax.devices()):\n"
        "            raise SystemExit('nested pin accepted')\n"
        "    except RuntimeError:\n"
        "        pass\n"
        "    r1 = api.compress_stream(x, out + '/b.gwtc', eb=1e-3, tile=(8, 8, 8),\n"
        "                             mem_budget=256 << 10)\n"
        "assert len(sharding.tile_devices()) == 4\n"
        "assert r4.batch_tiles % 4 == 0 and r4.n_batches > 1\n"
        "a = open(out + '/a.gwtc', 'rb').read()\n"
        "assert a == open(out + '/b.gwtc', 'rb').read()\n"
        "print(len(a))\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4").strip()
    proc = subprocess.run([sys.executable, "-c", code, str(tmp_path)], env=env,
                          capture_output=True, timeout=300)
    assert proc.returncode == 0, proc.stderr.decode()
    assert int(proc.stdout) > 0


# -- GWLZ over the tile grid ---------------------------------------------------


def test_gwlz_tiled_roundtrip_and_region(vol):
    gw = GWLZ(train_cfg=GWLZTrainConfig(n_groups=4, epochs=3, batch_size=8,
                                        min_group_pixels=64))
    art, stats = gw.compress_tiled(vol, (8, 16, 8), rel_eb=1e-3)
    assert "gwlz" in art.extras and stats.n_model_params > 0
    assert stats.max_err_sz <= stats.eb_abs * (1 + 1e-6)
    art2 = tiled.TiledCompressed.from_bytes(art.to_bytes())
    full = gw.decompress_tiled(art2)
    assert full.shape == vol.shape
    roi = (slice(2, 18), slice(5, 30), (0, 9))
    reg = gw.decompress_region(art2, roi)
    np.testing.assert_array_equal(
        np.asarray(reg), np.asarray(full)[2:18, 5:30, 0:9])


@pytest.mark.parametrize("pred", ["lorenzo", "interp"])
def test_gwlz_tiled_region_bitexact_both_predictors(vol, pred):
    """The enhanced region decode equals the enhanced full decode's crop for
    every registered predictor (the tile_transform contract)."""
    gw = GWLZ(train_cfg=GWLZTrainConfig(n_groups=4, epochs=2, batch_size=8,
                                        min_group_pixels=64))
    art, _ = gw.compress_tiled(vol, (8, 16, 8), rel_eb=1e-3, predictor=pred)
    assert art.predictor == pred
    full = np.asarray(gw.decompress_tiled(art))
    roi = (slice(1, 17), slice(9, 31), slice(2, 14))
    np.testing.assert_array_equal(
        np.asarray(gw.decompress_region(art, roi)), full[roi])


def test_batched_tile_enhancement_bitexact_vs_loop(vol):
    """The lax.map batched enhancer must reproduce the per-tile Python loop
    bit-for-bit (with and without bound clamping) — it replaces that loop on
    the decode hot path."""
    from repro.core.pipeline import deserialize_model
    from repro.core.trainer import enhance_tiles, enhance_tiles_looped

    gw = GWLZ(train_cfg=GWLZTrainConfig(n_groups=4, epochs=2, batch_size=8,
                                        min_group_pixels=64))
    art, _ = gw.compress_tiled(vol, (8, 16, 8), rel_eb=1e-3)
    model = deserialize_model(art.extras["gwlz"])
    recon_tiles, lanes = tiled.decode_lanes(art, range(art.n_tiles))
    assert lanes == art.n_tiles
    for clamp in (None, art.eb_abs):
        batched = enhance_tiles(recon_tiles, model, clamp_eb=clamp)
        looped = enhance_tiles_looped(recon_tiles, model, clamp_eb=clamp)
        np.testing.assert_array_equal(np.asarray(batched), np.asarray(looped))


def test_enhance_tiles_counts_the_path_it_takes(vol, monkeypatch):
    """One ``enhance_tiles`` call records one count, under the path its
    slices take, carrying the input tiles' bytes: the reference on the CPU;
    the kernel where the backend is a TPU and the slices fit its blocks."""
    from repro import obs
    from repro.core import trainer
    from repro.core.pipeline import deserialize_model
    from repro.kernels import ops

    gw = GWLZ(train_cfg=GWLZTrainConfig(n_groups=4, epochs=1, batch_size=8,
                                        min_group_pixels=64))
    art, _ = gw.compress_tiled(vol, (8, 16, 8), rel_eb=1e-3)
    model = deserialize_model(art.extras["gwlz"])
    recon_tiles, _ = tiled.decode_lanes(art, range(art.n_tiles))
    with obs.collect() as col:
        trainer.enhance_tiles(recon_tiles, model)
    st = col.stages()
    assert st["gwlz.enhance.jnp"][0] == 1
    assert st["gwlz.enhance.jnp"][2] == recon_tiles.nbytes
    assert "gwlz.enhance.kernel" not in st
    # the choice is by shape: on a TPU, 8 slices of 16x16 fill whole 128-lane
    # rows of the kernel's blocks, and 5 do not
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    with obs.collect() as col:
        trainer._count_path(8, (16, 16), 64, 4096)
        trainer._count_path(5, (16, 16), 64, 2560)
    assert col.stages()["gwlz.enhance.kernel"][::2] == (1, 4096)
    assert col.stages()["gwlz.enhance.jnp"][::2] == (1, 2560)


def test_gwlz_tiled_enhancement_improves_or_gates(vol):
    """With a real training budget the enhancer must help (or gate itself off
    to identity) — never hurt the tiled reconstruction."""
    gw = GWLZ(train_cfg=GWLZTrainConfig(n_groups=4, epochs=25, batch_size=8,
                                        min_group_pixels=64))
    _, stats = gw.compress_tiled(vol, (8, 16, 8), rel_eb=1e-3)
    assert stats.psnr_gwlz >= stats.psnr_sz - 1e-6


# -- bucketed dispatch + compile-cache accounting (ISSUE 10) -------------------


def test_bucket_helpers():
    assert tiled.bucket_for(1) == 1
    assert tiled.bucket_for(3) == 4
    assert tiled.bucket_for(32) == 32
    assert tiled.bucket_for(5, bucket_cap=4) == 4
    assert tiled.bucket_chunks(70, 32) == [32, 32, 8]
    assert tiled.bucket_chunks(5, 4) == [4, 1]
    assert tiled.bucket_chunks(7, 4) == [4, 4]
    assert tiled.bucket_chunks(70, 0) == [70]  # cap<=0 disables bucketing
    assert tiled.bucket_chunks(0) == []


def test_bucketed_decode_accounting(vol):
    """Dispatch/program counters must reflect the bucket plan exactly: 7
    lanes under cap 4 is two width-4 dispatches with one padded row, and the
    bucketed bytes equal the unbucketed ones."""
    art, _ = tiled.compress_tiled(
        vol, (8, 16, 8), abs_eb=float(jnp.max(vol) - jnp.min(vol)) * 1e-3)
    before = tiled.dispatch_stats()
    plain, _ = tiled.decode_lanes(art, range(7), bucket_cap=0)
    mid = tiled.dispatch_stats()
    # the unpadded call is still one counted device dispatch (width 7)
    assert mid["dispatches"] - before["dispatches"] == 1
    bucketed, _ = tiled.decode_lanes(art, range(7), bucket_cap=4)
    after = tiled.dispatch_stats()
    assert after["dispatches"] - mid["dispatches"] == 2  # chunks [4, 4]
    assert after["padded_tiles"] - mid["padded_tiles"] == 1  # 7 -> 4 + pad(3->4)
    assert after["batch_hist"].get(4, 0) - mid["batch_hist"].get(4, 0) == 2
    np.testing.assert_array_equal(np.asarray(bucketed), np.asarray(plain))


def test_register_program_key_counts_once():
    import random

    key = ("test-program", random.getrandbits(64))
    before = tiled.dispatch_stats()["programs"]
    assert tiled.register_program_key(key) is True, "first sighting compiles"
    assert tiled.register_program_key(key) is False, "re-registration is warm"
    assert tiled.dispatch_stats()["programs"] - before == 1


def test_quarantine_many_bad_lanes(vol):
    """The quarantine mask must be built in linear time and stay correct
    when MOST lanes are bad (the mask build used to rebuild ``set(good)``
    per lane, quadratic in the lane count) — every tampered lane decodes to
    the fill value, every healthy one to its clean bytes."""
    art, _ = tiled.compress_tiled(
        vol, (8, 16, 8), abs_eb=float(jnp.max(vol) - jnp.min(vol)) * 1e-3)
    clean = np.asarray(tiled.decode_lanes(art, range(art.n_tiles))[0])
    a = tiled.TiledCompressed.from_bytes(art.to_bytes())
    assert a.lane_crcs is not None
    keep = {3, 11, 20}
    a.lane_crcs = a.lane_crcs.copy()
    for i in range(a.n_tiles):
        if i not in keep:
            a.lane_crcs[i] ^= 0xBEEF
    a.verify, a.on_corrupt, a.fill_value = "lazy", "quarantine", -5.0
    recon, lanes, bad = tiled.decode_lanes(a, range(a.n_tiles),
                                           with_mask=True)
    assert lanes == len(keep)
    r = np.asarray(recon)
    for i in range(a.n_tiles):
        if i in keep:
            assert not bad[i]
            np.testing.assert_array_equal(r[i], clean[i])
        else:
            assert bad[i] and np.all(r[i] == -5.0)
    assert len(a.quarantined) == a.n_tiles - len(keep)
