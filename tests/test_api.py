"""The `repro.api` façade: one front door over both containers.

Covers the PR's acceptance surface: save -> open -> slice round trips for
monolithic, tiled (both predictors), and multi-field GWDS envelopes;
self-sniffing `api.open` on the pre-existing golden byte streams; lazy
slicing semantics (tiled slices decode only intersecting lanes and equal
the full decode's crop bit-for-bit); and the CLI smoke path in-process."""
import os

import numpy as np
import pytest

from repro import api, cli
from repro.core import GWLZ, GWLZTrainConfig
from repro.sz import artifact as A
from repro.sz import tiled
from repro.sz.szjax import SZCompressed, SZCompressor
from repro.sz.tiled import TiledCompressed

GOLDEN = os.path.join(os.path.dirname(__file__), "golden")


@pytest.fixture(scope="module")
def volume():
    return np.load(os.path.join(GOLDEN, "volume_12_20_9.npy"))


# ---------------------------------------------------------------------------
# handle semantics
# ---------------------------------------------------------------------------


def test_handle_metadata_and_protocol(volume):
    vol = api.compress(volume, abs_eb=1e-2)
    assert (vol.shape, vol.dtype, vol.ndim) == ((12, 20, 9), np.float32, 3)
    assert not vol.tiled and not vol.enhanced
    assert vol.nbytes == len(vol.to_bytes())
    assert vol.size_report()["total"] == vol.nbytes
    # both containers satisfy the common Artifact protocol
    assert isinstance(vol.artifact, A.Artifact)
    tv = api.compress(volume, abs_eb=1e-2, tiled=True, tile=(8, 8, 8))
    assert tv.tiled and isinstance(tv.artifact, A.Artifact)
    assert A.container_magics()[b"SZJX"] is SZCompressed
    assert A.container_magics()[b"GWTC"] is TiledCompressed


def test_monolithic_slicing_is_crop_after_decode(volume):
    vol = api.compress(volume, abs_eb=1e-2, predictor="interp")
    full = np.asarray(vol)
    assert full.shape == (12, 20, 9)
    assert np.max(np.abs(full - volume)) <= vol.eb_abs * (1 + 1e-6)
    # decode is cached once: slicing returns views of the same base buffer
    np.testing.assert_array_equal(vol[2:9, :, 3], full[2:9, :, 3])
    np.testing.assert_array_equal(vol[3], full[3])
    np.testing.assert_array_equal(vol[..., 1:7], full[..., 1:7])
    np.testing.assert_array_equal(vol[1:11:3, -2, ::2], full[1:11:3, -2, ::2])
    assert vol.decode() is vol.decode()
    # the cache is handed out directly, so it must be immutable ...
    assert not full.flags.writeable
    with pytest.raises(ValueError):
        full[0, 0, 0] = 1.0
    assert np.asarray(vol, dtype=np.float64).flags.writeable  # conversions copy
    # ... but slices are writable on BOTH containers (tiled ones are fresh
    # decodes, so monolithic crops copy out of the cache)
    assert vol[2:5].flags.writeable


def test_slicing_edge_cases(volume):
    vol = api.compress(volume, abs_eb=1e-2)
    full = np.asarray(vol)
    assert vol[5:5].shape == (0, 20, 9)
    np.testing.assert_array_equal(vol[-3:], full[-3:])
    with pytest.raises(IndexError):
        vol[0, 0, 0, 0]
    with pytest.raises(IndexError):
        vol[99]
    with pytest.raises(IndexError):
        vol[::-1]
    with pytest.raises(IndexError):
        vol[[1, 2]]


@pytest.mark.parametrize("pred", ["lorenzo", "interp"])
def test_tiled_slice_decodes_only_intersecting_lanes(volume, pred):
    """Acceptance: api.open(path)[roi] touches only intersecting lanes and is
    bit-identical to the same ROI cropped from np.asarray(vol)."""
    vol = api.compress(volume, abs_eb=1e-2, tiled=True, tile=(8, 8, 8),
                       predictor=pred)
    roi = (slice(2, 9), slice(8, 20), slice(0, 5))
    block = vol[roi]
    # grid is (2, 3, 2); the roi spans 2 x 2 x 1 of the 12 tiles
    assert (tiled.DECODE_STATS["tiles_decoded"], tiled.DECODE_STATS["tiles_total"]) == (4, 12)
    assert api.region_lane_count(vol, roi) == (4, 12)
    full = np.asarray(vol)
    np.testing.assert_array_equal(block, full[roi])
    # slicing stays a partial read even after the full decode warmed the cache
    vol[roi]
    assert tiled.DECODE_STATS["tiles_decoded"] == 4
    # int + stepped indexing rides the same region path
    np.testing.assert_array_equal(vol[3, 9:17:2, 1:8:3], full[3, 9:17:2, 1:8:3])


# ---------------------------------------------------------------------------
# persistence round trips
# ---------------------------------------------------------------------------


def test_monolithic_save_open_slice(tmp_path, volume):
    vol = api.compress(volume, abs_eb=1e-2)
    path = tmp_path / "mono.szjx"
    written = api.save(path, vol)
    assert written == os.path.getsize(path) == vol.nbytes
    vol2 = api.open(path)
    assert not vol2.tiled and vol2.shape == vol.shape
    np.testing.assert_array_equal(np.asarray(vol2), np.asarray(vol))
    np.testing.assert_array_equal(vol2[4:9, 2:5, :], np.asarray(vol)[4:9, 2:5, :])


@pytest.mark.parametrize("pred", ["lorenzo", "interp"])
def test_tiled_save_open_slice(tmp_path, volume, pred):
    vol = api.compress(volume, abs_eb=1e-2, tiled=True, tile=(8, 8, 8),
                       predictor=pred)
    path = tmp_path / f"tiled_{pred}.gwtc"
    assert api.save(path, vol) == os.path.getsize(path) == vol.nbytes
    vol2 = api.open(path)
    assert vol2.tiled and vol2.artifact.predictor == pred
    roi = (slice(0, 8), slice(10, 20), slice(1, 9))
    np.testing.assert_array_equal(vol2[roi], np.asarray(vol)[roi])


def test_enhanced_tiled_roundtrip_applies_enhancer_per_tile(tmp_path, volume):
    # normalize to O(1) so enhancement deltas are representable in f32
    x = volume / np.float32(np.abs(volume).max())
    cfg = GWLZTrainConfig(n_groups=2, epochs=4, batch_size=4, min_group_pixels=16)
    vol = api.compress(x, abs_eb=1e-3, tiled=True, tile=(8, 8, 8),
                       enhance=cfg, predictor="lorenzo")
    assert vol.enhanced and vol.stats is not None
    path = tmp_path / "enh.gwtc"
    api.save(path, vol)
    vol2 = api.open(path)
    assert vol2.enhanced, "enhancer model must survive the round trip"
    full = np.asarray(vol2)
    roi = (slice(2, 9), slice(8, 20), slice(0, 5))
    np.testing.assert_array_equal(vol2[roi], full[roi])
    # the decode really is the enhanced one, not the raw SZ recon
    raw = np.asarray(SZCompressor().decompress_tiled(vol2.artifact))
    assert not np.array_equal(full, raw)


def test_enhanced_monolithic_roundtrip(tmp_path, volume):
    cfg = GWLZTrainConfig(n_groups=2, epochs=2, batch_size=4, min_group_pixels=16)
    vol = api.compress(volume, abs_eb=1e-2, enhance=cfg)
    path = tmp_path / "enh.szjx"
    api.save(path, vol)
    vol2 = api.open(path)
    assert vol2.enhanced
    np.testing.assert_array_equal(np.asarray(vol2), np.asarray(vol))
    np.testing.assert_array_equal(
        np.asarray(vol2), np.asarray(GWLZ().decompress(vol.artifact)))


def test_gwds_multifield_roundtrip(tmp_path, volume):
    mono = api.compress(volume, abs_eb=1e-2)
    til = api.compress(volume, abs_eb=2e-2, tiled=True, tile=(8, 8, 8))
    path = tmp_path / "snap.gwds"
    written = api.save(path, {"temperature": mono, "baryon_density": til})
    assert written == os.path.getsize(path)
    ds = api.open(path)
    assert isinstance(ds, api.Dataset)
    assert ds.fields == ("temperature", "baryon_density") and len(ds) == 2
    assert set(ds.keys()) == {"temperature", "baryon_density"}
    np.testing.assert_array_equal(np.asarray(ds["temperature"]), np.asarray(mono))
    assert ds["baryon_density"].tiled
    np.testing.assert_array_equal(
        ds["baryon_density"][0:8, 2:11, :], np.asarray(til)[0:8, 2:11, :])
    rep = ds.size_report()
    assert rep["total"] == ds.nbytes == written
    assert rep["fields"]["temperature"] == mono.nbytes
    with pytest.raises(KeyError):
        ds["nope"]
    # a Dataset itself re-saves verbatim
    assert api.save(tmp_path / "snap2.gwds", ds) == written


def test_gwds_rejects_empty_and_bad_saves(tmp_path):
    with pytest.raises(ValueError):
        api.Dataset.build({})
    with pytest.raises(TypeError):
        api.save(tmp_path / "x", object())
    # uncompressed arrays inside a mapping get the friendly TypeError too
    with pytest.raises(TypeError, match="compress it first"):
        api.save(tmp_path / "x", {"temperature": np.zeros((4, 4, 4))})


def test_gwds_truncated_blob_raises_valueerror(tmp_path, volume):
    vol = api.compress(volume, abs_eb=1e-2)
    path = tmp_path / "snap.gwds"
    api.save(path, {"t": vol})
    blob = path.read_bytes()
    for cut in (6, 20, len(blob) - 50):  # mid-header, mid-index, mid-payload
        with pytest.raises(ValueError):
            api.from_bytes(blob[:cut])


def test_open_rejects_unknown_magic(tmp_path):
    path = tmp_path / "junk.bin"
    path.write_bytes(b"NOPE" + b"\x00" * 32)
    with pytest.raises(ValueError, match="unknown container magic"):
        api.open(path)


# ---------------------------------------------------------------------------
# golden byte streams keep opening through the façade
# ---------------------------------------------------------------------------


def test_open_golden_gwtc_v1():
    vol = api.open(os.path.join(GOLDEN, "gwtc_v1.bin"))
    assert vol.tiled and vol.artifact.predictor == "lorenzo"
    np.testing.assert_array_equal(
        np.asarray(vol), np.load(os.path.join(GOLDEN, "gwtc_v1_decode.npy")))


@pytest.mark.parametrize("pred", ["lorenzo", "interp"])
def test_open_golden_szjx(pred):
    vol = api.open(os.path.join(GOLDEN, f"szjx_{pred}.bin"))
    assert not vol.tiled and vol.artifact.predictor == pred
    np.testing.assert_array_equal(
        np.asarray(vol), np.load(os.path.join(GOLDEN, f"szjx_{pred}_decode.npy")))


# ---------------------------------------------------------------------------
# shims: the historical per-container GWLZ surface still works
# ---------------------------------------------------------------------------


def test_gwlz_decode_unifies_both_containers(volume):
    gw = GWLZ()
    art, _ = SZCompressor().compress(volume, abs_eb=1e-2)
    full = np.asarray(gw.decode(art))
    np.testing.assert_array_equal(np.asarray(gw.decompress(art)), full)
    roi = (slice(1, 7), slice(0, 9), slice(2, 8))
    np.testing.assert_array_equal(np.asarray(gw.decode(art, roi)), full[roi])

    tart, _ = SZCompressor().compress_tiled(volume, (8, 8, 8), abs_eb=1e-2)
    tfull = np.asarray(gw.decode(tart))
    np.testing.assert_array_equal(np.asarray(gw.decompress_tiled(tart)), tfull)
    np.testing.assert_array_equal(
        np.asarray(gw.decompress_region(tart, roi)), tfull[roi])


def test_compress_volume_matches_shim(volume):
    cfg = GWLZTrainConfig(n_groups=2, epochs=2, batch_size=4, min_group_pixels=16)
    vol = GWLZ(train_cfg=cfg).compress_volume(volume, abs_eb=1e-2)
    assert isinstance(vol, api.CompressedVolume) and vol.stats is not None
    assert vol.enhanced and vol.stats.eb_abs == vol.eb_abs


# ---------------------------------------------------------------------------
# CLI (in-process; CI runs the same flow as a subprocess smoke step)
# ---------------------------------------------------------------------------


def test_cli_roundtrip(tmp_path, volume):
    src = tmp_path / "x.npy"
    np.save(src, volume)
    out = tmp_path / "x.gwtc"
    assert cli.main(["compress", str(src), str(out), "--eb", "1e-3",
                     "--tiled", "--tile", "8"]) == 0
    assert cli.main(["info", str(out)]) == 0
    roi_npy = tmp_path / "roi.npy"
    assert cli.main(["region", str(out), "--roi", "2:9,8:20,0:5",
                     "--out", str(roi_npy)]) == 0
    full_npy = tmp_path / "full.npy"
    assert cli.main(["decompress", str(out), str(full_npy)]) == 0
    full = np.load(full_npy)
    np.testing.assert_array_equal(np.load(roi_npy), full[2:9, 8:20, 0:5])
    eb_abs = api.open(out).eb_abs
    assert np.max(np.abs(full - volume)) <= eb_abs * (1 + 1e-6)


def test_cli_synthetic_and_parse_roi(tmp_path):
    out = tmp_path / "s.szjx"
    assert cli.main(["compress", "synthetic:temperature:12", str(out),
                     "--eb", "1e-3"]) == 0
    assert cli.main(["info", str(out)]) == 0
    # region accepts everything vol[roi] accepts: steps, ints, partial rank
    assert cli.main(["region", str(out), "--roi", "0:8:2,3,:"]) == 0
    assert cli.main(["region", str(out), "--roi", "0:4"]) == 0
    assert cli.main(["region", str(out), "--roi", "2:2,:,:"]) == 0  # empty roi
    # bad ROIs exit cleanly instead of spilling tracebacks
    for bad in ("a:b", "0:8:-1,:,:", "99", "1,2,3,4"):
        with pytest.raises(SystemExit):
            cli.main(["region", str(out), "--roi", bad])


def test_cli_gwds_field_selection(tmp_path, volume, capsys):
    a = api.compress(volume, abs_eb=1e-2)
    path = tmp_path / "snap.gwds"
    api.save(path, {"t": a, "rho": a})
    out = tmp_path / "t.npy"
    assert cli.main(["decompress", str(path), str(out), "--field", "t"]) == 0
    np.testing.assert_array_equal(np.load(out), np.asarray(a))
    # usage errors print to stderr and exit 2 (the normalized CLI contract)
    with pytest.raises(SystemExit) as ei:
        cli.main(["decompress", str(path), str(out)])
    assert ei.value.code == 2
    assert "pick one with --field" in capsys.readouterr().err
    with pytest.raises(SystemExit) as ei:
        cli.main(["decompress", str(path), str(out), "--field", "nope"])
    assert ei.value.code == 2
    assert "no field" in capsys.readouterr().err
    with pytest.raises(SystemExit) as ei:
        out2 = tmp_path / "m.szjx"
        api.save(out2, a)
        cli.main(["decompress", str(out2), str(out), "--field", "t"])
    assert ei.value.code == 2
    assert "--field only applies" in capsys.readouterr().err
    assert cli.parse_roi("8:40,:,16:32") == (slice(8, 40), slice(None), slice(16, 32))
    assert cli.parse_roi("3,::2") == (3, slice(None, None, 2))
    with pytest.raises(ValueError):
        cli.parse_roi("1:2:3:4")


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_dir_rule(tmp_path, env_dir):
    """Entry points' compile cache: JAX_COMPILATION_CACHE_DIR when set (and
    no other path), else the fixed <checkout>/.jax_cache.  Checked in a
    child process that compiles nothing, so no test writes a cache entry."""
    import subprocess
    import sys

    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    code = ("import jax\n"
            "from repro.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    returned, configured = proc.stdout.splitlines()
    want = (str(tmp_path / env_dir) if env_dir is not None
            else os.path.join(root, ".jax_cache"))
    assert returned == configured == want
