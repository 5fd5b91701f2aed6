"""The decode mix end to end on the CPU, sound and broken underneath."""
import numpy as np
import pytest

from bench.tests.tiny import run

CELL = "nyx-dmd-512-eb1e-4.decode"


def test_decode_is_correct_and_control_is_not(trained_root):
    r = run(trained_root, CELL, control=True)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"decode_MBps", "setup_s"}
    assert set(r["control"]) == {"bf16", "fp8_enhancer"}
    assert not any(c["correct"] for c in r["control"].values()), r["control"]
    # the enhancer alone in float8 is caught by the enhancer's own number
    enh = r["control"]["fp8_enhancer"]["checks"]
    assert enh["enh_err"]["value"] > enh["enh_err"]["max"], enh
    assert enh["mismatch_share"]["value"] <= enh["mismatch_share"]["max"], enh


def test_enhancer_error_reads_the_worst_tile_against_the_median():
    import jax.numpy as jnp

    from bench import reference

    base = jnp.zeros((4, 4, 4))
    ref = base.at[:2].set(1.0)  # residual norm sqrt(8) in the 4 tiles of x < 2
    out = ref.at[0, 0, 0].add(2.0).at[3, 3, 3].add(2.0)
    # tile (0,0,0): 2 / sqrt(8); tile (1,1,1) has no residual, so the median
    # tile's, (0 + sqrt(8)) / 2, stands in: 2 / sqrt(2)
    assert reference.enhancer_error(out, ref, base, 2) == pytest.approx(2 ** 0.5)
    assert reference.enhancer_error(ref, ref, base, 2) == 0.0


@pytest.mark.parametrize("fault", ["altered", "half_batch"])
def test_decode_fault_is_not_correct(tiny_root, monkeypatch, fault):
    from repro.sz import tiled

    orig = tiled.decompress_tiled
    calls = []

    def broken(artifact, *a, **kw):
        if fault == "altered":
            out = np.array(orig(artifact, *a, **kw))
            calls.append(1)
            if len(calls) > 1:  # set-up's decode stays sound: break the window's
                out[0, 0, :4] += 10 * artifact.eb_abs
            return out
        # half of each decoded tile batch left out (zeros in its place)
        tf = kw.get("tile_transform")

        def half(tiles):
            done = tiles if tf is None else tf(tiles)
            return done.at[tiles.shape[0] // 2:].set(0.0)

        half.program_key = getattr(tf, "program_key", None)
        kw["tile_transform"] = half
        return orig(artifact, *a, **kw)

    monkeypatch.setattr(tiled, "decompress_tiled", broken)
    r = run(tiny_root, CELL)
    assert not r["correct"], r["checks"]
