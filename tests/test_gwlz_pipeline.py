"""End-to-end GWLZ: the paper's pipeline (Figs. 1-2) on synthetic Nyx."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import GWLZ, GWLZTrainConfig, deserialize_model, metrics, serialize_model
from repro.core.trainer import enhance, train_enhancers
from repro.sz import compress
from repro.sz.szjax import SZCompressed


@pytest.fixture(scope="module")
def compressed(nyx_small):
    x = jnp.asarray(nyx_small)
    cfg = GWLZTrainConfig(n_groups=4, epochs=40, batch_size=8, min_group_pixels=256)
    art, stats = GWLZ(train_cfg=cfg).compress(x, rel_eb=5e-3)
    return x, art, stats


def test_psnr_improves(compressed):
    x, art, stats = compressed
    # the gate guarantees enhancement never hurts on the training volume
    assert stats.psnr_gwlz >= stats.psnr_sz - 1e-3


def test_decompress_matches_compress_side(compressed):
    x, art, stats = compressed
    art2 = SZCompressed.from_bytes(art.to_bytes())
    out = GWLZ().decompress(art2)
    assert abs(float(metrics.psnr(x, out)) - stats.psnr_gwlz) < 1e-3


def test_overhead_accounting(compressed):
    x, art, stats = compressed
    assert stats.overhead > 0  # enhancer weights attached
    assert stats.cr_gwlz <= stats.cr_sz
    # ~200 params/model * 4 groups * 4B plus metadata
    assert stats.n_model_params < 1000


def test_model_serialization_roundtrip(compressed):
    x, art, stats = compressed
    model = deserialize_model(art.extras["gwlz"])
    blob2 = serialize_model(model)
    assert blob2 == art.extras["gwlz"]


def test_clamp_mode_bounds_error_at_2eb(nyx_small):
    """Clamped enhancement: |x_hat - x| <= 2e worst case (x and x_hat both lie
    in [x'-e, x'+e]); unclamped enhancement has no such guarantee."""
    x = jnp.asarray(nyx_small)
    cfg = GWLZTrainConfig(n_groups=2, epochs=15, batch_size=8)
    art, stats = GWLZ(train_cfg=cfg, clamp_to_bound=True).compress(x, rel_eb=1e-3)
    assert stats.max_err_gwlz <= 2 * art.eb_abs * (1 + 1e-5)


def test_groups_never_hurt(nyx_small):
    """With gating, any group count is >= the SZ baseline (the Table 3 trend
    itself is measured at benchmark scale — 48^3 / 150 epochs; a 32^3 CI
    volume is too noisy for strict monotonicity)."""
    x = jnp.asarray(nyx_small)
    art, recon = compress(x, rel_eb=5e-3, backend="zlib")
    resid = x - recon
    base = float(metrics.psnr(x, recon))
    for g in (1, 4):
        cfg = GWLZTrainConfig(n_groups=g, epochs=60, batch_size=8, min_group_pixels=256, seed=1)
        model, _ = train_enhancers(recon, resid, cfg)
        p = float(metrics.psnr(x, enhance(recon, model)))
        assert p >= base - 1e-3, (g, p, base)


def test_residual_beats_regular(nyx_small):
    """Paper Fig. 5: residual learning reconstructs better than direct
    regression (compared in the *denormalized* volume domain — the raw losses
    live in different normalized units)."""
    from repro.core import metrics

    x = jnp.asarray(nyx_small)
    art, recon = compress(x, rel_eb=5e-3, backend="zlib")
    resid = x - recon
    out_mse = {}
    for mode in (True, False):
        cfg = GWLZTrainConfig(n_groups=1, epochs=25, batch_size=8,
                              residual_learning=mode, gate_groups=False, seed=0)
        model, hist = train_enhancers(recon, resid, cfg)
        out = enhance(recon, model)
        out_mse[mode] = float(metrics.mse(x, out))
    assert out_mse[True] < out_mse[False]


def test_bn_calibrate_and_gate_match_whole_array_formulas():
    """The BN calibration and the gate scan slice batches (so no program
    grows with the reservoir); on 37 slices of 64x64 (three batches, the
    last one padded) they still give the whole-array masked statistics and
    the per-group MSE test."""
    import jax

    from repro.core import enhancer, grouping, trainer

    G, n = 5, 37
    rng = np.random.default_rng(11)
    xs = jnp.asarray(rng.normal(size=(n, 64, 64)).astype(np.float32))
    edges = grouping.compute_edges(xs, G, "quantile")
    ids = grouping.assign_groups(xs, edges)
    params = jax.vmap(lambda k: enhancer.init_params(k, 9))(
        jax.random.split(jax.random.PRNGKey(3), G))
    xn, masks = trainer._group_inputs(xs, ids, edges, G)

    h = jax.vmap(lambda p, x: enhancer._conv(x[..., None], p["w1"], p["b1"]))(params, xn)
    m = masks[..., None]
    cnt = jnp.maximum(m.sum(axis=(1, 2, 3)), 1.0)
    mean = (h * m).sum(axis=(1, 2, 3)) / cnt
    var = ((h - mean[:, None, None, None]) ** 2 * m).sum(axis=(1, 2, 3)) / cnt
    bn = trainer._bn_calibrate(params, xs, ids,
                               trainer._group_table(edges, jnp.zeros(G), G))
    np.testing.assert_allclose(bn["mean"], mean, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(bn["var"], var, rtol=1e-5, atol=1e-6)

    # residual = what the even-numbered groups' enhancers predict, so their
    # gates open and the odd groups' gates shut
    rscale = jnp.full((G,), 0.05)
    preds = jax.vmap(lambda p, st, x: enhancer.apply(p, st, x, train=False)[0])(
        params, bn, xn) * rscale[:, None, None, None]
    keep = (jnp.arange(G) % 2 == 0)[:, None, None, None]
    rs = (preds * masks * keep).sum(axis=0) + 1e-3
    want = ((((rs[None] - preds) * masks) ** 2).sum(axis=(1, 2, 3))
            < ((rs[None] * masks) ** 2).sum(axis=(1, 2, 3)))
    gate = trainer._gate_groups(params, bn, xs, rs, ids,
                                trainer._group_table(edges, rscale, G))
    np.testing.assert_array_equal(np.asarray(gate), np.asarray(want, np.float32))
    assert 0 < float(gate.sum()) < G
