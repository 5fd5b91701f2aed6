"""Enhancer training with its group axis split over the tile devices.

The G group models are independent, so ``train_enhancers`` gives each of n
tile devices a block of G/n groups (padded with inactive groups where n does
not divide G).  What must hold: the model, and so the container, does not
depend on n.  The multi-device checks run in a subprocess on four forced
host CPU devices, the way ``test_tiled.py`` rehearses the 4-chip ingest.

Also here: one training step against a plain ``jax.numpy`` reference of the
paper's per-group objective (the Fig. 3 CNN, masked MSE, Adam written out).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import enhancer, grouping, trainer
from repro.optim import AdamWConfig, adamw

ROOT = os.path.join(os.path.dirname(__file__), "..")

# Training data with a learnable residual, so some groups' gates open: the
# residual is a smooth function of the decoded value plus a little noise.
_TRAIN_DATA = """
import numpy as np
rng = np.random.default_rng(3)
x = rng.normal(size=(24, 16, 16)).astype(np.float32).cumsum(axis=1)
r = (0.2 * np.sin(2.0 * x) + 0.02 * rng.normal(size=x.shape)).astype(np.float32)
"""
_CFG = dict(epochs=6, batch_size=4, min_group_pixels=16)


def _train_data():
    env: dict = {}
    exec(_TRAIN_DATA, env)
    return env["x"], env["r"]


def _model_arrays(model, hist) -> dict:
    return {"params": jax.tree.map(np.asarray, model.params),
            "bn_state": jax.tree.map(np.asarray, model.bn_state),
            "edges": np.asarray(model.edges), "rscale": np.asarray(model.rscale),
            "gate": hist["gate"], "loss": hist["loss"]}


def _assert_same(a, b):
    la, lb = jax.tree_util.tree_leaves_with_path(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for (path, x), y in zip(la, lb):
        np.testing.assert_array_equal(x, y, err_msg=jax.tree_util.keystr(path))


# -- four host devices, in a subprocess ---------------------------------------

_MESH_SCRIPT = _TRAIN_DATA + """
import json, sys
import jax
from repro import api
from repro.core.trainer import GWLZTrainConfig, train_enhancers
from repro.data import nyx_like_field
from repro.launch import sharding

assert len(sharding.tile_devices()) == 4
out = {}

def arrays(model, hist):
    leaves = jax.tree.leaves((model.params, model.bn_state, model.edges,
                              model.rscale, hist["gate"], hist["loss"]))
    return [np.asarray(a).tolist() for a in leaves]

for g in (8, 6):
    cfg = GWLZTrainConfig(n_groups=g, **%(cfg)r)
    mesh = arrays(*train_enhancers(x, r, cfg))
    with sharding.pin_tile_devices(jax.devices()[:1]):
        one = arrays(*train_enhancers(x, r, cfg))
    out[g] = {"mesh": mesh, "one": one,
              "b1_shape": list(np.asarray(mesh[0]).shape)}

vol = np.asarray(nyx_like_field((32, 32, 32), "temperature", seed=4))
enh = GWLZTrainConfig(n_groups=6, epochs=2, batch_size=8, min_group_pixels=64)
reps = {}
for name, devs in (("mesh", jax.devices()), ("one", jax.devices()[:1])):
    with sharding.pin_tile_devices(devs):
        rep = api.compress_stream(vol, f"{sys.argv[1]}/{name}.gwtc", eb=1e-3,
                                  tile=(8, 8, 8), mem_budget=256 << 10, enhance=enh)
    reps[name] = {k: list(v) for k, v in rep.stages.items()}
out["stages"] = reps
out["same_bytes"] = (open(f"{sys.argv[1]}/mesh.gwtc", "rb").read()
                     == open(f"{sys.argv[1]}/one.gwtc", "rb").read())
# the same ingest again over the mesh, then the container read back both ways
again = api.compress_stream(vol, f"{sys.argv[1]}/again.gwtc", eb=1e-3,
                            tile=(8, 8, 8), mem_budget=256 << 10, enhance=enh)
out["again_compiled"] = again.programs_compiled
reads = {}
for name, devs in (("mesh", jax.devices()), ("one", jax.devices()[:1])):
    with sharding.pin_tile_devices(devs):
        reads[name] = np.asarray(api.open(f"{sys.argv[1]}/mesh.gwtc"))
out["same_decode"] = bool(np.array_equal(reads["mesh"], reads["one"]))
print(json.dumps(out))
""" % {"cfg": _CFG}


@pytest.fixture(scope="module")
def four_devices(tmp_path_factory):
    """Train and ingest on four host devices and pinned to one of them."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4").strip()
    proc = subprocess.run(
        [sys.executable, "-c", _MESH_SCRIPT, str(tmp_path_factory.mktemp("mesh"))],
        env=env, capture_output=True, timeout=600)
    assert proc.returncode == 0, proc.stderr.decode()[-4000:]
    return json.loads(proc.stdout.decode().strip().splitlines()[-1])


@pytest.mark.parametrize("n_groups", [8, 6], ids=["G8", "G6-padded-to-8"])
def test_train_enhancers_on_four_devices_matches_one(four_devices, n_groups):
    """Params, BN state, edges, rscale, gate and losses are bit-equal; at G=6
    the two padding groups of the 4-device split never reach the model."""
    got = four_devices[str(n_groups)]
    for mesh, one in zip(got["mesh"], got["one"]):
        np.testing.assert_array_equal(np.asarray(mesh), np.asarray(one))
    assert got["b1_shape"] == [n_groups, 9]


def test_some_gates_open_in_the_mesh_comparison(four_devices):
    """The comparison above covers the gated path: groups both kept and shut."""
    gate = np.asarray(four_devices["8"]["mesh"][-2])
    assert 0 < gate.sum() < gate.size


def test_enhanced_stream_on_four_devices_writes_the_same_container(four_devices):
    assert four_devices["same_bytes"]


def test_a_second_mesh_ingest_compiles_nothing(four_devices):
    """Every program of the mesh ingest, the per-device tile programs among
    them, is compiled once: an ingest of the same field compiles none."""
    assert four_devices["again_compiled"] == 0


def test_mesh_decode_reads_what_one_device_reads(four_devices):
    assert four_devices["same_decode"]


def test_mesh_training_records_its_spans_and_counter(four_devices):
    """``gwlz.train.shard``, ``gwlz.train.gather`` and one
    ``gwlz.train.group_mesh`` (bytes: one device's group state) reach
    ``StreamReport.stages`` on four devices, and none of them on one."""
    mesh, one = four_devices["stages"]["mesh"], four_devices["stages"]["one"]
    for name in ("gwlz.train.shard", "gwlz.train.gather", "gwlz.train.group_mesh"):
        assert name in mesh and name not in one
    n, _, nbytes = mesh["gwlz.train.group_mesh"]
    # 2 of the 8 padded groups: 190 params, 18 BN values and 380 Adam moments
    # of 4 bytes each per group, and Adam's int32 step count
    assert n == 1 and nbytes == 2 * (190 + 18 + 380) * 4 + 4
    assert mesh["gwlz.train.shard"][2] > mesh["gwlz.train.gather"][2] > 0
    assert mesh["gwlz.train.step"][0] == one["gwlz.train.step"][0]


# -- the share against the whole model, on one device -------------------------


def _train_block_alone(x, r, cfg, block):
    """The groups of one of the ``GROUP_BLOCKS`` blocks trained by themselves
    with the trainer's programs: the same batches, learning rates and passes
    as ``train_enhancers``, but no other group's state anywhere."""
    xs, rs = jnp.asarray(x), jnp.asarray(r)
    G = cfg.n_groups
    n_pad = -(-G // trainer.GROUP_BLOCKS) * trainer.GROUP_BLOCKS
    edges = grouping.compute_edges(xs, G, cfg.strategy)
    ids = grouping.assign_groups(xs, edges)
    rscale = trainer._per_group_scale(rs, ids, G)
    counts = jnp.zeros(G).at[ids.ravel()].add(1.0)
    rscale = jnp.where(counts >= cfg.min_group_pixels, rscale, 0.0)
    params = jax.vmap(lambda k: enhancer.init_params(k, cfg.channels))(
        jax.random.split(jax.random.PRNGKey(cfg.seed), G))
    bn = jax.vmap(lambda _: enhancer.init_state(cfg.channels))(jnp.arange(G))
    params, bn = trainer._pad_groups((params, bn), n_pad)
    rows = slice(block * n_pad // trainer.GROUP_BLOCKS,
                 (block + 1) * n_pad // trainer.GROUP_BLOCKS)
    cut = lambda t: jax.tree.map(lambda a: a[rows], t)  # noqa: E731
    params, bn = cut(params), cut(bn)
    groups = cut(trainer._group_table(edges, rscale, n_pad))
    opt = adamw.init(params)
    bs, n = cfg.batch_size, xs.shape[0]
    sched = trainer.step_decay(cfg.lr, cfg.lr_decay_factor,
                               cfg.lr_decay_every_epochs * (n // bs))
    rng, step = np.random.default_rng(cfg.seed), 0
    for _ in range(cfg.epochs):
        order = rng.permutation(n)
        for s in range(n // bs):
            idx = order[s * bs:(s + 1) * bs]
            params, bn, opt, _ = trainer.train_step(
                params, bn, opt, xs[idx], rs[idx], ids[idx], groups, sched(step),
                residual_learning=True, adam_cfg=AdamWConfig())
            step += 1
    bn = trainer._bn_calibrate(params, xs, ids, groups)
    gate = trainer._gate_groups(params, bn, xs, rs, ids, groups)
    return jax.tree.map(np.asarray, {"params": params, "bn_state": bn, "gate": gate})


def test_each_block_trained_alone_makes_up_the_model():
    """Each of the four blocks of two groups (one per device of a four-chip
    host), trained with no other group's state in its programs, gives
    exactly its groups of the whole training; the blocks together are the
    whole model."""
    x, r = _train_data()
    cfg = trainer.GWLZTrainConfig(n_groups=8, **_CFG)
    model, hist = trainer.train_enhancers(x, r, cfg)
    whole = _model_arrays(model, hist)
    alone = [_train_block_alone(x, r, cfg, b) for b in range(trainer.GROUP_BLOCKS)]
    for b, part in enumerate(alone):
        rows = slice(2 * b, 2 * b + 2)
        _assert_same(part, {"params": jax.tree.map(lambda a: a[rows], whole["params"]),
                            "bn_state": jax.tree.map(lambda a: a[rows], whole["bn_state"]),
                            "gate": whole["gate"][rows]})
    joined = jax.tree.map(lambda *a: np.concatenate(a), *alone)
    _assert_same(joined, {k: whole[k] for k in ("params", "bn_state", "gate")})


# -- one step against a plain reference ---------------------------------------


def _ref_conv(x, w, b):
    """3x3 SAME convolution, one tap at a time: x [B,H,W,Cin], w [3,3,Cin,Cout]."""
    h, wd = x.shape[1], x.shape[2]
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    out = jnp.zeros(x.shape[:3] + (w.shape[-1],), jnp.float32) + b
    for dy in range(3):
        for dx in range(3):
            out = out + jnp.einsum("bhwc,co->bhwo", xp[:, dy:dy + h, dx:dx + wd], w[dy, dx])
    return out


def _ref_group_loss(p, state, x, r, mask, lo, hi, rscale):
    """Paper Fig. 3 on one group: normalize the group's pixels to its value
    range, conv 1->C, BatchNorm over the group's pixels (training mode),
    ReLU, conv C->1; masked MSE against the residual over its scale."""
    xn = (x - lo) / jnp.maximum(hi - lo, 1e-12) * mask
    target = r / jnp.where(rscale > 0, rscale, 1.0) * mask
    h = _ref_conv(xn[..., None], p["w1"], p["b1"])
    m = mask[..., None]
    cnt = jnp.maximum(m.sum(), 1.0)
    mean = (h * m).sum(axis=(0, 1, 2)) / cnt
    var = (((h - mean) ** 2) * m).sum(axis=(0, 1, 2)) / cnt
    h = jnp.maximum((h - mean) / jnp.sqrt(var + enhancer.BN_EPS) * p["gamma"] + p["beta"], 0.0)
    pred = _ref_conv(h, p["w2"], p["b2"])[..., 0]
    loss = (((pred - target) ** 2) * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    running = {"mean": 0.9 * state["mean"] + 0.1 * mean,
               "var": 0.9 * state["var"] + 0.1 * var}
    return loss, running


def _ref_adam(p, m, v, g, t, lr, b1=0.9, b2=0.999, eps=1e-8):
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mhat, vhat = m / (1 - b1 ** t), v / (1 - b2 ** t)
    return p - lr * mhat / (jnp.sqrt(vhat) + eps), m, v


def test_train_step_matches_plain_reference():
    """One ``train_step`` of G=5 groups (one inactive) on seeded random
    weights, BN state and Adam moments, group by group against the plain
    reference in float32 at the highest matmul precision.

    Tolerances: both sides compute in float32 and differ only in the order of
    sums (shift+einsum convs against a tap loop, over 2x16x16 pixels), so
    they differ by a few float32 ulps, amplified by the BN normalisation and
    Adam's division.  ``rtol`` 1e-5 on the loss and BN statistics and 2e-5
    on parameters and moments (of order 1e-3 to 1; ``atol`` for the entries
    near 0) are ten or more times what was seen.  Rounding the reference's
    conv operands to bfloat16, as one TPU default pass does, puts every
    compared quantity 250 or more times past its tolerance."""
    G, C, B, H = 5, 9, 2, 16
    rng = np.random.default_rng(7)
    xb = jnp.asarray(rng.normal(size=(B, H, H)).astype(np.float32).cumsum(axis=2))
    rb = jnp.asarray((0.1 * rng.normal(size=(B, H, H))).astype(np.float32))
    edges = grouping.compute_edges(xb, G, "quantile")
    ids = grouping.assign_groups(xb, edges)
    rscale = jnp.asarray([0.3, 0.0, 0.2, 0.25, 0.4], jnp.float32)  # group 1 inactive

    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    params = jax.vmap(lambda k: enhancer.init_params(k, C))(jax.random.split(keys[0], G))
    params = {k: v + 0.05 * jax.random.normal(jax.random.fold_in(keys[1], i), v.shape)
              for i, (k, v) in enumerate(sorted(params.items()))}
    bn = {"mean": 0.1 * jax.random.normal(keys[2], (G, C)),
          "var": 1.0 + 0.5 * jax.random.uniform(keys[3], (G, C))}
    opt = adamw.init(params)
    opt = {"step": jnp.asarray(3, jnp.int32),
           "m": jax.tree.map(lambda a: 0.01 * jnp.ones_like(a) * jnp.sign(a + 1e-3), params),
           "v": jax.tree.map(lambda a: 1e-3 + 1e-3 * jnp.abs(a), params)}
    lr = jnp.float32(1e-3)

    got = trainer.train_step(params, bn, opt, xb, rb, ids,
                             trainer._group_table(edges, rscale, G), lr,
                             residual_learning=True, adam_cfg=AdamWConfig())
    new_p, new_bn, new_opt, losses = jax.tree.map(np.asarray, got)

    with jax.default_matmul_precision("highest"):
        for g in range(G):
            pg = jax.tree.map(lambda a: a[g], params)
            sg = jax.tree.map(lambda a: a[g], bn)
            mask = (ids == g).astype(jnp.float32)
            args = (xb, rb, mask, edges[g], edges[g + 1], rscale[g])
            (loss, running), grad = jax.value_and_grad(_ref_group_loss, has_aux=True)(
                pg, sg, *args)
            active = float(rscale[g] > 0)
            np.testing.assert_allclose(losses[g], active * float(loss), rtol=1e-5)
            for k in ("mean", "var"):
                np.testing.assert_allclose(new_bn[k][g], running[k], rtol=1e-5, atol=1e-7)
            for k in pg:
                p1, m1, v1 = _ref_adam(pg[k], opt["m"][k][g], opt["v"][k][g],
                                       active * grad[k], 4, lr)
                np.testing.assert_allclose(new_p[k][g], p1, rtol=2e-5, atol=1e-6,
                                           err_msg=f"group {g} {k}")
                np.testing.assert_allclose(new_opt["m"][k][g], m1, rtol=2e-5, atol=1e-6)
                np.testing.assert_allclose(new_opt["v"][k][g], v1, rtol=2e-5, atol=1e-9)
