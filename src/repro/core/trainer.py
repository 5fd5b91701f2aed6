"""Group-wise residual training (paper §3.2-3.3).

All G enhancers are trained *simultaneously*: the group axis is a leading
batch axis of the parameter pytree (``vmap`` over models).
The G models are independent (own parameters, BN state, Adam state and
masked loss), so the normal path splits the group axis into blocks dealt to
the tile devices (:func:`repro.launch.sharding.tile_devices`), like experts
in expert parallelism: on a multi-device host each device holds its blocks'
state, builds the masks of its own groups only, and no gradient crosses
devices; the slices are copied to every device.  The blocks and their
programs are the same on any device count (:data:`GROUP_BLOCKS`).
(``repro.launch.gwlz_dist`` is a dry-run sketch of the same mapping with the
slice batch also sharded.)

Faithful knobs (paper §4.1): C=9 channels / 2 convs (~200 params per model),
batch of 10 slices, 300 epochs, Adam lr 1e-3 with a step decay every 30
epochs.  ``residual_learning=False`` reproduces the "Regular" baseline of
Fig. 5 (predict the original data directly instead of the residual).
"""
from __future__ import annotations

from contextlib import nullcontext
from dataclasses import dataclass, field, replace
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import enhancer, grouping
from repro.kernels import ops
from repro.launch import sharding
from repro.optim import AdamWConfig
from repro.optim import adamw
from repro.optim.schedule import step_decay


@dataclass(frozen=True)
class GWLZTrainConfig:
    n_groups: int = 20
    strategy: str = "quantile"
    channels: int = 9
    epochs: int = 300
    batch_size: int = 10
    lr: float = 1e-3
    lr_decay_every_epochs: int = 30
    lr_decay_factor: float = 0.5
    seed: int = 0
    slice_axis: int = 0
    residual_learning: bool = True  # False -> Fig. 5 "Regular" baseline
    # Robustness beyond the paper (DESIGN.md §8): tiny groups can't train a
    # CNN (masked-BN variance degenerates), and a group whose enhancement
    # hurts on the training volume should be disabled — both get identity
    # enhancement via rscale=0.  Costs nothing in the stream.
    min_group_pixels: int = 1024
    gate_groups: bool = True


@dataclass
class GWLZModel:
    """Everything the reconstruction side needs (serialized into the stream)."""

    params: dict  # leaves have leading [G] axis
    bn_state: dict  # leading [G]
    edges: jax.Array  # [G+1]
    rscale: jax.Array  # [G] residual normalization scale
    cfg: GWLZTrainConfig = field(default_factory=GWLZTrainConfig)

    @property
    def n_params(self) -> int:
        return sum(int(p.size) for p in jax.tree_util.tree_leaves(self.params))


def _as_slices(x: jax.Array, axis: int) -> jax.Array:
    return jnp.moveaxis(x, axis, 0)


def _per_group_scale(r: jax.Array, ids: jax.Array, n_groups: int) -> jax.Array:
    """max |R| within each group (normalizes the learning target)."""
    absr = jnp.abs(r).ravel()
    s = jnp.zeros(n_groups).at[ids.ravel()].max(absr)
    return jnp.maximum(s, 1e-12)


def _block_inputs(xb, idsb, groups):
    """Normalized, masked inputs of a block of groups: [g, B, H, W] (+ masks).

    ``groups`` is the block's slice of a :func:`_group_table`; masks are
    built for the block's own groups only."""
    at = lambda v: v[:, None, None, None]  # noqa: E731
    masks = (idsb[None] == at(groups["id"])).astype(xb.dtype)  # [g,B,H,W]
    xn = (xb[None] - at(groups["lo"])) / at(groups["scale"])
    return xn * masks, masks


def _group_inputs(xb, idsb, edges, n_groups):
    """Normalized, masked inputs for every group: [G, B, H, W] (+ masks)."""
    return _block_inputs(xb, idsb, _group_table(edges, jnp.zeros(n_groups), n_groups))


def _pad_groups(tree, n: int):
    """Pad every leaf's leading group axis to ``n`` with copies of group 0."""
    def pad(a):
        k = n - a.shape[0]
        return a if k == 0 else jnp.concatenate([a, jnp.repeat(a[:1], k, axis=0)])
    return jax.tree.map(pad, tree)


def _group_table(edges, rscale, n: int) -> dict:
    """The per-group operands of the training programs, over ``n >= G``
    groups: each group's id, input normalizers and residual scale.

    The programs take the ids as an operand, never as constants, so every
    block of groups runs one program, whichever device holds it.  Groups
    past G are padding: rscale 0 (inactive, so no gradient) and an id no
    pixel has, so they never reach the model."""
    lo, scale = _pad_groups(grouping.group_normalizers(edges), n)
    return {"id": jnp.arange(n, dtype=jnp.int32), "lo": lo, "scale": scale,
            "rscale": jnp.pad(rscale, (0, n - rscale.shape[0]))}


def _loss_one_group(params, state, xg, maskg, target):
    pred, new_state = enhancer.apply(params, state, xg, train=True, mask=maskg)
    se = (pred - target) ** 2 * maskg
    loss = se.sum() / jnp.maximum(maskg.sum(), 1.0)
    return loss, new_state


@partial(jax.jit, static_argnames=("residual_learning", "adam_cfg"))
def train_step(
    params,
    bn_state,
    opt_state,
    xb,
    rb,
    idsb,
    groups,
    lr,
    *,
    residual_learning: bool,
    adam_cfg: AdamWConfig,
):
    """One Adam step for a block of group models at once.  ``groups`` is the
    block's :func:`_group_table`.  Returns per-group losses."""
    xn, masks = _block_inputs(xb, idsb, groups)
    rscale = groups["rscale"]
    if residual_learning:
        safe = jnp.where(rscale > 0, rscale, 1.0)
        target = rb[None] / safe[:, None, None, None] * masks
    else:
        # Regular baseline: predict the normalized original directly.
        orig = xb[None] + rb[None]  # X = X' + R
        target = ((orig - groups["lo"][:, None, None, None])
                  / groups["scale"][:, None, None, None] * masks)

    active = (rscale > 0.0).astype(jnp.float32)

    def lossfn(p):
        losses, new_states = jax.vmap(_loss_one_group)(p, bn_state, xn, masks, target)
        return (losses * active).sum(), (losses * active, new_states)

    grads, (losses, new_bn) = jax.grad(lossfn, has_aux=True)(params)
    new_params, new_opt = adamw.update(params, opt_state, grads, lr, adam_cfg)
    return new_params, new_bn, new_opt, losses


@jax.jit
def _take(arrays, idx):
    """One device's batch: the slices ``idx`` of each of its arrays, in one
    dispatch."""
    return tuple(a[idx] for a in arrays)


def _tree_bytes(tree) -> int:
    return sum(int(a.nbytes) for a in jax.tree.leaves(tree))


# The group axis always trains as this many equal blocks (G padded to a
# multiple), each block by the same programs: one block per chip of a
# four-chip host, and all four in turn on one device.  A block's rounding
# then does not depend on how many devices share the blocks: batching a
# different number of groups into one program changes how the TPU compiler
# folds the group axis into the weight-gradient convolutions' windows.
GROUP_BLOCKS = 4


def _split_groups(tree, placement) -> list:
    """Block ``b`` of every leaf's group axis, placed on ``placement[b]``
    (leaves with no group axis, like Adam's step count, go whole)."""
    n = len(placement)
    return [jax.device_put(jax.tree.map(
                lambda a: a[b * (a.shape[0] // n):(b + 1) * (a.shape[0] // n)]
                if a.ndim else a, tree), d)
            for b, d in enumerate(placement)]


def _join_groups(blocks, n_groups: int):
    """The blocks' group axes concatenated on the host, cut to ``n_groups``."""
    return jax.tree.map(lambda *a: jnp.asarray(np.concatenate(a)[:n_groups]),
                        *blocks)


def train_enhancers(
    xprime: jax.Array,
    residual: jax.Array,
    cfg: GWLZTrainConfig = GWLZTrainConfig(),
    *,
    callback=None,
) -> tuple[GWLZModel, dict]:
    """Fit G enhancers to map decompressed slices -> residual slices.

    Returns (model, history) where history["loss"][epoch, group] traces the
    per-group training loss (Fig. 5 reproduction).

    The group axis trains as :data:`GROUP_BLOCKS` blocks (G padded with
    inactive groups to a multiple), dealt round-robin to the tile devices
    (:func:`repro.launch.sharding.tile_devices`), each of which gets a copy
    of the slices.  Every block runs the same programs on any device, so
    the model does not depend on the device count.
    """
    G = cfg.n_groups
    devices = sharding.tile_devices()
    placement = [devices[b % len(devices)] for b in range(GROUP_BLOCKS)]
    n_pad = -(-G // GROUP_BLOCKS) * GROUP_BLOCKS
    with obs.span("gwlz.train.groups"):
        xs = _as_slices(jnp.asarray(xprime, jnp.float32), cfg.slice_axis)
        rs = _as_slices(jnp.asarray(residual, jnp.float32), cfg.slice_axis)
        n_slices = xs.shape[0]

        edges = grouping.compute_edges(xs, G, cfg.strategy)
        ids = grouping.assign_groups(xs, edges)
        rscale = _per_group_scale(rs, ids, G)
        counts = jnp.zeros(G).at[ids.ravel()].add(1.0)
        rscale = jnp.where(counts >= cfg.min_group_pixels, rscale, 0.0)

        key = jax.random.PRNGKey(cfg.seed)
        pkeys = jax.random.split(key, G)
        params = jax.vmap(lambda k: enhancer.init_params(k, cfg.channels))(pkeys)
        bn_state = jax.vmap(lambda _: enhancer.init_state(cfg.channels))(
            jnp.arange(G))
        params, bn_state = _pad_groups((params, bn_state), n_pad)
        groups = _group_table(edges, rscale, n_pad)
        adam_cfg = AdamWConfig()
        opt_state = adamw.init(params, adam_cfg)
    # each block: [params, bn_state, opt_state, groups]; each device: a copy
    # of (xs, rs, ids)
    state = [params, bn_state, opt_state, groups]
    used = list(dict.fromkeys(placement))
    on_mesh = len(used) > 1
    with (obs.span("gwlz.train.shard", _tree_bytes(state)
                   + len(used) * _tree_bytes((xs, rs, ids))) if on_mesh else nullcontext()):
        blocks = _split_groups(state, placement)
        data = {d: jax.device_put((xs, rs, ids), d) for d in used}
    if on_mesh:  # the group state of the device holding the most blocks
        obs.count("gwlz.train.group_mesh",
                  placement.count(used[0]) * _tree_bytes(blocks[0][:3]))

    bs = min(cfg.batch_size, n_slices)
    steps_per_epoch = max(n_slices // bs, 1)
    sched = step_decay(cfg.lr, cfg.lr_decay_factor, cfg.lr_decay_every_epochs * steps_per_epoch)

    rng = np.random.default_rng(cfg.seed)
    history = {"loss": np.zeros((cfg.epochs, G), np.float64), "lr": np.zeros(cfg.epochs)}
    gstep = 0
    for epoch in range(cfg.epochs):
        order = rng.permutation(n_slices)
        ep_loss = np.zeros(G, np.float64)
        for s in range(steps_per_epoch):
            with obs.span("gwlz.train.step"):
                idx = order[s * bs : (s + 1) * bs]
                lr = sched(gstep)
                batch = {d: _take(arrays, idx) for d, arrays in data.items()}
                losses = []
                for blk, d in zip(blocks, placement):
                    p, bn, opt, grp = blk
                    p, bn, opt, loss = train_step(
                        p, bn, opt, *batch[d], grp, lr,
                        residual_learning=cfg.residual_learning, adam_cfg=adam_cfg,
                    )
                    blk[:3] = p, bn, opt
                    losses.append(loss)
                ep_loss += np.concatenate(jax.device_get(losses))[:G]
            gstep += 1
        history["loss"][epoch] = ep_loss / steps_per_epoch
        history["lr"][epoch] = float(sched(gstep - 1))
        if callback is not None:
            callback(epoch, history["loss"][epoch])
    params = [blk[0] for blk in blocks]
    # Replace running BN stats with exact full-volume statistics (the data we
    # will enhance is exactly the data we trained on — see _bn_calibrate).
    with obs.span("gwlz.train.calibrate"):
        bn_state = [_bn_calibrate(p, data[d][0], data[d][2], blk[3])
                    for p, blk, d in zip(params, blocks, placement)]
    gate = None
    if cfg.gate_groups and cfg.residual_learning:
        with obs.span("gwlz.train.gate"):
            gate = [_gate_groups(p, bn, *data[d], blk[3])
                    for p, bn, blk, d in zip(params, bn_state, blocks, placement)]
    with (obs.span("gwlz.train.gather", _tree_bytes((params, bn_state, gate)))
          if on_mesh else nullcontext()):
        params, bn_state, gate = (part and _join_groups(part, G)
                                  for part in (params, bn_state, gate))
    if gate is not None:
        rscale = rscale * gate
        history["gate"] = np.asarray(gate)
    model = GWLZModel(params=params, bn_state=bn_state, edges=edges, rscale=rscale, cfg=cfg)
    return model, history


def tiles_as_slices(tiles: jax.Array) -> jax.Array:
    """[Nt, T0, ...] tile batch -> one slice stack along every tile's axis 0.

    Folds the tile-batch axis into the slice axis, so a whole tile grid
    trains as a single slice batch."""
    return tiles.reshape((-1,) + tuple(tiles.shape[2:]))


def train_enhancers_tiled(
    recon_tiles: jax.Array,
    residual_tiles: jax.Array,
    cfg: GWLZTrainConfig = GWLZTrainConfig(),
    *,
    callback=None,
) -> tuple[GWLZModel, dict]:
    """Group-wise training routed through the tile grid.

    Every tile contributes its axis-0 slices to ONE batched
    :func:`train_enhancers` call — per-tile group masks are computed inside
    the shared step over the stacked slices, so the tile grid trains exactly
    like a (taller) volume.  Requires 3D tiles ([Nt, T0, T1, T2]); the
    enhancers are 2D CNNs over each tile's (T1, T2) slices."""
    if recon_tiles.ndim != 4 or residual_tiles.shape != recon_tiles.shape:
        raise ValueError(f"expected matching [Nt, T, T, T] tile stacks, got "
                         f"{recon_tiles.shape} / {residual_tiles.shape}")
    cfg = replace(cfg, slice_axis=0)  # tile slices are already stacked on axis 0
    return train_enhancers(
        tiles_as_slices(recon_tiles), tiles_as_slices(residual_tiles), cfg,
        callback=callback)


class TileReservoir:
    """Bounded uniform sample of (recon, residual) tile pairs from a stream.

    Algorithm R over the tile stream: the streaming compressor
    (repro.exec.executor) cannot hold every tile's reconstruction for
    enhancer training the way the eager path does, so it offers each
    batch's tiles here and trains on the reservoir — an unbiased sample of
    the volume whatever its size, in ``capacity * tile_bytes * 2`` memory.
    """

    def __init__(self, capacity: int, seed: int = 0):
        if capacity < 1:
            raise ValueError("reservoir capacity must be >= 1")
        self.capacity = int(capacity)
        self.n_seen = 0
        self._rng = np.random.default_rng(seed)
        self._recon: list[np.ndarray] = []
        self._resid: list[np.ndarray] = []

    def __len__(self) -> int:
        return len(self._recon)

    @property
    def nbytes(self) -> int:
        return sum(a.nbytes for a in self._recon) + sum(a.nbytes for a in self._resid)

    def offer(self, recon_tiles: np.ndarray, resid_tiles: np.ndarray) -> int:
        """Offer one tile batch ([B, *tile] pairs); returns bytes GROWN (for
        the executor's memory accounting — replacements are size-neutral)."""
        if recon_tiles.shape != resid_tiles.shape:
            raise ValueError(
                f"recon/residual shape mismatch: {recon_tiles.shape} vs "
                f"{resid_tiles.shape}")
        grown = 0
        for rec, res in zip(recon_tiles, resid_tiles):
            self.n_seen += 1
            if len(self._recon) < self.capacity:
                self._recon.append(np.array(rec, np.float32))
                self._resid.append(np.array(res, np.float32))
                grown += self._recon[-1].nbytes + self._resid[-1].nbytes
            else:
                j = int(self._rng.integers(0, self.n_seen))
                if j < self.capacity:
                    self._recon[j] = np.array(rec, np.float32)
                    self._resid[j] = np.array(res, np.float32)
        return grown

    def stacks(self) -> tuple[np.ndarray, np.ndarray]:
        if not self._recon:
            raise ValueError("empty reservoir: offer at least one tile batch")
        return np.stack(self._recon), np.stack(self._resid)


def train_enhancers_streamed(
    reservoir: TileReservoir,
    cfg: GWLZTrainConfig = GWLZTrainConfig(),
    *,
    callback=None,
) -> tuple[GWLZModel, dict]:
    """Group-wise training for the streaming path: fit on the reservoir's
    sampled tile pairs exactly like :func:`train_enhancers_tiled` fits on
    the full grid.  The model is volume-agnostic (it maps decoded values to
    residuals through the group edges), so a uniform sample trains the same
    estimator the full stack would — just with sampling noise bounded by
    the reservoir size."""
    with obs.span("gwlz.train"):
        with obs.span("gwlz.train.stage", reservoir.nbytes):
            recon, resid = reservoir.stacks()
            recon, resid = jnp.asarray(recon), jnp.asarray(resid)
        return train_enhancers_tiled(recon, resid, cfg, callback=callback)


# Pixels per step of the whole-training-set passes below (BN calibration and
# the gate).  Each step holds a [G, B, H, W, 9, C] neighbourhood tensor, so
# the pass runs over slice batches and accumulates per-group sums: program
# size then depends on the slice shape only, never on the reservoir size.
_PASS_PIXELS = 1 << 16


# Group id of the pad slices of a scan: no group's, so their masks are zero
_NO_GROUP = -1


def _slice_batches(*arrays_and_fills):
    """Split [N, H, W] slice stacks into [nb, B, H, W] scan batches, padding
    N up to a multiple of B with the given fill (ids pad with ``_NO_GROUP``,
    whose mask is all zero, so pad slices add nothing to any sum)."""
    n, h, w = arrays_and_fills[0][0].shape
    b = max(1, min(n, _PASS_PIXELS // (h * w)))
    pad = (-n) % b
    out = []
    for a, fill in arrays_and_fills:
        if pad:
            a = jnp.concatenate([a, jnp.full((pad,) + a.shape[1:], fill, a.dtype)])
        out.append(a.reshape((-1, b) + a.shape[1:]))
    return out


@jax.jit
def _gate_groups(params, bn_state, xs, rs, ids, groups):
    """Per-group acceptance test on the training volume: keep a group's
    enhancer only if it reduces that group's residual MSE.  ``groups`` as
    in :func:`train_step`."""

    def one(p, st, xg):
        pred, _ = enhancer.apply(p, st, xg, train=False)
        return pred

    rscale = groups["rscale"][:, None, None, None]

    def step(acc, batch):
        xb, rb, ib = batch
        xn, masks = _block_inputs(xb, ib, groups)
        preds = jax.vmap(one)(params, bn_state, xn) * rscale
        err_with = (((rb[None] - preds) * masks) ** 2).sum(axis=(1, 2, 3))
        err_without = ((rb[None] * masks) ** 2).sum(axis=(1, 2, 3))
        return (acc[0] + err_with, acc[1] + err_without), None

    zero = jnp.zeros(groups["rscale"].shape, jnp.float32)
    batches = _slice_batches((xs, 0.0), (rs, 0.0), (ids, _NO_GROUP))
    (err_with, err_without), _ = jax.lax.scan(step, (zero, zero), tuple(batches))
    return (err_with < err_without).astype(jnp.float32)


@jax.jit
def _bn_calibrate(params, xs, ids, groups):
    """Exact masked BN statistics of the *final* model over the full volume.

    Per-batch BN statistics drift from the running average enough to cost
    ~1 dB at inference; since compression trains on exactly the data it will
    enhance, we can use the exact statistics (one extra forward pass).  Two
    passes over slice batches: per-group masked sums give the mean, then the
    masked squared deviations from it give the variance.  ``groups`` as in
    :func:`train_step`."""
    batches = tuple(_slice_batches((xs, 0.0), (ids, _NO_GROUP)))

    def conv1(xb, ib):
        xn, masks = _block_inputs(xb, ib, groups)
        h = jax.vmap(lambda p, xg: enhancer._conv(xg[..., None], p["w1"], p["b1"]))(
            params, xn)  # [g, B, H, W, C]
        return h, masks[..., None]

    def sums(acc, batch):
        h, m = conv1(*batch)
        return (acc[0] + (h * m).sum(axis=(1, 2, 3)),
                acc[1] + m.sum(axis=(1, 2, 3))), None

    zero = jnp.zeros(params["b1"].shape, jnp.float32)  # [g, C]
    (total, cnt), _ = jax.lax.scan(
        sums, (zero, jnp.zeros(zero.shape[:1] + (1,), jnp.float32)), batches)
    cnt = jnp.maximum(cnt, 1.0)
    mean = total / cnt

    def sq_dev(acc, batch):
        h, m = conv1(*batch)
        return acc + ((h - mean[:, None, None, None]) ** 2 * m).sum(axis=(1, 2, 3)), None

    var, _ = jax.lax.scan(sq_dev, zero, batches)
    return {"mean": mean, "var": var / cnt}


@partial(jax.jit, static_argnames=("n_groups", "residual_learning", "use_clamp"))
def _enhance_slices(params, bn_state, xs, edges, rscale, clamp_eb, *, n_groups,
                    residual_learning, use_clamp):
    return ops.enhancer_fused_op(xs, params, bn_state, edges, rscale, clamp_eb,
                                 n_groups=n_groups,
                                 residual_learning=residual_learning,
                                 use_clamp=use_clamp)


def _enhance_one_tile(params, bn_state, t, edges, rscale, clamp_eb, *,
                      n_groups, residual_learning, slice_axis, batch, use_clamp):
    """One tile's enhancement as a pure traced program — the same op sequence
    :func:`enhance` runs (moveaxis, slice-batched ``_enhance_slices``,
    concat, moveaxis back), so the two paths agree bit-for-bit on every
    backend."""
    xs = jnp.moveaxis(t, slice_axis, 0)
    outs = [_enhance_slices(params, bn_state, xs[i : i + batch], edges, rscale,
                            clamp_eb, n_groups=n_groups,
                            residual_learning=residual_learning,
                            use_clamp=use_clamp)
            for i in range(0, xs.shape[0], batch)]
    return jnp.moveaxis(jnp.concatenate(outs, axis=0), 0, slice_axis)


@partial(jax.jit, static_argnames=("n_groups", "residual_learning", "slice_axis",
                                   "batch", "use_clamp"))
def _enhance_tiles_mapped(params, bn_state, tiles, edges, rscale, clamp_eb, *,
                          n_groups, residual_learning, slice_axis, batch, use_clamp):
    return jax.lax.map(
        lambda t: _enhance_one_tile(
            params, bn_state, t, edges, rscale, clamp_eb, n_groups=n_groups,
            residual_learning=residual_learning, slice_axis=slice_axis,
            batch=batch, use_clamp=use_clamp),
        tiles)


def _count_path(n: int, plane: tuple, batch: int, nbytes: int) -> None:
    """Count an enhancement of n slices of ``plane`` shape, run in slice
    batches of ``batch``, under the path it takes: ``gwlz.enhance.kernel``
    when every batch runs the kernel, else ``gwlz.enhance.jnp``; the bytes
    are the input's."""
    paths = {ops.enhancer_path((min(batch, n - i),) + plane)
             for i in range(0, n, batch)}
    obs.count("gwlz.enhance.kernel" if paths == {"kernel"} else "gwlz.enhance.jnp",
              nbytes)


def enhance_tiles(
    tiles: jax.Array,
    model: GWLZModel,
    *,
    clamp_eb: float | None = None,
    batch: int = 64,
) -> jax.Array:
    """Batched per-tile enhancement: ``[K, *tile] -> [K, *tile]``.

    One ``lax.map`` over the tile batch compiles a single fixed-tile-shape
    per-tile program and runs it K times inside one dispatch — the per-tile
    program is independent of K, so region decode (small K) and full decode
    (K = n_tiles) enhance every tile bit-identically, which is the contract
    ``repro.sz.tiled`` requires of any ``tile_transform``.  Replaces the
    per-tile Python loop (~n_tiles jit dispatches on the decode hot path;
    speedup measured by ``throughput/tiled/enhance_batched``)."""
    cfg = model.cfg
    tile = tuple(tiles.shape[1:])
    _count_path(tile[cfg.slice_axis],
                tile[:cfg.slice_axis] + tile[cfg.slice_axis + 1:], batch, tiles.nbytes)
    clamp = jnp.float32(0.0 if clamp_eb is None else clamp_eb)
    fn = _tile_enhancer(cfg.n_groups, cfg.residual_learning, cfg.slice_axis,
                        batch, clamp_eb is not None)
    return sharding.map_tiles(fn, tiles, model.params, model.bn_state,
                              model.edges, model.rscale, clamp)


@lru_cache(maxsize=64)
def _tile_enhancer(n_groups, residual_learning, slice_axis, batch, use_clamp):
    """:func:`_enhance_tiles_mapped` with its static settings bound, one
    function object per setting, for ``sharding.map_tiles``: on a mesh each
    device enhances its share of the tiles (the kernel cannot be
    partitioned automatically); on one device it is a plain call."""
    def fn(tiles, params, bn_state, edges, rscale, clamp):
        return _enhance_tiles_mapped(
            params, bn_state, tiles, edges, rscale, clamp, n_groups=n_groups,
            residual_learning=residual_learning, slice_axis=slice_axis,
            batch=batch, use_clamp=use_clamp)
    return fn


def enhance_tiles_looped(
    tiles: jax.Array,
    model: GWLZModel,
    *,
    clamp_eb: float | None = None,
) -> jax.Array:
    """Per-tile Python-loop reference (the pre-batching hot path), kept as
    the parity baseline for tests and the enhancer-speedup benchmark."""
    return jnp.stack([enhance(t, model, clamp_eb=clamp_eb) for t in tiles])


def enhance(
    xprime: jax.Array,
    model: GWLZModel,
    *,
    clamp_eb: float | None = None,
    batch: int = 64,
) -> jax.Array:
    """Reconstruction module: X_hat = X' + R_hat, merged across groups.

    ``clamp_eb``: beyond-paper bounded-enhancement mode (DESIGN.md §8.1) —
    clips the enhanced value into [X'-e, X'+e].  Since the true value also
    lies in that interval, the worst-case error vs the original is 2e
    (the unclamped paper-faithful mode has no worst-case bound at all).
    """
    cfg = model.cfg
    xs = _as_slices(jnp.asarray(xprime, jnp.float32), cfg.slice_axis)
    _count_path(xs.shape[0], xs.shape[1:], batch, xs.nbytes)
    clamp = jnp.float32(0.0 if clamp_eb is None else clamp_eb)
    outs = [_enhance_slices(
                model.params, model.bn_state, xs[i : i + batch], model.edges,
                model.rscale, clamp, n_groups=cfg.n_groups,
                residual_learning=cfg.residual_learning,
                use_clamp=clamp_eb is not None)
            for i in range(0, xs.shape[0], batch)]
    enhanced = jnp.concatenate(outs, axis=0)
    return jnp.moveaxis(enhanced, 0, cfg.slice_axis)
