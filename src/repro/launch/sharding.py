"""Parameter / activation / cache sharding rules (DESIGN.md §5).

Rules are keyed by the parameter's leaf name (the last dict key on its tree
path) and give the PartitionSpec of the *base* (unstacked) tensor; leading
layer-stacking axes are padded with None automatically.  ``fsdp`` is a
placeholder resolved to the data axis when ZeRO-3-style parameter sharding is
on (the 405B/671B training cells), else to None.
"""
from __future__ import annotations

import contextlib
import functools
from dataclasses import dataclass

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

FSDP = "__fsdp__"
MODEL = "model"

# leaf name -> base spec (tail-aligned to the leaf's trailing dims)
PARAM_RULES: dict[str, tuple] = {
    # embeddings / heads
    "embed": (MODEL, FSDP),         # [V, d] vocab-sharded
    "lm_head": (FSDP, MODEL),       # [d, V]
    "pos_dec": (None, FSDP),
    # attention (GQA)
    "wq": (FSDP, MODEL, None),      # [d, H, hd]
    "wk": (FSDP, MODEL, None),
    "wv": (FSDP, MODEL, None),
    "wo": (MODEL, None, FSDP),      # [H, hd, d]
    "q_norm": (None,),
    "k_norm": (None,),
    # MLA
    "w_dq": (FSDP, None),
    "w_uq": (None, MODEL, None),
    "w_dkv": (FSDP, None),
    "w_kr": (FSDP, None),
    "w_uk": (None, MODEL, None),
    "w_uv": (None, MODEL, None),
    "q_ln": (None,),
    "kv_ln": (None,),
    # dense mlp
    "w_up": (FSDP, MODEL),          # [d, F]; moe [E, d, F] handled by pad rule
    "w_gate": (FSDP, MODEL),
    "w_down": (MODEL, FSDP),        # [F, d]
    # moe
    "router": (None, None),
    # rwkv6
    "wr": (FSDP, MODEL),
    "wg": (FSDP, MODEL),
    "mix_w1": (FSDP, None),
    "mix_w2": (None, None, FSDP),
    "decay_w1": (FSDP, None),
    "decay_w2": (None, FSDP),
    "u": (MODEL, None),
    "cm_wr": (FSDP, MODEL),
    "cm_wk": (FSDP, MODEL),
    "cm_wv": (MODEL, FSDP),
    # mamba2
    "w_in": (FSDP, MODEL),
    "conv_w": (None, MODEL),
    "conv_b": (MODEL,),
    "A_log": (MODEL,),
    "D": (MODEL,),
    "dt_bias": (MODEL,),
    "norm": (MODEL,),
    "w_out": (MODEL, FSDP),
}

# MoE expert-stacked tensors (distinct "we_*" names): expert axis gets the
# model axis and the rest stays unsharded (expert-parallel dispatch).
PARAM_RULES.update({
    "we_up": (MODEL, FSDP, None),    # [E, d, F]
    "we_gate": (MODEL, FSDP, None),
    "we_down": (MODEL, None, FSDP),  # [E, F, d]
})


@dataclass(frozen=True)
class ShardingOptions:
    fsdp: bool = False              # ZeRO-3 parameter sharding over "data"
    seq_axis: str | None = None     # "model"/"data" for sequence-parallel caches
    fsdp_axis: str = "data"


def _resolve(spec: tuple, shape: tuple, opts: ShardingOptions, axis_sizes: dict) -> P:
    tail = list(
        (opts.fsdp_axis if (s == FSDP and opts.fsdp) else (None if s == FSDP else s))
        for s in spec
    )
    # drop axes missing from the mesh or not dividing the dimension
    off = len(shape) - len(tail)
    for i, s in enumerate(tail):
        if s is None:
            continue
        size = axis_sizes.get(s)
        if size is None or shape[off + i] % size != 0:
            tail[i] = None
    pad = (None,) * off
    return P(*(pad + tuple(tail)))


def param_pspecs(params, opts: ShardingOptions, mesh) -> object:
    """PartitionSpec pytree matching ``params`` (works on ShapeDtypeStructs)."""
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def spec_for(path, leaf) -> P:
        names = [k.key for k in path if isinstance(k, jax.tree_util.DictKey)]
        name = names[-1] if names else ""
        base = PARAM_RULES.get(name, ())
        if len(base) > leaf.ndim:
            base = base[-leaf.ndim:]
        return _resolve(base, leaf.shape, opts, axis_sizes)

    return jax.tree_util.tree_map_with_path(spec_for, params)


def named(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda s: isinstance(s, P),
    )


def opt_pspecs(opt_state, pspecs, opts: ShardingOptions, mesh):
    """Optimizer moments inherit the parameter specs (int8 packs add a scalar
    scale, which stays replicated)."""

    def match(ps, leaf_state):
        if isinstance(leaf_state, dict) and set(leaf_state) == {"q", "s"}:
            return {"q": ps, "s": P()}
        return ps

    m = jax.tree.map(match, pspecs, opt_state["m"], is_leaf=lambda x: isinstance(x, P))
    v = jax.tree.map(match, pspecs, opt_state["v"], is_leaf=lambda x: isinstance(x, P))
    return {"step": P(), "m": m, "v": v}


def batch_pspec(mesh, *, seq_axis=None) -> P:
    from repro.launch.mesh import batch_axes_of

    return P(batch_axes_of(mesh), seq_axis)


# Device set the tile fan-out uses when a caller names none: every local
# device, unless pinned by :func:`pin_tile_devices`.  Process-wide on
# purpose, like JAX's own device list: a region server decodes on its
# request threads, and each of them must see the same pin.
_PINNED_DEVICES: tuple | None = None


@contextlib.contextmanager
def pin_tile_devices(devices):
    """Within the block, :func:`tile_mesh`, :func:`device_round` and
    :func:`map_tiles` default to ``devices`` instead of ``jax.devices()``,
    and enhancer training splits its groups over ``devices``.

    This is how one process runs the same path on one device and on a
    multi-device mesh: streamed ingest, training, decode and serving all take
    their devices from :func:`tile_devices`.  Pins do not nest."""
    global _PINNED_DEVICES
    if _PINNED_DEVICES is not None:
        raise RuntimeError("tile devices are already pinned")
    _PINNED_DEVICES = tuple(devices)
    try:
        yield _PINNED_DEVICES
    finally:
        _PINNED_DEVICES = None


def tile_devices() -> tuple:
    """The device set tile fan-out uses by default (see :func:`pin_tile_devices`)."""
    return _PINNED_DEVICES if _PINNED_DEVICES is not None else tuple(jax.devices())


def tile_mesh(devices=None):
    """1D mesh over the tile devices for tile-grid fan-out (axis ``tiles``).

    The tiled compression engine (repro.sz.tiled) treats the tile batch as a
    pure data axis: every tile is an independent prediction+quantization
    domain, so compress/decompress shard with no collectives at all."""
    import numpy as np

    devs = np.asarray(tile_devices() if devices is None else devices)
    return jax.sharding.Mesh(devs, ("tiles",))


def device_round(n: int, devices: int | None = None) -> int:
    """Round a tile-batch width DOWN to a device-count multiple (≥ 1).

    The streaming planner (repro.exec.plan) sizes device batches with this
    so ``map_tiles`` fan-out pads nothing in steady state; widths smaller
    than the device count stay as-is (the pad-with-repeats path handles
    them, and shrinking to 0 would be worse)."""
    d = len(tile_devices()) if devices is None else int(devices)
    if d <= 1 or n <= d:
        return max(1, int(n))
    return (int(n) // d) * d


def map_tiles(fn, tiles, *extra, mesh=None):
    """Fan a tile-batched op across the device mesh via ``jax.shard_map``.

    ``tiles`` may be one array or a pytree of arrays sharing the tile batch
    on axis 0 (e.g. the interp predictor's ``(codes, omask, ovals)``), and
    ``fn(tiles, *extra)`` may likewise return any pytree of batch-carrying
    arrays — both sides use ``P("tiles")`` as a pytree-prefix spec.  ``fn``
    must map axis 0 elementwise (tile-independent) and preserve the batch
    axis; ``extra`` operands are replicated.  The batch is padded to a device
    multiple with repeats of tile 0 (cheap, discarded).  On a single device
    this is a plain call — no dispatch overhead.  ``fn`` is compiled once
    per mesh for each function object, so callers pass the same object for
    the same program (a cached closure, never a fresh lambda per call)."""
    mesh = tile_mesh() if mesh is None else mesh
    n = int(mesh.devices.size)
    if n <= 1:
        return fn(tiles, *extra)
    import jax.numpy as jnp

    B = jax.tree.leaves(tiles)[0].shape[0]
    pad = (-B) % n
    if pad:
        tiles = jax.tree.map(
            lambda t: jnp.concatenate([t, jnp.repeat(t[:1], pad, axis=0)]), tiles)
    out = _shard_mapped(fn, mesh, len(extra))(tiles, *extra)
    return jax.tree.map(lambda o: o[:B], out) if pad else out


@functools.lru_cache(maxsize=64)
def _shard_mapped(fn, mesh, n_extra: int):
    """``fn`` as one jitted ``shard_map`` program per (fn, mesh, operand
    count).  A ``shard_map`` called outside ``jit`` runs op by op and traces
    and compiles its ops anew on every call, so every tile batch compiled."""
    in_specs = (P("tiles"),) + (P(),) * n_extra
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=P("tiles"), check_vma=False))


def cache_pspecs(cache, mesh, opts: ShardingOptions) -> object:
    """KV/SSM cache sharding: batch over data axes; the sequence axis of
    "global" caches over ``opts.seq_axis`` (flash-decode style); kv tensors'
    head axes unsharded (kv heads are often < mesh model size)."""
    from repro.launch.mesh import batch_axes_of

    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    baxes = batch_axes_of(mesh)

    def fit(shape, tail):
        """Drop axes that don't divide; pad leading dims with None."""
        off = len(shape) - len(tail)
        out = []
        for i, s in enumerate(tail):
            if s is None:
                out.append(None)
                continue
            axes = s if isinstance(s, tuple) else (s,)
            size = 1
            for a in axes:
                size *= axis_sizes.get(a, 1)
            out.append(s if shape[off + i] % size == 0 else None)
        return P(*(((None,) * off) + tuple(out)))

    def spec_for(path, leaf):
        names = [k.key for k in path if isinstance(k, jax.tree_util.DictKey)]
        name = names[-1] if names else ""
        nd = leaf.ndim
        if name == "pos" or nd == 0:
            return P()
        if name in ("k", "v", "c_kv", "k_rope"):
            tail_rank = 4 if name in ("k", "v") else 3
            return fit(leaf.shape, (baxes, opts.seq_axis) + (None,) * (tail_rank - 2))
        if name in ("wkv", "ssm"):  # [stack..., B, H, p, n]
            return fit(leaf.shape, (baxes, "model", None, None))
        if name in ("shift", "cm"):  # [stack..., B, d]
            return fit(leaf.shape, (baxes, None))
        if name == "conv":  # [stack..., B, W-1, C]
            return fit(leaf.shape, (baxes, None, "model"))
        return P(*((None,) * nd))

    return jax.tree_util.tree_map_with_path(spec_for, cache)
