"""Plain reference of what a GWLZ container must decode to.

Written from the paper and the container's documented semantics, in
straightforward ``jax.numpy``, importing nothing of the program:

* the error bound ``eb``, absolute, as the configuration states it;
* the Lorenzo path over prequantized values (cuSZ style): the decoder's
  base reconstruction is ``2 eb * rint(x / 2 eb)`` at every voxel, whatever
  the prediction and entropy stages did in between;
* the GWLZ enhancer (paper Fig. 3, §3.2-3.3): per 2D slice of a tile, each
  voxel's group is its value bin among ``G+1`` edges; every group's CNN
  (3x3 conv 1->C, BatchNorm with stored statistics, ReLU, 3x3 conv C->1,
  zero padding) sees the min-max normalised slice with other groups'
  voxels zeroed, and its output times the group's residual scale is added
  to the voxels of that group.

The enhancer's weights are the one thing taken from the container: they
are what the ingest trained and the decode must apply.  ``parse_model``
reads them from the container's model record by the published layout.

Everything runs in float32, convs at ``highest`` precision.  A lower
precision is emulated by rounding values with bit arithmetic, which no
compiler pass can drop as excess precision, so it reads alike on the CPU
and the chip.  ``dtype="bfloat16"`` rounds every value computed: the
control of the base reconstruction, whose configuration states float32.
``operands=...`` rounds only each conv's operands, as one pass of a
lower-precision matmul unit does: ``"bfloat16"`` is what the TPU's default
matmul precision does, at which the configuration runs the enhancer's convs
(the witness for where the program departs from the float32 reference on
the chip); ``"float8_e4m3"``, saturating, is the enhancer's control, one
precision below that.
"""
from __future__ import annotations

import struct
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_MODEL_MAGIC = b"GWLZ"
_BN_EPS = 1e-5


def _bf16(a: jax.Array) -> jax.Array:
    """float32 values rounded to the nearest bfloat16, ties to even, kept as
    float32."""
    b = jax.lax.bitcast_convert_type(a, jnp.uint32)
    b = (b + jnp.uint32(0x7FFF) + ((b >> 16) & 1)) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(b, jnp.float32)


def _e4m3(a: jax.Array) -> jax.Array:
    """float32 values rounded to the nearest float8 e4m3 (3 mantissa bits,
    ties to even, subnormal below 2**-6), saturating at +-448, kept as
    float32."""
    a = jnp.clip(a, -448.0, 448.0)
    b = jax.lax.bitcast_convert_type(a, jnp.uint32)
    b = (b + jnp.uint32(0x7FFFF) + ((b >> 20) & 1)) & jnp.uint32(0xFFF00000)
    normal = jax.lax.bitcast_convert_type(b, jnp.float32)
    return jnp.where(jnp.abs(a) < 2.0 ** -6, jnp.round(a * 512.0) / 512.0, normal)


_ROUND = {"float32": lambda a: a, "bfloat16": _bf16, "float8_e4m3": _e4m3}


@partial(jax.jit, static_argnames=("dtype",))
def base_recon(x: jax.Array, eb: float, *, dtype: str = "float32") -> jax.Array:
    """``2 eb * rint(x / 2 eb)``, each step rounded to ``dtype``."""
    r = _ROUND[dtype]
    two_eb = r(jnp.asarray(2.0 * eb, jnp.float32))
    return r(jnp.rint(r(r(x) / two_eb)) * two_eb)


def parse_model(blob: bytes) -> dict:
    """The enhancer record: header (magic, G, C, strategy, residual flag),
    then float32 arrays, each preceded by its u32 length, in the order
    b1, b2, beta, gamma, w1, w2, bn mean, bn var, edges, rscale."""
    if blob[:4] != _MODEL_MAGIC:
        raise ValueError("not a GWLZ enhancer record")
    g, c, _strategy, resid = struct.unpack_from("<IIIB", blob, 4)
    if not resid:
        raise ValueError("the reference covers residual learning only")
    off = 4 + struct.calcsize("<IIIB3x")
    shapes = [("b1", (g, c)), ("b2", (g, 1)), ("beta", (g, c)),
              ("gamma", (g, c)), ("w1", (g, 3, 3, 1, c)), ("w2", (g, 3, 3, c, 1)),
              ("mean", (g, c)), ("var", (g, c)), ("edges", (g + 1,)),
              ("rscale", (g,))]
    out = {}
    for name, shape in shapes:
        (n,) = struct.unpack_from("<I", blob, off)
        off += 4
        if n != int(np.prod(shape)):
            raise ValueError(f"model record: {name} has {n} values, want {shape}")
        out[name] = np.frombuffer(blob, np.float32, n, offset=off).reshape(shape)
        off += 4 * n
    return out


def _conv3x3(x, w):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)


@partial(jax.jit, static_argnames=("dtype", "operands"))
def enhance_tiles(tiles: jax.Array, model: dict, *, dtype: str = "float32",
                  operands: str = "float32") -> jax.Array:
    """[K, T0, T1, T2] base tiles -> enhanced tiles, slices along axis 0.
    Groups are assigned and normalised in float32 from the base values; the
    CNN's values are rounded to ``dtype``, its convs' operands also to
    ``operands``."""
    r = _ROUND[dtype]

    def conv(a, w):
        ro = _ROUND[operands]
        return r(_conv3x3(ro(r(a)), ro(r(w))))

    m = {k: jnp.asarray(v, jnp.float32) for k, v in model.items()}
    k, t0, t1, t2 = tiles.shape
    xs = tiles.reshape(k * t0, t1, t2).astype(jnp.float32)
    edges = m["edges"]
    n_groups = edges.shape[0] - 1
    ids = jnp.clip(jnp.searchsorted(edges, xs, side="right") - 1, 0, n_groups - 1)
    lo = edges[:-1]
    scale = jnp.maximum(edges[1:] - edges[:-1], 1e-12)
    rhat = jnp.zeros_like(xs)
    for g in range(n_groups):
        mask = ids == g
        xn = r(jnp.where(mask, (xs - lo[g]) / scale[g], 0))
        h = r(conv(xn[..., None], m["w1"][g]) + r(m["b1"][g]))
        h = r(r(h - r(m["mean"][g])) * r(jax.lax.rsqrt(r(m["var"][g] + _BN_EPS))))
        h = r(r(h * r(m["gamma"][g])) + r(m["beta"][g]))
        h = jnp.maximum(h, 0)
        p = r(conv(h, m["w2"][g])[..., 0] + r(m["b2"][g][0]))
        rhat = r(rhat + jnp.where(mask, r(p * r(m["rscale"][g])), 0))
    return (xs + rhat).reshape(tiles.shape)


def to_tiles(vol: jax.Array, tile: int) -> jax.Array:
    """[S, S, S] -> [n, T, T, T] in row-major tile order."""
    s = vol.shape[0]
    n = s // tile
    return (vol.reshape(n, tile, n, tile, n, tile)
            .transpose(0, 2, 4, 1, 3, 5).reshape(-1, tile, tile, tile))


def from_tiles(tiles: jax.Array, side: int) -> jax.Array:
    t = tiles.shape[1]
    n = side // t
    return (tiles.reshape(n, n, n, t, t, t).transpose(0, 3, 1, 4, 2, 5)
            .reshape(side, side, side))


def enhance(base: jax.Array, tile: int, model: dict, *, dtype: str = "float32",
            operands: str = "float32", block: int = 16) -> jax.Array:
    """The base reconstruction enhanced tile by tile, ``block`` tiles at a
    time.  ``base`` is a cube whose side the tile divides."""
    tiles = to_tiles(base, tile)
    out = [enhance_tiles(tiles[i:i + block], model, dtype=dtype, operands=operands)
           for i in range(0, tiles.shape[0], block)]
    return from_tiles(jnp.concatenate(out), base.shape[0])


def reconstruct(x: jax.Array, eb: float, tile: int, model: dict | None = None,
                *, dtype: str = "float32") -> jax.Array:
    """What the decoder should return for field ``x``: the base
    reconstruction, enhanced when a model is given, computed in ``dtype``."""
    base = base_recon(x, eb, dtype=dtype)
    if model is None:
        return base
    return enhance(base, tile, model, dtype=dtype)


def psnr(x, y) -> float:
    """Peak signal-to-noise ratio in dB over the value range of ``x``."""
    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    rng = jnp.max(x) - jnp.min(x)
    mse = jnp.mean((x - y) ** 2)
    return float(20 * jnp.log10(rng) - 10 * jnp.log10(mse))


@jax.jit
def _gap(out, ref, x, eb):
    d = jnp.abs(out - ref)
    err = jnp.abs(out - x)
    ax = jnp.abs(x)
    ulp = jnp.nextafter(ax, jnp.float32(jnp.inf)) - ax
    return (jnp.mean((d > 0.5 * eb).astype(jnp.float32)), jnp.max(d) / eb,
            jnp.max(err) / eb, jnp.max((err - eb) / ulp))


@partial(jax.jit, static_argnames=("tile",))
def _tile_norms(a, tile):
    return jnp.sqrt(jnp.sum(to_tiles(a, tile) ** 2, axis=(1, 2, 3)))


def enhancer_error(out, ref, base, tile: int) -> float:
    """How far the enhancer's output departs from the reference's, tile by
    tile: the norm of ``out - ref`` over the norm of the reference's residual
    ``ref - base`` in that tile, or over the median tile's where that is
    larger (a tile the enhancer barely moves would read noise); the worst
    tile.  Where the reference moves no voxel, any departure reads huge."""
    d = _tile_norms(jnp.asarray(out) - ref, tile)
    r = _tile_norms(ref - base, tile)
    den = jnp.maximum(jnp.maximum(r, jnp.median(r)), jnp.finfo(jnp.float32).tiny)
    return float(jnp.max(d / den))


def compare(out, ref, x, eb: float) -> dict:
    """Share of voxels more than half a bound away from the reference; the
    widest gap to the reference and to the original, in units of eb; and
    the most the error passes the bound by, in float32 ulps of the value
    (negative while every voxel is inside the bound)."""
    share, gap, err, over = _gap(jnp.asarray(out), jnp.asarray(ref),
                                 jnp.asarray(x), jnp.float32(eb))
    return {"mismatch_share": float(share), "gap_eb": float(gap),
            "err_eb": float(err), "over_bound_ulp": float(over)}
