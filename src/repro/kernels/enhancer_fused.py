"""Group-wise GWLZ enhancer inference as one Pallas kernel.

For a block of slices the kernel computes the whole G-fold forward that
``ref.enhancer_grouped_ref`` defines: each pixel's group id from the edges;
then, for every group g in turn, the normalized input masked to g, conv3x3
(1->C), inference BatchNorm folded into one scale and shift per channel,
ReLU, conv3x3 (C->1), and the prediction, scaled and masked to g, added to
an accumulator; last the residual (or direct) reconstruction and the
optional clamp to [x - eb, x + eb].

Layout: a slice's (H, W) plane sits on sublanes and lanes.  ``128 // W``
slices sit side by side on the 128 lanes (two for the 64x64 slices of a
64^3 tile) and their rows stack on the sublanes, so every vector operation
is full width.  Groups and channels are loops, and each group's parameters
are SMEM scalars: no array has a minor dimension of 1, 9 or 81.  A 3x3 tap
is the plane rolled by one row and/or one lane (``pltpu.roll``); the
``border`` plane says where a tap falls off its slice, which zeroes what
SAME padding zeroes, the lanes where two slices meet included.  Conv2 mixes
the channels first (s_t = sum_c w2[t, c] h_c, one plane per tap) and then
shifts 9 planes, not 81.  Only the input and output blocks touch HBM.

Arithmetic is float32 on the VPU throughout.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_BLOCK_ROWS = 256  # sublane rows per grid step: 4 pairs of 64x64 slices
# rows per step of the per-pixel loop: two vregs per plane, so the loop's
# 9 inputs, 9 tap sums and one channel (38 vregs) stay in registers, and each
# group's ~170 scalar weights are read once per 16 rows (2.2x faster than 8
# rows on a v5e, the same bits)
_STRIP = 16
# (dy, dx) of the 9 taps, row-major: tap t reads x[h + dy - 1, w + dx - 1]
_TAPS = tuple((dy, dx) for dy in range(3) for dx in range(3))
_CENTER = 4
# bits of the border plane: the pixel has a neighbour above, below, left, right
_UP, _DOWN, _LEFT, _RIGHT = 1, 2, 4, 8
# per-group row of the parameter table: lo, 1/scale, the output's scale and
# offset, b2, then w1 with BN's scale folded in (9 x C, tap-major), BN's
# shift (C) and w2 (9 x C, tap-major)
_LO, _INV, _OUT_A, _OUT_B, _B2, _HEAD = range(6)


def _row_width(channels: int) -> int:
    return _HEAD + 19 * channels


def fits(shape) -> bool:
    """Whether slices [B, H, W] pack onto full blocks: W divides the 128
    lanes, H is whole sublane tiles, B is whole packs of ``128 // W``."""
    if len(shape) != 3:
        return False
    b, h, w = shape
    return 0 < w <= _LANES and _LANES % w == 0 and h % 8 == 0 and b % (_LANES // w) == 0


def param_table(params, bn_state, edges, rscale, *, residual_learning: bool):
    """The G enhancers' parameters as one flat f32 table, ``_row_width(C)``
    entries per group (layout above).  ``rscale`` scales the prediction in
    the residual form; the direct form maps it back through the group's
    normalizer."""
    from repro.core import enhancer, grouping

    g, c = params["b1"].shape
    lo, scale = grouping.group_normalizers(edges)
    bn = jax.lax.rsqrt(bn_state["var"] + enhancer.BN_EPS) * params["gamma"]
    w1 = params["w1"].reshape(g, 9, c) * bn[:, None, :]
    shift = (params["b1"] - bn_state["mean"]) * bn + params["beta"]
    w2 = params["w2"].reshape(g, 9, c)
    a, b = (rscale, jnp.zeros_like(lo)) if residual_learning else (scale, lo)
    head = jnp.stack([lo, 1.0 / scale, a, b, params["b2"][:, 0]], axis=1)
    table = jnp.concatenate(
        [head, w1.reshape(g, 9 * c), shift, w2.reshape(g, 9 * c)], axis=1)
    return table.astype(jnp.float32).reshape(-1)


def _border(rows: int, h: int, w: int) -> np.ndarray:
    r = np.arange(rows)[:, None] % h
    lane = np.arange(_LANES)[None, :] % w
    return ((r > 0) * _UP + (r < h - 1) * _DOWN
            + (lane > 0) * _LEFT + (lane < w - 1) * _RIGHT).astype(np.int32)


def _tap(a, dy: int, dx: int):
    """Plane whose pixel p holds a at p + (dy - 1, dx - 1), circularly."""
    if dx != 1:
        a = pltpu.roll(a, (1 - dx) % a.shape[1], 1)
    if dy != 1:
        a = pltpu.roll(a, (1 - dy) % a.shape[0], 0)
    return a


def _tap_ok(border, dy: int, dx: int):
    need = ((_UP if dy == 0 else _DOWN if dy == 2 else 0)
            | (_LEFT if dx == 0 else _RIGHT if dx == 2 else 0))
    return (border & need) == need


def _kernel(edges_ref, table_ref, eb_ref, border_ref, x_ref, o_ref,
            sx_ref, sid_ref, s_ref, acc_ref, *, n_groups: int, channels: int,
            residual_learning: bool, use_clamp: bool):
    rows = x_ref.shape[0]
    step = math.gcd(rows, _STRIP)  # rows are whole 8-row tiles
    width = _row_width(channels)
    w1_at, sh_at = _HEAD, _HEAD + 9 * channels
    w2_at = sh_at + channels
    x = x_ref[...]
    border = border_ref[...]
    # group ids: searchsorted(edges, x, side="right") - 1, clipped to
    # [0, G), is the count of interior edges at or below x
    ids = jnp.zeros(x.shape, jnp.int32)
    for k in range(1, n_groups):
        ids = ids + jnp.where(x >= edges_ref[k], 1, 0)
    # the taps of x and of the ids, shared by every group; a tap off the
    # slice gets id -1, which no group owns, so its input is zero
    for t, (dy, dx) in enumerate(_TAPS):
        sx_ref[t] = _tap(x, dy, dx)
        sid_ref[t] = jnp.where(_tap_ok(border, dy, dx), _tap(ids, dy, dx), -1)
    acc_ref[...] = jnp.zeros(x.shape, jnp.float32)

    def group(g, carry):
        row = g * width
        lo, inv = table_ref[row + _LO], table_ref[row + _INV]

        def strip(i, c):
            r = pl.ds(pl.multiple_of(i * step, step), step)
            xn = [jnp.where(sid_ref[t, r, :] == g, (sx_ref[t, r, :] - lo) * inv, 0.0)
                  for t in range(9)]
            s = [None] * 9
            for ch in range(channels):
                h = xn[0] * table_ref[row + w1_at + ch]
                for t in range(1, 9):
                    h = h + xn[t] * table_ref[row + w1_at + t * channels + ch]
                h = jnp.maximum(h + table_ref[row + sh_at + ch], 0.0)
                for t in range(9):
                    v = h * table_ref[row + w2_at + t * channels + ch]
                    s[t] = v if s[t] is None else s[t] + v
            for t in range(9):
                s_ref[t, r, :] = s[t]
            return c

        jax.lax.fori_loop(0, rows // step, strip, 0)
        pred = s_ref[_CENTER] + table_ref[row + _B2]
        for t, (dy, dx) in enumerate(_TAPS):
            if t != _CENTER:
                pred = pred + jnp.where(_tap_ok(border, dy, dx),
                                        _tap(s_ref[t], dy, dx), 0.0)
        out = pred * table_ref[row + _OUT_A] + table_ref[row + _OUT_B]
        acc_ref[...] += jnp.where(sid_ref[_CENTER] == g, out, 0.0)
        return carry

    jax.lax.fori_loop(0, n_groups, group, 0)
    out = x + acc_ref[...] if residual_learning else acc_ref[...]
    if use_clamp:
        eb = eb_ref[0]
        out = jnp.clip(out, x - eb, x + eb)
    o_ref[...] = out


@partial(jax.jit, static_argnames=("n_groups", "channels", "residual_learning",
                                   "use_clamp", "interpret"))
def enhancer_grouped(x, table, edges, clamp_eb, *, n_groups: int, channels: int,
                     residual_learning: bool, use_clamp: bool,
                     interpret: bool = True) -> jax.Array:
    """x: [B, H, W] slices (``fits``); table: ``param_table``; edges: [G+1];
    clamp_eb: f32 scalar, used when ``use_clamp``.  Returns the enhanced
    slices [B, H, W]."""
    b, h, w = x.shape
    k = _LANES // w
    packs = b // k
    # k slices side by side on the lanes: [B, H, W] -> [packs * H, 128]
    xp = x.reshape(packs, k, h, w).transpose(0, 2, 1, 3).reshape(packs * h, _LANES)
    per_block = max(d for d in range(1, packs + 1)
                    if packs % d == 0 and (d * h <= _BLOCK_ROWS or d == 1))
    rows = per_block * h
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    block = pl.BlockSpec((rows, _LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        partial(_kernel, n_groups=n_groups, channels=channels,
                residual_learning=residual_learning, use_clamp=use_clamp),
        grid=(packs // per_block,),
        in_specs=[smem, smem, smem, pl.BlockSpec((rows, _LANES), lambda i: (0, 0)),
                  block],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(xp.shape, jnp.float32),
        scratch_shapes=[pltpu.VMEM((9, rows, _LANES), jnp.float32),
                        pltpu.VMEM((9, rows, _LANES), jnp.int32),
                        pltpu.VMEM((9, rows, _LANES), jnp.float32),
                        pltpu.VMEM((rows, _LANES), jnp.float32)],
        interpret=interpret,
    )(edges.astype(jnp.float32), table,
      jnp.reshape(clamp_eb, (1,)).astype(jnp.float32),
      jnp.asarray(_border(rows, h, w)), xp.astype(jnp.float32))
    return out.reshape(packs, h, k, w).transpose(0, 2, 1, 3).reshape(b, h, w)
