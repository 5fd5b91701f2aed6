"""Prediction transforms for the SZ substrate: Lorenzo and multi-level interpolation.

TPU adaptation (DESIGN.md §3):

* The Lorenzo path uses cuSZ-style *prequantization*: values are first snapped
  onto the 2*eb grid (the only lossy step), then an exact integer Lorenzo
  stencil decorrelates them.  Reconstruction is ``cumsum`` along each axis —
  no sequential sweep anywhere, unlike CPU SZ.
* The interpolation path follows SZ3's level-by-level spline predictor, but
  schedules each level as a fully vectorized slice/arith op; the only
  sequential dependence is across the ~log2(N) levels, which is negligible.

Both paths guarantee |x - x'| <= eb pointwise (interp handles float-rounding
stragglers through the outlier mechanism in :mod:`repro.sz.quantizer`).
"""
from __future__ import annotations

import math
import struct
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.sz.quantizer import (
    dequantize_pre,
    prequantize,
    quantize_residual,
)

# ---------------------------------------------------------------------------
# Lorenzo (prequantized, integer-exact)
# ---------------------------------------------------------------------------


def _diff_along(q: jax.Array, axis: int) -> jax.Array:
    """First difference with implicit zero at the leading boundary."""
    shifted = jnp.roll(q, 1, axis=axis)
    idx = [slice(None)] * q.ndim
    idx[axis] = slice(0, 1)
    shifted = shifted.at[tuple(idx)].set(0)
    return q - shifted


def lorenzo_encode(x: jax.Array, eb) -> jax.Array:
    """x -> int32 Lorenzo deltas of the prequantized grid (lossy only in prequant)."""
    q = prequantize(x, eb)
    for ax in range(x.ndim):
        q = _diff_along(q, ax)
    return q


def lorenzo_decode(codes: jax.Array, eb, dtype=jnp.float32) -> jax.Array:
    """Exact inverse: integer cumsum along each axis, then dequantize."""
    q = codes
    for ax in range(codes.ndim):
        q = jnp.cumsum(q, axis=ax, dtype=jnp.int32)
    return dequantize_pre(q, eb, dtype)


# ---------------------------------------------------------------------------
# Multi-level interpolation (SZ3-style)
# ---------------------------------------------------------------------------


def _num_levels(shape: tuple[int, ...], max_levels: int = 5) -> int:
    m = min(shape)
    if m < 3:
        return 1
    return max(1, min(max_levels, int(math.floor(math.log2(m - 1)))))


def _padded_shape(shape: tuple[int, ...], levels: int) -> tuple[int, ...]:
    """Pad each dim to M * 2**levels + 1 so every interp neighbor exists."""
    s = 1 << levels
    return tuple(((max(d - 1, 1) + s - 1) // s) * s + 1 for d in shape)


def _pad_edge(x: jax.Array, pshape: tuple[int, ...]) -> jax.Array:
    pads = [(0, p - d) for d, p in zip(x.shape, pshape)]
    return jnp.pad(x, pads, mode="edge")


def _axis_slices(ndim: int, axis: int, step_axis: int, known_strides: list[int]):
    """Slicers for one interpolation sweep along ``axis`` at stride ``s``.

    ``known_strides[d]`` is the stride at which dimension ``d`` is already
    reconstructed.  Targets sit at odd multiples of ``s`` along ``axis``.
    """
    s = step_axis
    tgt = [slice(0, None, st) for st in known_strides]
    tgt[axis] = slice(s, None, 2 * s)
    return tuple(tgt)


def _even_grid(r: jax.Array, axis: int, s: int, known_strides: list[int]) -> jax.Array:
    sl = [slice(0, None, st) for st in known_strides]
    sl[axis] = slice(0, None, 2 * s)
    return r[tuple(sl)]


def _interp_pred(e: jax.Array, axis: int, order: str) -> jax.Array:
    """Predict odd-multiple targets from the even grid ``e`` along ``axis``.

    ``e`` has M+1 entries along ``axis``; output has M (one per target).
    """

    def ax_slice(a, start, stop):
        idx = [slice(None)] * a.ndim
        idx[axis] = slice(start, stop)
        return a[tuple(idx)]

    lin = 0.5 * (ax_slice(e, 0, -1) + ax_slice(e, 1, None))
    if order == "linear" or e.shape[axis] < 4:
        return lin
    # 4-point cubic (Lagrange) in the interior, linear at the two borders.
    cub = (
        -ax_slice(e, 0, -3) + 9.0 * ax_slice(e, 1, -2) + 9.0 * ax_slice(e, 2, -1) - ax_slice(e, 3, None)
    ) / 16.0
    first = ax_slice(lin, 0, 1)
    last = ax_slice(lin, -1, None)
    return jnp.concatenate([first, cub, last], axis=axis)


def _level_strides(levels: int) -> list[int]:
    return [1 << (lv - 1) for lv in range(levels, 0, -1)]  # S/2 ... 1 where S=2**levels


@partial(jax.jit, static_argnames=("levels", "order"))
def _interp_encode_padded(xp: jax.Array, eb, levels: int, order: str):
    """Encode an edge-padded volume. Returns (codes, omask, ovals, recon)."""
    ndim = xp.ndim
    S = 1 << levels
    eb = jnp.asarray(eb, xp.dtype)

    codes = jnp.zeros(xp.shape, jnp.int32)
    omask = jnp.zeros(xp.shape, bool)
    ovals = jnp.zeros(xp.shape, xp.dtype)
    recon = jnp.zeros(xp.shape, xp.dtype)

    # Coarse grid: prequantize + integer Lorenzo (exact, parallel).
    coarse_sl = tuple(slice(0, None, S) for _ in range(ndim))
    xc = xp[coarse_sl]
    cc = lorenzo_encode(xc, eb)
    rc = lorenzo_decode(cc, eb, xp.dtype)
    codes = codes.at[coarse_sl].set(cc)
    recon = recon.at[coarse_sl].set(rc)

    for s in _level_strides(levels):
        known = [2 * s] * ndim
        for axis in range(ndim):
            tgt = _axis_slices(ndim, axis, s, known)
            e = _even_grid(recon, axis, s, known)
            pred = _interp_pred(e, axis, order)
            sub = xp[tgt]
            code, rec, outl = quantize_residual(sub, pred, eb)
            codes = codes.at[tgt].set(code)
            omask = omask.at[tgt].set(outl)
            ovals = ovals.at[tgt].set(jnp.where(outl, sub, 0.0))
            recon = recon.at[tgt].set(rec)
            known[axis] = s  # this axis is now dense at stride s
    return codes, omask, ovals, recon


@partial(jax.jit, static_argnames=("levels", "order"))
def _interp_decode_padded(codes: jax.Array, omask: jax.Array, ovals: jax.Array, eb, levels: int, order: str):
    ndim = codes.ndim
    S = 1 << levels
    eb = jnp.asarray(eb, ovals.dtype)

    recon = jnp.zeros(codes.shape, ovals.dtype)
    coarse_sl = tuple(slice(0, None, S) for _ in range(ndim))
    recon = recon.at[coarse_sl].set(lorenzo_decode(codes[coarse_sl], eb, ovals.dtype))

    for s in _level_strides(levels):
        known = [2 * s] * ndim
        for axis in range(ndim):
            tgt = _axis_slices(ndim, axis, s, known)
            e = _even_grid(recon, axis, s, known)
            pred = _interp_pred(e, axis, order)
            rec = pred + codes[tgt].astype(ovals.dtype) * (2.0 * eb)
            rec = jnp.where(omask[tgt], ovals[tgt], rec)
            recon = recon.at[tgt].set(rec)
            known[axis] = s
    return recon


def _promote_stragglers(xp, codes, omask, ovals, eb, coarse, decode_fn):
    """Bound enforcement shared by the monolithic and tiled interp encoders.

    Re-derives the recon the *decoder* will produce and promotes any point
    past the bound (outside the coarse grid, which is Lorenzo-coded and
    exact) to an exact-valued outlier, until clean.  The loop terminates:
    each iteration strictly grows ``omask`` (promoted points decode exactly
    thereafter), which is bounded by the volume size; in practice it runs
    1-2 rounds.  On exit ``recon == decode_fn(codes, omask, ovals)`` and the
    bound holds on every promotable point.
    """
    recon = decode_fn(codes, omask, ovals)
    while True:
        bad = (jnp.abs(recon - xp) > eb) & ~omask & ~coarse
        if not bool(bad.any()):
            break
        omask = omask | bad
        ovals = jnp.where(bad, xp, ovals)
        recon = decode_fn(codes, omask, ovals)
    return omask, ovals, recon


def interp_encode(x: jax.Array, eb, order: str = "cubic", max_levels: int = 5):
    """Multi-level interpolation encode.

    Returns ``(codes, omask, ovals, recon, meta)`` where arrays live on the
    padded grid and ``meta = (orig_shape, padded_shape, levels)``.  ``recon``
    cropped to ``orig_shape`` satisfies the error bound.

    ``recon`` is the *decode program's* output, not the encoder's internal
    reconstruction: the two are separately jitted, so fusion differences can
    drift a few ulps apart — enough to push points sitting exactly at the
    bound past it at decompression.  Running the decoder here and promoting
    any straggler to an outlier makes the bound hold by construction on the
    artifact the decompressor actually sees.
    """
    levels = _num_levels(x.shape, max_levels)
    pshape = _padded_shape(x.shape, levels)
    xp = _pad_edge(x, pshape)
    codes, omask, ovals, recon = _interp_encode_padded(xp, eb, levels, order)
    # The coarse grid bypasses the outlier mechanism (Lorenzo-coded; decode
    # never consults omask there), so only interp targets are promotable.
    S = 1 << levels
    coarse = jnp.zeros(pshape, bool).at[tuple(slice(0, None, S) for _ in pshape)].set(True)
    omask, ovals, recon = _promote_stragglers(
        xp, codes, omask, ovals, eb, coarse,
        lambda c, m, v: _interp_decode_padded(c, m, v, eb, levels, order))
    meta = (tuple(x.shape), pshape, levels)
    return codes, omask, ovals, recon, meta


def interp_decode(codes, omask, ovals, eb, meta, order: str = "cubic"):
    orig_shape, _pshape, levels = meta
    recon = _interp_decode_padded(codes, omask, ovals, eb, levels, order)
    return recon[tuple(slice(0, d) for d in orig_shape)]


# ---------------------------------------------------------------------------
# Tile-predictor registry (docs/ARCHITECTURE.md)
# ---------------------------------------------------------------------------
#
# The tiled engine (repro.sz.tiled) treats every tile as an independent
# prediction domain and dispatches the per-tile transform through this
# registry instead of hardwiring a predictor.  A tile predictor provides
#
#   * ``plan(tile, max_levels)``            -> static per-tile config (levels),
#   * ``encode_tiles(tiles, eb, ...)``      -> (payload pytree, recon tiles),
#   * ``decode_tiles(payload, eb, ...)``    -> recon tiles,
#   * ``lane_bytes`` / ``parse_lane``       -> per-tile lane (de)serialization,
#
# where all payload leaves carry the tile batch on axis 0.  Decoding any
# subset of tiles must reproduce the exact bits the full batch would — the
# region==full bit-identity contract random-access decode relies on.  No op
# may mix tiles, AND any float decode must run through a compiled program
# that does not vary with the batch size (integer transforms are exact under
# any batching; float ones pin a fixed-width executable — see
# ``_INTERP_DECODE_CHUNK``).  Batched encode passes fan across the device
# mesh via ``repro.launch.sharding.map_tiles``.

# Canonical wire ids shared by the SZJX and GWTC containers.
PRED_IDS = {"lorenzo": 0, "interp": 1}
PRED_NAMES = {v: k for k, v in PRED_IDS.items()}
ORDER_IDS = {"linear": 0, "cubic": 1}
ORDER_NAMES = {v: k for k, v in ORDER_IDS.items()}

PREDICTORS: dict[str, "TilePredictor"] = {}


def register_predictor(pred: "TilePredictor") -> "TilePredictor":
    PREDICTORS[pred.name] = pred
    return pred


def get_predictor(name: str) -> "TilePredictor":
    try:
        return PREDICTORS[name]
    except KeyError:
        raise ValueError(
            f"unknown predictor {name!r} (registered: {sorted(PREDICTORS)})"
        ) from None


class TilePredictor:
    """Protocol for per-tile prediction transforms (see module comment)."""

    name: str

    def plan(self, tile: tuple[int, ...], max_levels: int = 5) -> int:
        """Static per-tile-shape config: interp level count (0 when unused)."""
        raise NotImplementedError

    def encode_tiles(self, tiles, eb, *, order: str, levels: int,
                     use_pallas: bool | None = None):
        """[B, *tile] -> (payload pytree of [B, ...] arrays, recon [B, *tile]).

        ``recon`` must be the *decode program's own output* so the bound holds
        by construction on what the decompressor reconstructs."""
        raise NotImplementedError

    def decode_tiles(self, payload, eb, *, tile: tuple[int, ...], order: str,
                     levels: int):
        """Payload pytree ([B, ...]) -> recon [B, *tile] float32."""
        raise NotImplementedError

    def decode_program_key(self, *, tile: tuple[int, ...], order: str,
                           levels: int) -> tuple:
        """Identity of the compiled decode program for one artifact geometry.

        The bucketed dispatcher (``tiled.dispatch_bucketed``) appends the
        bucket width, so each (key, width) pair names exactly one XLA
        executable — the serving layer's compile-cache accounting hangs off
        this.  Every static argument that changes the traced program MUST be
        in the key; batch size must NOT be (that is the bucket's job)."""
        return ("decode", self.name, tuple(tile), order, int(levels))

    def lane_bytes(self, payload, i: int, backend: str, *,
                   use_pallas: bool | None = None) -> bytes:
        """Serialize tile ``i`` of a host-side (numpy) payload to one lane.

        ``use_pallas`` routes the entropy pack through the device encode
        kernel (bytes are bit-identical either way)."""
        raise NotImplementedError

    def lane_bytes_batch(self, payload, n: int, backend: str, *,
                         use_pallas: bool | None = None) -> list[bytes]:
        """Serialize all ``n`` tiles of a payload.  The default loops
        :meth:`lane_bytes`; the streaming executor's device stage calls this
        so a predictor can batch the device encode across lanes."""
        return [self.lane_bytes(payload, i, backend, use_pallas=use_pallas)
                for i in range(n)]

    def parse_lane(self, blob: bytes, *, tile: tuple[int, ...], levels: int,
                   use_pallas: bool | None = None) -> dict:
        """Inverse of :meth:`lane_bytes`: one lane -> unbatched payload dict."""
        raise NotImplementedError


# One function object per static setting, so ``sharding.map_tiles`` compiles
# each per-device program once per mesh.
@lru_cache(maxsize=64)
def _lorenzo_encoder(eb, use_pallas):
    from repro.kernels import ops

    return lambda t: ops.lorenzo_quant_tiles_op(t, eb, use_pallas=use_pallas)


@lru_cache(maxsize=64)
def _lorenzo_decoder(eb):
    from repro.kernels import ops

    return lambda c: ops.lorenzo_decode_tiles_op(c, eb)


@lru_cache(maxsize=64)
def _interp_encoder(eb, levels, order):
    return jax.vmap(lambda t: _interp_encode_padded(t, eb, levels, order))


@register_predictor
class _LorenzoTiles(TilePredictor):
    """Prequant + integer Lorenzo per tile (carry cut at tile boundaries).

    Payload: ``{"codes": int32 [B, *tile]}``.  The transform is lossless on
    the prequantized grid, so the tiled reconstruction is bit-identical to
    the untiled ``predictor="lorenzo"`` path."""

    name = "lorenzo"

    def plan(self, tile, max_levels=5):
        return 0

    def encode_tiles(self, tiles, eb, *, order, levels, use_pallas=None):
        from repro.launch import sharding

        codes = sharding.map_tiles(_lorenzo_encoder(eb, use_pallas), tiles)
        payload = {"codes": codes}
        recon = self.decode_tiles(payload, eb, tile=tuple(tiles.shape[1:]),
                                  order=order, levels=levels)
        return payload, recon

    def decode_tiles(self, payload, eb, *, tile, order, levels):
        from repro.launch import sharding

        return sharding.map_tiles(_lorenzo_decoder(eb), payload["codes"])

    def lane_bytes(self, payload, i, backend, *, use_pallas=None):
        from repro.sz import entropy

        return entropy.encode_codes(payload["codes"][i], backend,
                                    use_pallas=use_pallas)

    def parse_lane(self, blob, *, tile, levels, use_pallas=None):
        from repro.sz import entropy

        return {"codes": entropy.decode_codes(blob, tile, use_pallas=use_pallas)}


# Interp lane layout (inside the GWTC container, docs/TILED_FORMAT.md):
#   n_out u32 | zlen u32 | zlib(idx u32[n_out] + val f32[n_out]) | RPRE codes
# Codes live on the per-tile *interp-padded* shape, derived from the
# container's (tile, levels) as ``_padded_shape(tile, levels)``.
_INTERP_LANE_HDR = struct.Struct("<II")


# Fixed decode batch width.  The compiled program a float computation runs
# through must not depend on how many tiles are being decoded: XLA fuses the
# interp chains differently at different batch sizes (and unrolls trip-1
# scans), which drifts ulps between a 1-tile region decode and an n-tile full
# decode.  Padding every decode batch to this fixed width means ONE vmapped
# executable serves every decode — same machine code per tile, so region and
# full decode are bit-identical by construction.  (The Lorenzo decode needs
# none of this: integer cumsum + one multiply cannot reassociate.)
_INTERP_DECODE_CHUNK = 4


@partial(jax.jit, static_argnames=("levels", "order"))
def _interp_decode_chunk(codes, omask, ovals, eb, levels: int, order: str):
    return jax.vmap(
        lambda c, m, v: _interp_decode_padded(c, m, v, eb, levels, order)
    )(codes, omask, ovals)


def _interp_decode_tiles_padded(codes, omask, ovals, eb, levels: int, order: str):
    """Chunked fixed-width decode of a [K, *pshape] payload (see
    ``_INTERP_DECODE_CHUNK`` for why the width is pinned)."""
    B = _INTERP_DECODE_CHUNK
    K = codes.shape[0]
    pad = (-K) % B
    if pad:
        ext = lambda a: jnp.concatenate([a, jnp.repeat(a[:1], pad, axis=0)])
        codes, omask, ovals = ext(codes), ext(omask), ext(ovals)
    out = [
        _interp_decode_chunk(codes[i : i + B], omask[i : i + B],
                             ovals[i : i + B], eb, levels, order)
        for i in range(0, K + pad, B)
    ]
    recon = out[0] if len(out) == 1 else jnp.concatenate(out)
    return recon[:K]


@register_predictor
class _InterpTiles(TilePredictor):
    """SZ3-style multi-level interpolation, vmapped over the tile batch.

    Payload: ``{"codes": int32, "omask": bool, "ovals": f32}`` on the
    per-tile interp-padded grid ([B, *padded_tile]).  Each tile is an
    independent prediction domain, so interp tiles decode standalone and the
    random-access contract holds exactly like the Lorenzo path."""

    name = "interp"

    def plan(self, tile, max_levels=5):
        return _num_levels(tile, max_levels)

    def encode_tiles(self, tiles, eb, *, order, levels, use_pallas=None):
        from repro.launch import sharding

        tile = tuple(tiles.shape[1:])
        pshape = _padded_shape(tile, levels)
        pads = [(0, 0)] + [(0, p - d) for d, p in zip(tile, pshape)]
        xp = jnp.pad(tiles, pads, mode="edge")

        codes, omask, ovals, _ = sharding.map_tiles(
            _interp_encoder(eb, levels, order), xp)

        S = 1 << levels
        coarse = jnp.zeros(pshape, bool).at[
            tuple(slice(0, None, S) for _ in pshape)].set(True)
        # Shared straggler promotion, batched over all tiles at once; the
        # decode runs through the same fixed-width executable decompression
        # uses, NOT a sharded full-batch program, so the recon contract holds.
        omask, ovals, recon = _promote_stragglers(
            xp, codes, omask, ovals, eb, coarse[None],
            lambda c, m, v: _interp_decode_tiles_padded(c, m, v, eb, levels, order))
        payload = {"codes": codes, "omask": omask, "ovals": ovals}
        crop = (slice(None),) + tuple(slice(0, d) for d in tile)
        return payload, recon[crop]

    def decode_tiles(self, payload, eb, *, tile, order, levels):
        # Deliberately NOT fanned through sharding.map_tiles: the decode must
        # run through the one fixed-width executable (_INTERP_DECODE_CHUNK)
        # on every call, or region and full decode would compile different
        # programs and drift ulps apart.
        recon = _interp_decode_tiles_padded(
            payload["codes"], payload["omask"], payload["ovals"], eb, levels, order)
        return recon[(slice(None),) + tuple(slice(0, d) for d in tile)]

    def lane_bytes(self, payload, i, backend, *, use_pallas=None):
        import zlib

        from repro.sz import entropy

        omask = payload["omask"][i]
        idx = np.flatnonzero(omask.ravel()).astype(np.uint32)
        val = payload["ovals"][i].ravel()[idx].astype(np.float32)
        out = zlib.compress(idx.tobytes() + val.tobytes(), 6)
        return (_INTERP_LANE_HDR.pack(idx.size, len(out)) + out
                + entropy.encode_codes(payload["codes"][i], backend,
                                       use_pallas=use_pallas))

    def parse_lane(self, blob, *, tile, levels, use_pallas=None):
        import zlib

        from repro.sz import entropy

        pshape = _padded_shape(tile, levels)
        n_out, zlen = _INTERP_LANE_HDR.unpack_from(blob, 0)
        off = _INTERP_LANE_HDR.size
        raw = zlib.decompress(blob[off : off + zlen])
        idx = np.frombuffer(raw, np.uint32, n_out).astype(np.int64)
        val = np.frombuffer(raw, np.float32, n_out, offset=4 * n_out)
        n = int(np.prod(pshape))
        omask = np.zeros(n, bool)
        ovals = np.zeros(n, np.float32)
        omask[idx] = True
        ovals[idx] = val
        return {
            "codes": entropy.decode_codes(blob[off + zlen :], pshape,
                                          use_pallas=use_pallas),
            "omask": omask.reshape(pshape),
            "ovals": ovals.reshape(pshape),
        }


# Instantiate the registered classes (the decorator stored the class; replace
# with a singleton instance so callers get bound methods).
for _name, _cls in list(PREDICTORS.items()):
    if isinstance(_cls, type):
        PREDICTORS[_name] = _cls()
del _name, _cls
