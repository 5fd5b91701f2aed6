"""Dispatch layer: Pallas kernels on TPU, jnp reference on other backends.

``use_pallas=None`` auto-detects; the CPU dry-run path always lowers the pure
JAX reference (Pallas TPU kernels can't lower on the host platform), while
tests exercise the kernels in interpret mode.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.enhancer_fused import (
    enhancer_grouped, fits as enhancer_fits, param_table as enhancer_param_table)
from repro.kernels.group_hist import group_hist, symbol_hist
from repro.kernels.huffman_decode import huffman_decode_probe
from repro.kernels.huffman_encode import huffman_encode_pack
from repro.kernels.lorenzo_quant import lorenzo_quant, lorenzo_quant_tiles


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def lorenzo_quant_op(x, eb, *, use_pallas: bool | None = None, interpret: bool | None = None):
    use = _on_tpu() if use_pallas is None else use_pallas
    if use:
        return lorenzo_quant(x, eb, interpret=not _on_tpu() if interpret is None else interpret)
    return ref.lorenzo_quant_ref(x, eb)


def lorenzo_quant_tiles_op(x, eb, *, use_pallas: bool | None = None,
                           interpret: bool | None = None):
    """Tile-batched Lorenzo codes: x is [B, *tile] with axis 0 the tile batch.

    The Pallas kernel covers the 3D-tile case ([B, Z, Y, X]); other tile
    ranks run the jnp reference (the transform is identical per axis)."""
    use = _on_tpu() if use_pallas is None else use_pallas
    if use and x.ndim == 4:
        return lorenzo_quant_tiles(
            x, eb, interpret=not _on_tpu() if interpret is None else interpret)
    return ref.lorenzo_quant_tiles_ref(x, eb)


@partial(jax.jit, static_argnames=("eb",))
def _lorenzo_decode_tiles(codes, eb):
    from repro.sz.predictor import lorenzo_decode

    return jax.vmap(lambda c: lorenzo_decode(c, eb, jnp.float32))(codes)


def lorenzo_decode_tiles_op(codes, eb):
    """Batched exact inverse of :func:`lorenzo_quant_tiles_op`: integer cumsum
    per tile + dequantize ([B, *tile] int32 -> float32).

    Elementwise-exact in the batch axis (integer cumsums are exact, the
    dequantize multiply is per-element), so any subset of tiles reconstructs
    the bits the full batch would — the contract random-access region decode
    relies on.  Pure vectorized jnp on every backend (cumsum lowers well
    everywhere; no Pallas variant is needed)."""
    return _lorenzo_decode_tiles(codes, float(eb))


def enhancer_path(slice_shape) -> str:
    """``"kernel"`` where the grouped enhancer kernel runs slices of this
    [B, H, W] shape (on the TPU, in blocks it fits), else ``"jnp"``."""
    return "kernel" if _on_tpu() and enhancer_fits(slice_shape) else "jnp"


def enhancer_fused_op(xs, params, bn_state, edges, rscale, clamp_eb, *,
                      n_groups: int, residual_learning: bool, use_clamp: bool,
                      use_pallas: bool | None = None, interpret: bool | None = None):
    """Group-wise enhancement of slices xs [B, H, W] by the G enhancers
    (params/bn_state leaves with a leading G axis).  The kernel takes the
    shapes it fits (``enhancer_path``); other shapes run the reference."""
    use = _on_tpu() if use_pallas is None else use_pallas
    if use and enhancer_fits(xs.shape):
        table = enhancer_param_table(params, bn_state, edges, rscale,
                                     residual_learning=residual_learning)
        return enhancer_grouped(
            xs, table, edges, clamp_eb, n_groups=n_groups,
            channels=params["b1"].shape[-1], residual_learning=residual_learning,
            use_clamp=use_clamp,
            interpret=not _on_tpu() if interpret is None else interpret)
    return ref.enhancer_grouped_ref(params, bn_state, xs, edges, rscale, clamp_eb,
                                    n_groups=n_groups,
                                    residual_learning=residual_learning,
                                    use_clamp=use_clamp)


def symbol_hist_op(symbols, *, n_bins: int, use_pallas: bool | None = None,
                   interpret: bool | None = None):
    """Integer-symbol histogram over any-shaped int32 input.

    Values outside [0, n_bins) are ignored (they land in an internal
    sentinel bin, along with lane padding). Returns hist int32 [n_bins]."""
    flat = jnp.reshape(symbols, (-1,))
    sentinel = n_bins
    # bins round up to a power of two (at least 128, always > n_bins so the
    # sentinel fits): one compiled kernel then serves every alphabet span in
    # the bucket, instead of one compile per distinct span
    bins = max(128, 1 << n_bins.bit_length())
    flat = jnp.where((flat >= 0) & (flat < n_bins), flat, sentinel).astype(jnp.int32)
    # block size bounds the [BB, 128, bins] one-hot intermediate to ~1M cells,
    # in whole 8-row sublane tiles (the TPU block rule); rows pad to a block
    bb = max(8, min(256, 8192 // bins) // 8 * 8)
    rows = -(-max(int(flat.shape[0]), 1) // 128)
    rows = -(-rows // bb) * bb
    pad = rows * 128 - flat.shape[0]
    flat = jnp.concatenate([flat, jnp.full((pad,), sentinel, jnp.int32)])
    x2 = flat.reshape(rows, 128)
    use = _on_tpu() if use_pallas is None else use_pallas
    if use:
        hist = symbol_hist(x2, n_bins=bins, block_rows=bb,
                           interpret=not _on_tpu() if interpret is None else interpret)
    else:
        hist = ref.symbol_hist_ref(x2, bins)
    return hist[:n_bins]


def huffman_encode_op(lens, codes, *, use_pallas: bool | None = None,
                      interpret: bool | None = None):
    """Chunk-parallel canonical-Huffman encode pack.

    lens/codes: [C, CS] int32 per-chunk code lengths / codewords (0-length
    marks the pad slots of a short last chunk).  Returns (words [C, CS]
    int32 — each chunk's bit stream MSB-first across big-endian u32 lanes,
    chunk_bits [C] int32).  The entropy layer splices chunks into the
    continuous hc/hZ stream on host (``sz/entropy.py``)."""
    use = _on_tpu() if use_pallas is None else use_pallas
    if use:
        return huffman_encode_pack(
            lens, codes, interpret=not _on_tpu() if interpret is None else interpret)
    return ref.huffman_encode_ref(lens, codes)


def huffman_decode_op(words, starts, counts, lut, cw_map, order, len_sorted, *,
                      chunk_size: int, k: int, n_ids: int,
                      use_pallas: bool | None = None,
                      interpret: bool | None = None):
    """Lockstep multi-symbol-LUT Huffman decode probe.

    words: [C, W] int32 per-chunk windows of big-endian u32 stream words;
    starts/counts: [C, 1] int32 first-bit offsets / symbol targets; tables
    from ``HuffmanCodec._device_tables``.  Returns alphabet ids
    [C, chunk_size] int32 (zero-padded past each chunk's count) and the
    probe steps each block of 8 chunks ran, [ceil(C / 8)] int32."""
    use = _on_tpu() if use_pallas is None else use_pallas
    if use:
        return huffman_decode_probe(
            words, starts, counts, lut, cw_map, order, len_sorted,
            chunk_size=chunk_size, k=k, n_ids=n_ids,
            interpret=not _on_tpu() if interpret is None else interpret)
    return ref.huffman_decode_ref(words, starts, counts, lut, cw_map, order,
                                  len_sorted, chunk_size=chunk_size, k=k,
                                  n_ids=n_ids)


def group_hist_op(x, edges, *, n_groups: int, use_pallas: bool | None = None,
                  interpret: bool | None = None):
    """x: any shape with size % 128 == 0 (host pads); returns (ids, hist)."""
    shape = x.shape
    x2 = x.reshape(-1, 128)
    use = _on_tpu() if use_pallas is None else use_pallas
    if use:
        ids, hist = group_hist(x2, edges, n_groups=n_groups,
                               interpret=not _on_tpu() if interpret is None else interpret)
    else:
        ids, hist = ref.group_hist_ref(x2, edges)
    return ids.reshape(shape), hist
