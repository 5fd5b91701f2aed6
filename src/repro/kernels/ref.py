"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def lorenzo_quant_ref(x: jax.Array, eb: float) -> jax.Array:
    """Fused prequantize + 3D integer Lorenzo stencil (compression hot loop)."""
    q = jnp.rint(x / (2.0 * jnp.asarray(eb, x.dtype))).astype(jnp.int32)
    for ax in range(x.ndim):
        shifted = jnp.roll(q, 1, axis=ax)
        idx = [slice(None)] * q.ndim
        idx[ax] = slice(0, 1)
        shifted = shifted.at[tuple(idx)].set(0)
        q = q - shifted
    return q


def lorenzo_quant_tiles_ref(x: jax.Array, eb: float) -> jax.Array:
    """Tile-batched Lorenzo codes: axis 0 is the tile batch, each tile gets
    the per-volume stencil with its own zero boundary (independent domains).
    vmap of the single-volume oracle, so the stencil exists in one place."""
    return jax.vmap(lambda t: lorenzo_quant_ref(t, eb))(x)


def enhancer_fused_ref(x: jax.Array, w1, b1, gamma, beta, mean, var, w2, b2) -> jax.Array:
    """Conv3x3(1->C) + BN(inference) + ReLU + Conv3x3(C->1), zero-pad SAME.

    x: [B, H, W]; returns [B, H, W]."""
    from repro.core.enhancer import _conv

    h = _conv(x[..., None], w1, b1)
    h = (h - mean) * jax.lax.rsqrt(var + 1e-5) * gamma + beta
    h = jax.nn.relu(h)
    out = _conv(h, w2, b2)
    return out[..., 0]


def symbol_hist_ref(s: jax.Array, n_bins: int) -> jax.Array:
    """Integer-symbol histogram. s: [N, 128] int32 in [0, n_bins).

    Returns hist int32 [n_bins]."""
    return jnp.zeros((n_bins,), jnp.int32).at[s.ravel()].add(1)


def huffman_encode_ref(lens: jax.Array, codes: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Chunk-parallel Huffman encode pack. lens/codes: [C, CS] int32 (0-len =
    pad slot).  Returns (words [C, CS] int32, chunk_bits [C] int32) — the
    same block body the Pallas kernel runs, applied to the whole batch."""
    from repro.kernels.huffman_encode import _encode_block

    words, totals = _encode_block(lens, codes, lens.shape[1])
    return words, totals[:, 0]


def huffman_decode_ref(words, starts, counts, lut, cw_map, order, len_sorted,
                       *, chunk_size: int, k: int, n_ids: int) -> jax.Array:
    """Lockstep multi-symbol LUT decode probe over all chunks at once.
    Returns alphabet ids [C, chunk_size] int32."""
    from repro.kernels.huffman_decode import _decode_block

    return _decode_block(words, starts, counts, lut, cw_map, order, len_sorted,
                         chunk_size=chunk_size, k=k, n_ids=n_ids)


def group_hist_ref(x: jax.Array, edges: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Group-id assignment + histogram. x: [N, 128]; edges: [G+1].

    Returns (ids int32 [N,128], hist int32 [G])."""
    G = edges.shape[0] - 1
    ids = (x[..., None] >= edges[:-1]).sum(-1).astype(jnp.int32) - 1
    ids = jnp.clip(ids, 0, G - 1)
    hist = jnp.zeros((G,), jnp.int32).at[ids.ravel()].add(1)
    return ids, hist
