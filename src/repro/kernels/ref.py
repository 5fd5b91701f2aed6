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


def enhancer_grouped_ref(params, bn_state, xs, edges, rscale, clamp_eb, *,
                         n_groups: int, residual_learning: bool,
                         use_clamp: bool) -> jax.Array:
    """Group-wise enhancement of slices xs [B, H, W] by G enhancers (pytrees
    with a leading G axis): every group's forward over every pixel, each
    masked to its own group's pixels, then X' + R_hat (or the direct form)
    and the optional clamp to [X' - eb, X' + eb]."""
    from repro.core import enhancer, grouping
    from repro.core.trainer import _group_inputs

    ids = grouping.assign_groups(xs, edges)
    xn, masks = _group_inputs(xs, ids, edges, n_groups)
    preds = jax.vmap(lambda p, st, xg: enhancer.apply(p, st, xg, train=False)[0])(
        params, bn_state, xn)  # [G, B, H, W]
    if residual_learning:
        out = xs + (preds * rscale[:, None, None, None] * masks).sum(axis=0)
    else:
        lo, scale = grouping.group_normalizers(edges)
        out = ((preds * scale[:, None, None, None] + lo[:, None, None, None])
               * masks).sum(axis=0)
    if use_clamp:
        out = jnp.clip(out, xs - clamp_eb, xs + clamp_eb)
    return out


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
                       *, chunk_size: int, k: int, n_ids: int,
                       block_chunks: int = 8) -> tuple[jax.Array, jax.Array]:
    """Lockstep multi-symbol LUT decode probe, the kernel's blocks of
    ``block_chunks`` chunks mapped over at once.  Returns alphabet ids
    [C, chunk_size] int32 and the steps each block ran."""
    from repro.kernels.huffman_decode import _decode_block

    C, bb = words.shape[0], block_chunks
    pad = (-C) % bb  # count 0 => the lane never activates
    blocks = [jnp.pad(a, ((0, pad), (0, 0))).reshape(-1, bb, a.shape[1])
              for a in (words, starts, counts)]
    out, steps = jax.vmap(
        lambda w, s, c: _decode_block(w, s, c, lut, cw_map, order, len_sorted,
                                      chunk_size=chunk_size, k=k, n_ids=n_ids)
    )(*blocks)
    return out.reshape(-1, chunk_size)[:C], steps


def group_hist_ref(x: jax.Array, edges: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Group-id assignment + histogram. x: [N, 128]; edges: [G+1].

    Returns (ids int32 [N,128], hist int32 [G])."""
    G = edges.shape[0] - 1
    ids = (x[..., None] >= edges[:-1]).sum(-1).astype(jnp.int32) - 1
    ids = jnp.clip(ids, 0, G - 1)
    hist = jnp.zeros((G,), jnp.int32).at[ids.ravel()].add(1)
    return ids, hist
