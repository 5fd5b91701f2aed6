"""Device-side canonical-Huffman encode pack (Pallas).

The host codec (``sz/entropy.py``) packs the code stream with a bit-level
scatter over ``np.packbits`` — byte-sequential work with no TPU analogue.
This kernel reformulates the pack as chunk-parallel word assembly so it maps
onto the VPU:

* every chunk (``chunk_size`` symbols, the hc/hZ decode unit) is an
  independent bit stream, so chunks are grid-parallel;
* per-symbol bit offsets inside a chunk come from a Hillis-Steele prefix sum
  over the code lengths (log2(CS) roll+mask steps — ``jnp.cumsum`` is not
  relied on inside Mosaic);
* each codeword is left-aligned into a 32-bit lane (``code << (32 - len)``)
  and split into the two words it can straddle with logical shifts (two-step
  shifts keep every shift amount in [0, 31]);
* the word-level scatter/OR is a one-hot accumulate over the chunk's word
  axis — disjoint bit ranges make integer ADD equal OR, the same trick the
  ``symbol_hist`` kernel uses instead of scatter.

Each chunk's total bit count (the hc/hZ per-chunk bit table) falls out of the
prefix sum for free.  The cross-chunk splice into one continuous bit stream
(chunks are *not* byte-aligned in the wire format) stays on host — it is one
vectorized shift + bincount over word indices (``sz/entropy.py``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _encode_block(lens, codes, chunk_size: int):
    """Shared block body: [BB, CS] int32 lens/codes -> ([BB, CS] words, [BB, 1]
    totals).  ``lens == 0`` marks pad slots (last chunk short); real code
    lengths are in [1, 32]."""
    bb, cs = lens.shape
    # chunk-local inclusive prefix sum of code lengths (bit end per symbol)
    ends = lens
    d = 1
    while d < cs:
        pos = jax.lax.broadcasted_iota(jnp.int32, ends.shape, 1)
        ends = ends + jnp.where(pos >= d, jnp.roll(ends, d, axis=1), 0)
        d *= 2
    totals = ends[:, -1:]
    starts = ends - lens
    # left-align each codeword at bit 31; pad slots contribute nothing
    sh_align = jnp.where(lens > 0, 32 - lens, 0)
    aligned = jnp.where(lens > 0, codes << sh_align, 0)
    w0 = starts >> 5
    sh = starts & 31
    hi = jax.lax.shift_right_logical(aligned, sh)
    # spill into the next word; (x << (31-sh)) << 1 == x << (32-sh) without
    # ever shifting by 32 (sh == 0 -> spill is exactly 0)
    lo = (aligned << (31 - sh)) << 1
    # one-hot word accumulate: disjoint bit ranges => ADD == OR, and the full
    # [BB, W] assignment zero-fills words past each chunk's bit count
    wi = jax.lax.broadcasted_iota(jnp.int32, (bb, cs, cs), 2)
    contrib = (jnp.where(w0[..., None] == wi, hi[..., None], 0)
               + jnp.where((w0[..., None] + 1) == wi, lo[..., None], 0))
    return contrib.sum(axis=1), totals


def _kernel(lens_ref, codes_ref, words_ref, totals_ref, *, chunk_size: int):
    words, totals = _encode_block(lens_ref[...], codes_ref[...], chunk_size)
    words_ref[...] = words
    totals_ref[...] = totals


@partial(jax.jit, static_argnames=("interpret",))
def huffman_encode_pack(lens: jax.Array, codes: jax.Array, *,
                        interpret: bool = True) -> tuple[jax.Array, jax.Array]:
    """lens/codes: [C, CS] int32 (0-len = pad) -> (words [C, CS] int32 with the
    chunk bit stream MSB-first across big-endian u32 lanes, chunk_bits [C]
    int32).

    The one-hot intermediate is [BB, CS, CS] int32, so the block height BB is
    sized to keep it around ~1M cells (mirrors ``symbol_hist``'s bound),
    rounded down to the TPU's 8-row sublane tile (at least 8; short inputs
    are padded up to one block).
    """
    C, cs = lens.shape
    bb = max(8, 1_000_000 // max(cs * cs, 1) // 8 * 8)
    Cp = -(-C // bb) * bb
    if Cp != C:
        pad = ((0, Cp - C), (0, 0))
        lens = jnp.pad(lens, pad)
        codes = jnp.pad(codes, pad)
    words, totals = pl.pallas_call(
        partial(_kernel, chunk_size=cs),
        grid=(Cp // bb,),
        in_specs=[pl.BlockSpec((bb, cs), lambda i: (i, 0)),
                  pl.BlockSpec((bb, cs), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((bb, cs), lambda i: (i, 0)),
                   pl.BlockSpec((bb, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((Cp, cs), jnp.int32),
                   jax.ShapeDtypeStruct((Cp, 1), jnp.int32)],
        interpret=interpret,
    )(lens, codes)
    return words[:C], totals[:C, 0]
