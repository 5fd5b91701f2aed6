"""Device-side chunked canonical-Huffman decode probe (Pallas).

Mirrors the host ``_decode_lanes`` walk (``sz/entropy.py``): every chunk is an
independent lane, all lanes step in lockstep, and one step performs a single
k-bit multi-symbol LUT probe per lane — decoding *all* complete codes inside
the window (up to S).  Codes longer than k bits resolve through the escape
path: the index of the last left-aligned canonical codeword at or below the
window (the device form of the host's ``searchsorted``).

Device-specific reformulations:

* 32-bit windows instead of 64-bit: the encoder caps code lengths at 32, so
  code boundaries only depend on the window's top 32 bits and the host
  searchsorted escape resolves identically (dispatch gates deeper legacy
  tables back to the host codec);
* every lookup is a one-hot select-and-sum over a table row, never an
  in-kernel gather (Mosaic lowers only narrow 2D gathers).  Each chunk gets
  its own word window (``words[c]`` starts at the word holding the chunk's
  first bit, ``starts[c]`` is that bit's offset in it), so the word lookup
  spans one window, not the whole stream; the LUT is one [8, 2**k] table
  (count, consumed bits, up to six symbol ids), and the escape search is a
  count of codewords at or below the window;
* unsigned codeword comparison runs in int32 through the order-preserving
  ``x ^ 0x80000000`` map;
* decoded ids land in the output via a one-hot accumulate over the chunk's
  symbol axis (ADD == OR on disjoint slots), not a scatter;
* the lockstep loop is a ``while_loop`` that runs while any lane of the
  block is short of its symbol target, and never more than chunk_size steps
  (every probe yields >= 1 symbol, so a sound chunk needs no more; the cap
  bounds a corrupt one).  It checks once every ``CHECK_EVERY`` steps: on a
  TPU v5e the check, a reduce to a scalar, costs about a quarter of a step,
  and of 1, 2, 4 and 8 steps between checks 8 decoded fastest.  Lanes past
  their target stop advancing, so the steps run past the slowest lane are
  harmless.  The steps each block ran come back beside the ids.

Probe overshoot past a lane's symbol target is clamped exactly like the host
path clamps in ``_expand_entries``.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_MININT = -2147483648  # x ^ MININT maps unsigned order onto int32 (weak literal)
LUT_ROWS = 8  # count, consumed bits, then up to six symbol ids per probe
CHECK_EVERY = 8  # lockstep steps between the checks for a finished block


def _lookup(onehot, row):
    """Per-lane table read: onehot [bb, N] bool, row [1, N] -> [bb, 1]."""
    return jnp.sum(jnp.where(onehot, row, 0), axis=1, keepdims=True)


def _decode_block(words, starts, counts, lut, cw_map, order, len_sorted, *,
                  chunk_size: int, k: int, n_ids: int):
    """words [bb, W]; starts/counts [bb, 1]; lut [LUT_ROWS, 2**k];
    cw_map/order/len_sorted [1, N] (padded past the alphabet: cw_map with
    INT32_MAX, order/len_sorted with their last entry).  Returns the ids
    [bb, cs] and the steps run, an int32 scalar: until every lane met its
    count, rounded up to a whole check, at most chunk_size."""
    bb, nw = words.shape
    word_iota = jax.lax.broadcasted_iota(jnp.int32, (bb, nw), 1)
    lut_iota = jax.lax.broadcasted_iota(jnp.int32, (bb, lut.shape[1]), 1)
    cw_iota = jax.lax.broadcasted_iota(jnp.int32, (bb, cw_map.shape[1]), 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (bb, chunk_size), 1)

    def step(state):
        n, pos, cur, out = state
        wi = pos >> 5
        sh = pos & 31
        h = _lookup(word_iota == wi, words)
        nxt = _lookup(word_iota == wi + 1, words)
        w = (h << sh) | jax.lax.shift_right_logical(
            jax.lax.shift_right_logical(nxt, 31 - sh), 1)
        hot = lut_iota == jax.lax.shift_right_logical(w, 32 - k)
        cnt = _lookup(hot, lut[0:1])
        nb = _lookup(hot, lut[1:2])
        # escape: the first code in the window is longer than k bits.  The
        # first canonical code is 0 (maps to MININT <= any window), so the
        # count is >= 1; padding past the alphabet repeats the last entry.
        below = (cw_map <= (w ^ _MININT)).astype(jnp.int32)
        e_hot = cw_iota == jnp.sum(below, axis=1, keepdims=True) - 1
        esc = cnt == 0
        cnt = jnp.where(esc, 1, cnt)
        nb = jnp.where(esc, _lookup(e_hot, len_sorted), nb)
        active = cur < counts
        take_n = jnp.where(active, jnp.minimum(cnt, counts - cur), 0)
        for j in range(n_ids):
            idj = _lookup(hot, lut[2 + j:3 + j])
            if j == 0:
                idj = jnp.where(esc, _lookup(e_hot, order), idj)
            hit = (slot == cur + j) & (take_n > j)
            out = out + jnp.where(hit, idj, 0)
        pos = pos + jnp.where(active, nb, 0)
        return n + 1, pos, cur + take_n, out

    def between_checks(state):
        for _ in range(math.gcd(CHECK_EVERY, chunk_size)):
            state = step(state)
        return state

    def running(state):
        n, _, cur, _ = state
        return (n < chunk_size) & jnp.any(cur < counts)

    init = (jnp.int32(0), starts, jnp.zeros((bb, 1), jnp.int32),
            jnp.zeros((bb, chunk_size), jnp.int32))
    n, _, _, out = jax.lax.while_loop(running, between_checks, init)
    return out, n


def _kernel(words_ref, starts_ref, counts_ref, lut_ref, cw_map_ref, order_ref,
            len_sorted_ref, out_ref, steps_ref, *, chunk_size: int, k: int,
            n_ids: int):
    out_ref[...], n = _decode_block(
        words_ref[...], starts_ref[...], counts_ref[...], lut_ref[...],
        cw_map_ref[...], order_ref[...], len_sorted_ref[...],
        chunk_size=chunk_size, k=k, n_ids=n_ids)
    steps_ref[...] = jnp.full(steps_ref.shape, n, jnp.int32)


@partial(jax.jit, static_argnames=("chunk_size", "k", "n_ids", "block_chunks",
                                   "interpret"))
def huffman_decode_probe(words: jax.Array, starts: jax.Array, counts: jax.Array,
                         lut: jax.Array, cw_map: jax.Array, order: jax.Array,
                         len_sorted: jax.Array, *, chunk_size: int, k: int,
                         n_ids: int, block_chunks: int = 8,
                         interpret: bool = True) -> tuple[jax.Array, jax.Array]:
    """words: [C, W] int32 per-chunk word windows (big-endian u32 stream
    words from the one holding the chunk's first bit; zeros past the
    stream); starts/counts: [C, 1] int32 first-bit offset in the window /
    symbol target.  Tables are the codec's multi-symbol LUT and canonical
    order (``HuffmanCodec._device_tables``).  Returns alphabet ids
    [C, chunk_size] int32 (rows zero-padded past each chunk's count) and
    the probe steps each block of ``block_chunks`` chunks ran,
    [ceil(C / block_chunks)] int32."""
    C = words.shape[0]
    bb = block_chunks
    pad = (-C) % bb
    if pad:  # count 0 => the lane never activates
        words = jnp.pad(words, ((0, pad), (0, 0)))
        starts = jnp.pad(starts, ((0, pad), (0, 0)))
        counts = jnp.pad(counts, ((0, pad), (0, 0)))
    full = lambda a: pl.BlockSpec(a.shape, lambda i: (0,) * a.ndim)
    lanes = lambda a: pl.BlockSpec((bb, a.shape[1]), lambda i: (i, 0))
    out, steps = pl.pallas_call(
        partial(_kernel, chunk_size=chunk_size, k=k, n_ids=n_ids),
        grid=((C + pad) // bb,),
        in_specs=[lanes(words), lanes(starts), lanes(counts), full(lut),
                  full(cw_map), full(order), full(len_sorted)],
        out_specs=[pl.BlockSpec((bb, chunk_size), lambda i: (i, 0)),
                   pl.BlockSpec((bb, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((C + pad, chunk_size), jnp.int32),
                   jax.ShapeDtypeStruct((C + pad, 1), jnp.int32)],
        interpret=interpret,
    )(words, starts, counts, lut, cw_map, order, len_sorted)
    return out[:C], steps[::bb, 0]
