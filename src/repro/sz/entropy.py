"""Host-side entropy stage: chunked canonical Huffman + zlib backends.

Bitstream packing is byte-sequential with no TPU analogue (real SZ GPU
pipelines also run it on host).  The TPU side hands this module a dense int32
code tensor; encoding is fully vectorized numpy, decoding is a chunked,
table-driven, vectorized walk: the symbol stream is split into fixed-size
chunks at encode time (per-chunk bit lengths live in the header), and every
chunk steps forward in lockstep — one word-level gather against a k-bit
multi-symbol canonical-Huffman LUT decodes all complete codes in the window
(codes longer than k bits resolve through one searchsorted over the
left-aligned codewords).  Chunk lanes are dispatched across cores with
``concurrent.futures``.

Blob layout, tag registry, and backward compatibility (legacy ``hf``/``hz``
blobs still decode through the seed per-symbol walk) are specified in
``docs/ENTROPY_FORMAT.md``.
"""
from __future__ import annotations

import heapq
import os
import struct
import sys
import threading
import zlib
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import NamedTuple

import numpy as np

from repro import obs
from repro.sz.artifact import ENTROPY_MAGIC

_MAGIC = ENTROPY_MAGIC

DEFAULT_CHUNK = 256  # symbols per independently decodable chunk
_LUT_BITS = 12  # primary decode-table width cap (2**k uint64 entries)
_FMT_CODE_LEN = 32  # FROZEN in the hc/hZ blob format (chunk-table width rule)
_MAX_CODE_LEN = _FMT_CODE_LEN  # encoder policy; must never exceed _FMT_CODE_LEN
_ACCEL_SPAN = 4096  # dense alphabet span served by the symbol_hist kernel
_PROBE_ALPHABET = 1 << 14  # widest alphabet the device decode probe takes
_DENSE_SPAN = 1 << 22  # host bincount beyond this falls back to np.unique


def _chunk_bits_dtype(chunk_size: int) -> str:
    """Chunk-table entry width: u16 whenever a full chunk of max-length codes
    fits.  Part of the hc/hZ wire format — the rule is pinned to the frozen
    ``_FMT_CODE_LEN``, never to current encoder policy."""
    return "<u2" if chunk_size * _FMT_CODE_LEN <= 0xFFFF else "<u4"


def shannon_bits(symbols: np.ndarray) -> float:
    """Ideal entropy-coded size in bits (lower bound for any entropy coder).

    Dense integer alphabets count through ``bincount`` (O(n)) exactly like
    ``HuffmanCodec.fit``; only sparse/float inputs pay the ``np.unique``
    sort."""
    flat = np.asarray(symbols).ravel()
    if flat.size == 0:
        return 0.0
    counts = None
    if np.issubdtype(flat.dtype, np.integer):
        lo, hi = int(flat.min()), int(flat.max())
        if hi - lo + 1 <= _DENSE_SPAN:
            counts = np.bincount(flat.astype(np.int64) - lo)
            counts = counts[counts > 0]
    if counts is None:
        _, counts = np.unique(flat, return_counts=True)
    p = counts / counts.sum()
    return float(-(p * np.log2(p)).sum() * flat.size)


# ---------------------------------------------------------------------------
# Canonical Huffman
# ---------------------------------------------------------------------------


def _code_lengths(counts: np.ndarray) -> np.ndarray:
    """Huffman code length per symbol from frequency counts (heap build)."""
    n = len(counts)
    if n == 0:
        return np.zeros(0, np.int64)
    if n == 1:
        return np.array([1], np.int64)
    heap = [(int(c), i) for i, c in enumerate(counts)]
    heapq.heapify(heap)
    parent = np.full(2 * n - 1, -1, np.int64)
    nxt = n
    while len(heap) > 1:
        c1, i1 = heapq.heappop(heap)
        c2, i2 = heapq.heappop(heap)
        parent[i1] = nxt
        parent[i2] = nxt
        heapq.heappush(heap, (c1 + c2, nxt))
        nxt += 1
    depth = np.zeros(2 * n - 1, np.int64)
    for i in range(nxt - 2, -1, -1):  # parents always have higher index
        depth[i] = depth[parent[i]] + 1
    return depth[:n]


def _limited_code_lengths(counts: np.ndarray, max_len: int = _MAX_CODE_LEN) -> np.ndarray:
    """Code lengths capped at ``max_len`` by count-halving (pathological skew
    only; equal counts give a balanced tree, so the loop terminates)."""
    c = np.asarray(counts, np.int64)
    lengths = _code_lengths(c)
    while lengths.size and int(lengths.max()) > max_len:
        c = (c + 1) >> 1
        lengths = _code_lengths(c)
    return lengths


def _canonical_codes(lengths: np.ndarray) -> np.ndarray:
    """Assign canonical codewords (as uint64) given code lengths."""
    order = np.lexsort((np.arange(len(lengths)), lengths))
    codes = np.zeros(len(lengths), np.uint64)
    code = 0
    prev_len = 0
    for sym in order:
        L = int(lengths[sym])
        code <<= L - prev_len
        codes[sym] = code
        code += 1
        prev_len = L
    return codes


def _accel_default() -> bool:
    """Device entropy (``symbol_hist`` counts, the Pallas encode pack and
    decode probe) is the TPU's path; every other platform runs the host
    codec."""
    import jax

    return jax.default_backend() == "tpu"


# Process-wide count of which implementation each Huffman lane went
# through: ``pack_device``/``pack_host`` per encoded lane, ``probe_device``/
# ``probe_host`` per decoded lane.  ``pack_fallback``/``probe_fallback``
# count lanes for which the device path was asked for but the host ran them
# (an ineligible codec or stream), so a run on the chip can prove that no
# lane left the device path unannounced.
_PATH_LOCK = threading.Lock()
_PATH_KEYS = ("pack_device", "pack_host", "pack_fallback", "probe_device",
              "probe_host", "probe_fallback")
_PATHS = dict.fromkeys(_PATH_KEYS, 0)


def _count_lane(stage: str, asked: bool, on_device: bool) -> None:
    """Count one lane of ``stage`` ("pack" or "probe")."""
    with _PATH_LOCK:
        if on_device:
            _PATHS[f"{stage}_device"] += 1
        else:
            _PATHS[f"{stage}_host"] += 1
            _PATHS[f"{stage}_fallback"] += asked


def entropy_path_stats() -> dict:
    """Snapshot of the per-lane entropy implementation counters."""
    with _PATH_LOCK:
        return dict(_PATHS)


def reset_entropy_path_stats() -> None:
    with _PATH_LOCK:
        _PATHS.update(dict.fromkeys(_PATH_KEYS, 0))


def _accel_hist(flat: np.ndarray, lo: int, span: int) -> np.ndarray:
    import jax.numpy as jnp

    from repro.kernels import ops

    with obs.span("gwlz.entropy.hist", 4 * flat.size):
        shifted = jnp.asarray((flat.astype(np.int64) - lo).astype(np.int32))
        return np.asarray(ops.symbol_hist_op(shifted, n_bins=span), np.int64)


def _splice_chunks(local: np.ndarray, chunk_bits: np.ndarray) -> tuple[bytes, int]:
    """Concatenate per-chunk word-packed bit streams into one continuous
    MSB-first byte stream (hc/hZ chunks are *not* byte-aligned).

    ``local`` is the device pack output viewed as uint32 [C, W]: chunk c's
    bits live MSB-first in its first ``ceil(chunk_bits[c]/32)`` words, zeros
    beyond.  Each chunk's words shift right by its global bit offset mod 32
    (the spill re-split mirrors the kernel's two-step shifts), then land at
    word index offset>>5.  Adjacent chunks overlap in at most one boundary
    word with disjoint bits, so the scatter-OR is one exact float64
    ``bincount`` sum.  Output matches ``np.packbits`` byte-for-byte."""
    C, W = local.shape
    ends = np.cumsum(chunk_bits, dtype=np.int64)
    total = int(ends[-1]) if C else 0
    offs = ends - chunk_bits
    sh = (offs & 31).astype(np.uint32)[:, None]
    shifted = np.zeros((C, W + 1), np.uint32)
    shifted[:, :W] = local >> sh
    shifted[:, 1:] |= (local << (np.uint32(31) - sh)) << np.uint32(1)
    idx = (offs >> 5)[:, None] + np.arange(W + 1, dtype=np.int64)
    nwords = (total + 31) // 32
    out = np.bincount(idx.ravel(), weights=shifted.ravel().astype(np.float64),
                      minlength=nwords + 1)[:nwords]
    # disjoint bits per word => every float64 sum is exact and fits in u32
    stream = out.astype(np.int64).astype(np.uint32).astype(">u4").tobytes()
    return stream[: (total + 7) // 8], total


# ---------------------------------------------------------------------------
# Vectorized chunk decode machinery
# ---------------------------------------------------------------------------


def _sliding_words(stream: bytes, tail_pad: int = 16) -> tuple[np.ndarray, np.ndarray]:
    """(words, bytes) where words[i] holds stream[i:i+8] big-endian in uint64.

    Built once per decode so the per-step window gather is a single indexed
    load instead of eight.  ``tail_pad`` extra zero bytes keep gathers in
    bounds — decode_chunked sizes it so finished lanes can overrun the
    stream end harmlessly instead of clamping positions every step."""
    raw = np.frombuffer(stream, np.uint8)
    padded = np.zeros(raw.size + tail_pad, np.uint64)
    padded[: raw.size] = raw
    words = np.zeros(raw.size + tail_pad - 7, np.uint64)
    for j in range(8):
        words = (words << np.uint64(8)) | padded[j : j + words.size]
    return words, padded


def _gather_window(words: np.ndarray, padded: np.ndarray, p: np.ndarray) -> np.ndarray:
    """64-bit MSB-aligned window starting at bit position p (vectorized)."""
    byte = p >> np.uint64(3)
    sh = p & np.uint64(7)
    # sh == 0 is safe: x >> 8 on the uint64-widened byte is 0, not UB
    return (words[byte] << sh) | (padded[byte + 8] >> (np.uint64(8) - sh))


class _Tables(NamedTuple):
    """Canonical decode tables (per codec, built lazily)."""

    max_len: int
    k: int  # single-symbol LUT width in bits
    first_code: np.ndarray  # per-length canonical decode bases (bit walk)
    first_idx: np.ndarray
    count_at: np.ndarray
    order: np.ndarray  # symbol ids in canonical order
    lut: np.ndarray  # single-symbol LUT: (sym+1)<<8 | len, 0 = escape
    cw_left: np.ndarray  # left-aligned canonical codewords (monotone)
    L_sorted: np.ndarray  # code lengths in canonical order


class _MultiTables(NamedTuple):
    tables: _Tables
    mlut: np.ndarray  # multi-symbol probe LUT (see _multi_lut)
    B: int  # bits per symbol id slot
    S: int  # id slots per probe entry


def _resolve_long(w: np.ndarray, tables: _Tables) -> tuple[np.ndarray, np.ndarray]:
    """Escape path: windows whose code is longer than the LUT width.

    A complete prefix code partitions the 64-bit window space into intervals
    that start at the left-aligned codewords, so one searchsorted resolves
    any window regardless of code length."""
    i = np.searchsorted(tables.cw_left, w, side="right") - 1
    return tables.order[i], tables.L_sorted[i].astype(np.uint64)


def _id_shift0(B: int) -> int:
    """Bit offset of the first symbol id in a packed probe entry.

    Entries are byte-aligned so symbol expansion is a plain byte-view
    extraction: byte 0 = count, byte 1 = consumed bits, ids from byte 2
    (byte 4 for B=32 so the id stays dtype-aligned)."""
    return 32 if B == 32 else 16


def _multi_lut(lut1: np.ndarray, k: int, B: int, S: int) -> np.ndarray:
    """Multi-symbol LUT: entry packs count (byte 0), consumed bits (byte 1)
    and up to S symbol ids (B-bit slots from ``_id_shift0``), greedily
    covering every complete code in the k-bit window.

    ``lut1`` is the single-symbol table ((sym+1)<<8|len, 0 = escape).  An
    entry of 0 means even the first code overflows the window (escape)."""
    size = 1 << k
    W = np.arange(size, dtype=np.uint64)
    kmask = np.uint64(size - 1)
    consumed = np.zeros(size, np.uint64)
    count = np.zeros(size, np.uint64)
    acc = np.zeros(size, np.uint64)
    active = np.ones(size, bool)
    base = _id_shift0(B)
    for j in range(S):
        sub = (W << consumed) & kmask
        e1 = lut1[sub]
        ln = e1 & np.uint64(0xFF)
        ok = active & (e1 != 0) & (consumed + ln <= k)
        if not ok.any():
            break
        sym = (e1[ok] >> np.uint64(8)) - np.uint64(1)
        acc[ok] |= sym << np.uint64(base + j * B)
        consumed[ok] += ln[ok]
        count[ok] += np.uint64(1)
        active = ok
    return acc | (consumed << np.uint64(8)) | count


def _decode_lanes(words, padded, bit_pos, targets, out2d, mtables) -> int:
    """Lockstep decode: every lane (= chunk) runs one LUT probe per step.

    A probe decodes *all* complete codes inside its k-bit window (up to S,
    packed by ``_multi_lut``), so skewed streams advance several symbols per
    step.  ``out2d`` ([chunk_size, n_lanes] — step-major so the per-step
    store is contiguous) receives the raw packed entries; the caller expands
    them to symbols in one vectorized pass.  Finished lanes keep probing
    harmlessly into the zero tail pad — no per-lane bookkeeping in the hot
    loop.  Returns the number of steps taken."""
    tables, mlut = mtables.tables, mtables.mlut
    shift_k = np.uint64(64 - tables.k)
    pos = bit_pos.astype(np.uint64)
    cur = np.zeros(pos.size, np.uint64)
    targets = targets.astype(np.uint64)
    spill = tables.max_len > 56  # legacy-crafted deep tables need the 9th byte
    it = 0
    while not (cur >= targets).all():
        if it >= out2d.shape[0]:  # every probe yields >= 1 symbol
            raise ValueError("corrupt Huffman stream: chunk did not terminate")
        p = pos  # finished lanes overrun into the zero tail pad harmlessly
        if spill:
            w = _gather_window(words, padded, p)
        else:
            w = words[p >> np.uint64(3)] << (p & np.uint64(7))
        e = mlut[w >> shift_k]
        if not e.all():  # 0 entries = first code longer than the LUT width
            mi = np.flatnonzero(e == 0)
            s2, l2 = _resolve_long(w[mi], tables)
            e[mi] = ((s2.astype(np.uint64) << np.uint64(_id_shift0(mtables.B)))
                     | (l2 << np.uint64(8)) | np.uint64(1))
        out2d[it] = e
        pos = p + ((e >> np.uint64(8)) & np.uint64(0xFF))
        cur += e & np.uint64(0xFF)
        it += 1
    return it


def _expand_entries(used, targets, n_symbols, B, S) -> np.ndarray:
    """Unpack [n_lanes, n_steps] probe entries into the flat symbol-id stream.

    Each entry carries up to S byte-aligned symbol ids.  Because every lane
    owns a contiguous output region and probes emit ids in stream order, a
    single boolean extraction over the byte-view id slots in row-major
    order IS the symbol stream — no shifts, no scatter.  Overshoot ids
    (probes that crossed a chunk boundary) are dropped by the target
    clamp."""
    C, niter = used.shape
    cnts = (used & np.uint64(0xFF)).astype(np.int32)  # byteorder-safe
    excl = np.cumsum(cnts, axis=1, dtype=np.int32) - cnts
    take_n = np.minimum(cnts, np.maximum(targets[:, None].astype(np.int32) - excl, 0))
    if int(take_n.sum()) != n_symbols:
        raise ValueError("corrupt Huffman stream: symbol count mismatch")
    sel = np.arange(S) < take_n[..., None]
    if sys.byteorder == "little":
        off = _id_shift0(B) // 8
        if B == 8:
            ids = used.view(np.uint8).reshape(C, niter, 8)[:, :, off : off + S]
        elif B == 16:
            ids = used.view(np.uint16).reshape(C, niter, 4)[:, :, off // 2 : off // 2 + S]
        else:
            ids = used.view(np.uint32).reshape(C, niter, 2)[:, :, off // 4 : off // 4 + S]
    else:  # pragma: no cover — big-endian hosts take the shift path
        mask = np.uint64((1 << B) - 1)
        ids = np.stack([(used >> np.uint64(_id_shift0(B) + j * B)) & mask
                        for j in range(S)], axis=-1)
    return ids[sel].astype(np.int64)


# ---------------------------------------------------------------------------
# Codec
# ---------------------------------------------------------------------------


@dataclass
class HuffmanCodec:
    """Canonical Huffman over a dense alphabet.

    ``fit`` counts symbol frequencies through the ``symbol_hist`` accelerator
    op (dense-span alphabets; host bincount / np.unique otherwise), so the
    full volume never goes through a host sort."""

    alphabet: np.ndarray  # original symbol values, sorted
    lengths: np.ndarray
    codes: np.ndarray

    @staticmethod
    def fit(symbols: np.ndarray, *, use_accel: bool | None = None) -> "HuffmanCodec":
        flat = np.ascontiguousarray(symbols).ravel()
        if flat.size == 0:
            empty = np.zeros(0, np.int64)
            return HuffmanCodec(flat[:0].copy(), empty, empty.astype(np.uint64))
        dense_ok = np.issubdtype(flat.dtype, np.integer)
        if dense_ok:
            lo, hi = int(flat.min()), int(flat.max())
            span = hi - lo + 1
            dense_ok = span <= _DENSE_SPAN
        if dense_ok:
            accel = use_accel if use_accel is not None else _accel_default()
            shifted = flat.astype(np.int64) - lo
            if accel and span <= _ACCEL_SPAN:
                counts_full = _accel_hist(flat, lo, span)
            else:
                counts_full = np.bincount(shifted, minlength=span)
            nz = np.flatnonzero(counts_full)
            alphabet = (nz + lo).astype(flat.dtype)
            counts = counts_full[nz]
            rank = np.full(span, -1, np.int64)
            rank[nz] = np.arange(nz.size)
            inv = rank[shifted]
        else:
            alphabet, inv, counts = np.unique(flat, return_inverse=True, return_counts=True)
        lengths = _limited_code_lengths(counts)
        codec = HuffmanCodec(alphabet, lengths, _canonical_codes(lengths))
        codec._inv = inv  # cache the remap for the immediate encode
        return codec

    # -- encode (vectorized) ------------------------------------------------
    def _encode_bits(self, symbols: np.ndarray) -> tuple[np.ndarray, np.ndarray, int]:
        """Pack the code stream; returns (packed bytes, per-symbol cumulative
        bit ends, total bit count)."""
        flat = np.ascontiguousarray(symbols).ravel()
        # the fit-time remap is one-shot: it describes the fitted array, and a
        # size match alone can't prove `symbols` is that array
        inv = self.__dict__.pop("_inv", None)
        if inv is None or inv.size != flat.size:
            inv = np.searchsorted(self.alphabet, flat)
        lens = self.lengths[inv].astype(np.int64)
        cws = self.codes[inv]
        total = int(lens.sum())
        ends = np.cumsum(lens)
        starts = ends - lens
        # bit i belongs to symbol searchsorted(ends, i, 'right')
        bit_idx = np.arange(total, dtype=np.int64)
        sym_of_bit = np.searchsorted(ends, bit_idx, side="right")
        pos_in_code = bit_idx - starts[sym_of_bit]
        shift = (lens[sym_of_bit] - 1 - pos_in_code).astype(np.uint64)
        bits = ((cws[sym_of_bit] >> shift) & np.uint64(1)).astype(np.uint8)
        return np.packbits(bits), ends, total

    def encode(self, symbols: np.ndarray) -> bytes:
        packed, _, total = self._encode_bits(symbols)
        return struct.pack("<Q", total) + packed.tobytes()

    # -- decode tables -------------------------------------------------------
    def _decode_tables(self):
        cached = getattr(self, "_tables", None)
        if cached is not None:
            return cached
        n = len(self.lengths)
        max_len = int(self.lengths.max()) if n else 0
        k = min(max_len, _LUT_BITS)
        order = np.lexsort((np.arange(n), self.lengths))
        count_at = np.bincount(self.lengths.astype(np.int64), minlength=max_len + 2)
        first_code = np.zeros(max_len + 2, np.int64)
        first_idx = np.zeros(max_len + 2, np.int64)
        code = idx = 0
        for L in range(1, max_len + 1):
            first_code[L] = code
            first_idx[L] = idx
            code = (code + count_at[L]) << 1
            idx += count_at[L]
        # primary LUT: every k-bit window -> (symbol+1)<<8 | code_len packed in
        # one uint64 (single gather per decode step); canonical codes of
        # length <= k tile a contiguous prefix, the rest escapes (entry 0)
        lut = np.zeros(1 << k, np.uint64)
        L_sorted = self.lengths[order].astype(np.int64)
        if n:
            short = L_sorted <= k  # prefix of the canonical order
            widths = np.left_shift(1, k - L_sorted[short])
            packed = ((order[short] + 1) << 8) | L_sorted[short]
            lut[: int(widths.sum())] = np.repeat(packed, widths).astype(np.uint64)
        # left-aligned canonical codewords (monotone): escape resolution is
        # one searchsorted over them, whatever the code length
        cw_left = self.codes[order] << (64 - L_sorted).astype(np.uint64)
        tables = _Tables(max_len, k, first_code, first_idx, count_at, order,
                         lut, cw_left, L_sorted)
        self._tables = tables
        return tables

    def _multi_tables(self) -> _MultiTables:
        cached = getattr(self, "_mtables", None)
        if cached is not None:
            return cached
        tables = self._decode_tables()
        n = len(self.alphabet)
        B = 8 if n <= 256 else (16 if n <= 65536 else 32)
        S = (64 - _id_shift0(B)) // B  # 6 / 3 / 1 ids per probe entry
        mtables = _MultiTables(tables, _multi_lut(tables.lut, tables.k, B, S), B, S)
        self._mtables = mtables
        return mtables

    # -- decode (seed reference: per-symbol bit walk) -------------------------
    def decode_bitwalk(self, blob: bytes, n_symbols: int) -> np.ndarray:
        """Seed per-symbol decode, kept as the correctness reference and as
        the benchmark baseline for the vectorized path."""
        if n_symbols == 0:
            return self.alphabet[:0].copy()
        (total,) = struct.unpack_from("<Q", blob, 0)
        bits = np.unpackbits(np.frombuffer(blob, np.uint8, offset=8))[:total]
        t = self._decode_tables()
        sorted_syms = t.order
        out = np.empty(n_symbols, self.alphabet.dtype)
        pos = 0
        bits_list = bits.tolist()
        fl_code = t.first_code.tolist()
        fl_idx = t.first_idx.tolist()
        cnt = t.count_at.tolist()
        for i in range(n_symbols):
            code = 0
            L = 0
            while True:
                code = (code << 1) | bits_list[pos]
                pos += 1
                L += 1
                if cnt[L] and code - fl_code[L] < cnt[L]:
                    out[i] = self.alphabet[sorted_syms[fl_idx[L] + code - fl_code[L]]]
                    break
        return out

    decode = decode_bitwalk  # legacy API (hf/hz blobs, small streams)

    # -- decode (chunked, vectorized, parallel) -------------------------------
    def decode_chunked(
        self,
        stream: bytes,
        n_symbols: int,
        chunk_size: int,
        chunk_bits: np.ndarray,
        *,
        total_bits: int | None = None,
        workers: int | None = None,
        chunk_range: tuple[int, int] | None = None,
    ) -> np.ndarray:
        """Decode a chunked stream: each chunk's bit offset comes from the
        chunk table, so lanes decode independently and in parallel.

        ``chunk_range=(c0, c1)`` decodes only chunks ``[c0, c1)`` — the
        random-access primitive behind :func:`decode_codes_range`: the
        chunk table gives every chunk's bit offset, so a sub-range costs
        O(symbols in range), not O(stream)."""
        if n_symbols == 0:
            return self.alphabet[:0].copy()
        if self.alphabet.size == 0:
            raise ValueError("empty codec cannot decode a nonempty stream")
        mtables = self._multi_tables()
        if mtables.tables.max_len > 63:  # a 64-bit probe window can't hold the code
            raise ValueError("chunked decode supports code lengths <= 63")
        chunk_bits = np.asarray(chunk_bits, np.int64)
        C = chunk_bits.size
        if C != -(-n_symbols // chunk_size):
            raise ValueError("chunk table size inconsistent with symbol count")
        ends = np.cumsum(chunk_bits)
        if total_bits is not None and int(ends[-1]) != total_bits:
            raise ValueError("chunk table inconsistent with stream length")
        offsets = ends - chunk_bits
        counts = np.full(C, chunk_size, np.int64)
        counts[-1] = n_symbols - chunk_size * (C - 1)
        total = int(ends[-1])
        if chunk_range is not None:
            c0, c1 = chunk_range
            if not 0 <= c0 < c1 <= C:
                raise ValueError(f"chunk range {chunk_range} outside [0, {C})")
            offsets, counts = offsets[c0:c1], counts[c0:c1]
            n_symbols = int(counts.sum())
            C = c1 - c0
        if len(stream) < (total + 7) // 8:
            raise ValueError("truncated Huffman stream")
        # tail pad absorbs finished lanes overrunning the stream end (<= 63
        # bits per step for at most chunk_size steps) without clamping
        words, padded = _sliding_words(stream, tail_pad=8 * chunk_size + 16)
        if workers is not None:
            w = max(1, min(workers, C))
        else:
            # threads only pay off past GIL contention: need cores and lanes
            cores = os.cpu_count() or 1
            w = max(1, min(cores, 8, C // 256)) if cores > 2 else 1
        # step-major probe log; threaded runs zero it so a worker stopping
        # early leaves count=0 slots, single-lane runs fill every used row
        out2d = (np.empty if w <= 1 else np.zeros)((chunk_size, C), np.uint64)
        if w <= 1:
            niter = _decode_lanes(words, padded, offsets, counts, out2d, mtables)
        else:
            bounds = np.linspace(0, C, w + 1).astype(int)
            with ThreadPoolExecutor(w) as ex:
                futs = [
                    ex.submit(_decode_lanes, words, padded, offsets[a:b], counts[a:b],
                              out2d[:, a:b], mtables)
                    for a, b in zip(bounds[:-1], bounds[1:])
                    if b > a
                ]
                niter = max(f.result() for f in futs)
        used = np.ascontiguousarray(out2d[:niter].T)  # lane-major for expansion
        return self.alphabet[_expand_entries(used, counts, n_symbols,
                                             mtables.B, mtables.S)]

    # -- device (Pallas) pack / decode ---------------------------------------
    def _device_eligible(self) -> bool:
        """hc/hZ device kernels work in 32-bit windows: every code length must
        fit (true for any freshly fitted codec by encoder policy; crafted
        legacy tables can exceed it and stay on host)."""
        n = len(self.alphabet)
        return 0 < n < (1 << 31) and int(self.lengths.max()) <= 32

    def _device_tables(self):
        """Decode-probe tables as int32 device rows (packed uint64 entries
        have no device analogue).  Cached; ``None`` when the codec is
        device-ineligible or its alphabet is too wide for the probe's
        per-step escape count (``_PROBE_ALPHABET``).

        ``lut`` is [8, 2**_LUT_BITS]: count, consumed bits, then up to six
        symbol ids per window.  A LUT narrower than ``_LUT_BITS`` (every code
        shorter) repeats each entry over the ignored low bits, so one probe
        width serves every codec.  ``cw_map``/``order``/``len_sorted`` are
        [1, N] rows padded to a power of two (at least 128): ``cw_map`` with
        INT32_MAX, the others with their last entry, so a count of codewords
        at or below any window indexes a real entry."""
        cached = getattr(self, "_dev_tables", None)
        if cached is not None:
            return cached or None
        n = len(self.alphabet)
        if not self._device_eligible() or n > _PROBE_ALPHABET:
            self._dev_tables = False
            return None
        from repro.kernels.huffman_decode import LUT_ROWS

        mt = self._multi_tables()
        t = mt.tables
        base = _id_shift0(mt.B)
        mask = np.uint64((1 << mt.B) - 1)
        mlut = np.repeat(mt.mlut, 1 << (_LUT_BITS - t.k))
        lut = np.zeros((LUT_ROWS, mlut.size), np.int32)
        lut[0] = mlut & np.uint64(0xFF)
        lut[1] = (mlut >> np.uint64(8)) & np.uint64(0xFF)
        for j in range(mt.S):
            lut[2 + j] = (mlut >> np.uint64(base + j * mt.B)) & mask
        # top-32 truncation is faithful: codes occupy the top <= 32 bits, so
        # interval boundaries only depend on the window's top 32 bits, and
        # the XOR maps unsigned order onto int32 for the kernel's compares
        cw32 = (t.cw_left >> np.uint64(32)).astype(np.uint32)
        width = max(128, 1 << (n - 1).bit_length())

        def row(a, fill):
            out = np.full((1, width), fill, np.int32)
            out[0, :n] = a
            return out

        dev = {
            "lut": lut,
            "cw_map": row((cw32 ^ np.uint32(0x80000000)).view(np.int32),
                          np.iinfo(np.int32).max),
            "order": row(t.order, t.order[-1]),
            "len_sorted": row(t.L_sorted, t.L_sorted[-1]),
            "n_ids": mt.S,
        }
        self._dev_tables = dev
        return dev

    def _device_pack(self, flat: np.ndarray, chunk_size: int, *,
                     interpret: bool | None = None):
        """Device encode-pack: returns (stream bytes, chunk_bits int64, total)
        bit-identical to ``_encode_bits`` + the encode-side chunk table, or
        ``None`` when ineligible (caller falls back to the host pack)."""
        n = flat.size
        if n == 0 or not self._device_eligible() or chunk_size * 32 >= 1 << 31:
            return None
        import jax.numpy as jnp

        from repro.kernels import ops

        with obs.span("gwlz.entropy.pack"):
            # same one-shot fit-time remap contract as _encode_bits
            inv = self.__dict__.pop("_inv", None)
            if inv is None or inv.size != n:
                inv = np.searchsorted(self.alphabet, flat)
            C = -(-n // chunk_size)
            pad = C * chunk_size - n
            lens = self.lengths[inv].astype(np.int32)
            cws = self.codes[inv].astype(np.uint32).view(np.int32)
            if pad:
                lens = np.concatenate([lens, np.zeros(pad, np.int32)])
                cws = np.concatenate([cws, np.zeros(pad, np.int32)])
            words, chunk_bits = ops.huffman_encode_op(
                jnp.asarray(lens.reshape(C, chunk_size)),
                jnp.asarray(cws.reshape(C, chunk_size)),
                use_pallas=True, interpret=interpret)
            words = np.asarray(words).view(np.uint32)
            chunk_bits = np.asarray(chunk_bits).astype(np.int64)
        with obs.span("gwlz.entropy.splice", words.nbytes):
            stream, total = _splice_chunks(words, chunk_bits)
        return stream, chunk_bits, total

    def decode_chunked_device(
        self,
        stream: bytes,
        n_symbols: int,
        chunk_size: int,
        chunk_bits: np.ndarray,
        *,
        total_bits: int | None = None,
        chunk_range: tuple[int, int] | None = None,
        interpret: bool | None = None,
    ) -> np.ndarray | None:
        """Same contract as :meth:`decode_chunked`, running the lockstep
        multi-symbol LUT probe as a Pallas kernel.  Returns ``None`` when the
        codec or stream is device-ineligible (caller falls back to host)."""
        if n_symbols == 0:
            return self.alphabet[:0].copy()
        if self.alphabet.size == 0:
            raise ValueError("empty codec cannot decode a nonempty stream")
        chunk_bits = np.asarray(chunk_bits, np.int64)
        C = chunk_bits.size
        if C != -(-n_symbols // chunk_size):
            raise ValueError("chunk table size inconsistent with symbol count")
        ends = np.cumsum(chunk_bits)
        total = int(ends[-1])
        if total_bits is not None and total != total_bits:
            raise ValueError("chunk table inconsistent with stream length")
        dev = self._device_tables()
        # int32 bit positions bound the eligible stream/chunk size
        if dev is None or total >= 1 << 31 or chunk_size * 32 >= 1 << 31:
            return None
        if len(stream) < (total + 7) // 8:
            raise ValueError("truncated Huffman stream")
        offsets = (ends - chunk_bits).astype(np.int32)
        counts = np.full(C, chunk_size, np.int32)
        counts[-1] = n_symbols - chunk_size * (C - 1)
        if chunk_range is not None:
            c0, c1 = chunk_range
            if not 0 <= c0 < c1 <= C:
                raise ValueError(f"chunk range {chunk_range} outside [0, {C})")
            offsets, counts = offsets[c0:c1], counts[c0:c1]
            chunk_bits = chunk_bits[c0:c1]
            n_symbols = int(counts.sum())
        import jax
        import jax.numpy as jnp

        from repro.kernels import ops

        with obs.span("gwlz.entropy.probe", len(stream)):
            # one word window per chunk, from the word holding its first bit
            # to one word past its last (the probe reads word pairs); the
            # width rounds up to whole 128-lane rows, zeros past the stream
            first = offsets >> 5
            starts = offsets & 31
            span = (starts + chunk_bits + 31) >> 5
            W = -(-(int(span.max()) + 1) // 128) * 128
            raw = np.frombuffer(stream, np.uint8)
            padded = np.zeros(
                max(4 * (int(first.max()) + W), raw.size + 3) // 4 * 4, np.uint8)
            padded[: raw.size] = raw
            words = padded.view(">u4").astype(np.uint32).view(np.int32)
            win = words[first[:, None] + np.arange(W)]
            ids, steps = jax.device_get(ops.huffman_decode_op(
                jnp.asarray(win), jnp.asarray(starts[:, None]),
                jnp.asarray(counts[:, None]), jnp.asarray(dev["lut"]),
                jnp.asarray(dev["cw_map"]), jnp.asarray(dev["order"]),
                jnp.asarray(dev["len_sorted"]), chunk_size=chunk_size,
                k=_LUT_BITS, n_ids=dev["n_ids"], use_pallas=True,
                interpret=interpret))
            # only the last selected chunk can be short, so row-major
            # flatten + truncate is exactly the symbol stream
            flat_ids = ids.reshape(-1)[:n_symbols]
        # steps the probe's blocks ran, against the chunk_size each would
        # run without stopping early
        obs.count("gwlz.entropy.probe_steps", int(steps.sum()))
        obs.count("gwlz.entropy.probe_steps_bound", chunk_size * steps.size)
        return self.alphabet[flat_ids]

    # -- serialization --------------------------------------------------------
    def table_bytes(self) -> bytes:
        return (
            struct.pack("<I", len(self.alphabet))
            + self.alphabet.astype(np.int32).tobytes()
            + self.lengths.astype(np.uint8).tobytes()
        )

    @staticmethod
    def from_table(blob: bytes) -> tuple["HuffmanCodec", int]:
        (n,) = struct.unpack_from("<I", blob, 0)
        off = 4
        alphabet = np.frombuffer(blob, np.int32, n, offset=off).copy()
        off += 4 * n
        lengths = np.frombuffer(blob, np.uint8, n, offset=off).astype(np.int64)
        off += n
        return HuffmanCodec(alphabet, lengths, _canonical_codes(lengths)), off


# ---------------------------------------------------------------------------
# Backends
# ---------------------------------------------------------------------------


def encode_codes(
    codes: np.ndarray,
    backend: str = "huffman+zlib",
    *,
    chunk_size: int | None = None,
    use_accel: bool | None = None,
    use_pallas: bool | None = None,
) -> bytes:
    """Entropy-encode an int32 code tensor; returns a self-describing blob.

    Huffman backends emit the chunked ``hc``/``hcz`` format (see
    docs/ENTROPY_FORMAT.md); ``encode_codes_legacy`` still produces the seed
    ``hf``/``hz`` blobs for compatibility testing.

    ``use_pallas`` routes the bit-stream pack through the device encode
    kernel (``kernels/huffman_encode.py``): ``None`` auto-detects (device
    path on TPU only), ``True`` forces it (interpret mode off-TPU), ``False``
    keeps the host pack.  Bytes are bit-identical either way; a device-
    ineligible codec packs on the host and is counted as ``pack_fallback``
    in :func:`entropy_path_stats`."""
    flat = np.ascontiguousarray(codes, np.int32).ravel()
    if backend == "zlib":
        # int32 -> int16 when it fits (usual case): halves the zlib input
        if flat.size and abs(flat).max(initial=0) < 2**15:
            payload = _deflate(flat.astype(np.int16).tobytes())
            tag = b"z2"
        else:
            payload = _deflate(flat.tobytes())
            tag = b"z4"
        return _MAGIC + tag + struct.pack("<Q", flat.size) + payload
    if backend in ("huffman", "huffman+zlib"):
        with obs.span("gwlz.entropy.fit"):
            codec = HuffmanCodec.fit(flat, use_accel=use_accel)
        cs = int(chunk_size) if chunk_size else DEFAULT_CHUNK
        n = flat.size
        n_chunks = -(-n // cs) if n else 0
        dev = _accel_default() if use_pallas is None else use_pallas
        got = codec._device_pack(flat, cs) if dev and n_chunks else None
        _count_lane("pack", bool(dev and n_chunks), got is not None)
        if got is not None:
            stream, chunk_bits, total = got
        else:
            packed, ends, total = codec._encode_bits(flat)
            if n_chunks:
                bnd = np.minimum(np.arange(1, n_chunks + 1, dtype=np.int64) * cs, n) - 1
                chunk_bits = np.diff(np.concatenate([[0], ends[bnd]]))
            else:
                chunk_bits = np.zeros(0, np.int64)
            stream = packed.tobytes()
        # chunk table + bit stream travel together so zlib sees both
        payload = chunk_bits.astype(_chunk_bits_dtype(cs)).tobytes() + stream
        if backend == "huffman+zlib":
            payload = _deflate(payload)
            tag = b"hZ"
        else:
            tag = b"hc"
        table = codec.table_bytes()
        return (
            _MAGIC
            + tag
            + struct.pack("<QIII", n, cs, n_chunks, len(table))
            + table
            + struct.pack("<Q", total)
            + payload
        )
    raise ValueError(f"unknown entropy backend {backend!r}")


def _deflate(raw: bytes) -> bytes:
    """zlib level 6, as every deflating backend writes it, with the bytes in
    and out counted (``gwlz.entropy.deflate``/``gwlz.entropy.deflate_out``)."""
    with obs.span("gwlz.entropy.deflate", len(raw)):
        out = zlib.compress(raw, 6)
    obs.count("gwlz.entropy.deflate_out", len(out))
    return out


def _inflate(blob: bytes) -> bytes:
    with obs.span("gwlz.entropy.inflate", len(blob)):
        return zlib.decompress(blob)


def encode_codes_legacy(codes: np.ndarray, backend: str = "huffman+zlib") -> bytes:
    """Seed (pre-chunking) encoder: emits ``hf``/``hz`` blobs.  Kept so tests
    and benchmarks can exercise the backward-compat decode path."""
    flat = np.ascontiguousarray(codes, np.int32).ravel()
    if backend not in ("huffman", "huffman+zlib"):
        raise ValueError(f"legacy encoder only supports huffman backends, got {backend!r}")
    codec = HuffmanCodec.fit(flat, use_accel=False)
    stream = codec.encode(flat)
    if backend == "huffman+zlib":
        stream = zlib.compress(stream, 6)
        tag = b"hz"
    else:
        tag = b"hf"
    table = codec.table_bytes()
    return _MAGIC + tag + struct.pack("<QI", flat.size, len(table)) + table + stream


_CODEC_CACHE: dict[bytes, HuffmanCodec] = {}


def _cached_codec(table: bytes) -> HuffmanCodec:
    """Decode-side codec cache: repeated decodes of the same artifact (the
    steady-state serving pattern) skip canonical-table and LUT rebuilds."""
    codec = _CODEC_CACHE.get(table)
    if codec is None:
        codec, _ = HuffmanCodec.from_table(table)
        if len(_CODEC_CACHE) >= 16:
            _CODEC_CACHE.pop(next(iter(_CODEC_CACHE)))
        _CODEC_CACHE[table] = codec
    return codec


def decode_codes(blob: bytes, shape: tuple[int, ...], *, workers: int | None = None,
                 use_pallas: bool | None = None) -> np.ndarray:
    """Decode an entropy blob back to int32 codes.

    ``use_pallas`` routes chunked hc/hZ streams through the device decode
    kernel (``kernels/huffman_decode.py``): ``None`` auto-detects (TPU only),
    ``True`` forces it (interpret mode off-TPU), ``False`` keeps the host
    walk.  Device-ineligible streams decode on the host, counted as
    ``probe_fallback`` in :func:`entropy_path_stats`."""
    assert blob[:4] == _MAGIC, "bad entropy blob"
    tag = blob[4:6]
    if tag in (b"z2", b"z4"):
        (n,) = struct.unpack_from("<Q", blob, 6)
        raw = _inflate(blob[14:])
        dt = np.int16 if tag == b"z2" else np.int32
        return np.frombuffer(raw, dt).astype(np.int32).reshape(shape)
    if tag in (b"hc", b"hZ"):
        n, cs, n_chunks, tlen = struct.unpack_from("<QIII", blob, 6)
        off = 6 + 20
        codec = _cached_codec(blob[off : off + tlen])
        off += tlen
        (total,) = struct.unpack_from("<Q", blob, off)
        off += 8
        payload = blob[off:]
        if tag == b"hZ":
            payload = _inflate(payload)
        cb_dtype = _chunk_bits_dtype(cs)
        chunk_bits = np.frombuffer(payload, cb_dtype, n_chunks)
        stream = payload[np.dtype(cb_dtype).itemsize * n_chunks :]
        dev = _accel_default() if use_pallas is None else use_pallas
        out = None
        if dev:
            out = codec.decode_chunked_device(stream, n, cs, chunk_bits,
                                              total_bits=total)
        _count_lane("probe", bool(dev), out is not None)
        if out is None:
            out = codec.decode_chunked(stream, n, cs, chunk_bits,
                                       total_bits=total, workers=workers)
        return out.astype(np.int32).reshape(shape)
    if tag in (b"hf", b"hz"):
        n, tlen = struct.unpack_from("<QI", blob, 6)
        off = 6 + 12
        codec, used = HuffmanCodec.from_table(blob[off : off + tlen])
        stream = blob[off + tlen :]
        if tag == b"hz":
            stream = zlib.decompress(stream)
        return codec.decode_bitwalk(stream, n).astype(np.int32).reshape(shape)
    raise ValueError(f"unknown entropy tag {tag!r}")


def decode_codes_range(blob: bytes, lo: int, hi: int, *, workers: int | None = None,
                       use_pallas: bool | None = None) -> np.ndarray:
    """Decode symbols ``[lo, hi)`` of an entropy blob as a flat int32 array.

    On the chunked ``hc``/``hZ`` formats this is a true partial read: only
    the chunks covering the range run the table-driven walk (the per-chunk
    bit table localizes them), so the cost is O(hi - lo) symbols — the
    sub-lane primitive for plane- or pencil-granular reads inside one tile
    lane.  ``hZ`` still pays one zlib pass over the lane (zlib has no
    random access); the legacy / zlib formats fall back to full decode +
    slice.  Equals ``decode_codes(blob, (n,))[lo:hi]`` bit-for-bit."""
    assert blob[:4] == _MAGIC, "bad entropy blob"
    tag = blob[4:6]
    if tag in (b"hc", b"hZ"):
        n, cs, n_chunks, tlen = struct.unpack_from("<QIII", blob, 6)
        if not 0 <= lo <= hi <= n:
            raise ValueError(f"symbol range [{lo}, {hi}) outside [0, {n})")
        if lo == hi:
            return np.zeros(0, np.int32)
        off = 6 + 20
        codec = _cached_codec(blob[off : off + tlen])
        off += tlen
        (total,) = struct.unpack_from("<Q", blob, off)
        off += 8
        payload = blob[off:]
        if tag == b"hZ":
            payload = _inflate(payload)
        cb_dtype = _chunk_bits_dtype(cs)
        chunk_bits = np.frombuffer(payload, cb_dtype, n_chunks)
        stream = payload[np.dtype(cb_dtype).itemsize * n_chunks :]
        c0, c1 = lo // cs, -(-hi // cs)
        dev = _accel_default() if use_pallas is None else use_pallas
        out = None
        if dev:
            out = codec.decode_chunked_device(stream, n, cs, chunk_bits,
                                              total_bits=total,
                                              chunk_range=(c0, c1))
        _count_lane("probe", bool(dev), out is not None)
        if out is None:
            out = codec.decode_chunked(stream, n, cs, chunk_bits, total_bits=total,
                                       workers=workers, chunk_range=(c0, c1))
        return out.astype(np.int32)[lo - c0 * cs : hi - c0 * cs]
    flat = decode_codes(blob, (-1,), workers=workers).ravel()
    if not 0 <= lo <= hi <= flat.size:
        raise ValueError(f"symbol range [{lo}, {hi}) outside [0, {flat.size})")
    return flat[lo:hi]
