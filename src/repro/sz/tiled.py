"""Tile-based compression engine with random-access decode (``GWTC``).

The monolithic SZ path materializes one volume end to end; this engine splits
the (padded) volume into a fixed tile grid and makes every tile a fully
independent compression domain:

* the per-tile prediction transform is *pluggable*: the tile batch dispatches
  through the predictor registry (``repro.sz.predictor.get_predictor``) —
  ``"lorenzo"`` (prequant + batched integer Lorenzo) or ``"interp"`` (SZ3-
  style multi-level interpolation, vmapped per tile).  Batched passes fan
  across the device mesh via ``repro.launch.sharding.map_tiles``,
* each tile entropy-encodes as an independent lane on the chunked ``hc``/
  ``hZ`` codec (docs/ENTROPY_FORMAT.md), so lanes decode independently and
  in parallel,
* the ``GWTC`` container stores a per-tile offset index, so
  :func:`decompress_region` entropy-decodes *only* the tiles intersecting
  the requested ROI — partial reads never pay for the whole blob.

Every predictor's batched decode is elementwise-exact in the batch axis
(each tile is an independent prediction domain), so region decode is
bit-identical to the full decode's crop whichever predictor produced the
artifact.  Container layout (``GWTC`` v2; v1 blobs still decode) is
specified in docs/TILED_FORMAT.md; the layered stack is described in
docs/ARCHITECTURE.md.
"""
from __future__ import annotations

import os
import struct
import threading
import zlib
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.errors import CorruptContainerError, CorruptLaneError
from repro.sz import artifact as A
from repro.sz.predictor import ORDER_IDS, ORDER_NAMES, PRED_IDS, PRED_NAMES, get_predictor
from repro.sz.quantizer import resolve_eb

_MAGIC = A.GWTC_MAGIC
_VERSION = A.GWTC_VERSION
# v1: magic, version, ndim, backend, pad, eb bits, n_tiles
_HDR_V1 = struct.Struct("<4sBBBBQQ")
# v2 adds the predictor layer: magic, version, ndim, backend, predictor,
# order, levels, pad, eb bits, n_tiles
_HDR_V2 = struct.Struct("<4sBBBBBBBQQ")
# v3 keeps the v2 header fields but moves the tile index (and extras) BEHIND
# the lanes so the container can be written append-only by a streaming
# encoder; a fixed-size footer at the end of the blob locates them
# (docs/STREAMING.md).  Layout: header | shape | tile | lanes... | extras |
# index | footer, where the index region is either
#   u64 lens[n_tiles]                                  (legacy, no checksums)
#   u64 lens[n_tiles] | u32 crcs[n_tiles] | u32 meta   (current)
# — distinguished by its byte extent, so pre-checksum v3 blobs keep parsing
# (docs/ROBUSTNESS.md).  ``crcs[i]`` covers lane i's bytes; ``meta`` covers
# header+shape+tile plus the extras blob, so every non-lane byte of the
# container is checksummed too.
_HDR_V3 = _HDR_V2
_FOOTER_V3 = struct.Struct("<QQ")  # (extras offset, index offset)
_BACKENDS = {"zlib": 0, "huffman": 1, "huffman+zlib": 2}
_BACKENDS_INV = {v: k for k, v in _BACKENDS.items()}


def lane_crc(data) -> int:
    """Container lane checksum: CRC-32 (IEEE 802.3, via the stdlib's C
    ``zlib.crc32``).  The format reserves the field for CRC-32C, but no
    Castagnoli implementation ships with the interpreter and this stack
    adds no dependencies — the polynomial choice is recorded in
    docs/ROBUSTNESS.md so a future native-codec swap is a deliberate
    format bump, not an accident."""
    return zlib.crc32(bytes(data)) & 0xFFFFFFFF


def _pack_extras(extras: dict) -> bytes:
    """Extras blob shared by the eager serializer and the streaming writer:
    count u32, then per entry klen u32 | vlen u32 | key | value, sorted."""
    items = sorted(extras.items())
    out = [struct.pack("<I", len(items))]
    for k, v in items:
        kb = k.encode()
        out.append(struct.pack("<II", len(kb), len(v)) + kb + bytes(v))
    return b"".join(out)


def _unpack_extras(blob, off: int) -> dict:
    (n_extras,) = struct.unpack_from("<I", blob, off)
    off += 4
    extras = {}
    for _ in range(n_extras):
        klen, vlen = struct.unpack_from("<II", blob, off)
        off += 8
        k = bytes(blob[off : off + klen]).decode()
        off += klen
        extras[k] = bytes(blob[off : off + vlen])
        off += vlen
    return extras


class LaneStore:
    """Lazy per-lane byte access over one backing buffer.

    Holds (buffer, per-lane offsets/lengths) instead of materialized lane
    copies, so opening an mmap-backed container reads *no* lane bytes until
    a decode asks for them — ``store[i]`` copies exactly lane ``i`` out of
    the buffer (a page-granular read on mmap).  ``release()`` drops the
    buffer reference so the owning mmap can close."""

    __slots__ = ("_buf", "_offs", "_lens")

    def __init__(self, buf, offsets: np.ndarray, lengths: np.ndarray):
        self._buf = buf
        self._offs = np.asarray(offsets, np.int64)
        self._lens = np.asarray(lengths, np.int64)

    def __len__(self) -> int:
        return int(self._lens.size)

    def __getitem__(self, i: int) -> bytes:
        if self._buf is None:
            raise ValueError("lane store is closed (volume was released)")
        o, n = int(self._offs[i]), int(self._lens[i])
        return bytes(self._buf[o : o + n])

    def __iter__(self):
        return (self[i] for i in range(len(self)))

    @property
    def nbytes(self) -> int:
        """Total lane bytes — computed from the index, no lane is read."""
        return int(self._lens.sum())

    def lane_nbytes(self, i: int) -> int:
        return int(self._lens[i])

    def release(self) -> None:
        self._buf = None


def lanes_nbytes(tile_blobs) -> int:
    """Total lane payload bytes without forcing lazy lanes into memory."""
    if isinstance(tile_blobs, LaneStore):
        return tile_blobs.nbytes
    return sum(len(b) for b in tile_blobs)


def _index_nbytes(n_tiles: int) -> int:
    """Byte extent of the checksummed v3 index region the writer emits:
    u64 lens | u32 crcs | u32 meta_crc."""
    return 8 * n_tiles + 4 * n_tiles + 4


def lane_offset(artifact: "TiledCompressed", i: int) -> int:
    """Container-relative byte offset of lane ``i`` — error-path helper so
    :class:`CorruptLaneError` can point at the damaged region on disk."""
    tb = artifact.tile_blobs
    if isinstance(tb, LaneStore):
        return int(tb._offs[i])
    base = _HDR_V3.size + 16 * len(artifact.shape)
    return base + sum(len(tb[j]) for j in range(i))

# DEPRECATED module-global mirror: how many lanes the last decode touched.
# Kept as a best-effort alias for existing tests/benchmarks — new code should
# read the per-handle ``repro.api.CompressedVolume.stats`` counters
# (tiles_decoded / tiles_total / cache_hits), which are per-volume and not
# clobbered by concurrent decodes of other artifacts.  Written under
# _STATS_LOCK; :func:`decode_lanes` also *returns* the lane count, which is
# the race-free way to consume it.
DECODE_STATS = {"tiles_decoded": 0, "tiles_total": 0}
_STATS_LOCK = threading.Lock()


def _mirror_stats(tiles_decoded: int, tiles_total: int) -> None:
    with _STATS_LOCK:
        DECODE_STATS["tiles_decoded"] = tiles_decoded
        DECODE_STATS["tiles_total"] = tiles_total


# ---------------------------------------------------------------------------
# bucketed dispatch + compile-cache accounting
# ---------------------------------------------------------------------------
#
# Every distinct decode batch size K compiles a fresh XLA executable for the
# float decode programs (interp decode chunks, the GWLZ enhancer's lax.map) —
# under a serving workload with arbitrary ROI lane counts that is an unbounded
# program cache and recompiles on the hot path.  Bucketing pads each batch to
# a small fixed set of widths (powers of two up to DEFAULT_BUCKET_CAP), so a
# bounded set of compiled programs serves every request after warmup.
#
# Padding is bit-safe by the same invariant that makes region == full decode
# exact: no per-tile program mixes tiles (vmap / lax.map over axis 0), so the
# padded rows cannot perturb the real rows — the pad rows are simply cropped
# off the output.  Pad rows repeat row 0, the established idiom from
# predictor._interp_decode_tiles_padded.
#
# DISPATCH_STATS / _PROGRAM_KEYS are process-wide observability for the
# serving layer's /metrics and the load test's "zero recompiles after warmup"
# assertion: a *program* is a distinct (semantic key, bucket width) pair seen
# for the first time; a *dispatch* is one device invocation of such a program.

DEFAULT_BUCKET_CAP = int(os.environ.get("REPRO_DECODE_BUCKET_CAP", 32))

_DISPATCH_LOCK = threading.Lock()
_PROGRAM_KEYS: set = set()
DISPATCH_STATS = {"dispatches": 0, "programs": 0, "padded_tiles": 0,
                  "batch_hist": {}}


def bucket_for(n: int, bucket_cap: int | None = None) -> int:
    """Smallest power-of-two bucket >= n, capped at ``bucket_cap``."""
    cap = DEFAULT_BUCKET_CAP if bucket_cap is None else int(bucket_cap)
    if n <= 0:
        return 0
    b = 1
    while b < n and b < cap:
        b <<= 1
    return min(b, cap)


def bucket_chunks(n: int, bucket_cap: int | None = None) -> list[int]:
    """Split a batch of ``n`` tiles into bucket widths: full-cap chunks plus
    one power-of-two tail bucket (e.g. n=70, cap=32 -> [32, 32, 8]).  A
    non-positive cap disables bucketing ([n] verbatim)."""
    cap = DEFAULT_BUCKET_CAP if bucket_cap is None else int(bucket_cap)
    if cap <= 0 or n <= 0:
        return [n] if n > 0 else []
    out = [cap] * (n // cap)
    rem = n % cap
    if rem:
        out.append(bucket_for(rem, cap))
    return out


def register_program_key(key) -> bool:
    """Record one compiled-program identity in ``dispatch_stats()``; True the
    first time (a compile), False on a warm hit."""
    with _DISPATCH_LOCK:
        fresh = key not in _PROGRAM_KEYS
        if fresh:
            _PROGRAM_KEYS.add(key)
            DISPATCH_STATS["programs"] += 1
        return fresh


def _record_dispatch(key, bucket: int, padded: int) -> None:
    with _DISPATCH_LOCK:
        if key not in _PROGRAM_KEYS:
            _PROGRAM_KEYS.add(key)
            DISPATCH_STATS["programs"] += 1
        DISPATCH_STATS["dispatches"] += 1
        DISPATCH_STATS["padded_tiles"] += padded
        hist = DISPATCH_STATS["batch_hist"]
        hist[bucket] = hist.get(bucket, 0) + 1


def dispatch_stats() -> dict:
    """Snapshot of the process-wide dispatch/compile counters."""
    with _DISPATCH_LOCK:
        out = dict(DISPATCH_STATS)
        out["batch_hist"] = dict(DISPATCH_STATS["batch_hist"])
        return out


def reset_dispatch_stats() -> None:
    """Test/bench hook: zero the counters AND forget seen program keys."""
    with _DISPATCH_LOCK:
        _PROGRAM_KEYS.clear()
        DISPATCH_STATS.update(dispatches=0, programs=0, padded_tiles=0,
                              batch_hist={})


def dispatch_bucketed(fn, tree, n: int, *, key=(), bucket_cap=None):
    """Run ``fn`` (a per-tile batched program) over a [n, ...] pytree through
    bucket-padded fixed-shape invocations.

    ``key`` names the program semantics (predictor, tile, levels, ...); the
    bucket width is appended so each (key, width) pair is one compiled
    executable.  Pad rows repeat row 0 and are cropped from the output —
    bit-safe because no per-tile program mixes batch rows.  ``bucket_cap=0``
    disables bucketing (single unpadded call, still counted)."""
    cap = DEFAULT_BUCKET_CAP if bucket_cap is None else int(bucket_cap)
    if cap <= 0 or n <= 0:
        if n > 0:
            _record_dispatch(tuple(key) + (int(n),), int(n), 0)
        return fn(tree)
    outs = []
    off = 0
    for width in bucket_chunks(n, cap):
        take = min(width, n - off)
        part = jax.tree.map(lambda a: a[off:off + take], tree)
        pad = width - take
        if pad:
            part = jax.tree.map(
                lambda a: jnp.concatenate([a, jnp.repeat(a[:1], pad, axis=0)]),
                part)
        _record_dispatch(tuple(key) + (width,), width, pad)
        outs.append(fn(part)[:take])
        off += take
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs)


# ---------------------------------------------------------------------------
# tile grid geometry
# ---------------------------------------------------------------------------


def normalize_tile(tile, ndim: int) -> tuple[int, ...]:
    if isinstance(tile, int):
        tile = (tile,) * ndim
    tile = tuple(int(t) for t in tile)
    if len(tile) != ndim or any(t < 1 for t in tile):
        raise ValueError(f"tile {tile} invalid for a {ndim}-d volume")
    return tile


def tile_grid(shape: tuple[int, ...], tile: tuple[int, ...]) -> tuple[int, ...]:
    return tuple(-(-d // t) for d, t in zip(shape, tile))


def pad_to_tiles(x: jax.Array, tile: tuple[int, ...]) -> jax.Array:
    pshape = tuple(g * t for g, t in zip(tile_grid(x.shape, tile), tile))
    pads = [(0, p - d) for d, p in zip(x.shape, pshape)]
    return jnp.pad(x, pads, mode="edge")


def split_tiles(xp: jax.Array, tile: tuple[int, ...]) -> jax.Array:
    """[g0*t0, g1*t1, ...] -> [prod(g), t0, t1, ...] in row-major grid order."""
    grid = tuple(d // t for d, t in zip(xp.shape, tile))
    nd = len(tile)
    interleaved = xp.reshape(sum(((g, t) for g, t in zip(grid, tile)), ()))
    perm = tuple(range(0, 2 * nd, 2)) + tuple(range(1, 2 * nd, 2))
    return interleaved.transpose(perm).reshape((-1,) + tile)


def stitch_tiles(tiles: jax.Array, grid: tuple[int, ...]) -> jax.Array:
    """Inverse of :func:`split_tiles`: [prod(g), *tile] -> padded volume."""
    tile = tiles.shape[1:]
    nd = len(tile)
    blocks = tiles.reshape(grid + tile)
    perm = sum(((d, nd + d) for d in range(nd)), ())
    return blocks.transpose(perm).reshape(tuple(g * t for g, t in zip(grid, tile)))


# ---------------------------------------------------------------------------
# container
# ---------------------------------------------------------------------------


@dataclass
class TiledCompressed:
    """Self-describing tiled artifact (``GWTC`` v2, docs/TILED_FORMAT.md).

    ``tile_blobs[i]`` is an independent, self-describing lane for tile ``i``
    in row-major grid order (predictor-specific layout; for ``lorenzo`` a
    bare ``RPRE`` entropy blob, for ``interp`` outliers + ``RPRE`` codes).
    ``predictor``/``order``/``levels`` record the per-tile transform; v1
    blobs (always Lorenzo) still parse."""

    shape: tuple[int, ...]
    tile: tuple[int, ...]
    eb_abs: float
    backend: str
    tile_blobs: list[bytes]
    predictor: str = "lorenzo"
    order: str = "cubic"
    levels: int = 0
    extras: dict = field(default_factory=dict)
    # per-lane CRC32 from the container's footer index (None when the blob
    # predates checksums or the artifact was built in memory — verification
    # is then skipped), plus the runtime verification policy the opener
    # chose: ``verify`` in {"none","lazy","full"} and ``on_corrupt`` in
    # {"raise","quarantine"} (docs/ROBUSTNESS.md).  None of these affect
    # artifact identity, so they are excluded from equality.
    lane_crcs: np.ndarray | None = field(default=None, repr=False, compare=False)
    verify: str = field(default="lazy", repr=False, compare=False)
    on_corrupt: str = field(default="raise", repr=False, compare=False)
    fill_value: float = field(default=0.0, repr=False, compare=False)
    # lanes that already passed / failed their CRC — verification runs at
    # most once per lane under the lazy policy
    _verified: set = field(default_factory=set, init=False, repr=False, compare=False)
    quarantined: set = field(default_factory=set, init=False, repr=False, compare=False)
    # serialization cache keyed on the extras fingerprint (same scheme as
    # SZCompressed): GWLZ.compress_tiled asks for nbytes before and after
    # attaching the model, and size_report() asks again
    _blob_cache: tuple | None = field(default=None, init=False, repr=False, compare=False)

    @property
    def grid(self) -> tuple[int, ...]:
        return tile_grid(self.shape, self.tile)

    @property
    def padded_shape(self) -> tuple[int, ...]:
        return tuple(g * t for g, t in zip(self.grid, self.tile))

    @property
    def n_tiles(self) -> int:
        return int(np.prod(self.grid))

    @property
    def nbytes(self) -> int:
        """Serialized (v3) size, computed in O(index) from the lane index —
        never by materializing the container, so ``repr``/``size_report`` on
        an mmap-opened volume stay lazy."""
        return (_HDR_V3.size + 16 * len(self.shape)
                + lanes_nbytes(self.tile_blobs)
                + len(_pack_extras(self.extras))
                + _index_nbytes(len(self.tile_blobs)) + _FOOTER_V3.size)

    def size_report(self) -> dict:
        lanes = lanes_nbytes(self.tile_blobs)
        extras = len(_pack_extras(self.extras))
        index = _index_nbytes(len(self.tile_blobs)) + _FOOTER_V3.size
        header = _HDR_V3.size + 16 * len(self.shape)
        return {"lanes": lanes, "index": index, "extras": extras,
                "header": header, "total": header + lanes + extras + index}

    def to_bytes(self) -> bytes:
        key = tuple(sorted(self.extras.items()))
        if self._blob_cache is not None and self._blob_cache[0] == key:
            return self._blob_cache[1]
        blob = self._serialize()
        self._blob_cache = (key, blob)
        return blob

    def _serialize(self) -> bytes:
        """Eager v3 serialization — routed through the same incremental
        writer the streaming executor uses, so eager ``to_bytes`` and a
        finalized stream emit byte-identical containers."""
        import io

        from repro.exec.writer import GWTCWriter

        buf = io.BytesIO()
        w = GWTCWriter(buf, shape=self.shape, tile=self.tile, eb_abs=self.eb_abs,
                       backend=self.backend, predictor=self.predictor,
                       order=self.order, levels=self.levels)
        for lane in self.tile_blobs:
            w.append_lane(lane)
        w.extras.update(self.extras)
        w.finalize()
        return buf.getvalue()

    @staticmethod
    def from_bytes(blob) -> "TiledCompressed":
        """Rebuild from a container blob (``bytes`` or any buffer, e.g. a
        ``memoryview`` over an mmap).  Buffer inputs parse *lazily*: lanes
        stay in the backing buffer behind a :class:`LaneStore` and are only
        copied out when a decode touches them — the mmap-backed open path.

        Every structural failure raises :class:`CorruptContainerError` with
        the byte offset of the failed check; lane payloads are *not* read
        here — their CRCs (when the container carries them) are checked by
        :func:`decode_lanes` under the artifact's ``verify`` policy."""
        try:
            magic, ver = struct.unpack_from("<4sB", blob, 0)
        except struct.error as e:
            raise CorruptContainerError(
                f"truncated GWTC blob: {e}", offset=0) from e
        if magic != _MAGIC:
            raise CorruptContainerError(
                "bad GWTC magic", offset=0, expected=_MAGIC, actual=bytes(magic))
        try:
            if ver == 1:
                # v1 predates the predictor layer: lanes are always Lorenzo.
                _m, _v, nd, backend, _pad, ebbits, n_tiles = \
                    _HDR_V1.unpack_from(blob, 0)
                pred, order, levels = PRED_IDS["lorenzo"], ORDER_IDS["cubic"], 0
                off = _HDR_V1.size
            elif ver in (2, 3):
                (_m, _v, nd, backend, pred, order, levels, _pad, ebbits,
                 n_tiles) = _HDR_V2.unpack_from(blob, 0)
                off = _HDR_V2.size
            else:
                raise CorruptContainerError(
                    "unsupported GWTC version", offset=4,
                    expected="1..3", actual=int(ver))
            if not 1 <= nd <= 16:
                raise CorruptContainerError(
                    "implausible GWTC rank", offset=5, expected="1..16",
                    actual=int(nd))
            if backend not in _BACKENDS_INV:
                raise CorruptContainerError(
                    "unknown GWTC entropy backend id", offset=6,
                    expected=sorted(_BACKENDS_INV), actual=int(backend))
            if pred not in PRED_NAMES or order not in ORDER_NAMES:
                raise CorruptContainerError(
                    "unknown GWTC predictor/order id", offset=7,
                    actual=(int(pred), int(order)))
            shape = struct.unpack_from(f"<{nd}q", blob, off)
            off += 8 * nd
            tile = struct.unpack_from(f"<{nd}q", blob, off)
            off += 8 * nd
        except struct.error as e:
            raise CorruptContainerError(
                f"truncated GWTC header: {e}", offset=0) from e
        if any(d < 1 for d in shape) or any(t < 1 for t in tile):
            raise CorruptContainerError(
                "non-positive GWTC shape/tile dims", offset=_HDR_V3.size,
                actual=(tuple(map(int, shape)), tuple(map(int, tile))))
        want_tiles = int(np.prod(tile_grid(tuple(shape), tuple(tile))))
        if n_tiles != want_tiles:
            raise CorruptContainerError(
                "GWTC tile count disagrees with the shape/tile grid",
                offset=off - 16 * nd, expected=want_tiles, actual=int(n_tiles))
        lane_crcs = None
        if ver in (1, 2):
            # index-first layout: lane lengths precede the lane bytes
            if off + 8 * n_tiles > len(blob):
                raise CorruptContainerError(
                    "truncated GWTC index", offset=off,
                    expected=f">= {off + 8 * n_tiles} bytes", actual=len(blob))
            lens = np.frombuffer(blob, np.uint64, n_tiles, offset=off).astype(np.int64)
            # exact-int sum: garbage u64 lens must not wrap int64 past the
            # extent check and overflow the lane slicing below
            lens_sum = sum(map(int, np.frombuffer(
                blob, np.uint64, n_tiles, offset=off)))
            off += 8 * n_tiles
            lanes_start = off
            extras_off = lanes_start + lens_sum
            if (lens < 0).any() or extras_off + 4 > len(blob):
                raise CorruptContainerError(
                    "GWTC lane extent overruns the blob", offset=lanes_start,
                    expected=f"extras at byte {extras_off}", actual=len(blob))
        else:
            # v3 footer layout: lanes start right after the dims; the footer
            # locates the extras blob and the trailing index region, whose
            # byte extent tells us whether per-lane CRCs are present
            lanes_start = off
            if len(blob) < lanes_start + _FOOTER_V3.size:
                raise CorruptContainerError(
                    "truncated GWTC v3 blob (no footer)",
                    offset=max(0, len(blob) - _FOOTER_V3.size),
                    expected=f">= {lanes_start + _FOOTER_V3.size} bytes",
                    actual=len(blob))
            footer_off = len(blob) - _FOOTER_V3.size
            extras_off, index_off = _FOOTER_V3.unpack_from(blob, footer_off)
            if not lanes_start <= extras_off <= index_off <= footer_off:
                raise CorruptContainerError(
                    "corrupt GWTC v3 footer (offsets out of range)",
                    offset=footer_off,
                    actual=(int(extras_off), int(index_off)))
            region = footer_off - index_off
            if region == _index_nbytes(n_tiles):
                has_crcs = True
            elif region == 8 * n_tiles:
                has_crcs = False  # pre-checksum v3 container
            else:
                raise CorruptContainerError(
                    "GWTC v3 index region has an impossible extent",
                    offset=index_off,
                    expected=(_index_nbytes(n_tiles), 8 * n_tiles),
                    actual=int(region))
            lens = np.frombuffer(blob, np.uint64, n_tiles,
                                 offset=index_off).astype(np.int64)
            # exact-int sum: a damaged u64 len must not wrap int64 into a
            # coincidentally matching total
            lens_sum = sum(map(int, np.frombuffer(
                blob, np.uint64, n_tiles, offset=index_off)))
            if (lens < 0).any() or lanes_start + lens_sum != extras_off:
                raise CorruptContainerError(
                    "corrupt GWTC v3 blob (index / lane extent mismatch)",
                    offset=index_off,
                    expected=int(extras_off) - lanes_start,
                    actual=lens_sum)
            if has_crcs:
                lane_crcs = np.frombuffer(
                    blob, np.uint32, n_tiles, offset=index_off + 8 * n_tiles).copy()
                (meta_crc,) = struct.unpack_from(
                    "<I", blob, index_off + 12 * n_tiles)
                got = zlib.crc32(bytes(blob[extras_off:index_off]),
                                 zlib.crc32(bytes(blob[:lanes_start]))) & 0xFFFFFFFF
                if got != meta_crc:
                    raise CorruptContainerError(
                        "GWTC metadata checksum mismatch (header/shape/extras "
                        "bytes are damaged)", offset=index_off + 12 * n_tiles,
                        expected=f"0x{meta_crc:08x}", actual=f"0x{got:08x}")
        offs = lanes_start + np.concatenate([[0], np.cumsum(lens[:-1])]) \
            if n_tiles else np.zeros(0, np.int64)
        if isinstance(blob, (bytes, bytearray)):
            tile_blobs: "list[bytes] | LaneStore" = [
                bytes(blob[o : o + ln]) for o, ln in zip(offs, lens)]
        else:
            tile_blobs = LaneStore(blob, offs, lens)
        try:
            extras = _unpack_extras(blob, extras_off)
        except struct.error as e:
            raise CorruptContainerError(
                f"truncated GWTC extras blob: {e}", offset=int(extras_off)) from e
        return TiledCompressed(
            shape=tuple(shape), tile=tuple(tile),
            eb_abs=float(np.uint64(ebbits).view(np.float64)),
            backend=_BACKENDS_INV[backend], tile_blobs=tile_blobs,
            predictor=PRED_NAMES[pred], order=ORDER_NAMES[order],
            levels=int(levels), extras=extras, lane_crcs=lane_crcs,
        )


A.register_container(_MAGIC, TiledCompressed)


# ---------------------------------------------------------------------------
# lane dispatch (shared, size-capped executor)
# ---------------------------------------------------------------------------

_POOL_SIZE = max(1, min(os.cpu_count() or 1, 8))
_LANE_POOL: ThreadPoolExecutor | None = None
_LANE_POOL_LOCK = threading.Lock()


def _lane_pool() -> ThreadPoolExecutor:
    """One shared, size-capped executor for every encode/decode call — lane
    work is short and bursty, so per-call pool construction was pure churn."""
    global _LANE_POOL
    if _LANE_POOL is None:
        with _LANE_POOL_LOCK:
            if _LANE_POOL is None:
                _LANE_POOL = ThreadPoolExecutor(
                    _POOL_SIZE, thread_name_prefix="gwtc-lane")
    return _LANE_POOL


def _lane_workers(n_lanes: int, workers: int | None) -> int:
    if workers is not None:
        return max(1, min(workers, n_lanes))
    cores = os.cpu_count() or 1
    return max(1, min(cores, 8, n_lanes)) if cores > 2 else 1


def _map_lanes(fn, items, workers: int | None):
    """Run ``fn`` over lanes with at most ``workers`` concurrent lanes.

    The per-call concurrency cap is enforced by splitting the lane list into
    that many contiguous runs, each submitted as one serial task to the
    shared pool — order is preserved and no call ever spawns its own pool."""
    w = _lane_workers(len(items), workers)
    if w <= 1:
        return [fn(it) for it in items]
    bounds = np.linspace(0, len(items), w + 1).astype(int)
    chunks = [items[a:b] for a, b in zip(bounds[:-1], bounds[1:]) if b > a]
    futs = [_lane_pool().submit(lambda ch: [fn(it) for it in ch], ch)
            for ch in chunks]
    return [out for f in futs for out in f.result()]


# ---------------------------------------------------------------------------
# engine API
# ---------------------------------------------------------------------------


def compress_tiled(
    x: jax.Array,
    tile=(64, 64, 64),
    *,
    rel_eb: float | None = None,
    abs_eb: float | None = None,
    backend: str = "huffman+zlib",
    predictor: str = "lorenzo",
    order: str = "cubic",
    max_levels: int = 5,
    use_pallas: bool | None = None,
    workers: int | None = None,
) -> tuple[TiledCompressed, jax.Array]:
    """Tile-grid compress; returns (artifact, reconstruction).

    ``predictor`` selects the per-tile transform from the registry
    (``"lorenzo"`` or ``"interp"``; ``order``/``max_levels`` apply to interp
    only).  The reconstruction is the decode program's own output, cropped to
    ``x.shape`` — exactly what :func:`decompress_tiled` will produce."""
    if backend not in _BACKENDS:
        raise ValueError(f"unknown entropy backend {backend!r}")
    pred = get_predictor(predictor)
    x = jnp.asarray(x, jnp.float32)
    tile = normalize_tile(tile, x.ndim)
    eb = resolve_eb(x, rel_eb, abs_eb)
    levels = pred.plan(tile, max_levels)
    xp = pad_to_tiles(x, tile)
    tiles = split_tiles(xp, tile)
    payload, recon_tiles = pred.encode_tiles(
        tiles, eb, order=order, levels=levels, use_pallas=use_pallas)
    recon = stitch_tiles(recon_tiles, tile_grid(x.shape, tile))

    payload_np = jax.tree.map(np.asarray, payload)
    blobs = _map_lanes(
        lambda i: pred.lane_bytes(payload_np, i, backend, use_pallas=use_pallas),
        list(range(tiles.shape[0])), workers)
    artifact = TiledCompressed(
        shape=tuple(x.shape), tile=tile, eb_abs=eb, backend=backend,
        tile_blobs=blobs, predictor=predictor, order=order, levels=levels)
    return artifact, recon[tuple(slice(0, d) for d in x.shape)]


def _check_lane(artifact: TiledCompressed, i: int, blob) -> bool:
    """Verify lane ``i`` against its footer CRC (at most once per lane).

    Returns True when the lane is usable.  On mismatch: raises
    :class:`CorruptLaneError` under ``on_corrupt="raise"``, or records the
    lane in ``artifact.quarantined`` and returns False under
    ``on_corrupt="quarantine"``.  No-op (True) when the container carries no
    checksums or the policy is ``verify="none"``."""
    if i in artifact.quarantined:
        return False
    if (artifact.lane_crcs is None or artifact.verify == "none"
            or i in artifact._verified):
        return True
    expected = int(artifact.lane_crcs[i])
    actual = lane_crc(blob)
    if actual == expected:
        artifact._verified.add(i)
        return True
    if artifact.on_corrupt == "quarantine":
        artifact.quarantined.add(i)
        return False
    raise CorruptLaneError(i, lane_offset=lane_offset(artifact, i),
                           expected_crc=expected, actual_crc=actual)


def verify_lanes(artifact: TiledCompressed, lane_ids=None, *,
                 workers: int | None = None) -> list[int]:
    """Checksum the given lanes (all, by default) without decoding them —
    the ``verify="full"`` open policy.  Returns the quarantined lane ids
    (always empty under ``on_corrupt="raise"``, which raises instead);
    returns ``[]`` immediately when the container carries no checksums."""
    if artifact.lane_crcs is None or artifact.verify == "none":
        return []
    ids = list(range(artifact.n_tiles)) if lane_ids is None else list(lane_ids)
    _map_lanes(lambda i: _check_lane(artifact, i, artifact.tile_blobs[i]),
               ids, workers)
    return sorted(artifact.quarantined)


def decode_lanes(
    artifact: TiledCompressed, lane_ids, *, workers: int | None = None,
    with_mask: bool = False, use_pallas: bool | None = None,
    bucket_cap: int | None = None,
):
    """Decode the given lanes and reconstruct them; returns
    ``(recon [len(ids), *tile], lanes_decoded)`` — or, with
    ``with_mask=True``, ``(recon, lanes_decoded, bad_mask)`` where
    ``bad_mask[j]`` marks quarantined positions (filled with the artifact's
    ``fill_value``), so callers applying a tile transform can re-assert the
    fill afterwards.

    Only the named lanes are touched — this is the random-access primitive
    both :func:`decompress_tiled` and :func:`decompress_region` build on.
    When the container carries per-lane CRCs and the artifact's ``verify``
    policy is not ``"none"``, each lane is checksummed before its first
    decode; a mismatch raises :class:`CorruptLaneError` or — under
    ``on_corrupt="quarantine"`` — degrades that tile to ``fill_value``.
    The returned lane count is the race-free observability channel (the
    module-level ``DECODE_STATS`` mirror is best-effort, for convenience)."""
    pred = get_predictor(artifact.predictor)
    lane_ids = list(lane_ids)
    blobs = [artifact.tile_blobs[i] for i in lane_ids]
    good = [j for j, (i, b) in enumerate(zip(lane_ids, blobs))
            if _check_lane(artifact, i, b)]
    with obs.span("gwlz.decode.lanes"):
        items = _map_lanes(
            lambda b: pred.parse_lane(b, tile=artifact.tile,
                                      levels=artifact.levels,
                                      use_pallas=use_pallas),
            [blobs[j] for j in good], workers)
    with _STATS_LOCK:
        DECODE_STATS["tiles_decoded"] = len(good)
        DECODE_STATS["tiles_total"] = artifact.n_tiles
    if good:
        with obs.span("gwlz.decode.upload"):
            payload = {k: jnp.asarray(np.stack([it[k] for it in items]))
                       for k in items[0]}
        key = pred.decode_program_key(tile=artifact.tile, order=artifact.order,
                                      levels=artifact.levels)
        with obs.span("gwlz.decode.reconstruct"):
            recon = dispatch_bucketed(
                lambda p: pred.decode_tiles(
                    p, artifact.eb_abs, tile=artifact.tile,
                    order=artifact.order, levels=artifact.levels),
                payload, len(good), key=key, bucket_cap=bucket_cap)
    bad_mask = np.zeros(len(lane_ids), bool)
    if len(good) < len(lane_ids):
        good_set = set(good)
        bad_mask[[j for j in range(len(lane_ids)) if j not in good_set]] = True
        full = jnp.full((len(lane_ids),) + tuple(artifact.tile),
                        artifact.fill_value, jnp.float32)
        recon = full.at[jnp.asarray(good, jnp.int32)].set(recon) if good else full
    if with_mask:
        return recon, len(good), bad_mask
    return recon, len(good)


def apply_tile_transform(tile_transform, recon, *, bucket_cap=None):
    """Run a per-tile transform over a [K, *tile] batch, bucketed when the
    transform declares a ``program_key`` attribute naming its compiled
    program's identity (the GWLZ enhancer does).  Unkeyed transforms (ad-hoc
    callables) run in one unbucketed call — there is nothing safe to cache
    them under, and inflating the program counters with anonymous callables
    would poison the zero-recompile assertion."""
    key = getattr(tile_transform, "program_key", None)
    if key is None:
        return tile_transform(recon)
    return dispatch_bucketed(tile_transform, recon, int(recon.shape[0]),
                             key=tuple(key), bucket_cap=bucket_cap)


def decompress_tiled(
    artifact: TiledCompressed, *, workers: int | None = None, tile_transform=None,
    use_pallas: bool | None = None, bucket_cap: int | None = None,
) -> jax.Array:
    """Full decode: every lane, stitched and cropped to the original shape.

    ``tile_transform([K, *tile]) -> [K, *tile]`` post-processes decoded tiles
    before stitching (the GWLZ pipeline enhances per tile through it; it must
    act per-tile so region and full decode stay consistent)."""
    recon, _, bad = decode_lanes(artifact, range(artifact.n_tiles),
                                 workers=workers, with_mask=True,
                                 use_pallas=use_pallas, bucket_cap=bucket_cap)
    if tile_transform is not None:
        with obs.span("gwlz.decode.enhance"):
            recon = apply_tile_transform(tile_transform, recon,
                                         bucket_cap=bucket_cap)
            recon = _refill_quarantined(recon, bad, artifact.fill_value)
    with obs.span("gwlz.decode.stitch"):
        out = stitch_tiles(recon, artifact.grid)
        return out[tuple(slice(0, d) for d in artifact.shape)]


def _refill_quarantined(recon, bad_mask: np.ndarray, fill_value: float):
    """Re-assert the fill value on quarantined tile positions *after* a tile
    transform ran — an enhancer must not resurrect data for a tile whose
    lane failed its checksum."""
    if bad_mask.any():
        recon = recon.at[jnp.asarray(np.nonzero(bad_mask)[0], jnp.int32)].set(
            jnp.float32(fill_value))
    return recon


def normalize_roi(roi, shape: tuple[int, ...]) -> tuple[tuple[int, int], ...]:
    """ROI as slices or (start, stop) pairs -> clamped (start, stop) tuples."""
    if len(roi) != len(shape):
        raise ValueError(f"roi rank {len(roi)} != volume rank {len(shape)}")
    out = []
    for r, d in zip(roi, shape):
        if isinstance(r, slice):
            if r.step not in (None, 1):
                raise ValueError("roi slices must have step 1")
            start, stop, _ = r.indices(d)
        else:
            start, stop = r
            start = start + d if start < 0 else start
            stop = stop + d if stop < 0 else stop
            start, stop = max(0, min(start, d)), max(0, min(stop, d))
        if stop <= start:
            raise ValueError(f"empty roi extent {r} on a dim of size {d}")
        out.append((int(start), int(stop)))
    return tuple(out)


def region_tiles(artifact: TiledCompressed, roi) -> tuple[np.ndarray, tuple]:
    """(flat lane ids of tiles intersecting ``roi``, per-dim tile ranges)."""
    bounds = normalize_roi(roi, artifact.shape)
    ranges = tuple((lo // t, -(-hi // t))
                   for (lo, hi), t in zip(bounds, artifact.tile))
    axes = [np.arange(a, b) for a, b in ranges]
    coords = np.meshgrid(*axes, indexing="ij")
    ids = np.ravel_multi_index([c.ravel() for c in coords], artifact.grid)
    return ids, (bounds, ranges)


def assemble_region(recon, geom, tile: tuple[int, ...]):
    """Stitch + crop decoded region tiles: the pure-geometry back half of
    :func:`decompress_region`, shared with the façade's cached read path
    (``recon`` may be a jax array or a numpy stack of cached tiles —
    stitching is reshape/transpose either way)."""
    bounds, ranges = geom
    sub_grid = tuple(b - a for a, b in ranges)
    block = stitch_tiles(recon, sub_grid)
    crop = tuple(slice(lo - a * t, hi - a * t)
                 for (lo, hi), (a, _b), t in zip(bounds, ranges, tile))
    return block[crop]


def decompress_region(
    artifact: TiledCompressed, roi, *, workers: int | None = None,
    tile_transform=None, use_pallas: bool | None = None,
    bucket_cap: int | None = None,
) -> jax.Array:
    """Decode only the tiles intersecting ``roi``; returns the ROI's values.

    Bit-identical to ``decompress_tiled(artifact)[roi]`` — the per-tile
    transform is elementwise-exact, so the subset batch reconstructs the
    same values the full batch would (any ``tile_transform`` must preserve
    this by acting on each tile independently; bucket padding preserves it
    too, since pad rows are repeats of row 0 cropped from the output)."""
    ids, geom = region_tiles(artifact, roi)
    recon, _, bad = decode_lanes(artifact, ids.tolist(), workers=workers,
                                 with_mask=True, use_pallas=use_pallas,
                                 bucket_cap=bucket_cap)
    if tile_transform is not None:
        recon = apply_tile_transform(tile_transform, recon,
                                     bucket_cap=bucket_cap)
        recon = _refill_quarantined(recon, bad, artifact.fill_value)
    return assemble_region(recon, geom, artifact.tile)
