"""Chunked entropy codec: round trips, chunk boundaries, legacy-format decode,
accelerator-backed frequency counting (docs/ENTROPY_FORMAT.md)."""
import numpy as np
import pytest

from repro.sz.entropy import (
    DEFAULT_CHUNK,
    HuffmanCodec,
    decode_codes,
    decode_codes_range,
    encode_codes,
    encode_codes_legacy,
    shannon_bits,
)

BACKENDS = ("zlib", "huffman", "huffman+zlib")


def _cases():
    rng = np.random.default_rng(7)
    return {
        "skewed": rng.choice([0] * 8 + [1, -1, 2, -2, 9], size=60000).astype(np.int32),
        "uniform_wide": rng.integers(-600, 600, size=37777).astype(np.int32),
        "single_symbol": np.full(1234, -3, np.int32),
        "empty": np.zeros(0, np.int32),
        "one_element": np.array([5], np.int32),
        "big_magnitude": rng.integers(-(2**17), 2**17, size=4000).astype(np.int32),
        "extreme_magnitude": np.array([2**30, -(2**30), 0, 0, 7], np.int32),
    }


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("name", list(_cases()))
def test_roundtrip_distributions(name, backend):
    codes = _cases()[name]
    blob = encode_codes(codes, backend)
    np.testing.assert_array_equal(decode_codes(blob, codes.shape), codes)


@pytest.mark.parametrize("n", [
    0, 1, 7, DEFAULT_CHUNK - 1, DEFAULT_CHUNK, DEFAULT_CHUNK + 1,
    4 * DEFAULT_CHUNK - 1, 4 * DEFAULT_CHUNK, 4 * DEFAULT_CHUNK + 1,
])
def test_chunk_boundaries(n):
    rng = np.random.default_rng(n)
    codes = rng.integers(-9, 9, size=n).astype(np.int32)
    for cs in (8, 64, DEFAULT_CHUNK):
        blob = encode_codes(codes, "huffman", chunk_size=cs)
        np.testing.assert_array_equal(decode_codes(blob, codes.shape), codes)
        blob = encode_codes(codes, "huffman+zlib", chunk_size=cs)
        np.testing.assert_array_equal(decode_codes(blob, codes.shape), codes)


def test_chunked_decode_worker_counts():
    rng = np.random.default_rng(11)
    codes = rng.choice([0, 0, 0, 1, -1, 4], size=10000).astype(np.int32)
    blob = encode_codes(codes, "huffman+zlib", chunk_size=32)
    for workers in (1, 2, 5):
        np.testing.assert_array_equal(
            decode_codes(blob, codes.shape, workers=workers), codes)


@pytest.mark.parametrize("backend", ["huffman", "huffman+zlib"])
def test_legacy_tags_still_decode(backend):
    """Seed hf/hz blobs (pre-chunking format) must keep decoding bit-exactly."""
    rng = np.random.default_rng(3)
    for codes in (
        rng.choice([0, 0, 0, 1, -2], size=5000).astype(np.int32),
        np.full(10, 4, np.int32),
        np.zeros(0, np.int32),
    ):
        blob = encode_codes_legacy(codes, backend)
        assert blob[4:6] in (b"hf", b"hz")
        np.testing.assert_array_equal(decode_codes(blob, codes.shape), codes)


def test_new_tags_are_chunked():
    codes = np.arange(1000, dtype=np.int32) % 17
    assert encode_codes(codes, "huffman")[4:6] == b"hc"
    assert encode_codes(codes, "huffman+zlib")[4:6] == b"hZ"


def test_chunked_matches_bitwalk_reference():
    """The vectorized LUT decode must agree with the seed per-symbol walk."""
    rng = np.random.default_rng(5)
    codes = rng.choice([0] * 20 + list(range(-40, 40)), size=20000).astype(np.int32)
    codec = HuffmanCodec.fit(codes)
    stream = codec.encode(codes)
    want = codec.decode_bitwalk(stream, codes.size)
    blob = encode_codes(codes, "huffman")
    got = decode_codes(blob, codes.shape)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, codes)


def test_long_codes_take_escape_path():
    """An alphabet skewed enough to exceed the 12-bit LUT still decodes (the
    per-length escape table resolves the long codes)."""
    sizes = [2 ** i for i in range(18, 0, -1)] + [1, 1]  # ~20 lengths, max > 12
    codes = np.repeat(np.arange(len(sizes), dtype=np.int32), sizes)
    rng = np.random.default_rng(0)
    rng.shuffle(codes)
    codec = HuffmanCodec.fit(codes)
    assert int(codec.lengths.max()) > 12, "test needs codes longer than the LUT"
    blob = encode_codes(codes, "huffman+zlib")
    np.testing.assert_array_equal(decode_codes(blob, codes.shape), codes)


def test_code_lengths_are_limited():
    """Pathological (Fibonacci-like) skew must not exceed the 32-bit cap."""
    from repro.sz.entropy import _limited_code_lengths

    counts = np.asarray([1, 1] + [2 ** i for i in range(1, 45)], np.int64)
    lengths = _limited_code_lengths(counts)
    assert int(lengths.max()) <= 32
    assert lengths.size == counts.size


def test_fit_accel_parity():
    """Accelerator-backed frequency counting gives the identical codec."""
    rng = np.random.default_rng(13)
    codes = rng.choice([0, 0, 0, 0, 1, -1, 2, -3, 8], size=30000).astype(np.int32)
    a = HuffmanCodec.fit(codes, use_accel=True)
    b = HuffmanCodec.fit(codes, use_accel=False)
    np.testing.assert_array_equal(a.alphabet, b.alphabet)
    np.testing.assert_array_equal(a.lengths, b.lengths)
    np.testing.assert_array_equal(a.codes, b.codes)


def test_huffman_near_shannon():
    rng = np.random.default_rng(3)
    codes = rng.choice([0, 0, 0, 0, 0, 1, -1, 2], size=50000).astype(np.int32)
    codec = HuffmanCodec.fit(codes)
    enc = codec.encode(codes)
    ideal = shannon_bits(codes) / 8
    assert len(enc) - 8 <= ideal * 1.25 + 64


def test_chunk_table_overhead_is_small():
    """Chunking must not meaningfully hurt compression (paper §4.3 claim)."""
    rng = np.random.default_rng(2)
    codes = np.round(rng.normal(0, 3, size=64**3)).astype(np.int32)
    new = len(encode_codes(codes, "huffman+zlib"))
    old = len(encode_codes_legacy(codes, "huffman+zlib"))
    assert new <= old * 1.03, (new, old)


def test_truncated_stream_raises():
    codes = np.arange(100, dtype=np.int32) % 7
    blob = encode_codes(codes, "huffman")
    with pytest.raises(ValueError):
        decode_codes(blob[:-4], codes.shape)


def test_roundtrip_fuzz():
    """Seeded sweep over alphabet sizes, skews, and stream lengths."""
    rng = np.random.default_rng(99)
    for _ in range(25):
        n = int(rng.integers(1, 3000))
        alpha = int(rng.integers(1, 200))
        base = int(rng.integers(-(2**16), 2**16))
        p = rng.dirichlet(np.full(alpha, float(rng.uniform(0.05, 2.0))))
        codes = (base + rng.choice(alpha, size=n, p=p)).astype(np.int32)
        for backend in BACKENDS:
            blob = encode_codes(codes, backend)
            np.testing.assert_array_equal(decode_codes(blob, codes.shape), codes)


# ---------------------------------------------------------------------------
# shannon_bits: bincount fast path == np.unique reference (satellite)
# ---------------------------------------------------------------------------


def test_shannon_bits_matches_unique_reference():
    """The dense-alphabet bincount path and the sparse/float unique path must
    compute the identical entropy (and empty input is 0.0, not NaN)."""

    def want(x):
        flat = np.asarray(x).ravel()
        _, counts = np.unique(flat, return_counts=True)
        p = counts / flat.size
        return float(-(p * np.log2(p)).sum() * flat.size)

    rng = np.random.default_rng(31)
    dense_or_sparse = [
        rng.integers(-500, 500, size=20000).astype(np.int32),  # dense bincount
        np.full(100, 7, np.int32),                              # one symbol
        rng.choice([0, 1], size=64).astype(np.int64),
        np.array([-(2**40), 0, 2**40, 2**40], np.int64),        # sparse span
        rng.normal(size=3000),                                  # float: unique
    ]
    for x in dense_or_sparse:
        assert shannon_bits(x) == pytest.approx(want(x), rel=1e-12)
    assert shannon_bits(np.zeros(0, np.int32)) == 0.0


# ---------------------------------------------------------------------------
# device (Pallas interpret) codec path: byte identity with the host pack
# ---------------------------------------------------------------------------

HUFF_BACKENDS = ("huffman", "huffman+zlib")


def _device_cases():
    rng = np.random.default_rng(21)
    return {
        "skewed": rng.choice([0] * 8 + [1, -1, 2, -2, 9], size=6000).astype(np.int32),
        "wide_alphabet": rng.integers(-600, 600, size=4097).astype(np.int32),
        "single_symbol": np.full(1234, -3, np.int32),
        "one_element": np.array([5], np.int32),
        "empty": np.zeros(0, np.int32),
    }


@pytest.mark.parametrize("cs", [8, 64, DEFAULT_CHUNK])
@pytest.mark.parametrize("name", list(_device_cases()))
def test_device_blob_bytes_identical(name, cs):
    """Device encode must emit the SAME hc/hZ blob as the host pack, and the
    device decode must invert it — the container format cannot fork on the
    execution path."""
    codes = _device_cases()[name]
    for backend in HUFF_BACKENDS:
        host = encode_codes(codes, backend, chunk_size=cs, use_pallas=False)
        dev = encode_codes(codes, backend, chunk_size=cs, use_pallas=True)
        assert dev == host, f"{name}/{backend}/cs={cs} device blob diverged"
        np.testing.assert_array_equal(
            decode_codes(dev, codes.shape, use_pallas=True), codes)


@pytest.mark.parametrize("n", [
    1, 7, DEFAULT_CHUNK - 1, DEFAULT_CHUNK, DEFAULT_CHUNK + 1,
    4 * DEFAULT_CHUNK - 1, 4 * DEFAULT_CHUNK + 1,
])
def test_device_chunk_boundaries(n):
    """Short last chunks, exact multiples, and one-over lengths all pack to
    host-identical bytes (the pad lanes must contribute zero bits)."""
    rng = np.random.default_rng(n)
    codes = rng.integers(-9, 9, size=n).astype(np.int32)
    for cs in (8, DEFAULT_CHUNK):
        host = encode_codes(codes, "huffman", chunk_size=cs, use_pallas=False)
        dev = encode_codes(codes, "huffman", chunk_size=cs, use_pallas=True)
        assert dev == host
        np.testing.assert_array_equal(
            decode_codes(dev, codes.shape, use_pallas=True), codes)


def test_device_escape_path_parity():
    """Codes longer than the 12-bit LUT must flow through the kernel's
    binary-search escape and still match the host bytes exactly."""
    sizes = [2 ** i for i in range(14, 0, -1)] + [1, 1]
    codes = np.repeat(np.arange(len(sizes), dtype=np.int32), sizes)
    rng = np.random.default_rng(0)
    rng.shuffle(codes)
    codec = HuffmanCodec.fit(codes)
    assert int(codec.lengths.max()) > 12, "test needs codes longer than the LUT"
    for backend in HUFF_BACKENDS:
        host = encode_codes(codes, backend, chunk_size=64, use_pallas=False)
        dev = encode_codes(codes, backend, chunk_size=64, use_pallas=True)
        assert dev == host
        np.testing.assert_array_equal(
            decode_codes(dev, codes.shape, use_pallas=True), codes)


def test_device_decodes_host_blob_and_vice_versa():
    """Cross-path decode: blobs are one format, so either decoder must accept
    either encoder's output."""
    rng = np.random.default_rng(43)
    codes = rng.choice([0] * 5 + list(range(-15, 15)), size=3000).astype(np.int32)
    host = encode_codes(codes, "huffman+zlib", use_pallas=False)
    dev = encode_codes(codes, "huffman+zlib", use_pallas=True)
    np.testing.assert_array_equal(decode_codes(host, codes.shape, use_pallas=True), codes)
    np.testing.assert_array_equal(decode_codes(dev, codes.shape, use_pallas=False), codes)


def test_device_range_decode_matches_host():
    """decode_codes_range on the device path == host path == the slice."""
    rng = np.random.default_rng(17)
    codes = rng.choice([0] * 6 + list(range(-20, 20)), size=5000).astype(np.int32)
    blob = encode_codes(codes, "huffman+zlib", chunk_size=64, use_pallas=False)
    for lo, hi in [(0, 1), (63, 65), (100, 1000), (4990, 5000), (0, 5000),
                   (777, 777)]:
        got = decode_codes_range(blob, lo, hi, use_pallas=True)
        np.testing.assert_array_equal(got, codes[lo:hi])
        np.testing.assert_array_equal(
            got, decode_codes_range(blob, lo, hi, use_pallas=False))


def _probe_steps(codes, cs, c0, c1):
    """Steps the device probe runs on chunks [c0, c1) of ``codes``' stream:
    the host walk over blocks of 8 of those chunks, each rounded up to a
    whole check."""
    import math

    from repro.kernels.huffman_decode import CHECK_EVERY
    from repro.sz import entropy

    codec = HuffmanCodec.fit(codes)
    stream, chunk_bits, _total = codec._device_pack(codes, cs, interpret=True)
    offsets = np.cumsum(chunk_bits) - chunk_bits
    counts = np.full(chunk_bits.size, cs, np.int64)
    counts[-1] = codes.size - cs * (chunk_bits.size - 1)
    offsets, counts = offsets[c0:c1], counts[c0:c1]
    words, padded = entropy._sliding_words(stream, tail_pad=8 * cs + 16)
    check = math.gcd(CHECK_EVERY, cs)
    total = 0
    for a in range(0, c1 - c0, 8):
        walk = entropy._decode_lanes(words, padded, offsets[a:a + 8],
                                     counts[a:a + 8],
                                     np.empty((cs, min(8, c1 - c0 - a)), np.uint64),
                                     codec._multi_tables())
        total += -(-walk // check) * check
    return total


def test_device_decode_of_uneven_chunks_counts_probe_steps():
    """A skewed stream whose chunks take very different probe step counts
    (runs of the commonest symbol beside runs of rare, long codes): full and
    range decode on the device == the host walk == the source codes, and the
    probe counts the steps its blocks of 8 chunks ran, below chunk_size a
    block."""
    from repro import obs

    cs = 64
    rng = np.random.default_rng(31)
    sizes = [2 ** i for i in range(16, 0, -1)] + [1, 1]
    pool = np.repeat(np.arange(len(sizes), dtype=np.int32), sizes)
    runs = [rng.choice(pool[-40:], cs), np.zeros(7 * cs, np.int32),
            rng.choice(pool, 8 * cs), np.zeros(3 * cs, np.int32),
            rng.choice(pool, 2 * cs + 7)]
    codes = np.concatenate(runs + [pool[rng.permutation(pool.size)[:30]]])
    blob = encode_codes(codes, "huffman+zlib", chunk_size=cs, use_pallas=False)
    C = -(-codes.size // cs)
    for lo, hi in [(0, codes.size), (cs + 1, cs + 2), (5 * cs - 3, 9 * cs + 2),
                   (cs, codes.size - 5), (codes.size - 31, codes.size)]:
        with obs.collect() as col:
            got = decode_codes_range(blob, lo, hi, use_pallas=True)
        np.testing.assert_array_equal(got, codes[lo:hi])
        np.testing.assert_array_equal(
            got, decode_codes_range(blob, lo, hi, use_pallas=False))
        c0, c1 = lo // cs, -(-hi // cs)
        n, _s, steps = col.stages()["gwlz.entropy.probe_steps"]
        n_b, _s, bound = col.stages()["gwlz.entropy.probe_steps_bound"]
        assert n == n_b == 1
        assert bound == cs * -(-(c1 - c0) // 8)
        assert steps == _probe_steps(codes, cs, c0, c1)
        assert steps < bound, (steps, bound)
    with obs.collect() as col:
        got = decode_codes(blob, codes.shape, use_pallas=True)
    np.testing.assert_array_equal(got, codes)
    np.testing.assert_array_equal(got, decode_codes(blob, codes.shape,
                                                    use_pallas=False))
    _n, _s, steps = col.stages()["gwlz.entropy.probe_steps"]
    assert steps == _probe_steps(codes, cs, 0, C)


def test_device_host_fuzz_parity():
    """Seeded fuzz: random alphabets, skews, lengths, and chunk sizes — the
    device blob must stay bit-identical and decode must invert."""
    rng = np.random.default_rng(123)
    for _ in range(8):
        n = int(rng.integers(1, 2000))
        alpha = int(rng.integers(1, 300))
        p = rng.dirichlet(np.full(alpha, float(rng.uniform(0.05, 2.0))))
        codes = (rng.choice(alpha, size=n, p=p).astype(np.int32) - alpha // 2)
        cs = int(rng.choice([8, 32, DEFAULT_CHUNK]))
        backend = HUFF_BACKENDS[int(rng.integers(2))]
        host = encode_codes(codes, backend, chunk_size=cs, use_pallas=False)
        dev = encode_codes(codes, backend, chunk_size=cs, use_pallas=True)
        assert dev == host, f"n={n} alpha={alpha} cs={cs} {backend}"
        np.testing.assert_array_equal(
            decode_codes(dev, codes.shape, use_pallas=True), codes)


def test_entropy_path_counters_report_every_lane():
    """Each Huffman lane is counted under the path that ran it; a lane the
    device was asked for but the host ran (here: an alphabet wider than the
    decode probe takes) is counted as a fallback, never silently."""
    from repro.sz import entropy

    rng = np.random.default_rng(21)
    small = rng.integers(-40, 40, size=5000).astype(np.int32)
    wide = rng.permutation(np.arange(entropy._PROBE_ALPHABET + 100, dtype=np.int32))
    entropy.reset_entropy_path_stats()
    blob = encode_codes(small, "huffman+zlib", use_pallas=True)
    assert blob == encode_codes(small, "huffman+zlib", use_pallas=False)
    np.testing.assert_array_equal(decode_codes(blob, small.shape, use_pallas=True), small)
    np.testing.assert_array_equal(decode_codes(blob, small.shape, use_pallas=False), small)
    wide_blob = encode_codes(wide, "huffman", use_pallas=False)
    np.testing.assert_array_equal(decode_codes(wide_blob, wide.shape, use_pallas=True), wide)
    assert entropy.entropy_path_stats() == {
        "pack_device": 1, "pack_host": 2, "pack_fallback": 0,
        "probe_device": 1, "probe_host": 2, "probe_fallback": 1}
