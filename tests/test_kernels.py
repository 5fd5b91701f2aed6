"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import enhancer as E
from repro.kernels import ops, ref


def _assert_codes_equivalent(a, b, x, eb):
    """Interpret-mode rint may break exact .5 ties the other way (XLA uses
    round-half-even; the interpreter's path differs at ~ulp-probability).
    Both stay within the error bound; require agreement elsewhere."""
    a, b = np.asarray(a), np.asarray(b)
    mism = a != b
    assert mism.mean() <= 1e-3, f"too many mismatches: {mism.mean()}"
    # decoded output from the kernel's codes still satisfies the bound
    from repro.sz.predictor import lorenzo_decode

    x2 = lorenzo_decode(jnp.asarray(a), eb)
    # a tie mis-round reconstructs exactly AT the bound (+ float noise)
    assert float(jnp.max(jnp.abs(x2 - x))) <= eb * (1 + 1e-3)


@pytest.mark.parametrize("shape", [(8, 16, 32), (16, 32, 64), (4, 64, 128), (32, 8, 256)])
@pytest.mark.parametrize("eb", [0.5, 0.01])
def test_lorenzo_quant_matches_ref(shape, eb):
    rng = np.random.default_rng(hash(shape) % 2**31)
    x = jnp.asarray((np.cumsum(rng.normal(size=shape), axis=0) * 10).astype(np.float32))
    a = ops.lorenzo_quant_op(x, eb, use_pallas=True, interpret=True)
    b = ref.lorenzo_quant_ref(x, eb)
    _assert_codes_equivalent(a, b, x, eb)


@pytest.mark.parametrize("block_z", [1, 2, 4, 8])
def test_lorenzo_block_sweep(block_z):
    from repro.kernels.lorenzo_quant import lorenzo_quant

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 16, 32)).astype(np.float32))
    a = lorenzo_quant(x, 0.25, block_z=block_z, interpret=True)
    b = ref.lorenzo_quant_ref(x, 0.25)
    _assert_codes_equivalent(a, b, x, 0.25)


@pytest.mark.parametrize("shape", [(2, 8, 16, 32), (5, 4, 8, 128), (1, 16, 8, 32)])
@pytest.mark.parametrize("eb", [0.5, 0.01])
def test_lorenzo_tiles_matches_ref(shape, eb):
    rng = np.random.default_rng(hash(shape) % 2**31)
    x = jnp.asarray((np.cumsum(rng.normal(size=shape), axis=1) * 10).astype(np.float32))
    a = ops.lorenzo_quant_tiles_op(x, eb, use_pallas=True, interpret=True)
    b = ref.lorenzo_quant_tiles_ref(x, eb)
    a_np, b_np = np.asarray(a), np.asarray(b)
    assert (a_np != b_np).mean() <= 1e-3  # interpret-mode .5-tie rounding only
    # every tile's codes must decode within the bound via the production decoder
    from repro.sz.predictor import lorenzo_decode

    for t in range(shape[0]):
        x2 = lorenzo_decode(jnp.asarray(a_np[t]), eb)
        assert float(jnp.max(jnp.abs(x2 - x[t]))) <= eb * (1 + 1e-3)


def test_lorenzo_tiles_matches_per_tile_kernel():
    """Batched kernel == the unbatched kernel run tile by tile (carry reset)."""
    from repro.kernels.lorenzo_quant import lorenzo_quant, lorenzo_quant_tiles

    rng = np.random.default_rng(5)
    x = jnp.asarray((rng.normal(size=(3, 8, 16, 32)) * 20).astype(np.float32))
    batched = lorenzo_quant_tiles(x, 0.25, interpret=True)
    for t in range(x.shape[0]):
        single = lorenzo_quant(x[t], 0.25, interpret=True)
        np.testing.assert_array_equal(np.asarray(batched[t]), np.asarray(single))


def test_lorenzo_roundtrip_through_decoder():
    """Kernel codes must decode with the production cumsum decoder."""
    from repro.sz.predictor import lorenzo_decode

    rng = np.random.default_rng(1)
    x = jnp.asarray((rng.normal(size=(8, 16, 128)) * 100).astype(np.float32))
    eb = 0.5
    codes = ops.lorenzo_quant_op(x, eb, use_pallas=True, interpret=True)
    x2 = lorenzo_decode(codes, eb)
    assert float(jnp.max(jnp.abs(x2 - x))) <= eb * (1 + 1e-6)


def _enhancers(n_groups, channels=9, seed=0):
    """G perturbed enhancers (every weight and bias nonzero) with BN stats."""
    rng = np.random.default_rng(seed)
    p = jax.vmap(lambda k: E.init_params(k, channels))(
        jax.random.split(jax.random.PRNGKey(seed), n_groups))
    p = jax.tree.map(
        lambda a: a + jnp.asarray(rng.normal(size=a.shape) * 0.1, jnp.float32), p)
    s = {"mean": jnp.asarray(rng.normal(size=(n_groups, channels)), jnp.float32),
         "var": jnp.asarray(rng.uniform(0.5, 2, size=(n_groups, channels)),
                            jnp.float32)}
    return p, s


def _grouped_case(shape, n_groups, seed=0):
    """Smooth slices, edges whose last group is empty (its low edge lies
    above the data) when G > 1, and group 1 gated (rscale 0) when G > 2."""
    from repro.core import grouping

    rng = np.random.default_rng(seed)
    x = jnp.asarray(np.cumsum(rng.normal(size=shape), axis=1).astype(np.float32))
    if n_groups == 1:
        edges = jnp.stack([jnp.min(x), jnp.max(x)])
    else:
        q = jnp.quantile(x, jnp.linspace(0.0, 1.0, n_groups))
        edges = jnp.concatenate([q[:-1], q[-1:] + 1.0, q[-1:] + 2.0])
    rscale = jnp.asarray(rng.uniform(0.1, 1.0, size=n_groups), jnp.float32)
    if n_groups > 2:
        rscale = rscale.at[1].set(0.0)
    ids = np.asarray(grouping.assign_groups(x, edges))
    if n_groups > 1:
        assert not (ids == n_groups - 1).any()  # the empty group
    return x, edges, rscale


def _enhance_both(x, edges, rscale, n_groups, *, residual, clamp, seed=0):
    p, s = _enhancers(n_groups, seed=seed)
    kw = dict(n_groups=n_groups, residual_learning=residual, use_clamp=clamp)
    eb = jnp.float32(0.05)
    got = ops.enhancer_fused_op(x, p, s, edges, rscale, eb, use_pallas=True,
                                interpret=True, **kw)
    want = ref.enhancer_grouped_ref(p, s, x, edges, rscale, eb, **kw)
    return np.asarray(got), np.asarray(want)


def _assert_f32_close(got, want):
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-5 * max(1.0, float(np.abs(want).max())))


@pytest.mark.parametrize("shape", [(4, 64, 64), (4, 16, 32), (2, 24, 64)])
def test_enhancer_fused_matches_ref(shape):
    """The grouped kernel (interpret mode) against the G-fold jnp reference:
    two lane-packed pairs of 64x64 slices in one block, four 16x32 slices on
    the lanes, and 24-row slices (a block height that is not whole steps of
    the per-pixel loop)."""
    from repro.kernels.enhancer_fused import fits

    assert fits(shape)
    x, edges, rscale = _grouped_case(shape, 4, seed=shape[1])
    _assert_f32_close(*_enhance_both(x, edges, rscale, 4, residual=True,
                                     clamp=False, seed=shape[2]))


def test_enhancer_fused_matches_training_forward():
    """One group over every pixel: the kernel's residual is the training
    forward (``enhancer.apply``) of the normalized slices, times rscale."""
    x, edges, _ = _grouped_case((4, 32, 32), 1, seed=3)
    p, s = _enhancers(1, seed=3)
    rscale = jnp.asarray([0.5], jnp.float32)
    got = ops.enhancer_fused_op(x, p, s, edges, rscale, jnp.float32(0.0),
                                n_groups=1, residual_learning=True, use_clamp=False,
                                use_pallas=True, interpret=True)
    xn = (x - edges[0]) / (edges[1] - edges[0])
    pred, _ = E.apply(jax.tree.map(lambda a: a[0], p), jax.tree.map(lambda a: a[0], s),
                      xn, train=False)
    np.testing.assert_allclose(np.asarray(got - x), np.asarray(pred * 0.5),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("residual,clamp", [(True, False), (True, True),
                                            (False, False), (False, True)],
                         ids=["residual", "residual-clamp", "direct", "direct-clamp"])
@pytest.mark.parametrize("n_groups", [1, 4, 20])
def test_enhancer_grouped_matches_ref(n_groups, residual, clamp):
    """G = 1, 4, 20 groups of the published width (C = 9) on 64x64 slices,
    with an empty and a gated group where G > 2, clamp on and off, and the
    direct (non-residual) form."""
    x, edges, rscale = _grouped_case((2, 64, 64), n_groups, seed=n_groups)
    got, want = _enhance_both(x, edges, rscale, n_groups, residual=residual,
                              clamp=clamp, seed=n_groups)
    _assert_f32_close(got, want)
    if clamp:  # inside [x - eb, x + eb] as float32 computes the bounds
        xs, eb = np.asarray(x), np.float32(0.05)
        assert ((got >= xs - eb) & (got <= xs + eb)).all()


def test_enhancer_op_takes_the_reference_where_the_kernel_does_not_fit():
    """Slices whose width does not divide the lanes run the reference, even
    with the kernel asked for."""
    from repro.kernels.enhancer_fused import fits

    x, edges, rscale = _grouped_case((3, 8, 48), 4, seed=5)
    assert not fits(x.shape)
    got, want = _enhance_both(x, edges, rscale, 4, residual=True, clamp=False)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n_groups", [2, 5, 16])
@pytest.mark.parametrize("rows", [16, 64])
def test_group_hist_matches_ref(n_groups, rows):
    rng = np.random.default_rng(n_groups * rows)
    x = jnp.asarray(rng.uniform(-5, 5, size=(rows, 128)).astype(np.float32))
    edges = jnp.asarray(np.quantile(np.asarray(x), np.linspace(0, 1, n_groups + 1)).astype(np.float32))
    ids_a, h_a = ops.group_hist_op(x, edges, n_groups=n_groups, use_pallas=True, interpret=True)
    ids_b, h_b = ref.group_hist_ref(x, edges)
    np.testing.assert_array_equal(np.asarray(ids_a), np.asarray(ids_b))
    np.testing.assert_array_equal(np.asarray(h_a), np.asarray(h_b))
    assert int(h_a.sum()) == x.size


@pytest.mark.parametrize("n_bins", [2, 37, 1000])
@pytest.mark.parametrize("size", [128, 50000])
def test_symbol_hist_matches_ref(n_bins, size):
    rng = np.random.default_rng(n_bins + size)
    vals = jnp.asarray(rng.integers(0, n_bins, size=size).astype(np.int32))
    h_pal = ops.symbol_hist_op(vals, n_bins=n_bins, use_pallas=True, interpret=True)
    h_ref = ops.symbol_hist_op(vals, n_bins=n_bins, use_pallas=False)
    want = np.bincount(np.asarray(vals), minlength=n_bins)
    np.testing.assert_array_equal(np.asarray(h_pal), want)
    np.testing.assert_array_equal(np.asarray(h_ref), want)
    assert int(h_pal.sum()) == size


def test_symbol_hist_ignores_out_of_range():
    vals = jnp.asarray(np.array([-3, 0, 1, 1, 2, 99], np.int32))
    h = ops.symbol_hist_op(vals, n_bins=3, use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(h), [1, 2, 1])


def test_symbol_hist_feeds_huffman_fit():
    """The entropy stage's accelerated frequency count must match np.unique."""
    from repro.sz.entropy import HuffmanCodec

    rng = np.random.default_rng(4)
    codes = rng.choice([0, 0, 0, 1, -1, 2, -7], size=20000).astype(np.int32)
    codec = HuffmanCodec.fit(codes, use_accel=True)
    alphabet, counts = np.unique(codes, return_counts=True)
    np.testing.assert_array_equal(codec.alphabet, alphabet)
    # code lengths must come from the same counts either way
    ref_codec = HuffmanCodec.fit(codes, use_accel=False)
    np.testing.assert_array_equal(codec.lengths, ref_codec.lengths)


def _huffman_kernel_inputs(cs=64, size=4096, seed=77):
    """Codec + padded [C, cs] lens/codes arrays shaped for the encode op."""
    from repro.sz.entropy import HuffmanCodec

    rng = np.random.default_rng(seed)
    codes = rng.choice([0] * 10 + list(range(-30, 30)), size=size).astype(np.int32)
    codec = HuffmanCodec.fit(codes)
    inv = np.searchsorted(codec.alphabet, codes)
    C = -(-codes.size // cs)
    pad = C * cs - codes.size
    lens = np.pad(codec.lengths[inv].astype(np.int32), (0, pad)).reshape(C, cs)
    cws = np.pad(codec.codes[inv].astype(np.uint32).view(np.int32),
                 (0, pad)).reshape(C, cs)
    return codec, codes, lens, cws


@pytest.mark.parametrize("cs", [8, 64, 256])
def test_huffman_encode_matches_ref(cs):
    _codec, _codes, lens, cws = _huffman_kernel_inputs(cs=cs, size=4 * cs + 3)
    w_a, b_a = ops.huffman_encode_op(jnp.asarray(lens), jnp.asarray(cws),
                                     use_pallas=True, interpret=True)
    w_b, b_b = ref.huffman_encode_ref(jnp.asarray(lens), jnp.asarray(cws))
    np.testing.assert_array_equal(np.asarray(w_a), np.asarray(w_b))
    np.testing.assert_array_equal(np.asarray(b_a), np.asarray(b_b))
    # each chunk's bit total is the sum of its member code lengths
    np.testing.assert_array_equal(np.asarray(b_a), lens.sum(axis=1))


def test_huffman_decode_matches_ref():
    """Pallas decode probe == the pure-jnp block oracle == the source codes,
    through the real codec tables and a real packed stream cut into the
    probe's per-chunk word windows."""
    from repro.sz.entropy import _LUT_BITS

    cs = 64
    codec, codes, lens, cws = _huffman_kernel_inputs(cs=cs)
    stream, chunk_bits, _total = codec._device_pack(codes, cs, interpret=True)
    dev = codec._device_tables()
    offsets = (np.cumsum(chunk_bits) - chunk_bits).astype(np.int32)
    W = 128  # every chunk of this stream spans far fewer words
    raw = np.frombuffer(stream, np.uint8)
    padded = np.zeros(4 * (int(offsets.max() >> 5) + W), np.uint8)
    padded[: raw.size] = raw
    words = padded.view(">u4").astype(np.uint32).view(np.int32)
    win = words[(offsets >> 5)[:, None] + np.arange(W)]
    C = chunk_bits.size
    counts = np.full((C, 1), cs, np.int32)
    counts[-1] = codes.size - cs * (C - 1)
    args = [jnp.asarray(a) for a in (win, (offsets & 31)[:, None], counts,
                                     dev["lut"], dev["cw_map"], dev["order"],
                                     dev["len_sorted"])]
    kw = dict(chunk_size=cs, k=_LUT_BITS, n_ids=dev["n_ids"])
    ids_a, steps_a = ops.huffman_decode_op(*args, **kw, use_pallas=True,
                                           interpret=True)
    ids_b, steps_b = ref.huffman_decode_ref(*args, **kw)
    np.testing.assert_array_equal(np.asarray(ids_a), np.asarray(ids_b))
    np.testing.assert_array_equal(np.asarray(steps_a), np.asarray(steps_b))
    flat = np.asarray(ids_a).reshape(-1)[: codes.size]
    np.testing.assert_array_equal(codec.alphabet[flat], codes)


def test_huffman_decode_blocks_stop_when_their_lanes_are_done():
    """Blocks whose lanes need very different step counts: chunks of 1-bit
    codes (several symbols a probe), chunks of codes longer than the LUT
    (the escape, one symbol a probe), a short last chunk and padded lanes.
    Pallas interpret == the jnp reference == the source codes, and each
    block runs the steps its slowest lane needs on the host walk, rounded up
    to a whole check."""
    from repro.kernels import huffman_decode
    from repro.sz import entropy

    cs, bb = 64, 8
    sizes = [2 ** i for i in range(18, 0, -1)] + [1, 1]  # lengths 1 .. 19
    codec = entropy.HuffmanCodec.fit(
        np.repeat(np.arange(len(sizes), dtype=np.int32), sizes))
    long_syms = np.flatnonzero(codec.lengths > entropy._LUT_BITS)
    assert codec.lengths[0] == 1 and long_syms.size, "needs 1-bit and escape codes"
    rng = np.random.default_rng(5)
    ones = np.zeros(cs, np.int32)
    escapes = lambda: codec.alphabet[rng.choice(long_syms, cs)]
    mixed = lambda: rng.choice(np.repeat(np.arange(8, dtype=np.int32), 3), cs)
    chunks = ([ones, escapes()] + [ones] * 6  # block 0: one escape lane
              + [ones] * 8  # block 1: every lane at several symbols a step
              + [mixed(), escapes(), mixed()[:cs // 2 + 3]])  # block 2: 5 pads
    codes = np.concatenate(chunks)
    C = len(chunks)
    stream, chunk_bits, _total = codec._device_pack(codes, cs, interpret=True)
    dev = codec._device_tables()
    offsets = (np.cumsum(chunk_bits) - chunk_bits).astype(np.int64)
    counts = np.full(C, cs, np.int64)
    counts[-1] = codes.size - cs * (C - 1)
    W = 128  # a chunk of 64 19-bit codes spans 38 words
    raw = np.frombuffer(stream, np.uint8)
    padded = np.zeros(4 * (int(offsets.max() >> 5) + W), np.uint8)
    padded[: raw.size] = raw
    words = padded.view(">u4").astype(np.uint32).view(np.int32)
    args = [jnp.asarray(a) for a in (
        words[(offsets >> 5)[:, None] + np.arange(W)],
        (offsets & 31).astype(np.int32)[:, None],
        counts.astype(np.int32)[:, None], dev["lut"], dev["cw_map"],
        dev["order"], dev["len_sorted"])]
    kw = dict(chunk_size=cs, k=entropy._LUT_BITS, n_ids=dev["n_ids"])
    ids_a, steps_a = ops.huffman_decode_op(*args, **kw, use_pallas=True,
                                           interpret=True)
    ids_b, steps_b = ref.huffman_decode_ref(*args, **kw, block_chunks=bb)
    np.testing.assert_array_equal(np.asarray(ids_a), np.asarray(ids_b))
    np.testing.assert_array_equal(np.asarray(steps_a), np.asarray(steps_b))
    flat = np.asarray(ids_a).reshape(-1)[: codes.size]
    np.testing.assert_array_equal(codec.alphabet[flat], codes)
    # the host walk over a block's lanes runs until its slowest lane is
    # done; the kernel checks for that once every CHECK_EVERY steps
    hwords, hpad = entropy._sliding_words(stream, tail_pad=8 * cs + 16)
    walk = np.array([
        entropy._decode_lanes(hwords, hpad, offsets[a:a + bb], counts[a:a + bb],
                              np.empty((cs, min(bb, C - a)), np.uint64),
                              codec._multi_tables())
        for a in range(0, C, bb)])
    check = huffman_decode.CHECK_EVERY
    np.testing.assert_array_equal(np.asarray(steps_a), -(-walk // check) * check)
    assert walk[0] == cs and walk[1] < cs // 2, walk  # the blocks really differ


def test_group_hist_matches_grouping_module():
    """Kernel ids must agree with repro.core.grouping (the pipeline contract)."""
    from repro.core import grouping

    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.uniform(0, 100, size=(32, 128)).astype(np.float32))
    edges = grouping.compute_edges(x, 6, "quantile")
    ids_k, _ = ops.group_hist_op(x, edges, n_groups=6, use_pallas=True, interpret=True)
    ids_g = grouping.assign_groups(x, edges)
    np.testing.assert_array_equal(np.asarray(ids_k), np.asarray(ids_g))
