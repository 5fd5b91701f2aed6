"""The device field generator keeps the NumPy recipe's statistics."""
import numpy as np
import pytest

from bench import fields


@pytest.mark.parametrize("field", fields.FIELDS)
def test_statistics_match_the_numpy_generator(field):
    from repro.data import nyx_like_field

    ours = [np.asarray(fields.make_field(field, 64, s, 7, 16)) for s in (0, 1, 2**35 + 1)]
    theirs = [nyx_like_field((64,) * 3, field, seed=s) for s in (0, 1, 2)]
    for a in ours:
        assert a.dtype == np.float32 and a.shape == (64, 64, 64)
    if field == "temperature":  # rescaled to Table 1's min and max exactly
        for a in ours:
            assert a.min() == pytest.approx(2281.0, rel=1e-5)
            assert a.max() == pytest.approx(4.78e6, rel=1e-5)
        means = [a.mean() for a in ours + theirs]
        assert 3e3 < min(means) and max(means) < 2e4
    else:  # mean 1, clumped: most mass near 0, a long tail
        for a in ours + theirs:
            assert a.mean() == pytest.approx(1.0, rel=1e-4)
            assert 0.0 <= a.min() < 1e-3
            assert 3e2 < a.max() < 3e4


def _tiles(a, t):
    n = a.shape[0] // t
    return a.reshape(n, t, n, t, n, t).transpose(0, 2, 4, 1, 3, 5).reshape(n ** 3, -1)


def test_same_seed_same_field_and_seeds_differ():
    a = np.asarray(fields.make_field("temperature", 32, 5, 2**33 + 9, 8))
    b = np.asarray(fields.make_field("temperature", 32, 5, 2**33 + 9, 8))
    c = np.asarray(fields.make_field("temperature", 32, 5, 9, 8))
    d = np.asarray(fields.make_field("temperature", 32, 6, 9, 8))
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    # another run seed: the same tiles in another order
    ta, tc, td = _tiles(a, 8), _tiles(c, 8), _tiles(d, 8)
    key = lambda t: sorted(map(bytes, t))  # noqa: E731
    assert key(ta) == key(tc)
    # another field seed: other tiles
    assert key(tc) != key(td)


def test_tile_must_divide_the_side():
    with pytest.raises(ValueError):
        fields.make_field("temperature", 32, 0, 0, 12)
