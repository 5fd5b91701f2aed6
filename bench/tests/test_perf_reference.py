"""The reference's emulated lower precisions round as the formats do."""
import numpy as np
import pytest


@pytest.mark.parametrize("name,dtype", [("bfloat16", "bfloat16"),
                                        ("float8_e4m3", "float8_e4m3fn")])
def test_rounding_matches_the_format(name, dtype):
    import jax.numpy as jnp

    from bench import reference

    rng = np.random.default_rng(0)
    a = (rng.standard_normal(4096) * np.exp(rng.uniform(-8, 5, 4096))).astype(np.float32)
    a = np.clip(a, -440.0, 440.0)
    want = np.asarray(jnp.asarray(a).astype(getattr(jnp, dtype)).astype(jnp.float32))
    got = np.asarray(reference._ROUND[name](jnp.asarray(a)))
    np.testing.assert_array_equal(got, want)


def test_float8_saturates_and_keeps_subnormals():
    import jax.numpy as jnp

    from bench import reference

    got = reference._e4m3(jnp.asarray([500.0, -1e6, 0.3, 0.001], jnp.float32))
    np.testing.assert_array_equal(np.asarray(got), [448.0, -448.0, 0.3125, 1 / 512])
