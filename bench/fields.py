"""Nyx-like fields made on the device from a seed.

The recipe of ``repro.data.synthetic`` (power-law Gaussian random fields,
log-skewed, rescaled to the statistics of Table 1 of the GWLZ paper),
written again in ``jax.numpy`` so that a 512^3 field takes one jitted call
on the chip instead of a minute of host FFTs.  The random stream differs
from the NumPy generator's, so the two agree in distribution (min, mean,
max), not value by value.

A run's field is one base field, made from the configuration's
``field_seed``, with its tiles put in an order drawn from the run's seed.
Every seed then hands the compressor the same tiles in another order: the
same work, since each tile is coded on its own, while the program compiles
one small program per distinct alphabet span it meets (PERF.md).  The same
seed gives the same field.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

FIELDS = ("temperature", "dark_matter_density")
# Table 1 of the paper: temperature min 2281 / max 4.78e6
T_MIN, T_MAX = 2281.0, 4.78e6


def _key(seed: int) -> jax.Array:
    """A key for any whole number: the low and high 32 bits both count."""
    seed = int(seed)
    k = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def _grf(key: jax.Array, shape: tuple[int, int, int], power: float) -> jax.Array:
    """Isotropic Gaussian random field with spectrum k**power, unit variance."""
    white = jax.random.normal(key, shape, jnp.float32)
    f = jnp.fft.rfftn(white)
    k2 = jnp.zeros(f.shape, jnp.float32)
    for ax, n in enumerate(shape):
        fr = jnp.fft.rfftfreq(n) if ax == len(shape) - 1 else jnp.fft.fftfreq(n)
        bshape = [1] * len(shape)
        bshape[ax] = fr.shape[0]
        k2 = k2 + (fr.astype(jnp.float32) ** 2).reshape(bshape)
    k = jnp.sqrt(k2)
    origin = (k2 == 0)
    amp = jnp.where(origin, 0.0, jnp.where(origin, 1.0, k) ** (power / 2.0))
    g = jnp.fft.irfftn(f * amp, s=shape).astype(jnp.float32)
    return g / (jnp.std(g) + 1e-12)


def _base(key: jax.Array, shape: tuple[int, int, int], field: str) -> jax.Array:
    kg, kf = jax.random.split(key)
    g = _grf(kg, shape, -2.4)
    fine = _grf(kf, shape, -1.2)
    if field == "temperature":
        lnt = 0.6 * g + 0.18 * fine + 1.4 * jnp.clip(g - 1.1, 0.0, None) ** 2
        lo, hi = jnp.log(T_MIN), jnp.log(T_MAX)
        lnt = lo + (lnt - lnt.min()) * (hi - lo) / (lnt.max() - lnt.min() + 1e-9)
        return jnp.exp(lnt).astype(jnp.float32)
    x = jnp.exp(2.2 * g + 0.4 * fine)
    return (x / x.mean()).astype(jnp.float32)


@partial(jax.jit, static_argnames=("side", "field", "tile"))
def _make(base_key: jax.Array, order_key: jax.Array, *, side: int, field: str,
          tile: int) -> jax.Array:
    base = _base(base_key, (side, side, side), field)
    n = side // tile
    tiles = (base.reshape(n, tile, n, tile, n, tile).transpose(0, 2, 4, 1, 3, 5)
             .reshape(n ** 3, tile, tile, tile))
    tiles = tiles[jax.random.permutation(order_key, n ** 3)]
    return (tiles.reshape(n, n, n, tile, tile, tile).transpose(0, 3, 1, 4, 2, 5)
            .reshape(side, side, side))


def make_field(field: str, side: int, field_seed: int, seed: int, tile: int) -> jax.Array:
    """The ``side``^3 float32 field named ``field``, on the default device:
    the base field of ``field_seed`` with its ``tile``^3 tiles in the order
    that ``seed`` draws."""
    if field not in FIELDS:
        raise ValueError(f"unknown field {field!r}; known: {FIELDS}")
    if side % tile:
        raise ValueError(f"tile {tile} does not divide side {side}")
    return _make(_key(field_seed), _key(seed), side=side, field=field, tile=tile)
