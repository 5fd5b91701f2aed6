"""The GWLZ learnable enhancer (paper Fig. 3).

Encoder-decoder CNN: Conv3x3(1->C) -> BatchNorm -> ReLU -> Conv3x3(C->1),
C = 9 channels, ~190 trainable parameters + 2*C running BN stats.  Slices of
the volume are treated as single-channel images; the model predicts the
*normalized residual map* (DnCNN-style residual learning, §3.2).

Parameters are a flat dict pytree so a batch of G enhancers is just the same
pytree with a leading G axis (vmap over models — DESIGN.md §3.3).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

DEFAULT_CHANNELS = 9
BN_EPS = 1e-5
_BN_MOMENTUM = 0.1


def init_params(key: jax.Array, channels: int = DEFAULT_CHANNELS, ksize: int = 3) -> dict:
    k1, k2 = jax.random.split(key)
    fan1 = ksize * ksize * 1
    fan2 = ksize * ksize * channels
    return {
        "w1": jax.random.normal(k1, (ksize, ksize, 1, channels)) * (2.0 / fan1) ** 0.5,
        "b1": jnp.zeros((channels,)),
        "gamma": jnp.ones((channels,)),
        "beta": jnp.zeros((channels,)),
        "w2": jax.random.normal(k2, (ksize, ksize, channels, 1)) * (2.0 / fan2) ** 0.5,
        "b2": jnp.zeros((1,)),
    }


def init_state(channels: int = DEFAULT_CHANNELS) -> dict:
    """Non-trainable BN running statistics (stored in the artifact)."""
    return {"mean": jnp.zeros((channels,)), "var": jnp.ones((channels,))}


def param_count(params: dict) -> int:
    return sum(int(p.size) for p in jax.tree_util.tree_leaves(params))


def _shifts3x3(x: jax.Array) -> jax.Array:
    """[..., H, W, C] -> [..., H, W, 9, C]: the 3x3 neighborhood per pixel
    (zero-padded borders, identical to SAME conv)."""
    H, W = x.shape[-3], x.shape[-2]
    xp = jnp.pad(x, [(0, 0)] * (x.ndim - 3) + [(1, 1), (1, 1), (0, 0)])
    taps = [
        jax.lax.slice_in_dim(jax.lax.slice_in_dim(xp, dy, dy + H, axis=x.ndim - 3), dx, dx + W, axis=x.ndim - 2)
        for dy in range(3)
        for dx in range(3)
    ]
    return jnp.stack(taps, axis=-2)


def _conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """3x3 SAME conv expressed as shift+matmul.

    XLA CPU's conv *transpose* (the backward pass) is ~12x slower than the
    equivalent dot at these tiny channel counts, so the matmul form makes
    group-wise training tractable on the host (a choice tuned on the CPU).
    Training and the CPU's inference use it; inference on the TPU runs the
    grouped Pallas kernel (repro.kernels.enhancer_fused) instead.
    x: [B, H, W, Cin]; w: [3, 3, Cin, Cout].
    """
    p = _shifts3x3(x)  # [B,H,W,9,Cin]
    kh, kw, cin, cout = w.shape
    y = jnp.einsum("bhwkc,kco->bhwo", p, w.reshape(9, cin, cout))
    return y + b


def apply(
    params: dict,
    state: dict,
    x: jax.Array,
    *,
    train: bool,
    mask: jax.Array | None = None,
) -> tuple[jax.Array, dict]:
    """Forward pass.

    ``x``: [B, H, W] normalized single-channel slices (placeholder zeros
    outside the group).  Returns ([B, H, W] predicted normalized residual,
    new BN state).  In train mode BN uses batch statistics over in-group
    pixels only (placeholders would otherwise poison the statistics).
    """
    h = _conv(x[..., None], params["w1"], params["b1"])
    if train:
        if mask is not None:
            m = mask[..., None].astype(h.dtype)
            cnt = jnp.maximum(m.sum(axis=(0, 1, 2)), 1.0)
            mean = (h * m).sum(axis=(0, 1, 2)) / cnt
            var = ((h - mean) ** 2 * m).sum(axis=(0, 1, 2)) / cnt
        else:
            mean = h.mean(axis=(0, 1, 2))
            var = h.var(axis=(0, 1, 2))
        new_state = {
            "mean": (1 - _BN_MOMENTUM) * state["mean"] + _BN_MOMENTUM * mean,
            "var": (1 - _BN_MOMENTUM) * state["var"] + _BN_MOMENTUM * var,
        }
    else:
        mean, var = state["mean"], state["var"]
        new_state = state
    h = (h - mean) * lax.rsqrt(var + BN_EPS) * params["gamma"] + params["beta"]
    h = jax.nn.relu(h)
    out = _conv(h, params["w2"], params["b2"])
    return out[..., 0], new_state

