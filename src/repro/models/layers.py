"""Shared primitive layers (pure functions, explicit params)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32)).astype(dt)


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32)
                            / head_dim))


def yarn_freqs(head_dim: int, rope) -> np.ndarray:
    """YaRN (NTK-by-parts) inverse frequencies of a ``RopeConfig``:
    dimensions that turn more than ``beta_fast`` times over the original
    context keep their frequency, those that turn fewer than ``beta_slow``
    times are divided by ``yarn_factor``, with a linear ramp between (the
    correction range floored and ceiled, as in the published recipe)."""
    base, dim = float(rope.theta), head_dim

    def corr(rot):
        return dim * math.log(rope.original_max_position
                              / (rot * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(corr(rope.beta_fast)), 0)
    high = min(math.ceil(corr(rope.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    pos = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extra = 1.0 - ramp                      # share kept extrapolated
    inv = ((1.0 / (rope.yarn_factor * pos)) * (1.0 - extra)
           + (1.0 / pos) * extra)
    return inv.astype(np.float32)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               rope=None) -> jax.Array:
    """Rotary embedding, split-half (llama) convention.

    x: (B, S, H, hd); positions: (S,) or (B, S) int. ``rope`` (a
    ``RopeConfig`` with ``yarn_factor`` > 0) takes YaRN's frequencies and
    scales cos and sin by its ``attention_factor``; otherwise plain RoPE
    at ``theta``.
    """
    hd = x.shape[-1]
    yarn = rope is not None and rope.yarn_factor > 0
    inv = yarn_freqs(hd, rope) if yarn else rope_freqs(hd, theta)
    ang = positions.astype(jnp.float32)[..., None] * inv      # (..., S, hd/2)
    if ang.ndim == 2:                                          # (S, hd/2)
        ang = ang[None]                                        # (1, S, hd/2)
    cos = jnp.cos(ang)[:, :, None, :]                          # (B|1,S,1,hd/2)
    sin = jnp.sin(ang)[:, :, None, :]
    if yarn:
        cos = cos * rope.attention_factor
        sin = sin * rope.attention_factor
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def swiglu(x: jax.Array, wi: jax.Array, wg: jax.Array, wo: jax.Array,
           cdt) -> jax.Array:
    x = x.astype(cdt)
    h = jax.nn.silu(x @ wg.astype(cdt)) * (x @ wi.astype(cdt))
    return h @ wo.astype(cdt)


def embed_lookup(table: jax.Array, tokens: jax.Array, cdt) -> jax.Array:
    return jnp.take(table, tokens, axis=0).astype(cdt)


def causal_conv1d(x: jax.Array, w: jax.Array, b: jax.Array,
                  state: jax.Array | None = None):
    """Depthwise causal conv over time. x: (B, S, C); w: (W, C); b: (C,).

    If ``state`` is given — (B, W-1, C), the tail of the previous segment —
    it is prepended (decode / chunked prefill), and the new tail returned.
    """
    B, S, C = x.shape
    W = w.shape[0]
    if state is None:
        state = jnp.zeros((B, W - 1, C), x.dtype)
    xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)  # (B, S+W-1, C)
    out = jnp.zeros((B, S, C), jnp.float32)
    for i in range(W):                                        # W is tiny (4)
        out = out + xp[:, i:i + S, :].astype(jnp.float32) * \
            w[i].astype(jnp.float32)
    out = out + b.astype(jnp.float32)
    new_state = xp[:, S:, :] if W > 1 else state
    return out.astype(x.dtype), new_state
