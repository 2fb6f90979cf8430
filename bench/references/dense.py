"""The plain reference of a dense decoder-only transformer, in float32.

Written from the published model descriptions (Qwen3, Phi-3: pre-norm
RMSNorm blocks, grouped-query attention with split-half RoPE, SwiGLU, a
final RMSNorm and an output head, tied to the embedding where the config
says so; Qwen3 adds an RMSNorm over each head of q and k before RoPE). It
imports nothing of the program and takes only the benchmark's weights.

Every matmul runs at ``Precision.HIGHEST`` (true float32 on a TPU). The
layers are scanned one at a time, each upcast from the stored dtype inside
the scan, so a chip holds the stored weights plus one layer in float32.

``quant="fp8"`` is the control: the same forward with every weight matrix
rounded to float8 e4m3 (one absmax scale per output channel) first, the
lower precision that a later change would be tempted to serve in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import gumbel

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0          # largest finite float8_e4m3fn


def _fp8(w, axis):
    """Round ``w`` to float8 e4m3 with one scale per slice along the
    contracted ``axis`` (per output channel), back in float32."""
    w = w.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=axis, keepdims=True), 1e-30)
    s = s / F8_MAX
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _w(x, quant, axis=-2):
    return _fp8(x, axis) if quant == "fp8" else x.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, theta):
    """Split-half rotary embedding; x: (B, S, N, hd), positions 0..S-1."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(x.shape[1], dtype=np.float64)[:, None] * inv[None]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, p, m, quant):
    B, S, _ = x.shape
    H, K, hd = m.heads, m.kv_heads, m.head_dim
    h = _rms(x, p["ln1"], m.eps)
    q = jnp.einsum("bsd,de->bse", h, _w(p["wq"], quant),
                   precision=HI).reshape(B, S, H, hd)
    k = jnp.einsum("bsd,de->bse", h, _w(p["wk"], quant),
                   precision=HI).reshape(B, S, K, hd)
    v = jnp.einsum("bsd,de->bse", h, _w(p["wv"], quant),
                   precision=HI).reshape(B, S, K, hd)
    if m.qk_norm:
        q = _rms(q, p["q_norm"], m.eps)
        k = _rms(k, p["k_norm"], m.eps)
    q, k = _rope(q, m.rope_theta), _rope(k, m.rope_theta)
    k = jnp.repeat(k, H // K, axis=2)
    v = jnp.repeat(v, H // K, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / np.sqrt(hd)
    causal = np.tril(np.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                   precision=HI).reshape(B, S, H * hd)
    x = x + jnp.einsum("bse,ed->bsd", o, _w(p["wo"], quant), precision=HI)
    h = _rms(x, p["ln2"], m.eps)
    f = p["ffn"]
    g = jnp.einsum("bsd,df->bsf", h, _w(f["wg"], quant), precision=HI)
    u = jnp.einsum("bsd,df->bsf", h, _w(f["wi"], quant), precision=HI)
    return x + jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u,
                          _w(f["wo"], quant), precision=HI)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def logits(weights, tokens, pos, *, m, quant=None):
    """(B, P, vocab) float32 logits at positions ``pos`` (B, P) of
    ``tokens`` (B, S); the head runs at those positions only."""
    emb = weights["embed"]["tok"]
    table = _w(emb, quant, axis=-1)
    x = jnp.take(table, tokens, axis=0)

    def body(x, p):
        return _layer(x, p, m, quant), None
    x, _ = jax.lax.scan(body, x, weights["decoder"]["layers"]["block0"])
    x = jnp.take_along_axis(x, pos[..., None], axis=1)
    x = _rms(x, weights["decoder"]["final_norm"], m.eps)
    if m.tied:
        out = jnp.einsum("bsd,vd->bsv", x, table, precision=HI)
    else:
        out = jnp.einsum("bsd,dv->bsv", x, _w(weights["lm_head"], quant),
                         precision=HI)
    return out[..., :m.vocab]


@functools.partial(jax.jit, static_argnames=("m", "kmax", "margin"))
def gaps(weights, tokens, pos, probes, temp, top_k, keys, *, m, kmax,
         margin):
    """For every probed position: how far each probe token (probes
    (B, P, n)) lies from what the sampling rule picks on the float32
    reference's logits (``bench.gumbel.token_gaps``)."""
    lg = logits(weights, tokens, pos, m=m)
    return jnp.stack([
        gumbel.token_gaps(lg, probes[..., i], temp, top_k, keys, kmax=kmax,
                          margin=margin)
        for i in range(probes.shape[-1])], axis=-1)


@functools.partial(jax.jit, static_argnames=("m", "quant", "kmax"))
def pick(weights, tokens, pos, temp, top_k, keys, *, m, quant, kmax):
    """The token the reference at ``quant`` precision serves at each
    probed position, by the same sampling rule."""
    return gumbel.pick(logits(weights, tokens, pos, m=m, quant=quant), temp,
                       top_k, keys, kmax=kmax)
