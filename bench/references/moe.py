"""The plain reference of a sparse-expert decoder transformer with an
attention kind per layer (Mellum2, ``bench/families/moe.py``), in float32.

Written from the published description (``config.json``: ``layer_types``,
``sliding_window``, ``rope_parameters``, ``num_experts_per_tok``,
``norm_topk_prob``; the Qwen3-MoE block it follows): pre-norm RMSNorm
blocks; grouped-query attention with an RMSNorm over each head of q and k
before a split-half rotary embedding; a sliding layer lets position p
attend to (p - window, p] with plain RoPE, a full layer to [0, p] with
YaRN (NTK-by-parts frequencies, cos and sin scaled by the attention
factor); then a router softmax over every expert, the top k gates
renormalised to one, and the SwiGLU of each expert this chip holds,
evaluated densely for every token and weighted by its gate (zero where
the token did not pick it). What the absent experts add is left out, as
the program leaves it out. A final RMSNorm and an untied head. It imports
nothing of the program and takes only the benchmark's weights.

Every matmul runs at ``Precision.HIGHEST`` (true float32 on a TPU). The
periods are scanned one at a time, each layer upcast from the stored dtype
inside the scan, and attention is computed in blocks of query rows
(a sliding layer's block only against its window's keys), so a 16k-token
context fits beside the stored weights.

``quant="fp8"`` is the control: the same forward with every weight matrix
(the router's too) rounded to float8 e4m3 with one absmax scale per output
channel.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import gumbel

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0
#: query rows per attention block
Q_BLOCK = 256


def _fp8(w, axis):
    w = w.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=axis, keepdims=True), 1e-30)
    s = s / F8_MAX
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _w(x, quant, axis=-2):
    return _fp8(x, axis) if quant == "fp8" else x.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def inv_freq(hd: int, rope: tuple) -> np.ndarray:
    """Inverse frequencies of one RoPE section: plain, or YaRN's blend of
    the original and the ``factor``-interpolated frequencies over the
    correction range of (beta_fast, beta_slow) rotations."""
    theta, factor, orig, fast, slow, _ = rope
    base = theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    if not factor:
        return 1.0 / base

    def dim_of(rot):
        return (hd * math.log(orig / (rot * 2 * math.pi))
                / (2 * math.log(theta)))
    lo = max(math.floor(dim_of(fast)), 0)
    hi = min(math.ceil(dim_of(slow)), hd - 1)
    if lo == hi:
        hi += 0.001
    interp_share = np.clip((np.arange(hd // 2) - lo) / (hi - lo), 0.0, 1.0)
    return (1.0 / base) * (1.0 - interp_share) \
        + (1.0 / (factor * base)) * interp_share


def _rope(x, rope: tuple):
    """x: (B, S, N, hd) at positions 0..S-1."""
    hd = x.shape[-1]
    ang = np.arange(x.shape[1], dtype=np.float64)[:, None] \
        * inv_freq(hd, rope)[None]
    scale = rope[5] if rope[1] else 1.0
    cos = jnp.asarray(np.cos(ang) * scale, jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang) * scale, jnp.float32)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, window: int):
    """Causal GQA attention in blocks of query rows. q: (B, S, H, hd);
    k, v: (B, S, K, hd) -> (B, S, H, hd). A sliding layer's block reads
    only the keys of its window."""
    B, S, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    qb = min(Q_BLOCK, S)
    assert S % qb == 0
    span = S if not window else min(S, window + qb)
    pad = span - qb if window else 0
    kp = jnp.pad(k, ((0, 0), (pad, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (pad, 0), (0, 0), (0, 0)))

    def block(i):
        q0 = i * qb
        qi = jax.lax.dynamic_slice_in_dim(q, q0, qb, axis=1)
        k0 = q0 + qb - span if window else 0       # position of key row 0
        ki = jax.lax.dynamic_slice_in_dim(kp, k0 + pad, span, axis=1)
        vi = jax.lax.dynamic_slice_in_dim(vp, k0 + pad, span, axis=1)
        qpos = q0 + jnp.arange(qb)[:, None]
        kpos = k0 + jnp.arange(span)[None, :]
        ok = (kpos <= qpos) & (kpos >= 0)
        if window:
            ok = ok & (kpos > qpos - window)
        qg = qi.reshape(B, qb, K, G, hd)
        s = jnp.einsum("bqkgd,btkd->bkgqt", qg, ki, precision=HI) \
            / np.sqrt(hd)
        s = jnp.where(ok[None, None, None], s, -jnp.inf)
        o = jnp.einsum("bkgqt,btkd->bqkgd", jax.nn.softmax(s, axis=-1), vi,
                       precision=HI)
        return o.reshape(B, qb, H, hd)
    out = jax.lax.map(block, jnp.arange(S // qb))       # (nb, B, qb, H, hd)
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, hd)


def _experts(h, p, m, quant):
    """The held experts' part of the expert layer. h: (B, S, D)."""
    f = p["moe"]
    logits = jnp.einsum("bsd,de->bse", h, _w(f["router"], quant),
                        precision=HI)
    probs = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    gate = jnp.sum(jax.nn.one_hot(idx, m.experts, dtype=jnp.float32)
                   * top[..., None], axis=-2)                   # (B, S, E)

    def one(acc, e):
        wg = _w(f["wg"][e], quant)
        wi = _w(f["wi"][e], quant)
        wo = _w(f["wo"][e], quant)
        y = jnp.einsum("bsf,fd->bsd",
                       jax.nn.silu(jnp.einsum("bsd,df->bsf", h, wg,
                                              precision=HI))
                       * jnp.einsum("bsd,df->bsf", h, wi, precision=HI),
                       wo, precision=HI)
        g = jnp.take(gate, m.held_offset + e, axis=-1)
        return acc + g[..., None] * y, None
    y, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(m.experts_held))
    return y


def _layer(x, p, m, j, quant):
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
    q, k = _rope(q, m.ropes[j]), _rope(k, m.ropes[j])
    o = _attend(q, k, v, m.windows[j]).reshape(B, S, H * hd)
    x = x + jnp.einsum("bse,ed->bsd", o, _w(p["wo"], quant), precision=HI)
    return x + _experts(_rms(x, p["ln2"], m.eps), p, m, quant)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def logits(weights, tokens, pos, *, m, quant=None):
    """(B, P, vocab) float32 logits at positions ``pos`` (B, P) of
    ``tokens`` (B, S); the head runs at those positions only."""
    x = jnp.take(_w(weights["embed"]["tok"], quant, axis=-1), tokens, axis=0)
    layers = weights["decoder"]["layers"]

    def body(x, period):
        for j in range(m.period):
            x = _layer(x, period[f"block{j}"], m, j, quant)
        return x, None
    x, _ = jax.lax.scan(body, x, layers)
    x = jnp.take_along_axis(x, pos[..., None], axis=1)
    x = _rms(x, weights["decoder"]["final_norm"], m.eps)
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
