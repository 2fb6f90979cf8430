"""Attention: GQA reference implementation + kernel dispatch.

``backend='reference'`` is pure jnp (the dry-run uses it so
``cost_analysis`` counts true attention FLOPs); ``'pallas'`` routes to
the Pallas TPU kernels, compiled for the chip or — when the caller asks
for it with ``interpret`` — run by the Pallas interpreter (CPU tests).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _group(q, num_kv):
    B, S, H, hd = q.shape
    G = H // num_kv
    return q.reshape(B, S, num_kv, G, hd), G


def attention_ref(q, k, v, *, causal: bool, q_offset=0,
                  kv_len=None) -> jax.Array:
    """q: (B,Sq,H,hd); k,v: (B,T,K,hd). GQA-aware, fp32 softmax.

    q_offset: absolute position of q[0] (prefill continuation / decode).
    kv_len: optional valid KV length (int or scalar array) — masks t >= kv_len.
    """
    B, Sq, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    qg, G = _group(q, K)
    logits = jnp.einsum("bskgh,btkh->bkgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(hd)
    mask = None
    if causal:
        s_pos = jnp.arange(Sq)[:, None] + q_offset
        t_pos = jnp.arange(T)[None, :]
        mask = t_pos <= s_pos                                  # (Sq, T)
    if mask is not None:
        logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    if kv_len is not None:
        kv_len = jnp.asarray(kv_len)
        if kv_len.ndim == 0:                                   # scalar
            lm = (jnp.arange(T) < kv_len)[None, None, None, None, :]
        else:                                                  # per-batch (B,)
            lm = (jnp.arange(T)[None, :] <
                  kv_len[:, None])[:, None, None, None, :]
        logits = jnp.where(lm, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkgst,btkh->bskgh", p.astype(v.dtype), v)
    return o.reshape(B, Sq, H, hd)


def attention(q, k, v, *, causal: bool, backend: str = "reference",
              q_offset=0, kv_len=None, interpret: bool = False) -> jax.Array:
    # q_offset may be a traced offset (chunked prefill): the kernel takes
    # it as a prefetched scalar
    if backend == "pallas" and kv_len is None:
        from repro.kernels import ops as kops
        return kops.flash_attention(q, k, v, q_offset, causal=causal,
                                    backend="pallas", interpret=interpret)
    return attention_ref(q, k, v, causal=causal, q_offset=q_offset,
                         kv_len=kv_len)


def decode_attention(q, k_cache, v_cache, pos, *, backend: str = "reference",
                     interpret: bool = False) -> jax.Array:
    """Single-token decode. q: (B,1,H,hd); caches: (B,S,K,hd); pos: scalar —
    the index the current token was just written to (attend to <= pos).
    Per-slot pos (B,) is the continuous-batching shape; pos[b] < 0 marks an
    inactive slot (kv_len 0 — its output is meaningless and discarded)."""
    if backend == "pallas":
        from repro.kernels import ops as kops
        return kops.flash_decode(q, k_cache, v_cache, pos, backend="pallas",
                                 interpret=interpret)
    return attention_ref(q, k_cache, v_cache, causal=False, kv_len=pos + 1)


def paged_decode_attention(q, k_pages, v_pages, tables, pos, *,
                           k_scale=None, v_scale=None,
                           backend: str = "reference",
                           interpret: bool = False) -> jax.Array:
    """Single-token decode over the paged KV pool. q: (B,1,H,hd);
    k_pages/v_pages: (P,page,K,hd); tables: (B,NP) int32 page ids; pos:
    (B,) int32 last valid logical index (attend <= pos; < 0 = inactive
    slot, output row exactly zero). With ``k_scale``/``v_scale``
    ((P,page,K) fp32) the pools are int8 and the quantized kernel
    dequantizes in-tile."""
    from repro.kernels import ops as kops
    impl = "pallas" if backend == "pallas" else "ref"
    if k_scale is not None:
        return kops.paged_decode_quant(q, k_pages, v_pages, k_scale,
                                       v_scale, tables, pos, backend=impl,
                                       interpret=interpret)
    return kops.paged_decode(q, k_pages, v_pages, tables, pos, backend=impl,
                             interpret=interpret)
