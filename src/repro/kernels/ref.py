"""Pure-jnp oracles for every Pallas kernel (the `ref.py` contract).

These are the ground truth for the per-kernel sweep tests and the lowering
path used on non-TPU backends / in the dry-run (so cost_analysis counts
real FLOPs rather than opaque custom calls).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------
def flash_attention_ref(q, k, v, causal: bool = True, q_offset=0,
                        window: int = 0, k_offset=0):
    """q: (B,S,H,hd); k,v: (B,T,K,hd) with H % K == 0. fp32 softmax.
    q_offset: absolute position of q[:, 0] (causal: row s sees keys
    t <= s + q_offset). window > 0: keys at absolute positions
    (p - window, p] only, k[:, 0] being at position k_offset (keys at
    negative positions are masked)."""
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    qg = q.reshape(B, S, K, G, hd)
    logits = jnp.einsum("bskgh,btkh->bkgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(hd)
    if causal:
        qpos = jnp.arange(S)[:, None] + q_offset
        kpos = jnp.arange(T)[None, :]
        if window:
            kpos = kpos + k_offset
        mask = kpos <= qpos
        if window:
            mask = mask & (kpos > qpos - window) & (kpos >= 0)
        logits = jnp.where(mask[None, None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkgst,btkh->bskgh", p, v.astype(jnp.float32))
    return o.reshape(B, S, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# flash_decode
# ---------------------------------------------------------------------------
def flash_decode_ref(q, k, v, pos, window: int = 0):
    """q: (B,1,H,hd); k,v: (B,T,K,hd); attend to indices <= pos (a
    scalar, or (B,) per row), and > pos - window when window > 0."""
    B, _, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    qg = q.reshape(B, K, G, hd)
    logits = jnp.einsum("bkgh,btkh->bkgt", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(hd)
    t = jnp.arange(T)[None, :]
    pcol = jnp.reshape(pos, (-1, 1))
    valid = t <= pcol
    if window:
        valid = valid & (t > pcol - window)
    valid = valid[:, None, None, :]
    logits = jnp.where(valid, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkgt,btkh->bkgh", p, v.astype(jnp.float32))
    return o.reshape(B, 1, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# paged_decode (flash_decode over a block-table-indirected paged KV pool)
# ---------------------------------------------------------------------------
def _layer_rows(pages, layer, tables):
    """Each sequence's logical (NP*page)-row view of one layer's pool:
    pages (L,P,page,...) gathered at [layer, tables] -> (B,NP*page,...)."""
    rows = pages[layer, tables]
    B, NP, page = rows.shape[:3]
    return rows.reshape((B, NP * page) + rows.shape[3:])


def paged_decode_ref(q, k_pages, v_pages, tables, pos, layer,
                     window: int = 0):
    """q: (B,1,H,hd); k_pages/v_pages: (L,P,page,K,hd) — the shared page
    pools of L layers, stacked; layer: the one read; tables: (B,NP) int32
    page ids forming each sequence's logical (NP*page)-token view; pos:
    (B,) int32 — last valid logical index per sequence (attend to <= pos;
    pos < 0 means no valid tokens and the output row is exactly zero,
    matching the Pallas kernel's zero-init accumulator when every tile is
    skipped); window > 0: and to > pos - window."""
    return _paged_attend(q, _layer_rows(k_pages, layer, tables),
                         _layer_rows(v_pages, layer, tables), pos, window)


def _paged_attend(q, k, v, pos, window: int = 0):
    B, _, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    qg = q.reshape(B, K, G, hd)
    logits = jnp.einsum("bkgh,btkh->bkgt", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(hd)
    t = jnp.arange(T)[None, :]
    valid = t <= pos[:, None]
    if window:
        valid = valid & (t > pos[:, None] - window)
    valid = valid[:, None, None, :]
    logits = jnp.where(valid, logits, -1e30)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.where(valid, jnp.exp(logits - m), 0.0)
    denom = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    o = jnp.einsum("bkgt,btkh->bkgh", p / denom, v.astype(jnp.float32))
    return o.reshape(B, 1, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# grouped_ffn (dropless expert FFN over rows grouped by expert)
# ---------------------------------------------------------------------------
def grouped_ffn_ref(x, wg, wi, wo, group_sizes):
    """x: (M, D) rows sorted by group, group g owning the next
    group_sizes[g] rows; wg/wi (G, D, F), wo (G, F, D). SwiGLU of each row
    through its group's expert; rows past sum(group_sizes) are zero."""
    g = jax.lax.ragged_dot(x, wg, group_sizes,
                           preferred_element_type=jnp.float32)
    u = jax.lax.ragged_dot(x, wi, group_sizes,
                           preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    return jax.lax.ragged_dot(h, wo, group_sizes,
                              preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# int8 paged KV (per-row/head symmetric scales — see serve/paged.py)
# ---------------------------------------------------------------------------
def kv_quant_ref(x):
    """Symmetric int8 quantization of a KV tensor over its last (hd) axis.
    x: (..., hd) float -> (q int8 same shape, scale fp32 shape[:-1]).
    scale = max|x| / 127 per (page-row, head), so a later dequant-requant
    round-trip is exact (q_max lands on 127 by construction)."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def kv_dequant_ref(q, scale, dtype=jnp.float32):
    """Inverse of kv_quant_ref: (..., hd) int8 x (...) fp32 -> float."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def paged_decode_quant_ref(q, k_pages, v_pages, k_scale, v_scale,
                           tables, pos, layer):
    """paged_decode_ref over int8 page pools: k/v_pages (L,P,page,K,hd)
    int8 with per-(row,head) scales (L,P,page,K) fp32, dequantized before
    the fp32 attention math."""
    k = kv_dequant_ref(_layer_rows(k_pages, layer, tables),
                       _layer_rows(k_scale, layer, tables))
    v = kv_dequant_ref(_layer_rows(v_pages, layer, tables),
                       _layer_rows(v_scale, layer, tables))
    return _paged_attend(q, k, v, pos)


# ---------------------------------------------------------------------------
# fused_sample (in-kernel temperature/top-k Gumbel sampling)
# ---------------------------------------------------------------------------
def fused_sample_ref(logits, temp, top_k, keys, *, vocab_size: int):
    """jnp oracle for kernels/sampling.fused_sample: same prepare_rows
    front half, same portable counter-hash Gumbel noise, plain argmax.
    Bit-identical to both the Pallas kernel and ServeEngine._sample."""
    from repro.kernels.sampling import jnp_gumbel, prepare_rows
    z, noisy = prepare_rows(logits, temp, top_k, vocab_size=vocab_size)
    idx = jnp.arange(z.shape[1], dtype=jnp.uint32)
    g = jnp_gumbel(jnp.asarray(keys, jnp.int32)[:, None, :], idx[None, :])
    y = jnp.where(noisy[:, None], z + g, z)
    return jnp.argmax(y, axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# ssm_scan (chunked scalar-decay linear recurrence — see models/ssm.py)
# ---------------------------------------------------------------------------
def ssm_scan_ref(xdt, Bv, Cv, log_a, chunk: int = 128):
    from repro.models.ssm import ssd_chunked
    return ssd_chunked(xdt, Bv, Cv, log_a, h0=None, chunk=chunk)


def ssm_scan_sequential_ref(xdt, Bv, Cv, log_a):
    """O(S) sequential oracle (slow, exact)."""
    B, S, H, hd = xdt.shape

    def step(h, t):
        a = jnp.exp(log_a[:, t].astype(jnp.float32))
        h = a[..., None, None] * h + jnp.einsum(
            "bhd,bn->bhdn", xdt[:, t].astype(jnp.float32),
            Bv[:, t].astype(jnp.float32))
        y = jnp.einsum("bhdn,bn->bhd", h, Cv[:, t].astype(jnp.float32))
        return h, y

    h0 = jnp.zeros((B, H, hd, Bv.shape[-1]), jnp.float32)
    hf, ys = jax.lax.scan(step, h0, jnp.arange(S))
    return jnp.swapaxes(ys, 0, 1), hf


# ---------------------------------------------------------------------------
# qdma_pack / qdma_unpack
# ---------------------------------------------------------------------------
def qdma_pack_ref(x, block: int = 256):
    """Blockwise symmetric int8 quantization over the last dim.
    Returns (q int8 same shape, scale fp32 shape[:-1]+(L/block,))."""
    L = x.shape[-1]
    assert L % block == 0
    xb = x.astype(jnp.float32).reshape(x.shape[:-1] + (L // block, block))
    scale = jnp.max(jnp.abs(xb), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(xb / scale[..., None]), -127, 127).astype(jnp.int8)
    return q.reshape(x.shape), scale


def qdma_unpack_ref(q, scale, dtype="float32"):
    block = q.shape[-1] // scale.shape[-1]
    qb = q.reshape(q.shape[:-1] + (scale.shape[-1], block))
    x = qb.astype(jnp.float32) * scale[..., None]
    return x.reshape(q.shape).astype(dtype)


def qdma_pack_rows_ref(x, lo, rows: int, block: int = 256):
    """Pack rows [lo, lo+rows) of the 2-D row view of x (one descriptor)."""
    x2 = x.reshape(-1, x.shape[-1]) if x.ndim else x.reshape(1, 1)
    chunk = jax.lax.dynamic_slice_in_dim(x2, lo, rows, axis=0)
    return qdma_pack_ref(chunk, block=block)


def qdma_digest_ref(x):
    """Position-weighted 2x32-bit content fingerprint of x's raw bytes.
    Bit-equal arrays (same dtype) digest equal; differing bytes at any
    position flip the weighted sums with overwhelming probability."""
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.uint8)
    u8 = jax.lax.bitcast_convert_type(x, jnp.uint8).reshape(-1)
    v = u8.astype(jnp.uint32)
    idx = jnp.arange(v.shape[0], dtype=jnp.uint32)
    w1 = idx * jnp.uint32(2654435761) + jnp.uint32(0x9E3779B1)
    w2 = idx * jnp.uint32(0x85EBCA6B) + jnp.uint32(0xC2B2AE35)
    return jnp.stack([jnp.sum(v * w1, dtype=jnp.uint32),
                      jnp.sum(v * w2, dtype=jnp.uint32)])
