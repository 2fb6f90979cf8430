"""Flash-decode: single-token attention against a long KV cache.

Split-K tiling: grid (B, ns) walks the cache in block_k tiles with the
online-softmax state in VMEM scratch; the valid-length position is a
prefetched scalar (pltpu.PrefetchScalarGridSpec) so tiles past ``pos`` are
skipped with pl.when — for a ring cache where pos << T this makes decode
cost proportional to the *filled* cache, not the allocation. ``pos`` may
differ per batch row (continuous batching over a dense ring).

TPU layout: a block holds every head of its rows. q is viewed as
(B, H, hd) and the cache as (B, T, K*hd), so the last two dims of each
block are either the whole array's or (tile rows, K*hd) — the shapes the
TPU compiler accepts. The kernel loops over the K kv-heads and attends
the G = H/K query heads of each one with a (G, hd) x (hd, tile) matmul.
``decode_kernel`` is shared with ``paged_decode``, whose tile is one
page as the pool stores it, (page, K, hd): there every query head scores
the page's page * K rows in one matmul and keeps its own kv-head's. With
a sliding ``window`` W > 0 a row attends only [pos - W + 1, pos]: tiles
wholly before that are skipped too, or, with ``from_window``, the grid's
tile axis starts at the window's first tile, so that it spans the window
and not the whole cache.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def decode_kernel(*refs, scale: float, kv_heads: int, block: int,
                  paged: bool, quant: bool, window: int = 0,
                  from_window: bool = False):
    """One (batch row, KV tile) grid step of single-token decode.

    refs: [tables] pos (B,) q k v [k_scale v_scale] out m l acc. q block
    (1, H, hd); k/v blocks (1, block, K*hd), or with ``paged`` one page as
    the pool stores it, (1, block, K, hd); scales (1, block, K); scratch
    m, l (H, 1) and acc (H, hd), float32."""
    if paged:
        _, pos_ref, q_ref, k_ref, v_ref, *rest = refs
    else:
        pos_ref, q_ref, k_ref, v_ref, *rest = refs
    pos = pos_ref[pl.program_id(0)]
    if quant:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    ti = pl.program_id(1)
    start = ti * block
    if from_window:
        start = start + jnp.maximum(pos - window + 1, 0) // block * block

    @pl.when(ti == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    live = start <= pos
    if window:
        live = live & (start + block > pos - window + 1)

    @pl.when(live)
    def _compute():
        H, hd = q_ref.shape[1], q_ref.shape[2]
        G = H // kv_heads
        q_all = q_ref[0].astype(jnp.float32) * scale          # (H, hd)

        def visible(kpos):
            ok = kpos <= pos
            return ok & (kpos > pos - window) if window else ok

        def scores(q, k):
            return jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                       preferred_element_type=jnp.float32)

        if paged:
            # the page as stored, (block, K, hd), is block * K rows of hd:
            # row r holds position r // K of kv-head r % K. Every query
            # head scores all of them in one matmul and keeps the rows of
            # its own kv-head.
            n = block * kv_heads
            k = k_ref[0].reshape(n, hd).astype(jnp.float32)
            v = v_ref[0].reshape(n, hd).astype(jnp.float32)
            s = scores(q_all, k)                               # (H, n)
            col = jax.lax.broadcasted_iota(jnp.int32, (H, n), 1)
            head = jax.lax.broadcasted_iota(jnp.int32, (H, n), 0) // G
            valid = (col % kv_heads == head) & visible(
                start + col // kv_heads)
            v_scale = None
            if quant:
                # int8 rows dequantized through their scores and weights:
                # HBM traffic stays at the int8 width
                s = s * ks_ref[0].reshape(1, n)
                v_scale = vs_ref[0].reshape(1, n)
            _fold(s, valid, v, slice(None), m_scr, l_scr, acc_scr, v_scale)
        else:
            valid = visible(
                start + jax.lax.broadcasted_iota(jnp.int32, (G, block), 1))
            for g in range(kv_heads):
                rows = slice(g * G, (g + 1) * G)
                lanes = slice(g * hd, (g + 1) * hd)
                k = k_ref[0, :, lanes].astype(jnp.float32)     # (block, hd)
                v = v_ref[0, :, lanes].astype(jnp.float32)
                _fold(scores(q_all[rows], k), valid, v, rows, m_scr, l_scr,
                      acc_scr)

    @pl.when(ti == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


def _fold(s, valid, v, rows, m_scr, l_scr, acc_scr, v_scale=None):
    """Fold one tile's scores ``s`` (R, n), of which only the ``valid``
    ones count, and values ``v`` (n, hd) into the online-softmax state of
    scratch rows ``rows``; ``v_scale`` (1, n) scales each value row."""
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_scr[rows, :]
    m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[rows, :] = alpha * l_scr[rows, :] + jnp.sum(p, -1, keepdims=True)
    if v_scale is not None:
        p = p * v_scale
    acc_scr[rows, :] = acc_scr[rows, :] * alpha + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[rows, :] = m_new


def decode_scratch(H: int, hd: int) -> list:
    return [pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, hd), jnp.float32)]


def flash_decode(q, k, v, pos, *, block_k: int = 256, window: int = 0,
                 interpret: bool = False):
    """q: (B,1,H,hd); k,v: (B,T,K,hd); pos: int32 scalar or (B,) per-row
    (attend <= pos; a row with pos < 0 attends nothing and is zero);
    window > 0: attend only (pos - window, pos]."""
    B, _, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    if T > block_k and T % block_k:
        pad = ((0, 0), (0, -T % block_k), (0, 0), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)      # masked: past any pos
        T = k.shape[1]
    block_k = min(block_k, T)
    kern = functools.partial(decode_kernel, scale=1.0 / math.sqrt(hd),
                             kv_heads=K, block=block_k, paged=False,
                             quant=False, window=window)
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    kv_spec = pl.BlockSpec((1, block_k, K * hd),
                           lambda b, ki, pos_ref: (b, ki, 0))
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, T // block_k),
            in_specs=[
                pl.BlockSpec((1, H, hd), lambda b, ki, pos_ref: (b, 0, 0)),
                kv_spec,
                kv_spec,
            ],
            out_specs=pl.BlockSpec((1, H, hd),
                                   lambda b, ki, pos_ref: (b, 0, 0)),
            scratch_shapes=decode_scratch(H, hd),
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        interpret=interpret,
        name="flash_decode",
    )(pos_arr, q.reshape(B, H, hd), k.reshape(B, T, K * hd),
      v.reshape(B, T, K * hd))
    return out.reshape(B, 1, H, hd)
