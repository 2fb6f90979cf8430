"""Blocked flash attention (forward) for TPU — pl.pallas_call + BlockSpec.

Tiling: grid (B, nq, nk); the kv axis is innermost so the online-softmax
running state (m, l, acc) lives in VMEM scratch and persists across the kv
iteration (TPU grids execute sequentially over the trailing axis). A
block holds every head of its rows: q is viewed as (B, S, H*hd) and k/v
as (B, T, K*hd), so a block's last two dims are (tile rows, all heads) —
the shape the TPU compiler accepts. The kernel loops over the K kv-heads
and the G = H/K query heads of each (GQA), one (bq, hd) x (hd, bk) matmul
per head. Sequences are padded to the tile and the padding is masked.

Causal handling: logits inside a block are masked with position iotas;
fully-masked blocks are skipped via pl.when. ``q_offset`` (a prefetched
scalar, so it may be traced) is the absolute position of q's first row:
a chunked-prefill continuation attends its chunk against everything
cached before it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(off_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            causal: bool, scale: float, block_q: int, block_k: int,
            kv_heads: int, hd: int, kv_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    H = m_scr.shape[1]
    G = H // kv_heads

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = off_ref[0] + qi * block_q
    k_start = ki * block_k

    def compute():
        kpos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = kpos < kv_len
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            valid = valid & (kpos <= qpos)
        for g in range(kv_heads):
            k = k_ref[0, :, g * hd:(g + 1) * hd].astype(jnp.float32)
            v = v_ref[0, :, g * hd:(g + 1) * hd].astype(jnp.float32)
            for h in range(g * G, (g + 1) * G):
                lanes = slice(h * hd, (h + 1) * hd)
                q = q_ref[0, :, lanes].astype(jnp.float32) * scale
                s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
                s = jnp.where(valid, s, NEG_INF)
                m_prev = m_scr[:, h:h + 1]
                m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
                p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
                alpha = jnp.exp(m_prev - m_new)
                l_scr[:, h:h + 1] = alpha * l_scr[:, h:h + 1] + jnp.sum(
                    p, -1, keepdims=True)
                acc_scr[:, lanes] = acc_scr[:, lanes] * alpha + jax.lax.dot(
                    p, v, preferred_element_type=jnp.float32)
                m_scr[:, h:h + 1] = m_new

    if causal:
        # skip blocks entirely above the diagonal
        pl.when(k_start <= q_start + block_q - 1)(compute)
    else:
        compute()

    @pl.when(ki == nk - 1)
    def _finish():
        for h in range(H):
            lanes = slice(h * hd, (h + 1) * hd)
            o_ref[0, :, lanes] = (
                acc_scr[:, lanes] / jnp.maximum(l_scr[:, h:h + 1], 1e-30)
            ).astype(o_ref.dtype)


def _tile(n: int, block: int) -> tuple[int, int]:
    """(tile, padded length): one whole-length tile when it fits."""
    if n <= block:
        return n, n
    return block, -(-n // block) * block


def flash_attention(q, k, v, *, causal: bool = True, q_offset=0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: (B,S,H,hd); k,v: (B,T,K,hd); H % K == 0. Returns (B,S,H,hd).
    q_offset: absolute position of q[:, 0] (int or traced scalar)."""
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    block_q, Sp = _tile(S, block_q)
    block_k, Tp = _tile(T, block_k)
    q2 = jnp.pad(q.reshape(B, S, H * hd), ((0, 0), (0, Sp - S), (0, 0)))
    k2 = jnp.pad(k.reshape(B, T, K * hd), ((0, 0), (0, Tp - T), (0, 0)))
    v2 = jnp.pad(v.reshape(B, T, K * hd), ((0, 0), (0, Tp - T), (0, 0)))
    kern = functools.partial(_kernel, causal=causal,
                             scale=1.0 / math.sqrt(hd),
                             block_q=block_q, block_k=block_k,
                             kv_heads=K, hd=hd, kv_len=T)
    kv_spec = pl.BlockSpec((1, block_k, K * hd),
                           lambda b, qi, ki, off: (b, ki, 0))
    q_spec = pl.BlockSpec((1, block_q, H * hd),
                          lambda b, qi, ki, off: (b, qi, 0))
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Sp // block_q, Tp // block_k),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((block_q, H), jnp.float32),       # running max
                pltpu.VMEM((block_q, H), jnp.float32),       # running denom
                pltpu.VMEM((block_q, H * hd), jnp.float32),  # output accum
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Sp, H * hd), q.dtype),
        interpret=interpret,
        name="flash_attention",
    )(jnp.asarray(q_offset, jnp.int32).reshape((1,)), q2, k2, v2)
    return out[:, :S].reshape(B, S, H, hd)
