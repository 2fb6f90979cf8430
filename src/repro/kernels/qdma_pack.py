"""qdma_pack / qdma_unpack — the QDMA descriptor-queue analogue.

Blockwise symmetric int8 quantization used by the StagingEngine to shrink
pause-snapshot payloads (and, beyond-paper, gradient payloads) before they
cross the slow host link. Grid-chunked so arbitrary-size state tensors
stream through a fixed VMEM footprint — exactly the descriptor-queue shape
of the QDMA hardware (paper §IV-A), with the (rows, block) tile playing the
role of one descriptor.

pack:   x (M, L) -> q int8 (M, L), scale fp32 (M, L/block)
unpack: inverse (dequantize).
rows:   chunk-granular entry points (`qdma_pack_rows`) that pack ONE
        descriptor — a row range of the 2-D view — so the staging engine
        can overlap pack of descriptor i+1 with D2H of descriptor i.
digest: `qdma_digest` — a position-weighted 2x32-bit content fingerprint
        of the raw bytes, computed on device, used by the staging engine's
        dirty tracking to skip mutated-but-equal leaves without a D2H.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pack_kernel(x_ref, q_ref, s_ref, *, block: int):
    x = x_ref[...].astype(jnp.float32)                # (rows, tile)
    rows, tile = x.shape
    nb = tile // block
    xb = x.reshape(rows, nb, block)
    scale = jnp.max(jnp.abs(xb), axis=-1) / 127.0     # (rows, nb)
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(xb / scale[..., None]), -127, 127)
    q_ref[...] = q.reshape(rows, tile).astype(jnp.int8)
    s_ref[...] = scale


def _unpack_kernel(q_ref, s_ref, x_ref, *, block: int):
    q = q_ref[...].astype(jnp.float32)
    rows, tile = q.shape
    nb = tile // block
    x = q.reshape(rows, nb, block) * s_ref[...][..., None]
    x_ref[...] = x.reshape(rows, tile).astype(x_ref.dtype)


def _as2d(x):
    L = x.shape[-1]
    return x.reshape(-1, L)


def qdma_pack(x, *, block: int = 256, rows_per_tile: int = 256,
              interpret: bool = False, name: str = "qdma_pack"):
    """x: any shape with shape[-1] % block == 0. Returns (q, scale) shaped
    like ref.qdma_pack_ref. ``name`` is the kernel's name in a trace."""
    shape = x.shape
    x2 = _as2d(x)
    M, L = x2.shape
    rows = min(rows_per_tile, M)
    while M % rows:
        rows -= 1
    grid = (M // rows,)
    kern = functools.partial(_pack_kernel, block=block)
    q, scale = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec((rows, L), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rows, L), lambda i: (i, 0)),
                   pl.BlockSpec((rows, L // block), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((M, L), jnp.int8),
                   jax.ShapeDtypeStruct((M, L // block), jnp.float32)],
        interpret=interpret,
        name=name,
    )(x2)
    return (q.reshape(shape),
            scale.reshape(shape[:-1] + (L // block,)))


def qdma_pack_rows(x, lo, *, rows: int, block: int = 256,
                   rows_per_tile: int = 256, interpret: bool = False):
    """Pack ONE descriptor: rows [lo, lo+rows) of the 2-D row view of x.

    ``lo`` is a traced scalar (chunks of equal ``rows`` share one compiled
    executable); ``rows`` is static. Returns (q (rows, L) int8,
    scale (rows, L/block) fp32)."""
    x2 = _as2d(x)
    chunk = jax.lax.dynamic_slice_in_dim(x2, lo, rows, axis=0)
    return qdma_pack(chunk, block=block, rows_per_tile=rows_per_tile,
                     interpret=interpret, name="qdma_pack_rows")


def _digest_kernel(v_ref, out_ref, *, lanes: int):
    i = pl.program_id(0)
    v = v_ref[...].astype(jnp.uint32)                 # (rows, lanes)
    rows = v.shape[0]
    # global flat index of each element (uint32 wrap-around is fine: the
    # digest only needs determinism, not order)
    base = (i * rows * lanes)
    idx = (jax.lax.broadcasted_iota(jnp.uint32, v.shape, 0) *
           jnp.uint32(lanes) +
           jax.lax.broadcasted_iota(jnp.uint32, v.shape, 1) +
           jnp.uint32(base))
    w1 = idx * jnp.uint32(2654435761) + jnp.uint32(0x9E3779B1)
    w2 = idx * jnp.uint32(0x85EBCA6B) + jnp.uint32(0xC2B2AE35)
    # lane-dense partials (one per lane); the wrapper finishes the sum.
    # Summed as int32: the TPU reduces no unsigned type, and wrapping
    # addition gives the same bits either way
    for r, w in ((0, w1), (1, w2)):
        out_ref[0, r:r + 1, :] = jnp.sum(
            jax.lax.bitcast_convert_type(v * w, jnp.int32), axis=0,
            keepdims=True)


def _bytes_view(x):
    """Raw little-endian byte view of x as a flat uint8 vector."""
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.uint8)
    u8 = jax.lax.bitcast_convert_type(x, jnp.uint8)
    return u8.reshape(-1)


def qdma_digest(x, *, rows_per_tile: int = 512, lanes: int = 128,
                interpret: bool = False):
    """Content fingerprint of x's raw bytes: (2,) uint32. Equal bytes ->
    equal digest; position-weighted so permutations don't collide. Zero
    padding is digest-neutral (padded elements contribute 0)."""
    u8 = _bytes_view(x)
    n = int(u8.shape[0])
    per = rows_per_tile * lanes
    npad = (-n) % per
    if npad:
        u8 = jnp.pad(u8, (0, npad))
    v = u8.reshape(-1, lanes)
    grid = (v.shape[0] // rows_per_tile,)
    kern = functools.partial(_digest_kernel, lanes=lanes)
    parts = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec((rows_per_tile, lanes), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, 2, lanes), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((grid[0], 2, lanes), jnp.int32),
        interpret=interpret,
        name="qdma_digest",
    )(v)
    return jnp.sum(jax.lax.bitcast_convert_type(parts, jnp.uint32),
                   axis=(0, 2), dtype=jnp.uint32)


def qdma_unpack(q, scale, *, dtype="float32", rows_per_tile: int = 256,
                interpret: bool = False):
    shape = q.shape
    block = q.shape[-1] // scale.shape[-1]
    q2 = _as2d(q)
    s2 = _as2d(scale)
    M, L = q2.shape
    rows = min(rows_per_tile, M)
    while M % rows:
        rows -= 1
    grid = (M // rows,)
    kern = functools.partial(_unpack_kernel, block=block)
    x = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec((rows, L), lambda i: (i, 0)),
                  pl.BlockSpec((rows, L // block), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, L), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, L), jnp.dtype(dtype)),
        interpret=interpret,
        name="qdma_unpack",
    )(q2, s2)
    return x.reshape(shape)
