"""Paged flash-decode: single-token attention over a block-table-indirected
KV page pool (the serve plane's paged-KV cache, see ``repro.serve.paged``).

Extends ``flash_decode``'s split-K online-softmax scheme with one level of
indirection: the cache is a shared pool of fixed-size pages (P, page, K, hd)
and each sequence names its pages through a prefetched block table
(B, NP) — the k/v BlockSpec index_map reads ``table[b, pi]`` so the DMA
engine fetches exactly the pages a sequence owns, in logical order. The
per-sequence valid length is a second prefetched scalar vector: tiles past
``pos[b]`` are skipped with ``pl.when``, and their index_map repeats the
last valid page so the pipeline issues no DMA for them either — decode
cost is proportional to the tokens a sequence has actually written, not
to the pool size or the table width. ``pos[b] < 0`` (an inactive batch
slot) skips every tile and yields an exactly-zero output row.

A block is one whole page, all K kv-heads wide: the pool is viewed as
(P, page, K*hd), and the kernel body (``flash_decode.decode_kernel``)
loops over the kv-heads.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_decode import decode_kernel, decode_scratch


def _paged_call(q, k_pages, v_pages, scales, tables, pos, interpret):
    B, _, H, hd = q.shape
    P, page, K, _ = k_pages.shape
    NP = tables.shape[1]
    quant = scales is not None
    kern = functools.partial(decode_kernel, scale=1.0 / math.sqrt(hd),
                             kv_heads=K, block=page, paged=True,
                             quant=quant)

    def page_of(b, pi, tbl_ref, pos_ref):
        last = jnp.maximum(pos_ref[b], 0) // page
        return tbl_ref[b, jnp.minimum(pi, last)]

    kv_spec = pl.BlockSpec((1, page, K * hd),
                           lambda b, pi, t, p: (page_of(b, pi, t, p), 0, 0))
    sc_spec = pl.BlockSpec((1, page, K),
                           lambda b, pi, t, p: (page_of(b, pi, t, p), 0, 0))
    in_specs = [pl.BlockSpec((1, H, hd), lambda b, pi, t, p: (b, 0, 0)),
                kv_spec, kv_spec] + ([sc_spec, sc_spec] if quant else [])
    args = [k_pages.reshape(P, page, K * hd),
            v_pages.reshape(P, page, K * hd)] + (list(scales) if quant
                                                 else [])
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, NP),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, H, hd),
                                   lambda b, pi, t, p: (b, 0, 0)),
            scratch_shapes=decode_scratch(H, hd),
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        interpret=interpret,
        name="paged_decode",
    )(jnp.asarray(tables, jnp.int32),
      jnp.asarray(pos, jnp.int32).reshape((B,)),
      q.reshape(B, H, hd), *args)
    return out.reshape(B, 1, H, hd)


def paged_decode_quant(q, k_pages, v_pages, k_scale, v_scale, tables, pos, *,
                       interpret: bool = False):
    """paged_decode over an int8 page pool. k_pages/v_pages:
    (P,page,K,hd) int8; k_scale/v_scale: (P,page,K) fp32 per-(row,head)
    symmetric scales; everything else as paged_decode. Pages are fetched
    at int8 width and dequantized in-tile, halving the kernel's HBM
    bytes per token."""
    return _paged_call(q, k_pages, v_pages, (k_scale, v_scale), tables, pos,
                       interpret)


def paged_decode(q, k_pages, v_pages, tables, pos, *,
                 interpret: bool = False):
    """q: (B,1,H,hd); k_pages,v_pages: (P,page,K,hd); tables: (B,NP) int32;
    pos: (B,) int32 — attend to logical indices <= pos[b] (< 0: none)."""
    return _paged_call(q, k_pages, v_pages, None, tables, pos, interpret)
