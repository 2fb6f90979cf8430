"""Paged flash-decode: single-token attention over a block-table-indirected
KV page pool (the serve plane's paged-KV cache, see ``repro.serve.paged``).

Extends ``flash_decode``'s split-K online-softmax scheme with one level of
indirection: the cache is a shared pool of fixed-size pages (P, page, K, hd)
per layer, and each sequence names its pages through a prefetched block table
(B, NP) — the k/v BlockSpec index_map reads ``table[b, pi]`` so the DMA
engine fetches exactly the pages a sequence owns, in logical order. The
per-sequence valid length is a second prefetched scalar vector: tiles past
``pos[b]`` are skipped with ``pl.when``, and their index_map repeats the
last valid page so the pipeline issues no DMA for them either — decode
cost is proportional to the tokens a sequence has actually written, not
to the pool size or the table width. ``pos[b] < 0`` (an inactive batch
slot) skips every tile and yields an exactly-zero output row.

A block is one whole page, all K kv-heads wide, in the layout the pool
stores it, (1, page, K, hd). The kernel body (``flash_decode.
decode_kernel``) takes it as page * K rows of hd and scores every query
head against all of them in one matmul, each head keeping its own
kv-head's rows. Nothing views the pool in another layout: on the TPU
(the pool tiled on its (K, hd) minor dims) that would copy the layer's
whole pool on every call.

The kernel takes the pools of every layer, stacked as the serve cache
holds them, (L, P, page, K, hd), and the layer to read as a third
prefetched scalar: the block's index map picks the layer's page, so no
layer's pool is sliced out of the stack (a copy of the whole pool) to
feed the call.

A sliding ``window`` W > 0 bounds each row's reads from below as well:
the grid's page axis spans the (W - 1) // page + 2 pages a window can
touch, starting at the page of ``pos - W + 1``, so a windowed layer costs
the same grid steps and reads whatever the context length. Its table may
then name a per-slot ring of pages (``serve.paged``): only the pages of
the window are ever read.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_decode import decode_kernel, decode_scratch


def _paged_call(q, k_pages, v_pages, scales, tables, pos, layer, interpret,
                window=0):
    B, _, H, hd = q.shape
    page, K = k_pages.shape[2], k_pages.shape[3]
    NP = tables.shape[1]
    quant = scales is not None
    body = functools.partial(decode_kernel, scale=1.0 / math.sqrt(hd),
                             kv_heads=K, block=page, paged=True,
                             quant=quant, window=window,
                             from_window=bool(window))

    def kern(tbl_ref, pos_ref, layer_ref, *refs):
        # the layer index only steers the DMA (the index maps below)
        body(tbl_ref, pos_ref, *refs)
    steps = min(NP, (window - 1) // page + 2) if window else NP

    def page_of(b, pi, tbl_ref, pos_ref):
        last = jnp.maximum(pos_ref[b], 0) // page
        if window:
            pi = pi + jnp.maximum(pos_ref[b] - window + 1, 0) // page
        return tbl_ref[b, jnp.minimum(pi, last)]

    # the stack read as one pool of L * P pages, page l * P + i holding
    # page i of layer l: only the leading axes merge, so the view is free
    # (the tiled minor axes stay), and the body gets the blocks it always
    # had. A (1, 1, page, ...) block of the stack itself would need a
    # shape cast of the int8 scales' (page, K) tile that Mosaic refuses.
    L, P = k_pages.shape[:2]

    def pages(a):
        return a.reshape((L * P,) + a.shape[2:])

    kv_spec = pl.BlockSpec(
        (1, page, K, hd),
        lambda b, pi, t, p, l: (l[0] * P + page_of(b, pi, t, p), 0, 0, 0))
    sc_spec = pl.BlockSpec(
        (1, page, K),
        lambda b, pi, t, p, l: (l[0] * P + page_of(b, pi, t, p), 0, 0))
    in_specs = [pl.BlockSpec((1, H, hd), lambda b, pi, t, p, l: (b, 0, 0)),
                kv_spec, kv_spec] + ([sc_spec, sc_spec] if quant else [])
    args = [pages(a) for a in [k_pages, v_pages]
            + (list(scales) if quant else [])]
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, steps),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, H, hd),
                                   lambda b, pi, t, p, l: (b, 0, 0)),
            scratch_shapes=decode_scratch(H, hd),
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        interpret=interpret,
        name="paged_decode",
    )(jnp.asarray(tables, jnp.int32),
      jnp.asarray(pos, jnp.int32).reshape((B,)),
      jnp.asarray(layer, jnp.int32).reshape((1,)),
      q.reshape(B, H, hd), *args)
    return out.reshape(B, 1, H, hd)


def paged_decode_quant(q, k_pages, v_pages, k_scale, v_scale, tables, pos,
                       layer, *, interpret: bool = False):
    """paged_decode over int8 page pools. k_pages/v_pages:
    (L,P,page,K,hd) int8; k_scale/v_scale: (L,P,page,K) fp32 per-(row,head)
    symmetric scales; everything else as paged_decode. Pages are fetched
    at int8 width and dequantized in-tile, halving the kernel's HBM
    bytes per token."""
    return _paged_call(q, k_pages, v_pages, (k_scale, v_scale), tables, pos,
                       layer, interpret)


def paged_decode(q, k_pages, v_pages, tables, pos, layer, *, window: int = 0,
                 interpret: bool = False):
    """q: (B,1,H,hd); k_pages,v_pages: (L,P,page,K,hd), the pools of L
    layers stacked; layer: int32 scalar, the layer whose pool is read;
    tables: (B,NP) int32; pos: (B,) int32 — attend to logical indices
    <= pos[b] (< 0: none); window > 0: and > pos[b] - window."""
    return _paged_call(q, k_pages, v_pages, None, tables, pos, layer,
                       interpret, window)
