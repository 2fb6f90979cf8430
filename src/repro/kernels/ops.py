"""jit'd dispatch wrappers for the Pallas kernels.

``backend`` picks the implementation: ``'pallas'`` the kernel, ``'ref'``
the jnp oracle (used by the dry-run so cost_analysis sees real FLOPs, not
opaque calls), and ``'auto'`` the kernel wherever the TPU compiler runs
it (a TPU backend) or interpret mode was asked for, the oracle elsewhere.
``interpret`` is only ever the caller's explicit choice: on a TPU backend
the kernels always compile for the chip, and a kernel the compiler
refuses raises rather than falling back.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import ref as _ref
from repro.kernels import flash_attention as _fa
from repro.kernels import flash_decode as _fd
from repro.kernels import paged_decode as _pd
from repro.kernels import qdma_pack as _qp
from repro.kernels import sampling as _sp
from repro.kernels import ssm_scan as _ss


def _kernel(backend: str, interpret: bool) -> bool:
    """True when the Pallas kernel runs, False for the jnp oracle."""
    if backend not in ("auto", "pallas", "ref"):
        raise ValueError(f"unknown kernel backend {backend!r}")
    if backend == "auto":
        return interpret or jax.default_backend() == "tpu"
    return backend == "pallas"


@functools.partial(jax.jit, static_argnames=("causal", "interpret",
                                             "backend"))
def flash_attention(q, k, v, q_offset=0, *, causal: bool = True,
                    interpret: bool = False, backend: str = "auto"):
    if not _kernel(backend, interpret):
        return _ref.flash_attention_ref(q, k, v, causal=causal,
                                        q_offset=q_offset)
    return _fa.flash_attention(q, k, v, causal=causal, q_offset=q_offset,
                               interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret", "backend"))
def flash_decode(q, k, v, pos, *, interpret: bool = False,
                 backend: str = "auto"):
    if not _kernel(backend, interpret):
        return _ref.flash_decode_ref(q, k, v, pos)
    return _fd.flash_decode(q, k, v, pos, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret", "backend"))
def paged_decode(q, k_pages, v_pages, tables, pos, *,
                 interpret: bool = False, backend: str = "auto"):
    """Block-table-indirected decode over the paged KV pool (serve plane)."""
    if not _kernel(backend, interpret):
        return _ref.paged_decode_ref(q, k_pages, v_pages, tables, pos)
    return _pd.paged_decode(q, k_pages, v_pages, tables, pos,
                            interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret", "backend"))
def paged_decode_quant(q, k_pages, v_pages, k_scale, v_scale, tables, pos, *,
                       interpret: bool = False, backend: str = "auto"):
    """paged_decode over an int8 page pool with per-(row,head) scales —
    half the HBM bytes per decoded token, dequantized in-tile."""
    if not _kernel(backend, interpret):
        return _ref.paged_decode_quant_ref(q, k_pages, v_pages,
                                           k_scale, v_scale, tables, pos)
    return _pd.paged_decode_quant(q, k_pages, v_pages, k_scale, v_scale,
                                  tables, pos, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("vocab_size", "interpret",
                                             "backend"))
def fused_sample(logits, temp, top_k, keys, *, vocab_size: int,
                 interpret: bool = False, backend: str = "auto"):
    """In-kernel temperature/top-k Gumbel sampling: (B, Vp) logits ->
    (B,) int32 token ids, bit-identical to ServeEngine._sample (the
    host oracle) row by row. keys: (B, 3) int32 (seed, rid, counter)."""
    if not _kernel(backend, interpret):
        return _ref.fused_sample_ref(logits, temp, top_k, keys,
                                     vocab_size=vocab_size)
    return _sp.fused_sample(logits, temp, top_k, keys,
                            vocab_size=vocab_size, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret", "backend"))
def ssm_scan(xdt, Bv, Cv, log_a, *, chunk: int = 128,
             interpret: bool = False, backend: str = "auto"):
    if not _kernel(backend, interpret):
        return _ref.ssm_scan_ref(xdt, Bv, Cv, log_a, chunk=chunk)
    return _ss.ssm_scan(xdt, Bv, Cv, log_a, chunk=chunk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block", "interpret", "backend"))
def qdma_pack(x, *, block: int = 256, interpret: bool = False,
              backend: str = "auto"):
    if not _kernel(backend, interpret):
        return _ref.qdma_pack_ref(x, block=block)
    return _qp.qdma_pack(x, block=block, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("dtype", "interpret", "backend"))
def qdma_unpack(q, scale, *, dtype: str = "float32",
                interpret: bool = False, backend: str = "auto"):
    if not _kernel(backend, interpret):
        return _ref.qdma_unpack_ref(q, scale, dtype=dtype)
    return _qp.qdma_unpack(q, scale, dtype=dtype, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("rows", "block", "interpret",
                                             "backend"))
def qdma_pack_rows(x, lo, *, rows: int, block: int = 256,
                   interpret: bool = False, backend: str = "auto"):
    """Chunk-granular pack: one descriptor = rows [lo, lo+rows) of the 2-D
    row view. ``lo`` is traced, so equal-size chunks share an executable."""
    if not _kernel(backend, interpret):
        return _ref.qdma_pack_rows_ref(x, lo, rows, block=block)
    return _qp.qdma_pack_rows(x, lo, rows=rows, block=block,
                              interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret", "backend"))
def qdma_digest(x, *, interpret: bool = False, backend: str = "auto"):
    """On-device content fingerprint, (2,) uint32 — the staging engine's
    dirty-tracking primitive (skip mutated-but-equal leaves)."""
    if not _kernel(backend, interpret):
        return _ref.qdma_digest_ref(x)
    return _qp.qdma_digest(x, interpret=interpret)
