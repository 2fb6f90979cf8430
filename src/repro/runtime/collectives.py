"""Distributed-optimization collectives: int8-compressed gradient
all-reduce (beyond-paper; OptimizerConfig.grad_compression="int8").

Classic quantized ring all-reduce is re-expressed TPU-natively as
reduce-scatter (full precision within the shard reduction) followed by an
int8-quantized all-gather: each device owns an exact fp32 partial for its
shard, packs it with the qdma_pack blockwise quantizer, and gathers the
packed shards. Only the GATHER phase is lossy (one quantization per value
— error is NOT accumulated across devices like naive quantized rings).

Payload on the wire: ~4x smaller for the gather phase; the reduce-scatter
phase stays exact, so total bytes ≈ (1 + 1/4)/2 of a plain fp32
all-reduce. Used by examples / available to the trainer for DP meshes;
the dry-run default keeps the paper-faithful exact path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _pack(x, block):
    from repro.kernels import ops as kops
    return kops.qdma_pack(x, block=block)


def _unpack(q, s, dtype):
    from repro.kernels import ops as kops
    return kops.qdma_unpack(q, s, dtype=dtype)


def compressed_psum_mean(x: jax.Array, axis: str, *, block: int = 256):
    """Mean over ``axis`` with an int8-compressed gather phase.

    Call INSIDE shard_map. x: any shape; flattened internally to
    (n_dev, -1) rows padded to a block multiple.
    """
    n = jax.lax.axis_size(axis)
    flat = x.astype(jnp.float32).reshape(-1)
    per = -(-flat.size // n)                    # ceil
    per = -(-per // block) * block              # block multiple
    pad = n * per - flat.size
    flat = jnp.pad(flat, (0, pad))
    rows = flat.reshape(n, per)
    # exact reduce-scatter: each device ends with the true mean of its row
    mine = jax.lax.psum_scatter(rows, axis, scatter_dimension=0,
                                tiled=False) / n
    # lossy gather: quantize my exact shard once, gather packed shards
    q, s = _pack(mine.reshape(1, per), block=block)
    qg = jax.lax.all_gather(q, axis, axis=0)        # (n, 1, per) int8
    sg = jax.lax.all_gather(s, axis, axis=0)
    out = _unpack(qg.reshape(n, per), sg.reshape(n, per // block),
                  "float32")
    return out.reshape(-1)[:x.size].reshape(x.shape).astype(x.dtype)


def compressed_grad_allreduce(stacked_grads, mesh: Mesh,
                              axis: str = "data", block: int = 256):
    """Tree-wise compressed mean over per-replica gradients.

    stacked_grads: pytree whose leaves have a leading replica dim equal to
    the DP axis size (sharded over ``axis``). Returns the replica mean,
    replicated. Tiny leaves (< 4 blocks) use an exact pmean — compression
    overhead isn't worth the bytes there.
    """
    n = mesh.shape[axis]

    def inner(gs):
        def one(g):
            g = g[0]                              # my replica's partial
            if g.size < 4 * block:
                return jax.lax.pmean(g, axis)
            return compressed_psum_mean(g, axis, block=block)
        return jax.tree.map(one, gs)

    in_specs = (jax.tree.map(lambda _: P(axis), stacked_grads),)
    out_specs = jax.tree.map(lambda _: P(), stacked_grads)
    return jax.shard_map(inner, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(stacked_grads)
