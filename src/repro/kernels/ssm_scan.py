"""Chunked SSD scan kernel (Mamba-2 style scalar-per-head decay).

Grid (B, nc): the chunk axis is innermost, so the recurrent state
H (heads x hd x N) lives in VMEM scratch and is carried chunk-to-chunk —
the HBM<->VMEM traffic per chunk is just the chunk inputs/outputs, and
the intra-chunk work is MXU matmuls (C·Bᵀ and the masked-weight @ x).
A block holds every head of its chunk (x viewed as (B, S, H*hd)), the
shape the TPU compiler accepts; the kernel loops over the heads.

Per chunk and head (all fp32 in-kernel):
  cum   = cumsum(log_a)                              (Q,)
  y     = ((exp(cum_t - cum_s) ⊙ tril) ⊙ (C Bᵀ)) @ xdt  +  exp(cum) ⊙ (C H_prevᵀ)
  H_new = exp(cum_Q) H_prev + ((exp(cum_Q - cum) ⊙ xdt)ᵀ B)
The cumulative sums are matmuls with a triangular ones matrix, taken in
both orientations so that no in-kernel transpose is needed.

Inputs  xdt (B,S,H,hd), Bv (B,S,N), Cv (B,S,N), log_a (B,S,H).
Outputs y (B,S,H,hd) and the final state (B,H,hd,N).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST


def _kernel(x_ref, b_ref, c_ref, la_ref, y_ref, hout_ref, h_scr, *,
            chunk: int, hd: int):
    ci = pl.program_id(1)
    nc = pl.num_programs(1)
    heads = la_ref.shape[2]

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    Bv = b_ref[0].astype(jnp.float32)                  # (Q, N)
    Cv = c_ref[0].astype(jnp.float32)                  # (Q, N)
    la = la_ref[0].astype(jnp.float32)                 # (Q, H)
    t_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tril = t_i >= s_i
    ones = tril.astype(jnp.float32)
    cum_col = jax.lax.dot(ones, la, precision=_HI,
                          preferred_element_type=jnp.float32)   # (Q, H)
    cum_row = jax.lax.dot_general(la, ones, (((0,), (1,)), ((), ())),
                                  precision=_HI,
                                  preferred_element_type=jnp.float32)  # (H,Q)
    # every head's chunk total, replicated down the rows the state update
    # scales (a (1, 1) value broadcast both ways does not lower)
    tot_q = jax.lax.dot(jnp.ones((chunk, chunk), jnp.float32), la,
                        precision=_HI, preferred_element_type=jnp.float32)
    tot_hd = jax.lax.dot(jnp.ones((hd, chunk), jnp.float32), la,
                         precision=_HI, preferred_element_type=jnp.float32)
    GB = jax.lax.dot_general(Cv, Bv, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q,Q)
    for h in range(heads):
        lanes = slice(h * hd, (h + 1) * hd)
        x = x_ref[0, :, lanes].astype(jnp.float32)     # (Q, hd)
        c_col = cum_col[:, h:h + 1]                    # (Q, 1)
        # intra-chunk: masked decay-weighted attention-like matmul
        M = jnp.where(tril, jnp.exp(c_col - cum_row[h:h + 1, :]), 0.0)
        y = jax.lax.dot(M * GB, x, preferred_element_type=jnp.float32)
        # inter-chunk: contribution of the carried state
        h_prev = h_scr[h]                              # (hd, N)
        y = y + jnp.exp(c_col) * jax.lax.dot_general(
            Cv, h_prev, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # (Q, hd)
        # state update
        w = jnp.exp(tot_q[:, h:h + 1] - c_col)         # (Q, 1)
        h_new = jnp.exp(tot_hd[:, h:h + 1]) * h_prev + jax.lax.dot_general(
            w * x, Bv, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # (hd, N)
        h_scr[h] = h_new
        y_ref[0, :, lanes] = y.astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _finish():
        hout_ref[0] = h_scr[...].astype(hout_ref.dtype)


def ssm_scan(xdt, Bv, Cv, log_a, *, chunk: int = 128,
             interpret: bool = False):
    """See module docstring. Returns (y fp32 (B,S,H,hd), state (B,H,hd,N))."""
    B, S, H, hd = xdt.shape
    N = Bv.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0
    kern = functools.partial(_kernel, chunk=Q, hd=hd)
    y, hfinal = pl.pallas_call(
        kern,
        grid=(B, S // Q),
        in_specs=[
            pl.BlockSpec((1, Q, H * hd), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, Q, N), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, Q, N), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, Q, H), lambda b, ci: (b, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, H * hd), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, H, hd, N), lambda b, ci: (b, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H * hd), jnp.float32),
            jax.ShapeDtypeStruct((B, H, hd, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((H, hd, N), jnp.float32)],
        interpret=interpret,
        name="ssm_scan",
    )(xdt.reshape(B, S, H * hd), Bv, Cv, log_a)
    return y.reshape(B, S, H, hd), hfinal
