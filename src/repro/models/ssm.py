"""Mamba block — Mamba-2 (SSD) scalar-per-head-decay formulation.

Hardware adaptation (DESIGN.md §2): Mamba-1's per-(channel,state) selective
scan is a gather/scan pattern that is VPU-bound on TPU; the SSD dual form
turns the recurrence into chunked matmuls (MXU-friendly):

  H_t = a_t * H_{t-1} + (dt_t x_t) ⊗ B_t        a_t = exp(dt_t * A_h) <= 1
  y_t = H_t · C_t + D_h x_t

Within a chunk of Q tokens the output is an attention-like einsum with the
decay mask M_ts = exp(cum_t - cum_s); across chunks an associative scan
carries the (decayed) state. All exponents are <= 0, so everything is
numerically tame without stabilizers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import causal_conv1d, rms_norm
from repro.models.params import mamba_dims


def _split_proj(cfg: ModelConfig, proj):
    d_inner, n_heads, _, _ = mamba_dims(cfg)
    N = cfg.ssm.d_state
    idx = [d_inner, 2 * d_inner, 2 * d_inner + N, 2 * d_inner + 2 * N]
    x = proj[..., :idx[0]]
    z = proj[..., idx[0]:idx[1]]
    Bv = proj[..., idx[1]:idx[2]]
    Cv = proj[..., idx[2]:idx[3]]
    dt = proj[..., idx[3]:]
    return x, z, Bv, Cv, dt


def ssd_chunked(xh, Bv, Cv, log_a, h0=None, chunk: int = 128):
    """Chunkwise SSD scan.

    xh:   (B, S, H, hd)   — dt-scaled inputs (dt_t * x_t)
    Bv:   (B, S, N)       — input maps (shared across heads, ngroups=1)
    Cv:   (B, S, N)       — output maps
    log_a:(B, S, H)       — per-head log decay (<= 0), fp32
    h0:   (B, H, hd, N)   — optional initial state
    Returns y (B,S,H,hd) fp32 and final state (B,H,hd,N) fp32.
    """
    B, S, H, hd = xh.shape
    N = Bv.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0
    nc = S // Q
    cdt = xh.dtype

    xq = xh.reshape(B, nc, Q, H, hd)
    Bq = Bv.reshape(B, nc, Q, N)
    Cq = Cv.reshape(B, nc, Q, N)
    la = log_a.astype(jnp.float32).reshape(B, nc, Q, H)
    cum = jnp.cumsum(la, axis=2)                               # (B,nc,Q,H)

    # ---- intra-chunk (dual / attention-like form) -------------------------
    Lt = jnp.transpose(cum, (0, 1, 3, 2))                      # (B,nc,H,Q)
    M = Lt[..., :, None] - Lt[..., None, :]                    # t - s
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    M = jnp.where(mask, jnp.exp(M), 0.0)                       # (B,nc,H,Q,Q)
    GB = jnp.einsum("bcqn,bcsn->bcqs", Cq.astype(jnp.float32),
                    Bq.astype(jnp.float32))
    W = (M * GB[:, :, None]).astype(cdt)                       # (B,nc,H,Q,Q)
    y_intra = jnp.einsum("bchqs,bcshd->bcqhd", W, xq)

    # ---- chunk-boundary states --------------------------------------------
    wlast = jnp.exp(cum[:, :, -1:, :] - cum)                   # (B,nc,Q,H)
    S_c = jnp.einsum("bcqh,bcqhd,bcqn->bchdn",
                     wlast.astype(cdt), xq, Bq.astype(cdt)
                     ).astype(jnp.float32)                     # (B,nc,H,hd,N)
    d_c = jnp.exp(cum[:, :, -1, :])                            # (B,nc,H)

    if h0 is None:
        h0 = jnp.zeros((B, H, hd, N), jnp.float32)
    h0 = h0.astype(jnp.float32)

    def combine(ea, eb):
        (da, sa), (db, sb) = ea, eb
        return da * db, db[..., None, None] * sa + sb

    ds, ss = jax.lax.associative_scan(combine, (d_c, S_c), axis=1)
    # state after chunk c including h0: H_c = ds_c * h0 + ss_c
    H_after = ds[..., None, None] * h0[:, None] + ss           # (B,nc,H,hd,N)
    H_prev = jnp.concatenate([h0[:, None], H_after[:, :-1]], axis=1)

    # ---- inter-chunk contribution ------------------------------------------
    y_inter = jnp.einsum("bcqn,bchdn->bcqhd", Cq.astype(jnp.float32),
                         H_prev) * jnp.exp(cum)[..., None]
    y = y_intra.astype(jnp.float32).reshape(B, S, H, hd) + \
        y_inter.reshape(B, S, H, hd)
    return y, H_after[:, -1]


def mamba_block(cfg: ModelConfig, p: dict, x, cdt, mode: str = "train",
                cache: dict | None = None, backend: str = "reference",
                interpret: bool = False):
    """Full mamba mixer. x: (B,S,D). Returns (y, new_cache)."""
    d_inner, n_heads, _, d_conv_ch = mamba_dims(cfg)
    N = cfg.ssm.d_state
    B_, S, D = x.shape

    h = rms_norm(x, p["ln"], cfg.norm_eps).astype(cdt)
    proj = h @ p["in_proj"].astype(cdt)                        # (B,S,dproj)
    xs, z, Bv, Cv, dt = _split_proj(cfg, proj)

    conv_in = jnp.concatenate([xs, Bv, Cv], axis=-1)           # (B,S,ch)
    conv_state = cache.get("conv") if cache else None
    conv_out, new_conv = causal_conv1d(conv_in, p["conv_w"], p["conv_b"],
                                       conv_state)
    conv_out = jax.nn.silu(conv_out.astype(jnp.float32)).astype(cdt)
    xs = conv_out[..., :d_inner]
    Bv = conv_out[..., d_inner:d_inner + N]
    Cv = conv_out[..., d_inner + N:]

    dt = jax.nn.softplus(dt.astype(jnp.float32) +
                         p["dt_bias"].astype(jnp.float32))     # (B,S,H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))               # (H,) < 0
    log_a = dt * A                                             # (B,S,H)
    xh = xs.reshape(B_, S, n_heads, -1)
    xdt = (xh.astype(jnp.float32) * dt[..., None]).astype(cdt)

    if mode == "decode":
        # single-token state update
        h0 = cache["ssm"].astype(jnp.float32)                  # (B,H,hd,N)
        a = jnp.exp(log_a[:, 0])                               # (B,H)
        upd = jnp.einsum("bhd,bn->bhdn", xdt[:, 0].astype(jnp.float32),
                         Bv[:, 0].astype(jnp.float32))
        h_new = a[..., None, None] * h0 + upd
        y = jnp.einsum("bhdn,bn->bhd", h_new,
                       Cv[:, 0].astype(jnp.float32))[:, None]  # (B,1,H,hd)
        new_state = h_new
    else:
        if backend == "pallas":
            from repro.kernels import ops as kops
            y, new_state = kops.ssm_scan(xdt, Bv, Cv, log_a,
                                         chunk=cfg.ssm.chunk,
                                         backend="pallas",
                                         interpret=interpret)
        else:
            y, new_state = ssd_chunked(xdt, Bv, Cv, log_a,
                                       chunk=cfg.ssm.chunk)

    y = y + p["D"].astype(jnp.float32)[None, None, :, None] * \
        xh.astype(jnp.float32)
    y = y.reshape(B_, S, d_inner)
    y = y * jax.nn.silu(z.astype(jnp.float32))                 # gate
    y = rms_norm(y.astype(cdt), p["norm"], cfg.norm_eps)
    out = y.astype(cdt) @ p["out_proj"].astype(cdt)

    new_cache = None
    if mode in ("prefill", "decode"):
        new_cache = {"conv": new_conv.astype(cdt),
                     "ssm": new_state.astype(jnp.float32)}
    return out, new_cache
