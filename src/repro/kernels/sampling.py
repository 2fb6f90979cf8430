"""Fused temperature/top-k Gumbel sampling — the decode hot path's last
host round-trip, moved on-device.

Before this kernel a decode step was: Pallas ``paged_decode`` -> (B, V)
logits D2H -> per-request numpy sampling on the host. The logits transfer
and the per-token host work scale with batch x vocab and sit squarely on
the serve plane's critical path. Here the whole sampler — vocab-tail mask,
temperature scale, top-k filter, Gumbel-max draw, argmax — runs where the
logits already live, and only the sampled token ids (B,) int32 ever leave
the device.

Bit-identity contract (invariant I10). ``ServeEngine._sample`` is the
HOST-side oracle: a request's token t must be the same whether it was
sampled on the host or in-kernel, before or after any pause / migrate /
CoW. That forces every arithmetic op here to be *portably exact* between
numpy (host) and XLA/Pallas (device):

  noise      a counter-seeded integer hash (uint32 avalanche mixing of
             (seed, rid, token_counter, vocab_index)) — wrapping uint32
             arithmetic is bit-exact everywhere
  u32 -> u   ``(h >> 8) + 0.5) * 2^-24`` — every step exactly
             representable in float32, u in (0, 1) strictly
  gumbel     ``-log(-log(u))`` with ``log`` implemented HERE from
             exponent extraction + an atanh polynomial using only
             IEEE-correctly-rounded float32 +,-,*,/ — numpy and XLA agree
             on those bit-for-bit, which libm/XLA's transcendental
             ``log`` does not guarantee
  argmax     first-max-index semantics in both numpy and jnp

The same generic implementation (parameterized over the array namespace)
is instantiated for numpy (``host_gumbel`` — what ``ServeEngine._sample``
draws) and jnp (the ref oracle and the Pallas kernel), so the two paths
cannot drift.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# uint32 avalanche constants (splitmix/murmur-style finalizer)
_M1 = 0x7FEB352D
_M2 = 0x846CA68B
_GOLD = 0x9E3779B9
_SALT = 0x5E12C0DE     # the serve plane's sampling-stream domain tag

# portable-log constants (float32 exact values)
_LN2 = np.float32(0.6931471805599453)
_SQRT2 = np.float32(1.4142135623730951)
_C3 = np.float32(1.0 / 3.0)
_C5 = np.float32(1.0 / 5.0)
_C7 = np.float32(1.0 / 7.0)
_C9 = np.float32(1.0 / 9.0)
_HALF = np.float32(0.5)
_ONE = np.float32(1.0)
_TWO = np.float32(2.0)
_U24 = np.float32(2.0 ** -24)


def _mix(h, xp):
    """Finalizing avalanche mix over uint32 (wrapping arithmetic)."""
    h = h ^ (h >> 16)
    h = h * xp.uint32(_M1)
    h = h ^ (h >> 15)
    h = h * xp.uint32(_M2)
    h = h ^ (h >> 16)
    return h


def _log32(x, xp, to_i32, to_f32):
    """Portable float32 natural log for x > 0 (normal range).

    Exponent/mantissa split via bitcast, then ln(m) from the atanh series
    2s(1 + s^2/3 + s^4/5 + ...) with s = (m-1)/(m+1), |s| < 0.1716 after
    centering m into [sqrt(2)/2, sqrt(2)). Only +,-,*,/ on float32 — all
    correctly rounded, so numpy and XLA produce identical bits.
    """
    bits = to_i32(x)
    e = ((bits >> 23) & 0xFF) - 127
    m = to_f32((bits & 0x007FFFFF) | 0x3F800000)          # [1, 2)
    big = m > _SQRT2
    m = xp.where(big, m * _HALF, m)
    e = xp.where(big, e + 1, e)
    s = (m - _ONE) / (m + _ONE)
    t = s * s
    poly = _ONE + t * (_C3 + t * (_C5 + t * (_C7 + t * _C9)))
    return e.astype(xp.float32) * _LN2 + (_TWO * s) * poly


def _gumbel(base_u32, idx_u32, xp, to_i32, to_f32):
    """Gumbel(0,1) noise for each vocab index, from the mixed base key.
    base_u32: uint32 scalar/array broadcastable against idx_u32 (uint32
    vocab indices). Returns float32 of idx's shape."""
    h = _mix(base_u32 ^ idx_u32, xp)
    # h >> 8 < 2^24, so its int32 view converts to the same float32
    u = (to_i32(h >> 8).astype(xp.float32) + _HALF) * _U24   # (0,1) open
    return -_log32(-_log32(u, xp, to_i32, to_f32), xp, to_i32, to_f32)


def _base_key(seed, rid, counter, xp):
    """Counter-seeded stream key: token ``counter`` of request
    (seed, rid) always derives the same key — sampling stays a pure
    function of the request, which is what makes pause/migrate/replay
    token-identical (I10)."""
    h = _mix(xp.uint32(_SALT) ^ (seed.astype(xp.uint32) * xp.uint32(_GOLD)),
             xp)
    h = _mix(h ^ rid.astype(xp.uint32), xp)
    h = _mix(h ^ counter.astype(xp.uint32), xp)
    return h


# ---------------------------------------------------------------------------
# numpy instantiation (the host oracle's noise source)
# ---------------------------------------------------------------------------
def _np_to_i32(x):
    return np.ascontiguousarray(x).view(np.int32)


def _np_to_f32(x):
    return np.ascontiguousarray(x).astype(np.uint32).view(np.float32) \
        if x.dtype != np.int32 else np.ascontiguousarray(x).view(np.float32)


def host_gumbel(seed: int, rid: int, counter: int, n: int) -> np.ndarray:
    """(n,) float32 Gumbel noise for token ``counter`` of request
    (seed, rid) — numpy twin of the in-kernel draw, bit-identical."""
    base = _base_key(np.uint32(np.asarray([seed], np.int64) & 0xFFFFFFFF),
                     np.uint32(np.asarray([rid], np.int64) & 0xFFFFFFFF),
                     np.uint32(np.asarray([counter],
                                          np.int64) & 0xFFFFFFFF), np)
    idx = np.arange(n, dtype=np.uint32)
    return _gumbel(base, idx, np, _np_to_i32, _np_to_f32)


# ---------------------------------------------------------------------------
# jnp instantiation (ref oracle + inside the Pallas kernel)
# ---------------------------------------------------------------------------
def _jnp_to_i32(x):
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def _jnp_to_f32(x):
    return jax.lax.bitcast_convert_type(x.astype(jnp.int32), jnp.float32) \
        if x.dtype != jnp.int32 else jax.lax.bitcast_convert_type(
            x, jnp.float32)


def jnp_gumbel(keys, idx):
    """keys: (..., 3) int32 (seed, rid, counter); idx: uint32 indices
    broadcastable against keys[..., 0]. Returns float32 noise."""
    base = _base_key(keys[..., 0], keys[..., 1], keys[..., 2], jnp)
    return _gumbel(base, idx, jnp, _jnp_to_i32, _jnp_to_f32)


def prepare_rows(logits, temp, top_k, *, vocab_size: int):
    """Shared sampler front half (runs as plain XLA either way): cast to
    float32, mask the padded vocab tail, temperature-scale, top-k filter.
    Greedy rows (temp <= 0) pass through unscaled so the argmax equals
    the host's greedy ``argmax(logits)``. Returns (B, V) float32 rows
    ready for noise + argmax, plus the (B,) bool noisy-row mask."""
    B, Vp = logits.shape
    lg = logits.astype(jnp.float32)
    vmask = jnp.arange(Vp) < vocab_size
    lg = jnp.where(vmask[None, :], lg, -jnp.inf)
    temp = jnp.asarray(temp, jnp.float32)
    noisy = temp > 0
    z = lg / jnp.where(noisy, temp, _ONE)[:, None]
    # per-row k-th largest of the SCALED row (matches the host's
    # np.partition threshold); k outside (0, V) disables the filter
    top_k = jnp.asarray(top_k, jnp.int32)
    use_k = noisy & (top_k > 0) & (top_k < vocab_size)
    kth = _kth_largest(z, jnp.clip(top_k, 1, Vp))
    thr = jnp.where(use_k, kth, -jnp.inf)
    z = jnp.where(z >= thr[:, None], z, -jnp.inf)
    return z, noisy


def _kth_largest(z, k):
    """Per-row k-th largest value of z (B, V) float32, k (B,) in [1, V].

    Exact, like a sort, but without one: the float bits are mapped to a
    uint32 key that orders as the floats do, and the key of the k-th
    largest element — the largest r with #{key >= r} >= k — is built one
    bit at a time from the top, 32 counting passes over the row."""
    bits = jax.lax.bitcast_convert_type(z, jnp.int32)
    key = jax.lax.bitcast_convert_type(
        jnp.where(bits < 0, ~bits, bits ^ jnp.int32(-2 ** 31)), jnp.uint32)

    def bit(i, r):
        cand = r | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        n = jnp.sum(key >= cand[:, None], axis=-1, dtype=jnp.int32)
        return jnp.where(n >= k, cand, r)

    r = jax.lax.fori_loop(0, 32, bit, jnp.zeros(z.shape[:1], jnp.uint32))
    rb = jax.lax.bitcast_convert_type(r, jnp.int32)
    return jax.lax.bitcast_convert_type(
        jnp.where(rb < 0, rb ^ jnp.int32(-2 ** 31), ~rb), jnp.float32)


# ---------------------------------------------------------------------------
# the Pallas kernel: tiled noise + online first-index argmax
# ---------------------------------------------------------------------------
_MAX_VTILE = 8192


def _kernel(base_ref, noisy_ref, z_ref, o_ref, val_scr, idx_scr, *,
            vtile: int):
    """One vocab tile of every row: base_ref (B,1) int32 (the rows'
    uint32 stream keys), noisy_ref (B,1) int32, z_ref (B, vtile)."""
    ti = pl.program_id(0)
    B = z_ref.shape[0]

    @pl.when(ti == 0)
    def _init():
        val_scr[...] = jnp.full_like(val_scr, NEG_INF)
        idx_scr[...] = jnp.zeros_like(idx_scr)

    col = ti * vtile + jax.lax.broadcasted_iota(jnp.int32, (B, vtile), 1)
    base = jax.lax.bitcast_convert_type(base_ref[...], jnp.uint32)
    g = _gumbel(base, jax.lax.bitcast_convert_type(col, jnp.uint32), jnp,
                _jnp_to_i32, _jnp_to_f32)
    z = z_ref[...]
    y = jnp.where(noisy_ref[...] != 0, z + g, z)
    # -inf rows (vocab padding / top-k filtered) can never win: noise is
    # finite, so -inf + g stays -inf < any finite running best
    tmax = jnp.max(y, axis=1, keepdims=True)                   # (B, 1)
    # first index attaining the tile max (argmax's tie rule)
    targ = jnp.min(jnp.where(y == tmax, col, jnp.iinfo(jnp.int32).max),
                   axis=1, keepdims=True)
    better = tmax > val_scr[...]
    val_scr[...] = jnp.where(better, tmax, val_scr[...])
    idx_scr[...] = jnp.where(better, targ, idx_scr[...])

    @pl.when(ti == pl.num_programs(0) - 1)
    def _finish():
        o_ref[...] = idx_scr[...]


def fused_sample(logits, temp, top_k, keys, *, vocab_size: int,
                 interpret: bool = False):
    """logits: (B, Vp); temp: (B,) float32; top_k: (B,) int32; keys:
    (B, 3) int32 (seed, rid, token_counter). Returns (B,) int32 sampled
    token ids, bit-identical to ``ServeEngine._sample`` row by row.

    A block is every row of one vocab tile (B, vtile), so the kernel
    walks the vocabulary once for the whole batch."""
    B, Vp = logits.shape
    z, noisy = prepare_rows(logits, temp, top_k, vocab_size=vocab_size)
    vtile = min(_MAX_VTILE, -(-Vp // 128) * 128)
    pad = (-Vp) % vtile
    if pad:
        z = jnp.pad(z, ((0, 0), (0, pad)), constant_values=-jnp.inf)
    nt = (Vp + pad) // vtile
    keys = jnp.asarray(keys, jnp.int32)
    base = jax.lax.bitcast_convert_type(
        _base_key(keys[:, 0], keys[:, 1], keys[:, 2], jnp), jnp.int32)
    # replace -inf with a finite floor: the kernel adds noise to every
    # lane and -inf + finite is -inf (fine), but NEG_INF keeps the
    # scratch compare total-ordered even if a row is entirely masked
    z = jnp.maximum(z, NEG_INF)
    row_spec = pl.BlockSpec((B, 1), lambda ti: (0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, vtile=vtile),
        grid=(nt,),
        in_specs=[row_spec, row_spec,
                  pl.BlockSpec((B, vtile), lambda ti: (0, ti))],
        out_specs=row_spec,
        scratch_shapes=[pltpu.VMEM((B, 1), jnp.float32),
                        pltpu.VMEM((B, 1), jnp.int32)],
        out_shape=jax.ShapeDtypeStruct((B, 1), jnp.int32),
        interpret=interpret,
        name="fused_sample",
    )(base[:, None], noisy.astype(jnp.int32)[:, None], z)
    return out[:, 0]
