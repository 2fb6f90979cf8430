"""The serving stream's sampling rule, as the benchmark's reference applies
it: a sampled token is the argmax, over the ``top_k`` largest logits, of
``logit / temperature + g``, where ``g`` is Gumbel noise drawn from a
counter hash of (request seed, request id, token index, vocabulary index).

This is the specification the program's fused sampler states (a uint32
avalanche mix, salted for the serve plane), written out again here so that
the reference imports nothing of the program. The hash is exact integer
arithmetic; the two logarithms use ``jnp.log``, whose last-bit differences
from the program's own are far below any limit the check sets.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_M1, _M2 = 0x7FEB352D, 0x846CA68B
_GOLD = 0x9E3779B9
_SALT = 0x5E12C0DE


def _mix(h):
    h = h ^ (h >> 16)
    h = h * jnp.uint32(_M1)
    h = h ^ (h >> 15)
    h = h * jnp.uint32(_M2)
    return h ^ (h >> 16)


def noise(keys, idx):
    """Gumbel(0, 1) noise. ``keys``: (..., 3) uint32 (seed, rid, token
    index); ``idx``: uint32 vocabulary indices, broadcastable against
    ``keys[..., 0]``."""
    keys = keys.astype(jnp.uint32)
    h = _mix(jnp.uint32(_SALT) ^ (keys[..., 0] * jnp.uint32(_GOLD)))
    h = _mix(h ^ keys[..., 1])
    h = _mix(h ^ keys[..., 2])
    h = _mix(h ^ idx.astype(jnp.uint32))
    u = ((h >> 8).astype(jnp.float32) + 0.5) * (2.0 ** -24)
    return -jnp.log(-jnp.log(u))


def _at(x, idx):
    return jnp.take_along_axis(x, idx[..., None], axis=-1)[..., 0]


def token_gaps(lg, tok, temp, top_k, keys, *, kmax: int, margin: float):
    """How far each served token lies from what the sampling rule picks on
    the logits ``lg`` (B, P, V), in logit units (0 where it is the pick).

    Greedy positions (``temp`` <= 0): the best logit minus the token's.
    Sampled positions: the larger of how far the token's logit lies below
    the ``top_k``-th largest, and how far its perturbed logit
    ``l + temp * g`` lies below the best perturbed logit among the tokens
    clearly inside the top-k: ``margin`` or more above its boundary, so
    that a token at the boundary that rounding moved in or out of the set
    is no competitor. An id outside the vocabulary reads +inf.
    ``tok``, ``temp``, ``top_k``: (B, P); ``keys``: (B, P, 3); sampled
    positions need 1 <= ``top_k`` <= ``kmax``."""
    V = lg.shape[-1]
    ok = (tok >= 0) & (tok < V)
    t = jnp.clip(tok, 0, V - 1)
    l_tok = _at(lg, t)
    greedy = jnp.max(lg, axis=-1) - l_tok
    topv, topi = jax.lax.top_k(lg, kmax)
    kth = _at(topv, jnp.clip(top_k, 1, kmax) - 1)
    core = topv >= (kth + margin)[..., None]
    best = jnp.max(jnp.where(core, topv + temp[..., None]
                             * noise(keys[..., None, :], topi), -jnp.inf),
                   axis=-1)
    sampled = jnp.maximum(best - (l_tok + temp * noise(keys, t)), kth - l_tok)
    return jnp.where(ok, jnp.where(temp > 0, sampled, greedy), jnp.inf)


def pick(lg, temp, top_k, keys, *, kmax: int):
    """The token the sampling rule picks on the logits ``lg`` (B, P, V)."""
    topv, topi = jax.lax.top_k(lg, kmax)
    live = jnp.arange(kmax) < jnp.clip(top_k, 1, kmax)[..., None]
    score = jnp.where(live, topv + temp[..., None]
                      * noise(keys[..., None, :], topi), -jnp.inf)
    sampled = _at(topi, jnp.argmax(score, axis=-1))
    return jnp.where(temp > 0, sampled,
                     jnp.argmax(lg, axis=-1)).astype(jnp.int32)
