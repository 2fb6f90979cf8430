"""Mixture-of-Experts FFN: two layers.

``moe_ffn`` — GShard-style top-k routing with capacity. SPMD mapping (see
DESIGN.md): tokens are grouped into fixed-size groups so the
dispatch/combine one-hots stay small — groups shard over the data axes,
the expert dim shards over the model axis (EP). All communication is left
to the XLA SPMD partitioner (all-to-all between the token layout and the
expert layout, all-gather for FSDP expert weights). Tokens over a group's
capacity are dropped, so a token's output depends on its group.

``moe_ffn_dropless`` (``MoEConfig.dropless``) — every routed (token, held
expert) pair is computed: the router scores all ``num_experts`` and picks
the top k, the pairs whose expert this chip holds are sorted by expert,
and one grouped matmul per projection (``kernels/grouped_ffn``) runs them.
No capacity and no one-hot dispatch: the work follows the pairs routed
here, and a token's output depends on that token alone. Experts outside
the held share add nothing (expert parallelism without its exchange: the
other chips' shares add up to the rest of the layer).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.runtime.partitioning import constrain

GROUP_SIZE = 512  # tokens per routing group (capacity is per-group)
#: the dropless layer's counters, summed over layers into the stack's aux
#: (not losses): (token, held expert) pairs computed, and the largest
#: count of pairs on one held expert
EXPERT_COUNTERS = ("expert_tokens", "expert_max_load")


def route(x2, router, moe: MoEConfig):
    """Router over all ``num_experts``: float32 softmax of the logits, top
    k, renormalised when ``norm_topk_prob``. x2: (T, D) -> (probs (T, E),
    gates (T, k), expert ids (T, k))."""
    logits = jnp.dot(x2.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, moe.num_experts_per_token)
    if moe.norm_topk_prob:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    return logits, probs, gates, idx


def moe_ffn_dropless(x: jax.Array, p: dict, moe: MoEConfig, cdt,
                     backend: str = "reference", interpret: bool = False):
    """x: (B, S, D) -> (y float32, aux): this chip's experts' part of the
    layer for every token, with the aux losses and ``EXPERT_COUNTERS``."""
    from repro.kernels import ops as kops
    B, S, D = x.shape
    T = B * S
    E, K = moe.num_experts, moe.num_experts_per_token
    Eh, off = moe.experts_held, moe.held_offset
    x2 = x.reshape(T, D)
    with jax.named_scope("moe.route"):
        logits, probs, gates, idx = route(x2, p["router"], moe)
        local = idx - off
        held = (local >= 0) & (local < Eh)
        e = jnp.where(held, local, Eh).reshape(T * K)   # absent: sorts last
        order = jnp.argsort(e, stable=True)
        sizes = jnp.bincount(e, length=Eh + 1)[:Eh].astype(jnp.int32)
        rows = x2.astype(cdt)[order // K]
    with jax.named_scope("moe.experts"):
        out = kops.grouped_ffn(
            rows, p["wg"].astype(cdt), p["wi"].astype(cdt),
            p["wo"].astype(cdt), sizes, interpret=interpret,
            backend="pallas" if backend == "pallas" else "ref")
        out = jnp.where((e[order] < Eh)[:, None], out, 0.0)
        # back to (token, choice) order, then each token's gated sum in a
        # fixed order: nothing of another token enters it
        pairs = jnp.zeros_like(out).at[order].set(out).reshape(T, K, D)
        w = jnp.where(held, gates, 0.0)
        y = jnp.sum(pairs * w[..., None], axis=1)
    frac = jnp.mean(jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32),
                            axis=1), axis=0)
    lb = E * jnp.sum(frac * jnp.mean(probs, axis=0)) / K
    z = jnp.mean(jnp.square(jax.scipy.special.logsumexp(logits, axis=-1)))
    aux = {"load_balance": moe.load_balance_loss * lb,
           "router_z": moe.router_z_loss * z,
           "expert_tokens": jnp.sum(sizes).astype(jnp.float32),
           "expert_max_load": jnp.max(sizes).astype(jnp.float32)}
    return y.reshape(B, S, D), aux


def moe_ffn(x: jax.Array, p: dict, moe: MoEConfig, cdt):
    """x: (B, S, D) -> (y, aux) where aux = {load_balance, router_z} losses.

    Routing/capacity semantics: top-k per token, per-group capacity
    C = ceil(Sg * k / E * capacity_factor); overflow tokens drop (their
    combine weight is zero) — standard GShard "dropping" behaviour.
    """
    B, S, D = x.shape
    E, K = moe.num_experts, moe.num_experts_per_token
    sg = min(GROUP_SIZE, S)
    # under sequence-parallel activations, groups must not straddle the
    # sequence shards (routing then stays local; EP comm is the small
    # token-sized all-to-all XLA inserts at the expert einsums)
    from repro.runtime.partitioning import current_rules
    rules = current_rules()
    if rules is not None and rules.run.sharding.seq_shard_acts:
        m = rules.axis_size.get("model", 1)
        if m > 1 and S % m == 0:
            sg = min(sg, S // m)
    assert (B * S) % sg == 0
    G = (B * S) // sg
    xg = x.reshape(G, sg, D)

    # ---- router: bf16 matmul (keeps the bwd cotangent of the hidden
    # stream in bf16 — an fp32 router input promotes the entire residual
    # cotangent to f32, doubling every reshard; §Perf HC2), fp32 softmax.
    logits = (xg.astype(cdt) @ p["router"].astype(cdt)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                    # (G, sg, E)
    gate_vals, idx = jax.lax.top_k(probs, K)                   # (G, sg, K)
    gate_vals = gate_vals / (jnp.sum(gate_vals, -1, keepdims=True) + 1e-9)

    cap = int(max(1, round(sg * K / E * moe.capacity_factor)))

    # ---- capacity assignment (sequential over the K choices) --------------
    # dispatch/combine are built in the COMPUTE dtype: their (G,sg,E,C)
    # one-hots are the largest tensors in the layer and fp32 versions drag
    # f32 cotangents through both dispatch einsums (§Perf HC2).
    # NOTE (§Perf HC2 it.4, REFUTED): constraining dispatch/combine with E
    # sharded over the model axis ("moe_dispatch" kind) was hypothesized to
    # kill the bwd dispatch-cotangent gather; measured instead +71% flops
    # and 2.5x temp memory (XLA materializes full-E one-hots before the
    # forced reshard). Left unconstrained: GSPMD's own placement wins.
    counts = jnp.zeros((G, E), jnp.float32)
    dispatch = jnp.zeros((G, sg, E, cap), cdt)
    combine = jnp.zeros((G, sg, E, cap), cdt)
    for i in range(K):
        m = jax.nn.one_hot(idx[:, :, i], E, dtype=jnp.float32)  # (G,sg,E)
        pos = counts[:, None, :] + jnp.cumsum(m, axis=1) - m    # slot index
        keep = (pos < cap).astype(jnp.float32) * m
        counts = counts + jnp.sum(keep, axis=1)
        slot = jax.nn.one_hot(pos.astype(jnp.int32), cap,
                              dtype=jnp.float32)                # (G,sg,E,C)
        d_i = keep[..., None] * slot
        dispatch = dispatch + d_i.astype(cdt)
        combine = combine + (gate_vals[:, :, i][..., None, None] *
                             d_i).astype(cdt)

    # ---- expert computation ------------------------------------------------
    ein = dispatch
    expert_in = jnp.einsum("gsec,gsd->egcd", ein, xg.astype(cdt))
    expert_in = constrain(expert_in, "expert")                  # (E,G,C,D)
    h = jax.nn.silu(jnp.einsum("egcd,edf->egcf", expert_in,
                               p["wg"].astype(cdt)))
    h = h * jnp.einsum("egcd,edf->egcf", expert_in, p["wi"].astype(cdt))
    out = jnp.einsum("egcf,efd->egcd", h, p["wo"].astype(cdt))
    out = constrain(out, "expert")
    y = jnp.einsum("gsec,egcd->gsd", combine, out)

    # ---- aux losses --------------------------------------------------------
    # load balance: E * sum_e mean_prob_e * mean_dispatch_frac_e
    frac = jnp.mean(jnp.sum(dispatch.astype(jnp.float32), axis=-1),
                    axis=(0, 1))                                # (E,)
    mean_p = jnp.mean(probs, axis=(0, 1))
    lb = E * jnp.sum(frac * mean_p) / K
    z = jnp.mean(jnp.square(jax.scipy.special.logsumexp(logits, axis=-1)))
    aux = {"load_balance": moe.load_balance_loss * lb,
           "router_z": moe.router_z_loss * z}
    return y.reshape(B, S, D), aux
