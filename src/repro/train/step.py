"""Train-step builder: value_and_grad + clip + optimizer, with optional
microbatch gradient accumulation, under the active sharding scope.

The returned step function is pure (state, batch) -> (state, metrics) and is
what Tenants execute and the dry-run lowers.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import RunConfig
from repro.models.model import Model, build_model
from repro.runtime.partitioning import ShardingRules, sharding_scope
from repro.train.optim import (build_optimizer, clip_by_global_norm,
                               lr_schedule)


def init_train_state(run: RunConfig, rng: jax.Array) -> dict:
    model = build_model(run)
    params = model.init(rng)
    opt = build_optimizer(run.optimizer)
    return {"params": params, "opt": opt.init(params),
            "step": jnp.zeros((), jnp.int32)}


def train_state_shapes(run: RunConfig) -> dict:
    """ShapeDtypeStructs of the full train state (dry-run: no allocation)."""
    model = build_model(run)
    opt = build_optimizer(run.optimizer)
    pshapes = model.param_shapes()
    oshapes = jax.eval_shape(opt.init, pshapes)
    return {"params": pshapes, "opt": oshapes,
            "step": jax.ShapeDtypeStruct((), jnp.int32)}


def train_state_specs(run: RunConfig, rules: ShardingRules) -> dict:
    """PartitionSpec tree matching train_state_shapes."""
    from jax.sharding import PartitionSpec as P
    model = build_model(run)
    pshapes = model.param_shapes()
    pspecs = rules.param_specs(pshapes)
    opt = build_optimizer(run.optimizer)
    ospecs = opt.state_specs(rules, pspecs, pshapes)
    return {"params": pspecs, "opt": ospecs, "step": P()}


def batch_specs(run: RunConfig, rules: ShardingRules) -> dict:
    from jax.sharding import PartitionSpec as P
    model = build_model(run)
    specs = model.input_specs()
    out = {}
    for k, v in specs.items():
        if v.shape == ():
            out[k] = P()
        else:
            out[k] = P(rules._fit(v.shape[0], rules.dp_axes),
                       *([None] * (len(v.shape) - 1)))
    return out


def make_train_step(run: RunConfig, rules: Optional[ShardingRules] = None,
                    total_steps: int = 10000):
    model = build_model(run)
    opt = build_optimizer(run.optimizer)
    sched = lr_schedule(run.optimizer, total_steps)

    def loss_fn(params, batch):
        with sharding_scope(rules):
            return model.loss(params, batch)

    def grads_of(params, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        return grads, metrics

    def train_step(state, batch):
        params = state["params"]
        if run.microbatch > 1:
            mb = run.microbatch

            def split(x):
                return x.reshape((mb, x.shape[0] // mb) + x.shape[1:])
            mbatch = jax.tree.map(split, batch)

            def body(acc, b):
                g, m = grads_of(params, b)
                acc = jax.tree.map(jnp.add, acc, g)
                return acc, m
            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            grads, ms = jax.lax.scan(
                body, zero, mbatch,
                unroll=mb if run.sharding.unroll_microbatch else 1)
            grads = jax.tree.map(lambda g: (g / mb).astype(jnp.float32),
                                 grads)
            metrics = jax.tree.map(lambda x: jnp.mean(x, axis=0), ms)
        else:
            grads, metrics = grads_of(params, batch)

        grads, gnorm = clip_by_global_norm(grads, run.optimizer.grad_clip)
        lr = sched(state["step"])
        with sharding_scope(rules):
            new_params, new_opt = opt.update(grads, state["opt"], params, lr)
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        metrics = dict(metrics)
        metrics["grad_norm"] = gnorm
        metrics["lr"] = lr
        return new_state, metrics

    return train_step


def make_eval_step(run: RunConfig, rules: Optional[ShardingRules] = None):
    model = build_model(run)

    def eval_step(params, batch):
        with sharding_scope(rules):
            loss, metrics = model.loss(params, batch)
        return metrics

    return eval_step


def make_serve_steps(run: RunConfig, rules: Optional[ShardingRules] = None):
    """Returns (prefill_fn, decode_fn) under the sharding scope."""
    model = build_model(run)

    def prefill(params, batch):
        with sharding_scope(rules), jax.named_scope("prefill"):
            return model.prefill(params, batch)

    def decode(params, cache, tokens, pos):
        with sharding_scope(rules), jax.named_scope("decode"):
            return model.decode_step(params, cache, tokens, pos)

    return prefill, decode


def make_decode_step(run: RunConfig,
                     rules: Optional[ShardingRules] = None, *,
                     paged: bool = False, fused: bool = False,
                     counters: bool = False):
    """Continuous-batching decode step with an active-slot mask; with
    ``paged`` the cache is the paged-KV page pool and a block table rides
    along (see ``Model.decode_step``).

    With ``fused`` the step also takes per-slot sampling params
    (temp (B,) f32, top_k (B,) i32, keys (B,3) i32 = (seed, rid,
    token_counter)) and returns SAMPLED TOKEN IDS (B,) i32 instead of
    logits — temperature/top-k Gumbel sampling runs on-device
    (``kernels/sampling``), bit-identical to ``ServeEngine._sample``, and
    the (B, V) logits never leave the device.

    With ``counters`` (paged and fused, a dropless expert stack) the token
    ids are followed by the stack's counters (``models.moe.
    EXPERT_COUNTERS``, summed over layers), (B + 2,) int32: one readback."""
    model = build_model(run)

    def _sample_on_device(logits, temp, topk, keys):
        from repro.kernels import ops as kops
        return kops.fused_sample(
            logits, temp, topk, keys, vocab_size=run.model.vocab_size,
            interpret=run.interpret,
            backend="pallas" if run.kernels == "pallas" else "ref")

    if counters:
        assert paged and fused, "counters ride on the paged fused step"
        from repro.models.moe import EXPERT_COUNTERS

        def decode(params, cache, tokens, pos, tables, active,
                   temp, topk, keys):
            with sharding_scope(rules), jax.named_scope("decode"):
                logits, cache, cnt = model.decode_step(
                    params, cache, tokens, pos, tables=tables,
                    active=active, counters=True)
                toks = _sample_on_device(logits, temp, topk, keys)
                extra = jnp.stack([cnt[k] for k in EXPERT_COUNTERS])
                return jnp.concatenate(
                    [toks, extra.astype(toks.dtype)]), cache
    elif paged and fused:
        def decode(params, cache, tokens, pos, tables, active,
                   temp, topk, keys):
            with sharding_scope(rules), jax.named_scope("decode"):
                logits, cache = model.decode_step(params, cache, tokens,
                                                  pos, tables=tables,
                                                  active=active)
                return _sample_on_device(logits, temp, topk, keys), cache
    elif paged:
        def decode(params, cache, tokens, pos, tables, active):
            with sharding_scope(rules), jax.named_scope("decode"):
                return model.decode_step(params, cache, tokens, pos,
                                         tables=tables, active=active)
    elif fused:
        def decode(params, cache, tokens, pos, active, temp, topk, keys):
            with sharding_scope(rules), jax.named_scope("decode"):
                logits, cache = model.decode_step(params, cache, tokens,
                                                  pos, active=active)
                return _sample_on_device(logits, temp, topk, keys), cache
    else:
        def decode(params, cache, tokens, pos, active):
            with sharding_scope(rules), jax.named_scope("decode"):
                return model.decode_step(params, cache, tokens, pos,
                                         active=active)
    return decode


def make_prefill_chunk(run: RunConfig,
                       rules: Optional[ShardingRules] = None):
    """Chunked-prefill step (attention-pattern stacks only)."""
    model = build_model(run)

    def chunk(params, cache, tokens, offset):
        with sharding_scope(rules), jax.named_scope("prefill_chunk"):
            return model.prefill_chunk(params, cache, tokens, offset)

    return chunk
