"""Optional pipeline parallelism: a GPipe-style microbatched pipeline over
a dedicated "pipe" mesh axis, built on shard_map + collective_permute.

Not used by the fixed production meshes (axes pod/data/model — see
DESIGN.md §3); provided for deployments that trade a mesh axis for
pipeline stages (e.g. very deep models across slower inter-slice links).

The schedule is plain GPipe: M microbatches flow through S stages in
M + S - 1 ticks; each tick every stage computes its resident microbatch
and the activations rotate one hop with collective_permute. Bubble
fraction = (S-1)/(M+S-1), reported by ``bubble_fraction``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def bubble_fraction(num_microbatches: int, num_stages: int) -> float:
    return (num_stages - 1) / (num_microbatches + num_stages - 1)


def serve_schedule(num_microbatches: int, num_stages: int):
    """The GPipe work-item order for the HOST-side serving pipeline
    (``repro.serve.pipeline_engine``): (stage, microbatch) pairs in tick
    order, tick t = stage + microbatch. Executing items in this order
    satisfies both dependencies of item (s, m) — (s-1, m) ran at tick
    t-1 (activation hand-off) and (s, m-1) ran at tick t-1 (the stage's
    KV cache threads through its own microbatches)."""
    M, S = num_microbatches, num_stages
    for t in range(M + S - 1):
        for s in range(S):
            m = t - s
            if 0 <= m < M:
                yield s, m


@dataclasses.dataclass(frozen=True)
class ScheduleStats:
    """Measured pipeline utilization from per-item wall times.

    ``walls[s][m]`` is the measured wall of work item (stage s,
    microbatch m). The makespan is the GPipe critical path —
    finish(s, m) = max(finish(s-1, m), finish(s, m-1)) + walls[s][m] —
    and the measured bubble fraction is the idle share of the S-stage
    schedule area: 1 - sum(walls) / (S * makespan). With uniform walls
    this reduces exactly to ``bubble_fraction(M, S)``; with real walls
    it is the number the autoscaler's width actions should be justified
    by, not the analytic one."""
    num_stages: int
    num_microbatches: int
    makespan: float
    busy: float
    stage_busy: tuple

    @property
    def bubble(self) -> float:
        if self.makespan <= 0.0:
            return 0.0
        return max(0.0, 1.0 - self.busy /
                   (self.num_stages * self.makespan))


def schedule_stats(walls) -> ScheduleStats:
    """Fold per-item walls (list of S lists of M floats) into
    ``ScheduleStats`` via the GPipe finish-time recurrence."""
    S = len(walls)
    M = len(walls[0]) if S else 0
    finish = [[0.0] * M for _ in range(S)]
    for s, m in serve_schedule(M, S):
        up = finish[s - 1][m] if s > 0 else 0.0
        left = finish[s][m - 1] if m > 0 else 0.0
        finish[s][m] = max(up, left) + walls[s][m]
    makespan = finish[S - 1][M - 1] if S and M else 0.0
    stage_busy = tuple(float(sum(row)) for row in walls)
    return ScheduleStats(num_stages=S, num_microbatches=M,
                         makespan=float(makespan),
                         busy=float(sum(stage_busy)),
                         stage_busy=stage_busy)


def pipeline_apply(stage_fn: Callable, stage_params, x, mesh: Mesh,
                   axis: str = "pipe"):
    """Run ``y = stage_{S-1}(...stage_0(x))`` as a GPipe pipeline.

    stage_fn: (params_for_one_stage, (mb, ...)) -> (mb, ...)   same shape
    stage_params: pytree with leading dim S (one slice per stage), sharded
                  over ``axis``
    x: (M, mb, ...) microbatches (replicated over ``axis``)
    Returns (M, mb, ...) outputs (replicated).
    """
    S = mesh.shape[axis]
    M = x.shape[0]

    def per_stage(params, xs):
        params = jax.tree.map(lambda p: p[0], params)   # local stage slice
        idx = jax.lax.axis_index(axis)
        T = M + S - 1
        state = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)
        perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(t, carry):
            state, outs = carry
            # stage 0 ingests microbatch t (while available)
            mb_in = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, M - 1), axis=0, keepdims=False)
            inp = jnp.where(idx == 0, mb_in, state)
            y = stage_fn(params, inp)
            # the last stage emits microbatch t-(S-1)
            ot = t - (S - 1)
            valid = (idx == S - 1) & (ot >= 0) & (ot < M)
            outs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(ot, 0, M - 1), axis=0),
                lambda o: o, outs)
            state = jax.lax.ppermute(y, axis, perm)
            return state, outs

        _, outs = jax.lax.fori_loop(0, T, tick, (state, outs))
        # only the last stage holds real outputs; share them around
        outs = jax.lax.psum(
            jnp.where(idx == S - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    pspec = jax.tree.map(lambda _: P(axis), stage_params)
    others = tuple(None for _ in range(x.ndim - 1))
    xspec = P(*((None,) + others))
    fn = jax.shard_map(per_stage, mesh=mesh, in_specs=(pspec, xspec),
                       out_specs=xspec, check_vma=False)
    return fn(stage_params, x)
