"""Tenant — the VM/guest analogue.

A tenant owns a *logical* workload (training loop or serving engine) and
never touches physical devices directly: binding is the Manager/VF's job.
Its step code is byte-identical across reconfigurations ("no driver
modification on the guest", paper §III). While PAUSED it keeps answering
queries from its emulated view (the guest still sees the device, fig. 2
right panel) but actual work raises DevicePausedError — "can not do any
actual I/O operations until the device is unpaused".
"""
from __future__ import annotations

import collections
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import MeshConfig, RunConfig
from repro.core.vf import VirtualFunction
from repro.data.pipeline import SyntheticSource
from repro.runtime.partitioning import ShardingRules
from repro.train.step import (batch_specs, init_train_state, make_train_step,
                              train_state_specs)


class DevicePausedError(RuntimeError):
    """I/O attempted on a paused device."""


class Tenant:
    def __init__(self, tid: str, run: RunConfig, *, workload: str = "train",
                 local_batch: int = 4, seq_len: int = 32, seed: int = 0):
        assert workload in ("train", "serve")
        self.tid = tid
        self.run = run.replace(seed=seed)
        self.workload = workload
        self.status = "created"        # created|running|paused|detached
        self.vf_id: Optional[str] = None
        self.steps_done = 0
        self._state = None             # device pytree while attached
        self._rules: Optional[ShardingRules] = None
        self._mesh = None
        self._exec_cache: dict = {}    # (kind, mesh_shape) -> compiled fn
        self._local_batch = local_batch
        self._seq = seq_len
        self._source = SyntheticSource(self.run, batch_override=local_batch,
                                       seq_override=seq_len)
        #: the last 64 step times (the straggler monitor reads the last)
        self.step_times: collections.deque = collections.deque(maxlen=64)
        self._fail_next = False        # fault-injection hook (tests)

    # ------------------------------------------------------------------ utils
    def _make_rules(self, vf: VirtualFunction) -> ShardingRules:
        mesh_cfg = MeshConfig(tuple(vf.mesh_shape), tuple(vf.mesh_axes))
        return ShardingRules(mesh_cfg, self.run, vf.mesh())

    def state_shardings(self, rules: ShardingRules):
        specs = train_state_specs(self.run, rules)
        return rules.named(specs)

    # -- manager/pause protocol (duck-typed; repro.sim substitutes these) ----
    def shardings_for(self, vf: VirtualFunction):
        """Target shardings for placing this tenant's state on ``vf``."""
        return self.state_shardings(self._make_rules(vf))

    def state_template(self):
        """Shape-only pytree matching export_state (checkpoint restore)."""
        from repro.train.step import train_state_shapes
        return train_state_shapes(self.run)

    def export_specs(self):
        """PartitionSpec tree of the current layout (config-space save)."""
        return train_state_specs(self.run, self._rules)

    # --------------------------------------------------------------- lifecycle
    def bind(self, vf: VirtualFunction, state=None, *,
             flash: bool = True) -> float:
        """Attach to a VF slice: place (or adopt restored) state, ensure a
        compiled step executable exists ("bitstream flash" on first bind).
        Returns seconds spent compiling (0.0 on executable-cache hit)."""
        rules = self._make_rules(vf)
        self._rules = rules
        self._mesh = vf.mesh()
        if state is not None:
            self._state = state
        elif self._state is None:
            shardings = self.state_shardings(rules)
            rng = jax.random.key(self.run.seed)
            self._state = jax.jit(
                lambda r: init_train_state(self.run, r),
                out_shardings=shardings)(rng)
            jax.block_until_ready(self._state)
        compile_s = 0.0
        # Executable cache ("bitstream cache"): compiled code is bound to
        # the physical devices, so the key includes the slice identity — an
        # unpause onto the same slice is a cache hit (the paper's "skips
        # some of the realize operations"); migration to new devices pays
        # an honest recompile.
        key = (self.workload, tuple(vf.mesh_shape),
               tuple(d.id for d in vf.devices))
        if key not in self._exec_cache:
            t0 = time.perf_counter()
            step = make_train_step(self.run, rules)
            # batch shardings from the tenant's ACTUAL batch shapes (its
            # local batch may not divide a larger slice's data axis)
            from jax.sharding import PartitionSpec as P
            sample = self._source.batch_at(0)
            bspecs = rules.named({
                k: P(rules._fit(v.shape[0], rules.dp_axes),
                     *([None] * (v.ndim - 1)))
                for k, v in sample.items()})
            # pin state shardings on BOTH sides: the state must round-trip
            # through the executable bit-stable (otherwise XLA may re-lay
            # it out and the next call mismatches)
            sshard = self.state_shardings(rules)
            fn = jax.jit(step, in_shardings=(sshard, bspecs),
                         out_shardings=(sshard, None))
            if flash:   # eager compile = the "flash the bitstream" step
                batch = self._place_batch(self._source.batch_at(0), bspecs)
                fn = fn.lower(self._state, batch).compile()
            self._exec_cache[key] = (fn, bspecs)
            compile_s = time.perf_counter() - t0
        self._active_key = key
        self.vf_id = vf.vf_id
        self.status = "running"
        vf.emulated.update({"tenant": self.tid, "status": "running",
                            "steps_done": self.steps_done})
        return compile_s

    def _place_batch(self, batch, bspecs):
        return {k: jax.device_put(v, bspecs[k]) for k, v in batch.items()}

    # -- guest-visible work (the unmodified driver) -----------------------------
    def run_steps(self, n: int = 1) -> dict:
        if self.status == "paused":
            raise DevicePausedError(
                f"{self.tid}: device {self.vf_id} is paused")
        if self.status != "running":
            raise RuntimeError(f"{self.tid}: no device attached")
        if self._fail_next:
            self._fail_next = False
            raise RuntimeError(f"{self.tid}: injected device failure")
        fn, bspecs = self._exec_cache[self._active_key]
        metrics = {}
        for _ in range(n):
            t0 = time.perf_counter()
            batch = self._place_batch(self._source.batch_at(self.steps_done),
                                      bspecs)
            self._state, metrics = fn(self._state, batch)
            jax.block_until_ready(self._state)
            self.steps_done += 1
            self.step_times.append(time.perf_counter() - t0)
        return {k: float(v) for k, v in metrics.items()}

    # -- pause plumbing (called by core.pause, not by guests) --------------------
    def export_state(self):
        return self._state

    def suspend(self):
        """Paper step 2: unregister host-side handles; the guest keeps its
        emulated view (status queries still answered)."""
        self._state = None
        self._mesh = None
        self.status = "paused"

    def resume(self, state, vf: VirtualFunction):
        self._state = state
        self.status = "running"
        self.bind(vf, state=state)

    def detach(self):
        self._state = None
        self._mesh = None
        self._rules = None
        self.vf_id = None
        self.status = "detached"

    # -- guest-visible introspection (works while paused: emulated view) ---------
    def query(self) -> dict:
        return {"tenant": self.tid, "status": self.status,
                "vf": self.vf_id, "steps_done": self.steps_done,
                "workload": self.workload,
                "exec_keys": [list(map(str, k)) for k in self._exec_cache]}

    def loss(self) -> Optional[float]:
        return None

    def inject_failure(self):
        self._fail_next = True
