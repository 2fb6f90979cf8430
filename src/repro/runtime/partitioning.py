"""Sharding context + partition rules.

Model code never names mesh axes directly: it calls ``constrain(x, kind)``
with a *logical* kind ("hidden", "logits", ...). The active
``ShardingRules`` (installed by the step builder / dry-run via
``sharding_scope``) resolves kinds to PartitionSpecs for the current mesh,
with divisibility fallbacks so the same model code runs on the unit mesh
(CPU tests), the single-pod 16x16 mesh, and the multi-pod 2x16x16 mesh.
"""
from __future__ import annotations

import contextlib
import re
import threading
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import MeshConfig, RunConfig

_TLS = threading.local()


def current_rules() -> Optional["ShardingRules"]:
    return getattr(_TLS, "rules", None)


@contextlib.contextmanager
def sharding_scope(rules: Optional["ShardingRules"]):
    prev = getattr(_TLS, "rules", None)
    _TLS.rules = rules
    try:
        yield
    finally:
        _TLS.rules = prev


class ShardingRules:
    """Resolves logical activation kinds and parameter paths to specs."""

    def __init__(self, mesh_cfg: MeshConfig, run_cfg: RunConfig,
                 mesh: Optional[Mesh] = None):
        self.mesh_cfg = mesh_cfg
        self.run = run_cfg
        self.mesh = mesh
        self.axis_size = dict(zip(mesh_cfg.axes, mesh_cfg.shape))
        self.dp_axes = mesh_cfg.data_axes           # e.g. ("pod", "data")
        self.model_axis = "model" if "model" in mesh_cfg.axes else None
        self.fsdp_axes = self.dp_axes if run_cfg.sharding.fsdp else ()

    def attn_mode(self, num_heads=None) -> str:
        """'heads' when kv heads divide the model axis, else 'seq'."""
        kv = self.run.model.num_kv_heads
        m = self.axis_size.get("model", 1)
        if m <= 1:
            return "heads"
        if kv % m == 0 and (num_heads is None or num_heads % m == 0):
            return "heads"
        return "seq"

    # -- helpers -----------------------------------------------------------
    def _size(self, axes) -> int:
        if axes is None:
            return 1
        if isinstance(axes, str):
            axes = (axes,)
        return int(np.prod([self.axis_size.get(a, 1) for a in axes]))

    def _fit(self, dim: int, axes):
        """Return ``axes`` if ``dim`` is divisible by their product else None."""
        if not axes:
            return None
        sz = self._size(axes)
        if sz <= 1:
            return None
        if dim % sz != 0:
            return None
        if isinstance(axes, tuple) and len(axes) == 1:
            return axes[0]
        return axes

    def spec(self, kind: str, shape) -> P:
        """Activation PartitionSpec by logical kind."""
        dp = tuple(self.dp_axes)
        mdl = self.model_axis
        if kind == "batch":          # (B, S) token ids
            return P(self._fit(shape[0], dp), None)
        if kind == "hidden":         # (B, S, D)
            sp = (mdl if (self.run.sharding.seq_shard_acts and mdl) else None)
            return P(self._fit(shape[0], dp),
                     self._fit(shape[1], (sp,) if sp else ()), None)
        if kind == "hidden_full":    # (B, S, D) gathered for TP matmuls
            if not self.run.sharding.seq_shard_acts:
                raise KeyError(kind)     # no-op unless SP mode (constrain
                                         # returns x unchanged)
            return P(self._fit(shape[0], dp), None, None)
        if kind == "logits":         # (B, S, V) or (B, V)
            if self.run.sharding.seq_shard_acts and mdl and len(shape) == 3:
                # SP: logits sequence-sharded, vocab local -> softmax/CE
                # fully local (lm_head is replicated over model in SP mode)
                return P(self._fit(shape[0], dp),
                         self._fit(shape[1], (mdl,)), None)
            v_ax = self._fit(shape[-1], (mdl,) if mdl else ())
            if len(shape) == 3:
                return P(self._fit(shape[0], dp), None, v_ax)
            return P(self._fit(shape[0], dp), v_ax)
        if kind == "attn_q":         # (B, S, H, hd) — q/o inside attention
            # Heads-TP when the kv heads divide the model axis (classic
            # Megatron); otherwise sequence-parallel attention: q sharded
            # on S, k/v replicated over model — the (S,T) logits stay
            # LOCAL. Without this, GSPMD may shard the hd contraction and
            # all-reduce the quadratic logits tensor (§Perf iteration 2).
            dpq = self._fit(shape[0], dp)
            if self.attn_mode(shape[2]) == "heads":
                return P(dpq, None, self._fit(shape[2], (mdl,)), None)
            return P(dpq, self._fit(shape[1], (mdl,) if mdl else ()),
                     None, None)
        if kind == "attn_kv":        # (B, T, K, hd)
            dpq = self._fit(shape[0], dp)
            if self.attn_mode(None) == "heads":
                return P(dpq, None, self._fit(shape[2], (mdl,)), None)
            return P(dpq, None, None, None)
        if kind == "kv_cache":       # (B, S, K, h) — decode cache
            b_ax = self._fit(shape[0], dp)
            if b_ax is None and self.run.sharding.shard_kv_seq:
                # batch too small (long_500k): shard sequence over everything
                all_ax = tuple(a for a in (*dp, mdl) if a)
                return P(None, self._fit(shape[1], all_ax), None, None)
            seq_ax = (self._fit(shape[1], (mdl,) if mdl else ())
                      if self.run.sharding.shard_kv_seq else None)
            return P(b_ax, seq_ax, None, None)
        if kind == "state":          # (B, ...) recurrent state
            return P(self._fit(shape[0], dp), *([None] * (len(shape) - 1)))
        if kind == "expert":         # (E, G, C, D) MoE expert inputs
            return P(self._fit(shape[0], (mdl,) if mdl else ()),
                     self._fit(shape[1], dp), None, None)
        if kind == "moe_mask":       # (G, sg, E) routing one-hots
            return P(self._fit(shape[0], dp), None,
                     self._fit(shape[2], (mdl,) if mdl else ()))
        if kind == "moe_counts":     # (G, E)
            return P(self._fit(shape[0], dp),
                     self._fit(shape[1], (mdl,) if mdl else ()))
        if kind == "moe_dispatch":   # (G, sg, E, C) dispatch/combine
            # E sharded over model from CONSTRUCTION: both dispatch einsums
            # and (critically) their transposes then stay local on the
            # model axis — otherwise bwd gathers the full-E dispatch
            # cotangent (~17 GB/layer on arctic; §Perf HC2 it.4)
            return P(self._fit(shape[0], dp), None,
                     self._fit(shape[2], (mdl,) if mdl else ()), None)
        raise KeyError(kind)

    # -- parameters --------------------------------------------------------
    # Rules matched (first hit) against '/'-joined path suffixes. %F = fsdp
    # axes, %M = model axis. Specs are for the LOGICAL (unstacked) leaf;
    # period-stacked leaves get a leading None.
    PARAM_RULES = [
        (r"embed/tok$",            ("%M", None)),
        (r"lm_head$",              ("%F", "%M")),
        (r"(wq|wk|wv|xq|xk|xv)$",  ("%F", "%M")),
        (r"(wo|xo)$",              ("%M", "%F")),
        (r"ffn/(wi|wg)$",          ("%F", "%M")),
        (r"ffn/wo$",               ("%M", "%F")),
        (r"moe/router$",           (None, None)),
        (r"moe/(wi|wg)$",          ("%M", "%F", None)),
        (r"moe/wo$",               ("%M", None, "%F")),
        (r"in_proj$",              ("%F", "%M")),
        (r"out_proj$",             ("%M", "%F")),
        (r"conv_w$",               (None, "%M")),
        (r"conv_b$",               ("%M",)),
        (r"w_up$",                 ("%F", "%M")),
        (r"w_out$",                ("%M", "%F")),
        (r"(w_i|w_f)$",            ("%F", None)),
        (r"(w_z|w_o)$",            ("%F", "%M")),
        (r"(r_z|r_i|r_f|r_o)$",    (None, None, None)),
        (r"up_(wi|wg)$",           ("%F", "%M")),
        (r"up_wo$",                ("%M", "%F")),
    ]

    def param_spec(self, path: str, shape) -> P:
        stacked = "/layers/" in path           # period-stacked leaf
        logical = shape[1:] if stacked else shape
        spec: list = [None] * len(logical)
        if self.run.sharding.seq_shard_acts and re.search(r"lm_head$", path):
            # SP mode: lm_head vocab-replicated so logits stay seq-sharded
            return P(self._fit(logical[0], self.fsdp_axes), None)
        for pat, axes in self.PARAM_RULES:
            if re.search(pat, path):
                for i, a in enumerate(axes):
                    if a == "%F":
                        spec[i] = self._fit(logical[i], self.fsdp_axes)
                    elif a == "%M":
                        spec[i] = self._fit(
                            logical[i], (self.model_axis,)
                            if self.model_axis else ())
                    else:
                        spec[i] = None
                break
        if stacked:
            spec = [None] + spec
        return P(*spec)

    def param_specs(self, tree) -> dict:
        def one(path, leaf):
            p = jax.tree_util.keystr(path, simple=True, separator="/")
            return self.param_spec(p, leaf.shape)
        return jax.tree_util.tree_map_with_path(one, tree)

    def named(self, spec_tree):
        assert self.mesh is not None
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), spec_tree,
            is_leaf=lambda x: isinstance(x, P))


def constrain(x: jax.Array, kind: str) -> jax.Array:
    """Apply a logical sharding constraint if a scope is active (no-op on
    the unit mesh / in plain CPU tests)."""
    rules = current_rules()
    if rules is None or rules.mesh_cfg.num_devices <= 1:
        return x
    try:
        spec = rules.spec(kind, x.shape)
    except KeyError:
        return x
    if rules.mesh is not None:
        spec = NamedSharding(rules.mesh, spec)
    return jax.lax.with_sharding_constraint(x, spec)
