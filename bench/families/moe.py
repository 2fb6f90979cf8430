"""Sparse-expert decoder transformers with an attention kind per layer
(Mellum2: sliding-window and full GQA layers with their own RoPE, q/k
RMSNorm, a routed-expert SwiGLU FFN in every layer, untied head): how a
configuration file becomes the program's run config, and the weights the
benchmark makes for it.

The configuration file keeps the published ``config.json`` keys. Its
``num_experts`` is the number of experts held on this chip (listed in
``reduced``); ``router_experts`` is the published count the router scores
and ``held_offset`` the first expert held. ``layer_types`` must repeat with
a period; each period position takes its ``rope_parameters`` section and,
for ``sliding_attention``, the ``sliding_window``. The weights are the
benchmark's own, drawn from the seed on the device one leaf at a time (so
that set-up never holds more than one leaf in float32 beside the served
weights), in the program's parameter tree (layers stacked over periods)
and in the dtype they are served in.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.families.dense import EMBED_STD, VOCAB_PAD, seed_word


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    vocab_padded: int
    experts: int             # the router's width (published)
    experts_held: int        # held on this chip
    held_offset: int
    top_k: int
    norm_topk: bool
    expert_ff: int
    qk_norm: bool
    eps: float
    dtype: str
    #: per period position: window (0 = full) and RoPE as
    #: (theta, yarn factor (0 = plain), original length, beta_fast,
    #: beta_slow, attention factor)
    windows: tuple
    ropes: tuple

    @property
    def period(self) -> int:
        return len(self.windows)


def _period(types: list) -> int:
    for p in range(1, len(types) + 1):
        if len(types) % p == 0 and types == types[:p] * (len(types) // p):
            return p
    raise AssertionError("unreachable")


def _rope(sec: dict) -> tuple:
    theta = float(sec["rope_theta"])
    if sec.get("rope_type", "default") == "default":
        return (theta, 0.0, 0, 0.0, 0.0, 1.0)
    if sec["rope_type"] != "yarn":
        raise ValueError(f"rope_type {sec['rope_type']!r}")
    return (theta, float(sec["factor"]),
            int(sec["original_max_position_embeddings"]),
            float(sec.get("beta_fast", 32)), float(sec.get("beta_slow", 1)),
            float(sec["attention_factor"]))


def dims(cfg: dict) -> Dims:
    if cfg.get("family") != "moe":
        raise ValueError(f"not an expert configuration: {cfg.get('family')!r}")
    types = list(cfg["layer_types"])
    if len(types) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types does not cover num_hidden_layers")
    if any(t != "sparse" for t in cfg["mlp_layer_types"]):
        raise ValueError("only sparse MLP layers")
    p = _period(types)
    v = cfg["vocab_size"]
    return Dims(
        layers=cfg["num_hidden_layers"], d=cfg["hidden_size"],
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        vocab=v, vocab_padded=-(-v // VOCAB_PAD) * VOCAB_PAD,
        experts=cfg["router_experts"], experts_held=cfg["num_experts"],
        held_offset=cfg["held_offset"], top_k=cfg["num_experts_per_tok"],
        norm_topk=bool(cfg["norm_topk_prob"]),
        expert_ff=cfg["moe_intermediate_size"], qk_norm=bool(cfg["qk_norm"]),
        eps=float(cfg["rms_norm_eps"]), dtype=cfg["torch_dtype"],
        windows=tuple(cfg["sliding_window"] if t == "sliding_attention"
                      else 0 for t in types[:p]),
        ropes=tuple(_rope(cfg["rope_parameters"][t]) for t in types[:p]))


def run_config(cfg: dict):
    """The program's ``RunConfig`` for this configuration as it is served.
    ``interpret`` (tests only) runs the Pallas kernels in the interpreter."""
    from repro.configs import (ATTN, AttnKind, ModelConfig, MoEConfig,
                               PrecisionConfig, RopeConfig, RunConfig)
    from repro.configs.base import ShapeConfig
    m = dims(cfg)
    kinds = tuple(
        AttnKind(window=w, rope=RopeConfig(
            theta=r[0], yarn_factor=r[1], original_max_position=r[2],
            beta_fast=r[3], beta_slow=r[4], attention_factor=r[5]))
        for w, r in zip(m.windows, m.ropes))
    model = ModelConfig(
        name=cfg["name"], family="moe", num_layers=m.layers, d_model=m.d,
        num_heads=m.heads, num_kv_heads=m.kv_heads, d_ff=0,
        vocab_size=m.vocab, head_dim=m.head_dim, qk_norm=m.qk_norm,
        rope_theta=m.ropes[0][0], norm_eps=m.eps, tie_embeddings=False,
        block_pattern=(ATTN,) * m.period, attn_kinds=kinds,
        moe=MoEConfig(num_experts=m.experts, num_experts_per_token=m.top_k,
                      d_ff=m.expert_ff, dropless=True,
                      norm_topk_prob=m.norm_topk,
                      experts_held=m.experts_held,
                      held_offset=m.held_offset),
        source=cfg["source"])
    serve = cfg["serve"]
    shape = ShapeConfig("serve", "decode", serve["max_len"], serve["slots"])
    return RunConfig(model=model, shape=shape,
                     precision=PrecisionConfig(params=m.dtype,
                                               compute=m.dtype,
                                               logits="float32"),
                     interpret=bool(cfg.get("interpret", False)))


def shapes(m: Dims) -> dict:
    """Leaf name -> shape, in the program's tree layout."""
    n, D, F = m.layers // m.period, m.d, m.expert_ff
    q, kv, Eh = m.heads * m.head_dim, m.kv_heads * m.head_dim, m.experts_held
    block = {"ln1": (n, D), "wq": (n, D, q), "wk": (n, D, kv),
             "wv": (n, D, kv), "wo": (n, q, D), "ln2": (n, D),
             "moe": {"router": (n, D, m.experts), "wi": (n, Eh, D, F),
                     "wg": (n, Eh, D, F), "wo": (n, Eh, F, D)}}
    if m.qk_norm:
        block["q_norm"] = (n, m.head_dim)
        block["k_norm"] = (n, m.head_dim)
    return {"embed": {"tok": (m.vocab_padded, D)},
            "decoder": {"layers": {f"block{j}": block
                                   for j in range(m.period)},
                        "final_norm": (D,)},
            "lm_head": (D, m.vocab_padded)}


@functools.partial(jax.jit, static_argnames=("name", "shape", "vocab",
                                             "dtype"))
def _leaf(key, *, name: str, shape: tuple, vocab: int, dtype: str):
    return _init_leaf(name, shape, key, vocab).astype(jnp.dtype(dtype))


def _init_leaf(name, shape, key, vocab: int):
    if len(shape) <= 2 and (name.startswith("ln") or name.endswith("norm")):
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    x = jax.random.normal(key, shape, jnp.float32)
    if name in ("tok", "lm_head"):
        x = EMBED_STD * x
        vaxis = 0 if name == "tok" else 1
        live = jnp.arange(shape[vaxis]) < vocab
        return jnp.where(live[:, None] if vaxis == 0 else live[None, :], x,
                         0.0)
    return x / np.sqrt(shape[-2])          # fan-in: the contracted axis


def make_weights(cfg: dict, seed: int):
    """The weights for ``seed``, made on the default device leaf by leaf,
    in the configuration's ``torch_dtype``."""
    m = dims(cfg)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes(m), is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(jax.random.key(seed_word(seed)), len(leaves))
    return jax.tree_util.tree_unflatten(treedef, [
        _leaf(k, name=p[-1].key, shape=s, vocab=m.vocab, dtype=m.dtype)
        for (p, s), k in zip(leaves, keys)])
