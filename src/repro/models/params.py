"""Single source of truth for parameter trees.

``param_shapes(cfg)`` builds the full parameter tree as ShapeDtypeStructs;
``init_params`` materializes it; ``count_params_config`` folds it. Sharding
rules (runtime/shardings.py) and the dry-run consume the same tree, so the
three can never disagree.

Layer stacks are *period-stacked*: the repeating block pattern (len divides
num_layers) is scanned over ``num_periods``, so every leaf belonging to block
position ``j`` of the pattern carries a leading ``(num_periods,)`` dim. This
keeps the HLO O(pattern) instead of O(layers) (95-layer deepseek compiles as
one scanned block) while supporting heterogeneous stacks (jamba, xlstm).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (ATTN, MAMBA, MLSTM, SLSTM, ModelConfig)

VOCAB_PAD = 128  # pad vocab so TP over the model axis always divides


def padded_vocab(cfg: ModelConfig) -> int:
    return ((cfg.vocab_size + VOCAB_PAD - 1) // VOCAB_PAD) * VOCAB_PAD


# ---------------------------------------------------------------------------
# derived dims
# ---------------------------------------------------------------------------
def mamba_dims(cfg: ModelConfig):
    d_inner = cfg.ssm.expand * cfg.d_model
    n_heads = d_inner // cfg.ssm.head_dim
    # in_proj emits [x (d_inner), z (d_inner), B (d_state), C (d_state),
    #                dt (n_heads)]
    d_in_proj = 2 * d_inner + 2 * cfg.ssm.d_state + n_heads
    d_conv_ch = d_inner + 2 * cfg.ssm.d_state   # conv over x, B, C
    return d_inner, n_heads, d_in_proj, d_conv_ch


def mlstm_dims(cfg: ModelConfig):
    di = int(cfg.xlstm.proj_factor * cfg.d_model)
    di = (di // cfg.xlstm.head_dim) * cfg.xlstm.head_dim
    n_heads = di // cfg.xlstm.head_dim
    return di, n_heads


def slstm_dims(cfg: ModelConfig):
    # simplified sLSTM: recurrence at d_model width, per-head block-diagonal
    # recurrent weights, post-recurrence GLU at slstm_proj_factor.
    heads = cfg.num_heads
    dh = cfg.d_model // heads
    d_up = int(cfg.xlstm.slstm_proj_factor * cfg.d_model)
    d_up = (d_up // 8) * 8
    return heads, dh, d_up


# ---------------------------------------------------------------------------
# per-block shapes (logical, un-stacked)
# ---------------------------------------------------------------------------
def _sd(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(int(s) for s in shape), dtype)


def ffn_shapes(cfg: ModelConfig, layer_idx: int, dtype) -> dict:
    """FFN half of a block — orthogonal to the mixer kind (jamba has an
    MLP/MoE after *every* mixer, attention or mamba alike)."""
    D = cfg.d_model
    has_moe = cfg.layer_has_moe(layer_idx)
    dense = (cfg.d_ff > 0) and (not has_moe or
                                (cfg.moe and cfg.moe.dense_residual))
    p = {}
    if dense or has_moe:
        p["ln2"] = _sd((D,), dtype)
    if dense:
        F = cfg.d_ff
        p["ffn"] = {"wi": _sd((D, F), dtype), "wg": _sd((D, F), dtype),
                    "wo": _sd((F, D), dtype)}
    if has_moe:
        # the router scores every expert; the expert weights are the held
        # share (all of them unless the config holds a share)
        E, Eh, Fe = cfg.moe.num_experts, cfg.moe.experts_held, cfg.moe.d_ff
        p["moe"] = {
            "router": _sd((D, E), jnp.float32),   # router in fp32 (stability)
            "wi": _sd((Eh, D, Fe), dtype),
            "wg": _sd((Eh, D, Fe), dtype),
            "wo": _sd((Eh, Fe, D), dtype),
        }
    return p


def attn_block_shapes(cfg: ModelConfig, layer_idx: int, dtype,
                      cross: bool = False) -> dict:
    D, H, K, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p = {
        "ln1": _sd((D,), dtype),
        "wq": _sd((D, H * hd), dtype),
        "wk": _sd((D, K * hd), dtype),
        "wv": _sd((D, K * hd), dtype),
        "wo": _sd((H * hd, D), dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = _sd((hd,), dtype)
        p["k_norm"] = _sd((hd,), dtype)
    if cross:
        p["ln_x"] = _sd((D,), dtype)
        p["xq"] = _sd((D, H * hd), dtype)
        p["xk"] = _sd((D, K * hd), dtype)
        p["xv"] = _sd((D, K * hd), dtype)
        p["xo"] = _sd((H * hd, D), dtype)
    p.update(ffn_shapes(cfg, layer_idx, dtype))
    return p


def mamba_block_shapes(cfg: ModelConfig, dtype) -> dict:
    D = cfg.d_model
    d_inner, n_heads, d_in_proj, d_conv_ch = mamba_dims(cfg)
    return {
        "ln": _sd((D,), dtype),
        "in_proj": _sd((D, d_in_proj), dtype),
        "conv_w": _sd((cfg.ssm.conv_dim, d_conv_ch), dtype),
        "conv_b": _sd((d_conv_ch,), dtype),
        "A_log": _sd((n_heads,), jnp.float32),
        "D": _sd((n_heads,), jnp.float32),
        "dt_bias": _sd((n_heads,), jnp.float32),
        "norm": _sd((d_inner,), dtype),
        "out_proj": _sd((d_inner, D), dtype),
    }


def mlstm_block_shapes(cfg: ModelConfig, dtype) -> dict:
    D = cfg.d_model
    di, n_heads = mlstm_dims(cfg)
    return {
        "ln": _sd((D,), dtype),
        "w_up": _sd((D, 2 * di), dtype),       # x and gate branches
        "wq": _sd((di, di), dtype),
        "wk": _sd((di, di), dtype),
        "wv": _sd((di, di), dtype),
        "w_i": _sd((di, n_heads), jnp.float32),  # exp-gate projections
        "w_f": _sd((di, n_heads), jnp.float32),
        "b_i": _sd((n_heads,), jnp.float32),
        "b_f": _sd((n_heads,), jnp.float32),
        "norm": _sd((di,), dtype),
        "w_out": _sd((di, D), dtype),
    }


def slstm_block_shapes(cfg: ModelConfig, dtype) -> dict:
    D = cfg.d_model
    heads, dh, d_up = slstm_dims(cfg)
    gates = {}
    for g in ("z", "i", "f", "o"):
        gates[f"w_{g}"] = _sd((D, D), dtype)
        gates[f"r_{g}"] = _sd((heads, dh, dh), dtype)   # block-diag recurrent
        gates[f"b_{g}"] = _sd((D,), jnp.float32)
    return {
        "ln": _sd((D,), dtype),
        **gates,
        "norm": _sd((D,), dtype),
        "up_wi": _sd((D, d_up), dtype),
        "up_wg": _sd((D, d_up), dtype),
        "up_wo": _sd((d_up, D), dtype),
    }


def block_shapes(cfg: ModelConfig, layer_idx: int, dtype,
                 cross: bool = False) -> dict:
    kind = cfg.layer_kind(layer_idx)
    if kind == ATTN:
        return attn_block_shapes(cfg, layer_idx, dtype, cross=cross)
    if kind == MAMBA:
        p = mamba_block_shapes(cfg, dtype)
    elif kind == MLSTM:
        p = mlstm_block_shapes(cfg, dtype)
    elif kind == SLSTM:
        p = slstm_block_shapes(cfg, dtype)
    else:
        raise ValueError(kind)
    p.update(ffn_shapes(cfg, layer_idx, dtype))
    return p


# ---------------------------------------------------------------------------
# full tree
# ---------------------------------------------------------------------------
def _stack(tree: dict, n: int) -> dict:
    return jax.tree.map(lambda s: _sd((n,) + s.shape, s.dtype), tree)


def stack_param_shapes(cfg: ModelConfig, dtype, num_layers: int,
                       cross: bool = False) -> dict:
    """Period-stacked shapes for a stack of ``num_layers`` blocks."""
    plen = len(cfg.block_pattern)
    assert num_layers % plen == 0
    periods = num_layers // plen
    out = {}
    for j in range(plen):
        out[f"block{j}"] = _stack(block_shapes(cfg, j, dtype, cross=cross),
                                  periods)
    return out


def param_shapes(cfg: ModelConfig, param_dtype=jnp.float32) -> dict:
    dt = param_dtype
    D, Vp = cfg.d_model, padded_vocab(cfg)
    tree = {
        "embed": {"tok": _sd((Vp, D), dt)},
        "decoder": {
            "layers": stack_param_shapes(cfg, dt, cfg.num_layers,
                                         cross=cfg.is_encoder_decoder),
            "final_norm": _sd((D,), dt),
        },
    }
    if cfg.is_encoder_decoder:
        # encoder blocks are plain attention blocks (bidirectional at apply
        # time); the audio frontend itself is a STUB (precomputed frames).
        enc_cfg = cfg  # same dims
        tree["encoder"] = {
            "layers": stack_param_shapes(enc_cfg, dt, cfg.num_encoder_layers),
            "final_norm": _sd((D,), dt),
        }
    if not cfg.tie_embeddings:
        tree["lm_head"] = _sd((D, Vp), dt)
    return tree


def count_params_config(cfg: ModelConfig, active_only: bool = False) -> int:
    shapes = param_shapes(cfg)
    total = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    if active_only and cfg.moe is not None:
        # subtract the inactive expert fraction of MoE weights
        moe_leaves = []

        def _collect(path, leaf):
            if any(getattr(p, "key", None) == "moe" for p in path):
                name = path[-1].key if hasattr(path[-1], "key") else ""
                if name != "router":
                    moe_leaves.append(int(np.prod(leaf.shape)))

        jax.tree_util.tree_map_with_path(_collect, shapes)
        moe_total = sum(moe_leaves)
        frac = cfg.moe.num_experts_per_token / cfg.moe.num_experts
        total = total - int(moe_total * (1.0 - frac))
    return total


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init_params(cfg: ModelConfig, rng: jax.Array,
                param_dtype=jnp.float32) -> dict:
    """Fan-in scaled truncated-normal init over the shape tree."""
    shapes = param_shapes(cfg, param_dtype)
    leaves, treedef = jax.tree.flatten(shapes)
    keys = jax.random.split(rng, len(leaves))

    def one(key, sd: jax.ShapeDtypeStruct):
        shp = sd.shape
        if len(shp) >= 2:
            fan_in = int(np.prod(shp[:-1]))  # period/expert dims count as fan
            scale = 1.0 / math.sqrt(max(fan_in, 1))
            x = scale * jax.random.truncated_normal(
                key, -2.0, 2.0, shp, jnp.float32)
        else:
            x = jnp.ones(shp, jnp.float32)   # norms / biases -> 1 (gates fix below)
        return x.astype(sd.dtype)

    inited = jax.tree.unflatten(treedef, [one(k, s)
                                          for k, s in zip(keys, leaves)])

    # Targeted overrides where ones/noise are wrong:
    def fix(path, leaf):
        names = [p.key for p in path if hasattr(p, "key")]
        if not names:
            return leaf
        last = names[-1]
        if last in ("b_i", "b_f", "b_z", "b_o"):
            return jnp.zeros_like(leaf)
        if last == "A_log":      # mamba: A in [-eps, -~8] -> A_log ~ log range
            n = leaf.shape[-1]
            return jnp.log(jnp.linspace(1.0, 8.0, n)).astype(leaf.dtype)
        if last == "dt_bias":    # softplus^-1 of dt in [1e-3, 1e-1]
            n = leaf.shape[-1]
            dt = jnp.exp(jnp.linspace(math.log(1e-3), math.log(1e-1), n))
            return jnp.log(jnp.expm1(dt)).astype(leaf.dtype)
        if last == "D":
            return jnp.ones_like(leaf)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, inited)
