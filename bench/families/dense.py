"""Dense decoder-only transformers (GQA attention, RMSNorm, SwiGLU, RoPE):
how a configuration file becomes the program's run config, and the weights
the benchmark makes for it.

The configuration file keeps the published ``config.json`` keys
(``hidden_size``, ``num_hidden_layers``, ...); ``model_type`` "qwen3" adds
the per-head q/k RMSNorm. The weights are the benchmark's own, drawn from
the seed in one jitted call on the device, in the program's parameter tree
(layers stacked on a leading axis) and in the dtype they are served in.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

#: the program pads the vocabulary rows of the embedding and head to this
VOCAB_PAD = 128
#: standard deviation of the embedding and output-head entries; with the
#: final RMSNorm this gives logits a spread of one to three units
EMBED_STD = 0.05


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    vocab_padded: int
    qk_norm: bool
    tied: bool
    rope_theta: float
    eps: float
    dtype: str


def dims(cfg: dict) -> Dims:
    if cfg.get("family") != "dense":
        raise ValueError(f"not a dense configuration: {cfg.get('family')!r}")
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    v = cfg["vocab_size"]
    return Dims(
        layers=cfg["num_hidden_layers"], d=d, heads=h,
        kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or d // h,
        ff=cfg["intermediate_size"], vocab=v,
        vocab_padded=-(-v // VOCAB_PAD) * VOCAB_PAD,
        qk_norm=cfg["model_type"] == "qwen3",
        tied=bool(cfg.get("tie_word_embeddings", False)),
        rope_theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
        dtype=cfg["torch_dtype"])


def run_config(cfg: dict):
    """The program's ``RunConfig`` for this configuration as it is served.
    ``interpret`` (tests only) runs the Pallas kernels in the interpreter."""
    from repro.configs import ModelConfig, PrecisionConfig, RunConfig
    from repro.configs.base import ShapeConfig
    m = dims(cfg)
    model = ModelConfig(
        name=cfg["name"], family="dense", num_layers=m.layers, d_model=m.d,
        num_heads=m.heads, num_kv_heads=m.kv_heads, d_ff=m.ff,
        vocab_size=m.vocab, head_dim=m.head_dim, qk_norm=m.qk_norm,
        rope_theta=m.rope_theta, norm_eps=m.eps, tie_embeddings=m.tied,
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
    L, D, F = m.layers, m.d, m.ff
    q, kv = m.heads * m.head_dim, m.kv_heads * m.head_dim
    block = {"ln1": (L, D), "wq": (L, D, q), "wk": (L, D, kv),
             "wv": (L, D, kv), "wo": (L, q, D), "ln2": (L, D),
             "ffn": {"wi": (L, D, F), "wg": (L, D, F), "wo": (L, F, D)}}
    if m.qk_norm:
        block["q_norm"] = (L, m.head_dim)
        block["k_norm"] = (L, m.head_dim)
    tree = {"embed": {"tok": (m.vocab_padded, D)},
            "decoder": {"layers": {"block0": block}, "final_norm": (D,)}}
    if not m.tied:
        tree["lm_head"] = (D, m.vocab_padded)
    return tree


def _init_leaf(path, shape, key, m: Dims):
    name = path[-1].key
    if len(shape) <= 2 and (name.startswith("ln") or name.endswith("norm")):
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    x = jax.random.normal(key, shape, jnp.float32)
    if name in ("tok", "lm_head"):
        x = EMBED_STD * x
        vaxis = 0 if name == "tok" else 1
        live = jnp.arange(shape[vaxis]) < m.vocab
        x = jnp.where(live[:, None] if vaxis == 0 else live[None, :], x, 0.0)
        return x
    return x / np.sqrt(shape[-2])          # fan-in: the contracted axis


def make_weights(cfg: dict, seed: int):
    """The weights for ``seed``, made on the default device in one jitted
    call, in the configuration's ``torch_dtype``."""
    m = dims(cfg)
    tree = shapes(m)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    dt = jnp.dtype(m.dtype)

    def gen(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree_util.tree_unflatten(treedef, [
            _init_leaf(p, s, k, m).astype(dt)
            for (p, s), k in zip(leaves, keys)])
    return jax.jit(gen)(jax.random.key(seed_word(seed)))


def seed_word(seed: int) -> int:
    """A 32-bit key word from any whole-number seed (``--seed`` may exceed
    what 32 bits hold)."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0])
