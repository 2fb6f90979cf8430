"""Mellum2's shape at a CPU size: one period of 3 sliding-window (8) + 1
full (YaRN) attention layers, 16 routed experts top 4 with 4 held on this
chip, served through ``ServeEngine`` (paged, whole and chunked prefill)
and checked against the benchmark's plain float32 reference
(``bench/references/moe.py``), which imports nothing of the program.

Tolerances: the program runs here in float32 (params and compute), so it
differs from the reference only in summation order and in the kernels'
online softmax: a relative L2 error of 1e-4 on the logits. A dropped
window bound, a ring page read out of order, a wrong YaRN ramp or a
missing expert moves the logits by tens of percent.
"""
import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.families.moe import Dims  # noqa: E402
from bench.references import moe as ref  # noqa: E402
from repro.configs import (PrecisionConfig, RopeConfig,  # noqa: E402
                           list_archs, make_run_config)
from repro.configs.mellum2_12b_a2_5b import ep_share, smoke  # noqa: E402
from repro.models.layers import apply_rope, yarn_freqs  # noqa: E402
from repro.models.model import build_model  # noqa: E402
from repro.models.moe import moe_ffn_dropless  # noqa: E402
from repro.serve.engine import Request, ServeEngine  # noqa: E402

#: relative L2 error of float32 program logits against the reference
RTOL = 1e-4
CHIPS = 4


def dims_of(cfg) -> Dims:
    kinds = [cfg.attn_kind(j) for j in range(len(cfg.block_pattern))]
    return Dims(
        layers=cfg.num_layers, d=cfg.d_model, heads=cfg.num_heads,
        kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
        vocab=cfg.vocab_size, vocab_padded=cfg.vocab_size,
        experts=cfg.moe.num_experts, experts_held=cfg.moe.experts_held,
        held_offset=cfg.moe.held_offset,
        top_k=cfg.moe.num_experts_per_token,
        norm_topk=cfg.moe.norm_topk_prob, expert_ff=cfg.moe.d_ff,
        qk_norm=cfg.qk_norm, eps=cfg.norm_eps, dtype="float32",
        windows=tuple(k.window for k in kinds),
        ropes=tuple((k.rope.theta, k.rope.yarn_factor,
                     k.rope.original_max_position, k.rope.beta_fast,
                     k.rope.beta_slow, k.rope.attention_factor)
                    for k in kinds))


def run_of(cfg, interpret=False):
    run = make_run_config("mellum2-12b-a2.5b", "train_4k", smoke=True)
    return run.replace(model=cfg, interpret=interpret,
                       precision=PrecisionConfig(params="float32",
                                                 compute="float32"))


@pytest.fixture(scope="module")
def share():
    """Chip 1's share of the 4-chip deployment, and its weights."""
    cfg = ep_share(CHIPS, 1, smoke())
    params = build_model(run_of(cfg)).init(jax.random.key(0))
    return cfg, params


def _serve(cfg, params, prompts, *, chunk, interpret=False, slots=3,
           max_new=10):
    """Serve ``prompts`` together; returns the requests and the logits row
    each token was sampled from (the host sampler sees every row)."""
    eng = ServeEngine(run_of(cfg, interpret), params, slots=slots,
                      max_len=64, paged=True, page_size=4,
                      prefill_chunk=chunk)
    rows = {}
    sample = eng._sample

    def spy(req, row):
        rows.setdefault(req.rid, []).append(np.asarray(row, np.float32))
        return sample(req, row)
    eng._sample = spy
    reqs = [Request(rid=i, prompt=p, max_new_tokens=max_new,
                    temperature=0.0 if i % 2 == 0 else 0.8, top_k=20,
                    seed=7) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    assert eng.run_until_idle().drained
    return eng, reqs, rows


def _reference_rows(cfg, params, req):
    seq = np.concatenate([req.prompt, req.out[:-1]])[None]
    pos = (len(req.prompt) - 1 + np.arange(len(req.out)))[None]
    return np.asarray(ref.logits(params, jnp.asarray(seq), jnp.asarray(pos),
                                 m=dims_of(cfg)))[0]


@pytest.mark.parametrize("chunk,interpret", [(0, False), (8, False),
                                             (8, True)])
def test_served_logits_match_the_reference_past_the_window(share, chunk,
                                                           interpret):
    """Prefill (whole, or in chunks through the staging ring) then paged
    decode through the per-slot rings and the full-layer pages: every
    served row equals the reference's logits, with contexts up to five
    windows long; the kernels in interpret mode in the last case."""
    cfg, params = share
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
               for n in (5, 19, 37, 30)]
    _, reqs, rows = _serve(cfg, params, prompts, chunk=chunk,
                           interpret=interpret)
    for r in reqs:
        want = _reference_rows(cfg, params, r)
        got = np.stack(rows[r.rid])
        err = np.linalg.norm(got - want) / np.linalg.norm(want)
        assert err < RTOL, (r.rid, err)


def test_a_token_is_served_alike_whole_chunked_and_batched(share):
    """The dropless property: the same request gives the same logits
    prefilled whole or in chunks, alone or decoded in a batch with
    others (a capacity-dropping layer changes with its group)."""
    cfg, params = share
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
               for n in (33, 12, 26)]
    runs = [_serve(cfg, params, prompts, chunk=c, slots=s)[2]
            for c, s in ((0, 3), (8, 3), (8, 1))]
    for rid in range(len(prompts)):
        base = np.stack(runs[0][rid])
        for other in runs[1:]:
            np.testing.assert_allclose(np.stack(other[rid]), base,
                                       rtol=1e-5, atol=1e-5)


def test_the_chips_shares_add_up_to_the_whole_layer():
    """Each of the 4 chips computes its held experts' part; the parts sum
    to the uncut layer, which equals a dense evaluation of the top-k
    experts of every token."""
    cfg = smoke()
    moe = cfg.moe
    D, F, E = cfg.d_model, moe.d_ff, moe.num_experts
    ks = jax.random.split(jax.random.key(3), 5)
    p = {"router": jax.random.normal(ks[0], (D, E)) / math.sqrt(D),
         "wg": jax.random.normal(ks[1], (E, D, F)) / math.sqrt(D),
         "wi": jax.random.normal(ks[2], (E, D, F)) / math.sqrt(D),
         "wo": jax.random.normal(ks[3], (E, F, D)) / math.sqrt(F)}
    x = jax.random.normal(ks[4], (2, 9, D))
    whole, _ = moe_ffn_dropless(x, p, moe, jnp.float32)
    parts = []
    for i in range(CHIPS):
        m = ep_share(CHIPS, i, cfg).moe
        sl = slice(m.held_offset, m.held_offset + m.experts_held)
        mine = {"router": p["router"], "wg": p["wg"][sl],
                "wi": p["wi"][sl], "wo": p["wo"][sl]}
        parts.append(moe_ffn_dropless(x, mine, m, jnp.float32)[0])
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    top, idx = jax.lax.top_k(probs, moe.num_experts_per_token)
    top = top / top.sum(-1, keepdims=True)
    dense = jnp.zeros_like(x)
    for e in range(E):
        g = jnp.sum(jnp.where(idx == e, top, 0.0), axis=-1)
        h = jax.nn.silu(x @ p["wg"][e]) * (x @ p["wi"][e])
        dense = dense + g[..., None] * (h @ p["wo"][e])
    np.testing.assert_allclose(np.asarray(whole), np.asarray(dense),
                               rtol=1e-4, atol=1e-5)


def test_yarn_cos_sin_equal_the_hand_written_formula():
    """YaRN at Mellum2's settings (theta 5e5, x16 over 8192, beta 32/1,
    attention factor 1.27726): the NTK-by-parts ramp and the scaled
    cos/sin, written out here from the published recipe."""
    rope = RopeConfig(theta=5e5, yarn_factor=16.0, original_max_position=8192,
                      beta_fast=32.0, beta_slow=1.0,
                      attention_factor=1.2772588722239782)
    hd = 128
    dim = np.arange(0, hd, 2) / hd
    base_freq = 1.0 / 5e5 ** dim

    def corr_dim(rot):   # dimension index where a frequency turns ``rot``
        return hd * np.log(8192 / (rot * 2 * np.pi)) / (2 * np.log(5e5))
    lo, hi = np.floor(corr_dim(32.0)), np.ceil(corr_dim(1.0))
    ramp = np.clip((np.arange(hd // 2) - lo) / (hi - lo), 0, 1)
    want = base_freq * (1 - ramp) + base_freq / 16.0 * ramp
    np.testing.assert_allclose(yarn_freqs(hd, rope), want, rtol=1e-6)
    pos = np.array([0, 1, 1000, 8191, 100000])
    x = np.zeros((1, len(pos), 1, hd), np.float32)
    x[..., 0] = 1.0                      # picks cos / sin of frequency 0
    x[..., hd // 2 + 5] = 1.0            # and of frequency 5
    out = np.asarray(apply_rope(jnp.asarray(x), jnp.asarray(pos), 5e5, rope))
    ang = pos[:, None] * want[None].astype(np.float32)
    a = rope.attention_factor
    np.testing.assert_allclose(out[0, :, 0, 0], a * np.cos(ang[:, 0]),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(out[0, :, 0, hd // 2], a * np.sin(ang[:, 0]),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(out[0, :, 0, hd // 2 + 5],
                               a * np.cos(ang[:, 5]), rtol=2e-3, atol=2e-3)


def test_a_sliding_layer_holds_at_most_window_plus_chunk(share):
    """After prompts of up to eight windows a sliding layer's pool holds
    one ring of window + chunk positions per slot, whatever the context,
    and the allocator's pages go to the full layer alone."""
    cfg, params = share
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
               for n in (50, 63 - 12)]
    eng, reqs, _ = _serve(cfg, params, prompts, chunk=8, slots=2, max_new=12)
    window = max(cfg.windows.values())
    assert eng.ring_len <= window + 8
    cache = eng._cache
    for j, kind in enumerate(cfg.block_pattern):
        pool = cache[f"block{j}"]["k"]          # (nper, P, page, K, hd)
        if f"block{j}" in cfg.windows:
            assert pool.shape[1] == 1 + eng.slots * eng.ring_len // 4
        else:
            assert pool.shape[1] == eng.num_pages
    held = eng.stats["window_kv_positions"] / eng.stats["window_kv_steps"]
    assert 0 < held <= eng.slots * eng.ring_len
    assert eng.stats["expert_tokens"] == 0      # host sampler: no counters


def test_decode_counters_ride_on_the_fused_readback(share):
    """With fused sampling the decode step's token ids are followed by the
    expert layer's counters: pairs computed on held experts (at most
    slots x top-k x layers) and the busiest expert's load."""
    cfg, params = share
    eng = ServeEngine(run_of(cfg), params, slots=2, max_len=64, paged=True,
                      page_size=4, prefill_chunk=8, fused_sampling=True)
    prompt = np.arange(20, dtype=np.int32)
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=6))
    eng.run_until_idle()
    steps = 5                                    # the first token: prefill
    tokens = eng.stats["expert_tokens"]
    cap = steps * cfg.num_layers * cfg.moe.num_experts_per_token
    assert 0 < tokens <= cap
    assert 0 < eng.stats["expert_max_load"] <= tokens


def test_existing_configs_keep_their_shape():
    """Every other registered config has no attention kinds (full causal,
    plain RoPE at its theta) and holds all of its experts with the
    capacity layer, as before."""
    from repro.configs import get_model_config
    for arch in list_archs():
        if arch.startswith("mellum2"):
            continue
        cfg = get_model_config(arch)
        assert cfg.attn_kinds == () and cfg.windows == {}
        k = cfg.attn_kind(0)
        assert k.window == 0 and k.rope.theta == cfg.rope_theta
        assert k.rope.yarn_factor == 0
        if cfg.moe is not None:
            assert not cfg.moe.dropless
            assert cfg.moe.experts_held == cfg.moe.num_experts


def test_a_windowed_stack_refuses_prefix_sharing(share):
    cfg, params = share
    with pytest.raises(ValueError, match="prefix sharing"):
        ServeEngine(run_of(cfg), params, slots=2, max_len=64, paged=True,
                    page_size=4, share_prefix=True)


def test_the_full_config_and_its_share():
    """The registered config is the published one; the share holds 8 of
    64 experts behind a router that keeps all 64."""
    from repro.configs import get_model_config
    full = get_model_config("mellum2-12b-a2.5b")
    assert (full.num_layers, full.d_model, full.vocab_size) == (28, 2304,
                                                                98304)
    assert full.windows == {"block0": 1024, "block1": 1024, "block2": 1024}
    assert full.attn_kind(3).rope.yarn_factor == 16.0
    share8 = ep_share(8, 0, full)
    assert share8.moe.experts_held == 8 and share8.moe.num_experts == 64
    shapes = build_model(dataclasses.replace(
        make_run_config("mellum2-12b-a2.5b", "train_4k"),
        model=share8)).param_shapes()
    blk = shapes["decoder"]["layers"]["block0"]["moe"]
    assert blk["router"].shape == (7, 2304, 64)
    assert blk["wg"].shape == (7, 8, 2304, 896)
