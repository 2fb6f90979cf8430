"""The plain reference against the program at smoke size, with the Pallas
kernels in interpret mode (the code the chip runs): prefill logits, the
first paged decode after ``admit_kv``, and a whole served run; and the
control (the reference with fp8 weights) failing the limit that the
program meets."""
import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

from conftest import make_root
from test_bench_harness import _run

from bench.families import dense
from bench.references import dense as ref

#: relative L2 error allowed between the program's bf16 logits and the
#: float32 reference: bf16 keeps 8 mantissa bits (eps 2^-8), and rounding
#: compounds over the layers; a dropped head, page or mask is off by tens
#: of percent
RTOL = 2e-2


def _cfg(root):
    with open(os.path.join(root, "bench", "configs", "qwen3-0.6b.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "qwen3-0.6b"
    return cfg


def _program_logits(cfg, weights, prompt):
    """Last-prompt-position logits of the program's prefill, and the logits
    of its first paged decode step after ``admit_kv``."""
    from repro.models.model import build_model
    from repro.serve.paged import admit_kv, init_paged_cache
    from repro.train.step import make_decode_step, make_serve_steps
    run = dense.run_config(cfg)
    prefill, _ = make_serve_steps(run)
    req_cache, last = jax.jit(prefill)(weights,
                                       {"tokens": jnp.asarray(prompt)[None]})
    page, max_len = cfg["serve"]["page_size"], cfg["serve"]["max_len"]
    maxp = math.ceil(max_len / page)
    shape = dataclasses.replace(run.shape, seq_len=max_len, global_batch=1)
    cache = init_paged_cache(build_model(run), shape, 1 + maxp, page)
    pages = list(range(1, 1 + math.ceil((len(prompt) + 1) / page)))
    cache = admit_kv(cache, req_cache, pages, page, slot=0)
    tables = np.zeros((1, maxp), np.int32)
    tables[0, :len(pages)] = pages
    tok = jnp.argmax(last[0]).astype(jnp.int32).reshape(1, 1)
    decode = jax.jit(make_decode_step(run, paged=True))
    logits, _ = decode(weights, cache, tok, jnp.asarray([len(prompt)],
                                                        jnp.int32),
                       jnp.asarray(tables), jnp.asarray([True]))
    return (np.asarray(last[0], np.float32),
            np.asarray(logits[0], np.float32), int(tok[0, 0]))


def test_prefill_and_paged_decode_match_the_reference(tmp_path):
    root = make_root(tmp_path, interpret=True)
    cfg = _cfg(root)
    m = dense.dims(cfg)
    weights = dense.make_weights(cfg, 2**40 + 3)
    prompt = np.random.default_rng(0).integers(0, m.vocab, 40,
                                               dtype=np.int32)
    pre, dec, tok = _program_logits(cfg, weights, prompt)
    seq = np.concatenate([prompt, [tok]])[None]
    pos = np.asarray([[len(prompt) - 1, len(prompt)]], np.int32)
    want = np.asarray(ref.logits(weights, jnp.asarray(seq), pos, m=m))[0]
    for got, exp in ((pre, want[-2]), (dec, want[-1])):
        got = got[:m.vocab]
        err = np.linalg.norm(got - exp) / np.linalg.norm(exp)
        assert err < RTOL, err
    ctl = np.asarray(ref.logits(weights, jnp.asarray(seq), pos, m=m,
                                quant="fp8"))[0]
    err_ctl = np.linalg.norm(ctl[-1] - want[-1]) / np.linalg.norm(want[-1])
    assert err_ctl > 3 * RTOL, err_ctl


def test_served_run_is_correct_and_the_control_is_not(tmp_path, counter):
    """The pause cell with kernels in interpret mode: the served tokens
    pass the limit; judged by the same verdict, the fp8 control's tokens
    read not correct."""
    root = make_root(tmp_path, interpret=True)
    out = _run(root, "qwen3-chat-pause", counter, seconds=3.0,
               control=True)
    prog = out["program_checks"]
    limit = prog["max_logit_gap"]["limit"]
    assert prog["max_logit_gap"]["value"] <= limit
    assert prog["tokens_checked"]["value"] >= 1
    assert out["correct"] is False
    assert out["checks"]["max_logit_gap"]["value"] > limit
    assert out["metrics"]["reconf_stall_ms"]["value"] > 0


def test_the_sampling_rule_matches_the_program():
    """The reference's sampling rule picks the token the program's own
    sampler draws, and gaps read 0 there and grow away from it."""
    from bench import gumbel
    from repro.kernels.ref import fused_sample_ref
    rng = np.random.default_rng(5)
    B, V = 6, 300
    lg = rng.normal(0, 2, (B, V)).astype(np.float32)
    temp = np.asarray([0.0, 0.8, 0.8, 1.3, 0.5, 0.0], np.float32)
    top_k = np.asarray([0, 20, 5, 50, 1, 0], np.int32)
    keys = np.asarray([[7, 1000 + i, 3 * i] for i in range(B)], np.int32)
    want = np.asarray(fused_sample_ref(jnp.asarray(lg), temp, top_k,
                                       jnp.asarray(keys), vocab_size=V))
    k = np.maximum(top_k, 1)
    got = np.asarray(gumbel.pick(jnp.asarray(lg)[:, None], temp[:, None],
                                 k[:, None], keys[:, None].astype(np.uint32),
                                 kmax=50))[:, 0]
    assert np.array_equal(got, want)
    g = np.asarray(gumbel.token_gaps(
        jnp.asarray(lg)[:, None], jnp.asarray(want)[:, None], temp[:, None],
        k[:, None], keys[:, None].astype(np.uint32), kmax=50, margin=0.0))
    assert np.allclose(g, 0.0, atol=1e-5)
    outside = np.argsort(lg, axis=-1)[:, 0]        # the lowest logit
    g = np.asarray(gumbel.token_gaps(
        jnp.asarray(lg)[:, None], jnp.asarray(outside)[:, None],
        temp[:, None], k[:, None], keys[:, None].astype(np.uint32), kmax=50,
        margin=0.0))
    assert (g > 1.0).all()
