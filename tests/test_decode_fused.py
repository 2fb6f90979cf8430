"""PR-8 surfaces: in-kernel fused sampling (bit-identity against the
host oracle, invariant I10), int8-quantized paged KV (tolerance-bounded
parity against fp), the nearest-rank percentile fix, typed allocator
errors, Request temperature validation, and injectable roofline peaks."""
import math
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pool import token_devices
from repro.configs import make_run_config
from repro.models.model import build_model
from repro.serve import (Request, ServeEngine, ServeFleet,
                         UnknownRequestError, percentile)
from repro.serve.paged import BlockAllocator


@pytest.fixture(scope="module")
def setup():
    run = make_run_config("qwen3-0.6b", "decode_32k", smoke=True)
    model = build_model(run)
    params = model.init(jax.random.key(0))
    return run, model, params


# ===========================================================================
# percentile: ceil-based nearest rank (banker's-rounding regression)
# ===========================================================================
@pytest.mark.parametrize("n", range(2, 22))
def test_percentile_nearest_rank_exact(n):
    """Canonical nearest-rank over 1..n is the value ceil(q*n) — checked
    by DEFINITION for every window size the autoscaler actually sees, not
    against the implementation's own formula. The old round()-based index
    broke .5 ties toward even (p50 of n=4 picked rank 3, not 2)."""
    xs = list(range(1, n + 1))
    rng = np.random.default_rng(n)
    shuffled = list(rng.permutation(xs))
    for q in (0.5, 0.9, 0.95, 0.99):
        want = min(n, math.ceil(q * n))
        assert percentile(shuffled, q) == want, (n, q)


def test_percentile_banker_rounding_regression():
    # old round(q*(n-1)) code: round(1.5) = 2 -> the 3rd smallest; the
    # canonical nearest rank for p50 of n=4 is ceil(2) = 2 -> the 2nd
    assert percentile([1.0, 2.0, 3.0, 4.0], 0.5) == 2.0
    assert percentile([], 0.5) == 0.0
    assert percentile([3.0], 0.95) == 3.0


# ===========================================================================
# typed allocator errors (UnknownRequestError)
# ===========================================================================
def test_extend_and_cow_unknown_rid_raise_typed_error():
    alloc = BlockAllocator(num_pages=8, page_size=4)
    alloc.allocate(1, 2)
    with pytest.raises(UnknownRequestError):
        alloc.extend(42, 1)
    with pytest.raises(UnknownRequestError):
        alloc.cow(42, 0)
    assert isinstance(UnknownRequestError("x"), RuntimeError)


def test_unknown_rid_surfaces_through_engine_lazy_growth(setup):
    """Only CacheExhausted is swallowed (admission backoff); a control-
    plane bug — the engine extending a rid the allocator no longer owns —
    must crash loudly through step(), not decode into page 0."""
    run, model, params = setup
    eng = ServeEngine(run, params, slots=1, max_len=48, paged=True,
                      page_size=4)
    req = Request(rid=0, prompt=np.arange(6) % 100, max_new_tokens=12)
    eng.submit(req)
    eng.step()                                    # admit + first decode
    eng.alloc.free(req.rid)                       # simulated stale slot map
    with pytest.raises(UnknownRequestError):
        for _ in range(12):
            eng.step()


# ===========================================================================
# Request temperature validation (the dead-clamp satellite)
# ===========================================================================
def test_request_rejects_subnormal_temperature():
    for bad in (1e-7, 5e-9, 9.9e-7):
        with pytest.raises(ValueError):
            Request(rid=0, prompt=[1, 2], max_new_tokens=1,
                    temperature=bad)
    # the boundary and greedy cases are all valid
    Request(rid=0, prompt=[1, 2], max_new_tokens=1, temperature=0.0)
    Request(rid=1, prompt=[1, 2], max_new_tokens=1, temperature=1e-6)
    Request(rid=2, prompt=[1, 2], max_new_tokens=1, temperature=-1.0)


# ===========================================================================
# kernels: int8 paged decode parity, fused sampling bit-identity
# ===========================================================================
def _paged_inputs(key, B=3, NP=3, page=8, H=4, K=2, hd=16, L=2):
    """Queries, L layers' pools stacked, tables and positions; the tests
    read the last layer."""
    ks = jax.random.split(key, 4)
    P = 1 + B * NP
    q = jax.random.normal(ks[0], (B, 1, H, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (L, P, page, K, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (L, P, page, K, hd), jnp.float32)
    tables = (1 + jnp.arange(B * NP, dtype=jnp.int32)).reshape(B, NP)
    pos = jnp.asarray([NP * page - 1, page + 3, -1], jnp.int32)[:B]
    return q, kp, vp, tables, pos


def test_paged_decode_int8_parity_with_fp():
    from repro.kernels import ops
    from repro.kernels.ref import kv_quant_ref
    q, kp, vp, tables, pos = _paged_inputs(jax.random.key(1))
    want = ops.paged_decode(q, kp, vp, tables, pos, 1, backend="ref")
    kq, ksc = kv_quant_ref(kp)
    vq, vsc = kv_quant_ref(vp)
    got = ops.paged_decode_quant(q, kq, vq, ksc, vsc, tables, pos, 1,
                                 backend="ref")
    # int8 is lossy: bounded by the quantization step, not exact
    assert jnp.max(jnp.abs(got - want)) < 0.05
    # pos=-1 row (no valid tokens) is exactly zero on both paths
    if q.shape[0] >= 3:
        assert jnp.all(got[2] == 0)


def test_paged_decode_quant_kernel_matches_ref():
    from repro.kernels.paged_decode import paged_decode_quant
    from repro.kernels.ref import kv_quant_ref, paged_decode_quant_ref
    q, kp, vp, tables, pos = _paged_inputs(jax.random.key(2))
    kq, ksc = kv_quant_ref(kp)
    vq, vsc = kv_quant_ref(vp)
    want = paged_decode_quant_ref(q, kq, vq, ksc, vsc, tables, pos, 1)
    got = paged_decode_quant(q, kq, vq, ksc, vsc, tables, pos, 1,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_kv_quant_dequant_roundtrip_is_idempotent():
    """Migration invariant: dequantize -> requantize reproduces the same
    int8 bytes (row max lands exactly on +-127), so a request migrated
    out of an int8 pool and re-admitted is bit-identical."""
    from repro.kernels.ref import kv_dequant_ref, kv_quant_ref
    x = jax.random.normal(jax.random.key(3), (4, 8, 2, 16), jnp.float32)
    q1, s1 = kv_quant_ref(x)
    q2, s2 = kv_quant_ref(kv_dequant_ref(q1, s1, jnp.float32))
    assert jnp.array_equal(q1, q2)
    assert jnp.array_equal(s1, s2)


def test_int8_pool_pages_at_most_0_6x_the_bf16_pool():
    """At qwen3-0.6b's published widths an int8 page, with its fp32
    per-(row, head) scales, holds at most 0.6x the bytes of a bf16 page:
    the pages a decode step reads shrink by that factor. Shapes only,
    nothing is allocated."""
    import dataclasses
    from repro.serve.paged import init_paged_cache
    run = make_run_config("qwen3-0.6b", "decode_32k")
    model = build_model(run)
    shape = dataclasses.replace(run.shape, seq_len=64, global_batch=2)

    def pool_bytes(kv_dtype):
        cache = jax.eval_shape(lambda: init_paged_cache(
            model, shape, num_pages=2, page_size=16, kv_dtype=kv_dtype))
        return sum(leaf.size * leaf.dtype.itemsize
                   for leaf in jax.tree.leaves(cache))

    bf16 = pool_bytes(None)
    assert bf16 == 2 * 28 * 2 * 16 * 8 * 128 * 2       # k and v, bf16
    assert pool_bytes("int8") <= 0.6 * bf16


@pytest.mark.parametrize("temp,top_k", [(0.0, 0), (1e-6, 0), (0.7, 1),
                                        (0.7, 8), (1.3, 0), (2.5, 512)])
def test_fused_sample_bit_identical_to_host_oracle(setup, temp, top_k):
    """I10's oracle is ServeEngine._sample (host numpy); the fused kernel
    (ref lowering AND Pallas interpret) must reproduce it bit-for-bit —
    same argmax index, every row, greedy and noisy alike."""
    from repro.kernels import ops
    from repro.kernels.sampling import fused_sample as pallas_fused
    run, model, params = setup
    eng = ServeEngine(run, params, slots=1, max_len=48)
    V = run.model.vocab_size
    B, Vp = 5, V + 8                              # padded vocab tail
    logits = np.asarray(jax.random.normal(jax.random.key(4), (B, Vp)),
                        np.float32)
    reqs = [Request(rid=100 + i, prompt=[1], max_new_tokens=1,
                    temperature=temp, top_k=top_k, seed=7 + i)
            for i in range(B)]
    for i, r in enumerate(reqs):
        r.out = [0] * i                           # distinct counters
    want = [eng._sample(r, logits[i]) for i, r in enumerate(reqs)]

    lt = jnp.full((B,), temp, jnp.float32)
    lk = jnp.full((B,), top_k, jnp.int32)
    keys = jnp.asarray([[r.seed, r.rid, len(r.out)] for r in reqs],
                       jnp.int32)
    got_ref = ops.fused_sample(jnp.asarray(logits), lt, lk, keys,
                               vocab_size=V, backend="ref")
    got_pl = pallas_fused(jnp.asarray(logits), lt, lk, keys,
                          vocab_size=V, interpret=True)
    assert [int(t) for t in got_ref] == want
    assert [int(t) for t in got_pl] == want


# ===========================================================================
# engines: fused/int8 streams == host-sampled streams (I10 composed)
# ===========================================================================
def _serve(run, params, reqs_fn, **kw):
    eng = ServeEngine(run, params, slots=2, max_len=48, paged=True,
                      page_size=8, **kw)
    reqs = reqs_fn()
    for r in reqs:
        eng.submit(r)
    eng.run_until_idle()
    assert all(r.done and not r.error for r in reqs)
    return [r.out for r in reqs]


def _mixed_reqs():
    return [Request(rid=i, prompt=(np.arange(4 + i) * (i + 1)) % 100,
                    max_new_tokens=6,
                    temperature=0.8 if i % 2 else 0.0,
                    top_k=16 if i % 2 else 0, seed=5 + i)
            for i in range(4)]


def test_fused_engine_streams_bit_identical_to_host(setup):
    run, model, params = setup
    host = _serve(run, params, _mixed_reqs)
    fused = _serve(run, params, _mixed_reqs, fused_sampling=True)
    assert fused == host


def test_int8_fused_streams_match_int8_host(setup):
    """int8 KV perturbs logits, so its oracle is the host-sampled int8
    twin — same quantized cache, sampling on the host."""
    run, model, params = setup
    host = _serve(run, params, _mixed_reqs, kv_dtype="int8")
    fused = _serve(run, params, _mixed_reqs, kv_dtype="int8",
                   fused_sampling=True)
    assert fused == host


def test_i10_int8_fused_prefix_sharing_through_pause_live(setup):
    """The composed I10 regression: int8 KV + fused sampling + prefix
    sharing, served THROUGH a fleet pause_live/unpause, must emit the
    same token streams as the same engine with no reconfiguration."""
    run, model, params = setup
    shared = (np.arange(9) * 3) % 100             # trie-shared prefix

    def reqs_fn():
        return [Request(rid=i, prompt=np.concatenate([shared, [i]]),
                        max_new_tokens=6,
                        temperature=0.8 if i % 2 else 0.0,
                        top_k=16 if i % 2 else 0, seed=5 + i)
                for i in range(4)]

    kw = dict(slots=2, max_len=48, paged=True, page_size=8,
              kv_dtype="int8", fused_sampling=True, share_prefix=True)

    def fleet_serve(pause):
        fleet = ServeFleet(run, params, num_engines=1, devices=token_devices(2),
                           workdir=tempfile.mkdtemp(), **kw)
        reqs = reqs_fn()
        for r in reqs:
            fleet.submit(r)
        for _ in range(2):
            fleet.step()
        if pause:
            fleet.pause_live("serve0", rounds=2)
            fleet.unpause("serve0")
        res = fleet.drain()
        assert res.drained and all(r.done and not r.error for r in reqs)
        return [r.out for r in reqs]

    oracle = fleet_serve(pause=False)
    assert fleet_serve(pause=True) == oracle
    # and the plain engine (no fleet loop) agrees too
    assert _serve(run, params, reqs_fn, kv_dtype="int8",
                  fused_sampling=True, share_prefix=True) == oracle


# ===========================================================================
# roofline: peaks are injectable, defaults preserved
# ===========================================================================
def test_roofline_peaks_injectable():
    """Injected ``Peaks`` reach every term of a ``Roofline`` and its
    ``row()``; without them the v5e defaults apply."""
    from repro.runtime.roofline import DEFAULT_PEAKS, Peaks, Roofline
    cell = dict(arch="a", shape="s", mesh="m", chips=1, hlo_flops=4e9,
                hlo_bytes=1e9, collective_bytes=5e8)
    slow = Peaks(flops=1e9, hbm_bw=1e9, ici_bw=1e9)
    r = Roofline(**cell, peaks=slow)
    assert r.compute_s == pytest.approx(4.0)
    assert r.memory_s == pytest.approx(1.0)
    assert r.collective_s == pytest.approx(0.5)
    assert r.bound == "compute"
    row = r.row()
    assert (row["peak_flops"], row["peak_hbm_bw"], row["peak_ici_bw"]) \
        == (1e9, 1e9, 1e9)
    assert row["compute_s"] == pytest.approx(4.0)
    d = Roofline(**cell)
    assert d.peaks == DEFAULT_PEAKS
    assert d.compute_s == pytest.approx(4e9 / DEFAULT_PEAKS.flops)
    assert d.memory_s == pytest.approx(1e9 / DEFAULT_PEAKS.hbm_bw)
    assert d.row()["peak_hbm_bw"] == DEFAULT_PEAKS.hbm_bw
