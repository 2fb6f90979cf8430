"""Serve plane: continuous batching correctness (dense + paged KV),
chunked prefill, sampling, pause semantics, fleet placement, and the I10
token-determinism invariant."""
import dataclasses
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pool import token_devices
from repro.configs import make_run_config
from repro.models.model import build_model
from repro.serve.engine import DrainResult, Request, ServeEngine
from repro.serve.fleet import EngineTenant, ServeFleet
from repro.serve.paged import (BlockAllocator, CacheExhausted,
                               RequestRejected)


@pytest.fixture(scope="module")
def setup():
    run = make_run_config("qwen3-0.6b", "decode_32k", smoke=True)
    model = build_model(run)
    params = model.init(jax.random.key(0))
    return run, model, params


def naive_generate(model, params, prompt, n, max_len=48):
    batch = {"tokens": jnp.asarray(prompt, jnp.int32)[None]}
    cache, last = jax.jit(model.prefill)(params, batch)

    def pad(path, x):
        name = path[-1].key if hasattr(path[-1], "key") else ""
        if name in ("k", "v"):
            return jnp.pad(x, ((0, 0), (0, 0), (0, max_len - x.shape[2]),
                               (0, 0), (0, 0)))
        return x
    cache = jax.tree_util.tree_map_with_path(pad, cache)
    toks = [int(jnp.argmax(last[0]))]
    pos = len(prompt) - 1
    dec = jax.jit(model.decode_step)
    for _ in range(n - 1):
        pos += 1
        lg, cache = dec(params, cache,
                        jnp.asarray([[toks[-1]]], jnp.int32), jnp.int32(pos))
        toks.append(int(jnp.argmax(lg[0])))
    return toks


def test_engine_matches_naive_with_slot_recycling(setup):
    run, model, params = setup
    prompts = [np.arange(4) % 100, (np.arange(7) * 3) % 100,
               (np.arange(5) * 5 + 2) % 100]
    want = [naive_generate(model, params, p, 6) for p in prompts]
    eng = ServeEngine(run, params, slots=2, max_len=48)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=6)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    steps = 0
    while (eng.step() or eng.queue) and steps < 100:
        steps += 1
    for r, w in zip(reqs, want):
        assert r.out == w, (r.rid, r.out, w)
        assert r.done


def test_engine_pause_queues_requests(setup):
    run, model, params = setup
    eng = ServeEngine(run, params, slots=2, max_len=48)
    eng.pause()
    eng.submit(Request(rid=0, prompt=np.arange(4) % 50, max_new_tokens=3))
    assert eng.step() == 0 and len(eng.queue) == 1   # held while paused
    eng.unpause()
    steps = 0
    while (eng.step() or eng.queue) and steps < 50:
        steps += 1
    assert len(eng.queue) == 0


def test_run_until_idle_returns_finished_requests(setup):
    """Regression: run_until_idle used to always return [] — finished
    requests (decode-finished AND prefill-finished) must be collected."""
    run, model, params = setup
    eng = ServeEngine(run, params, slots=2, max_len=48)
    reqs = [Request(rid=0, prompt=np.arange(4) % 100, max_new_tokens=4),
            Request(rid=1, prompt=(np.arange(6) * 3) % 100,
                    max_new_tokens=1),       # finishes at prefill
            Request(rid=2, prompt=(np.arange(5) * 5 + 2) % 100,
                    max_new_tokens=3)]
    for r in reqs:
        eng.submit(r)
    done = eng.run_until_idle()
    assert sorted(r.rid for r in done) == [0, 1, 2]
    assert all(r.done for r in done)
    assert len(done[0].out) >= 1
    # a second call returns only newly-finished work, not stale requests
    eng.submit(Request(rid=3, prompt=np.arange(4) % 100, max_new_tokens=2))
    done2 = eng.run_until_idle()
    assert [r.rid for r in done2] == [3]


def test_engine_dirty_set_tracks_per_step_mutations(setup):
    """Serving tenants pre-copy params-free: params are clean after the
    first export; decode steps dirty only the cache/positions."""
    run, model, params = setup
    eng = ServeEngine(run, params, slots=1, max_len=48)
    assert "params" in eng.dirty_keys()          # never exported yet
    st = eng.export_state()
    assert set(st) == {"params", "cache", "pos", "last_token"}
    assert st["params"] is params
    assert eng.dirty_keys() == set()
    eng.submit(Request(rid=0, prompt=np.arange(4) % 50, max_new_tokens=2))
    eng.run_until_idle()
    assert eng.dirty_keys() == {"cache", "pos", "last_token"}
    st2 = eng.export_state()
    assert st2["params"] is params               # identity-clean for memo


# ===========================================================================
# satellite bugfixes
# ===========================================================================
def test_overlong_request_rejected_typed_engine_survives(setup):
    """Regression: _admit used a bare ``assert`` (gone under python -O) —
    one over-long request killed the engine and its whole batch. Now it
    is rejected typed, marked done-with-error, and serving continues."""
    run, model, params = setup
    eng = ServeEngine(run, params, slots=2, max_len=48)
    bad = Request(rid=0, prompt=np.arange(40) % 100, max_new_tokens=20)
    good = Request(rid=1, prompt=np.arange(4) % 100, max_new_tokens=3)
    empty = Request(rid=2, prompt=np.zeros((0,), np.int32),
                    max_new_tokens=3)
    for r in (bad, good, empty):
        eng.submit(r)
    done = eng.run_until_idle()
    assert done.drained
    assert sorted(r.rid for r in done) == [0, 1, 2]
    assert bad.done and bad.error and "exceeds max_len" in bad.error
    assert empty.done and empty.error
    assert good.done and good.error is None and len(good.out) == 3


def test_idle_slot_masked_out_of_decode(setup):
    """Regression: inactive slots were decoded too — stale last_token/pos
    burned FLOPs and ``np.maximum(pos+1, 0)`` wrote KV at position 0 for
    EMPTY slots. Idle slots' cache bytes must stay bit-untouched."""
    run, model, params = setup
    eng = ServeEngine(run, params, slots=3, max_len=48)
    eng.submit(Request(rid=0, prompt=np.arange(5) % 100, max_new_tokens=4))
    eng.step()                                   # slot 0 active, 1/2 idle
    idle = jax.tree.map(
        lambda l: np.asarray(l[:, 1:]).copy(), eng._cache)
    while eng.step() or eng.queue:
        pass
    after = jax.tree.map(lambda l: np.asarray(l[:, 1:]), eng._cache)
    for a, b in zip(jax.tree.leaves(idle), jax.tree.leaves(after)):
        assert np.array_equal(a, b), "idle slot cache bytes changed"
    # and nothing was ever written at position 0 of an idle slot
    ksum = np.abs(np.asarray(
        jax.tree.leaves(after)[0])).sum()        # still all-zero KV
    assert ksum == 0.0
    assert eng.pos[1] == -1 and eng.pos[2] == -1


def test_run_until_idle_on_paused_engine_breaks_out(setup):
    """Regression: a paused engine with a non-empty queue used to spin all
    max_steps doing nothing, then report the early-finished requests as
    if the queue had drained. It must return immediately and surface the
    undrained state."""
    run, model, params = setup
    eng = ServeEngine(run, params, slots=2, max_len=48)
    eng.pause()
    eng.submit(Request(rid=0, prompt=np.arange(4) % 50, max_new_tokens=3))
    res = eng.run_until_idle(max_steps=10_000)
    assert isinstance(res, DrainResult)
    assert res == [] and res.drained is False     # work remains, none done
    assert len(eng.queue) == 1                    # queue intact
    eng.unpause()
    res2 = eng.run_until_idle()
    assert res2.drained and [r.rid for r in res2] == [0]


def test_prefill_finishing_requests_share_one_slot(setup):
    """Regression: a request finishing at prefill left its KV in the slot
    and consumed it for the rest of the admission pass. Both max_new=1
    requests must finish through ONE free slot in one pass, leaving the
    slot's cache untouched."""
    run, model, params = setup
    eng = ServeEngine(run, params, slots=1, max_len=48)
    r0 = Request(rid=0, prompt=np.arange(4) % 100, max_new_tokens=1)
    r1 = Request(rid=1, prompt=(np.arange(6) * 3) % 100, max_new_tokens=1)
    eng.submit(r0)
    eng.submit(r1)
    eng.step()                                    # a single admission pass
    assert r0.done and r1.done and len(eng.queue) == 0
    assert eng.active[0] is None and eng.pos[0] == -1
    # nothing was ever inserted: the whole cache is pristine
    for leaf in jax.tree.leaves(eng._cache or {}):
        arr = np.asarray(leaf)
        assert np.all((arr == 0) | (arr == -1e30))


# ===========================================================================
# paged KV
# ===========================================================================
def test_block_allocator_mirrors_device_pool_semantics():
    a = BlockAllocator(num_pages=9, page_size=4)
    assert a.capacity == 8
    p0 = a.allocate(0, 3)
    p1 = a.allocate(1, 2)
    assert not set(p0) & set(p1) and 0 not in p0 + p1
    a.check_invariants()
    with pytest.raises(CacheExhausted):
        a.allocate(2, 4)                          # only 3 free
    with pytest.raises(RequestRejected):
        a.allocate(3, 9)                          # > capacity: permanent
    a.free(0)
    holes = a.allocate(4, 2)                      # reuses freed low ids
    assert holes == [1, 2]
    a.check_invariants()
    a.free(1)
    moves = a.defragment()                        # compact to the front
    a.check_invariants()
    assert sorted(q for ps in a.owners().values() for q in ps) == [1, 2]
    assert all(new < old for old, new in moves.items())


def test_paged_engine_matches_dense_and_naive(setup):
    run, model, params = setup
    prompts = [np.arange(4) % 100, (np.arange(7) * 3) % 100,
               (np.arange(5) * 5 + 2) % 100, (np.arange(9) * 11 + 1) % 100]
    want = [naive_generate(model, params, p, 6) for p in prompts]

    def serve(**kw):
        eng = ServeEngine(run, params, slots=2, max_len=48, **kw)
        reqs = [Request(rid=i, prompt=p, max_new_tokens=6)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        res = eng.run_until_idle()
        assert res.drained and all(r.done for r in reqs)
        return [r.out for r in reqs]

    assert serve(paged=True, page_size=8) == want
    assert serve(prefill_chunk=3) == want
    assert serve(paged=True, page_size=8, prefill_chunk=3) == want


def test_paged_pool_exhaustion_backs_off_then_serves(setup):
    """A pool too small for all requests at once serves them anyway —
    admission backs off (requests stay queued) until pages free up."""
    run, model, params = setup
    eng = ServeEngine(run, params, slots=4, max_len=48, paged=True,
                      page_size=8, num_pages=4)     # 3 usable pages
    reqs = [Request(rid=i, prompt=np.arange(4 + i) % 100,
                    max_new_tokens=4) for i in range(4)]
    for r in reqs:
        eng.submit(r)
    res = eng.run_until_idle()
    assert res.drained and all(r.done and not r.error for r in reqs)
    assert eng.alloc.num_free == eng.alloc.capacity  # all pages returned


def test_paged_defragment_preserves_decode(setup):
    run, model, params = setup
    eng = ServeEngine(run, params, slots=2, max_len=48, paged=True,
                      page_size=4)
    reqs = [Request(rid=i, prompt=(np.arange(5) * (i + 2)) % 100,
                    max_new_tokens=8) for i in range(3)]
    for r in reqs:
        eng.submit(r)
    for _ in range(3):                             # mid-flight
        eng.step()
    eng.defragment()
    eng.alloc.check_invariants()
    res = eng.run_until_idle()
    assert res.drained and all(r.done for r in reqs)
    # outputs equal an engine that never defragmented
    eng2 = ServeEngine(run, params, slots=2, max_len=48, paged=True,
                       page_size=4)
    reqs2 = [Request(rid=i, prompt=(np.arange(5) * (i + 2)) % 100,
                     max_new_tokens=8) for i in range(3)]
    for r in reqs2:
        eng2.submit(r)
    eng2.run_until_idle()
    assert [r.out for r in reqs] == [r.out for r in reqs2]


# ===========================================================================
# sampling
# ===========================================================================
def test_sampling_deterministic_and_temperature_zero_is_greedy(setup):
    run, model, params = setup

    def serve(temp, top_k, seed=7):
        eng = ServeEngine(run, params, slots=2, max_len=48)
        reqs = [Request(rid=i, prompt=(np.arange(4) * (i + 1)) % 100,
                        max_new_tokens=5, temperature=temp, top_k=top_k,
                        seed=seed) for i in range(3)]
        for r in reqs:
            eng.submit(r)
        eng.run_until_idle()
        return [r.out for r in reqs]

    greedy = serve(0.0, 0)
    assert greedy == serve(0.0, 0)
    sampled = serve(0.9, 8)
    assert sampled == serve(0.9, 8)               # counter-seeded RNG
    assert sampled != serve(0.9, 8, seed=8)       # stream actually varies


def test_mid_run_pause_roundtrip_token_identical(setup):
    """The real-engine I10: a pause/export/import round-trip mid-decode
    (sampled!) must not change any request's tokens."""
    run, model, params = setup
    prompts = [np.arange(4) % 100, (np.arange(7) * 3) % 100]

    def serve(pause_at=None):
        eng = ServeEngine(run, params, slots=2, max_len=48, paged=True,
                          page_size=8)
        reqs = [Request(rid=i, prompt=p, max_new_tokens=6,
                        temperature=0.8, top_k=16)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        steps = 0
        while (eng.step() or eng.queue) and steps < 100:
            steps += 1
            if pause_at is not None and steps == pause_at:
                eng.pause()
                st = eng.export_state()
                eng._cache = None
                eng.import_state(st)
                eng.unpause()
        return [r.out for r in reqs]

    assert serve() == serve(pause_at=2)


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "jamba-1.5-large-398b"])
def test_paged_pool_updated_in_place(arch, monkeypatch):
    """A paged engine writes its pool where it lies: the layer scan
    carries the stacked pool (a hybrid stack's recurrent state is sliced
    out of it and written back), and decode and admission donate it.
    Every decode and admit call consumes its input pool (``kv_in_place``),
    the scan emits the tokens of the unrolled stack, and a pause ->
    unpause -> step cycle leaves the snapshot's host payload as it was
    staged (the restored pool shares no memory with it)."""
    import repro.serve.engine as engine_mod
    run = make_run_config(arch, "decode_32k", smoke=True)
    params = build_model(run).init(jax.random.key(0))
    prompts = [np.arange(4) % 100, (np.arange(11) * 3) % 100,
               (np.arange(5) * 5 + 2) % 100, (np.arange(9) * 7 + 1) % 100]
    admits = []
    admit = engine_mod.admit_kv
    monkeypatch.setattr(engine_mod, "admit_kv",
                        lambda *a, **kw: admits.append(1) or admit(*a, **kw))

    def serve(run):
        eng = ServeEngine(run, params, slots=2, max_len=48, paged=True,
                          page_size=8, prefill_chunk=4, fused_sampling=True)
        decodes = []
        step = eng._decode
        eng._decode = lambda *a: decodes.append(1) or step(*a)
        admits.clear()
        reqs = [Request(rid=i, prompt=p, max_new_tokens=6,
                        temperature=0.8 * (i % 2), top_k=16)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        assert eng.run_until_idle().drained
        assert all(r.done and not r.error for r in reqs)
        assert decodes and len(admits) == len(prompts)
        assert eng.stats["kv_in_place"] == len(decodes) + len(admits)
        return [r.out for r in reqs]

    unrolled = dataclasses.replace(run, sharding=dataclasses.replace(
        run.sharding, scan_layers=False))
    assert serve(run) == serve(unrolled)

    fleet = ServeFleet(run, params, num_engines=1, devices=token_devices(1),
                       slots=2, max_len=48, paged=True, page_size=8,
                       workdir=tempfile.mkdtemp())
    for i, p in enumerate(prompts):
        fleet.submit(Request(rid=i, prompt=p, max_new_tokens=12))
    for _ in range(4):
        fleet.step()
    fleet.pause_live("serve0")
    payload = fleet.mgr.snapshots["serve0"].payload["cache"]
    staged = [np.array(x, copy=True) for x in jax.tree.leaves(payload)]
    fleet.unpause("serve0")
    eng = fleet.tenants["serve0"].engine
    for a, b in zip(jax.tree.leaves(eng._cache), jax.tree.leaves(payload)):
        assert a.unsafe_buffer_pointer() != np.asarray(b).ctypes.data
    before = eng.stats["kv_in_place"]
    for _ in range(3):
        fleet.step()
    assert eng.stats["kv_in_place"] > before
    for a, b in zip(staged, jax.tree.leaves(payload)):
        assert np.array_equal(a, np.asarray(b)), "snapshot bytes changed"


# ===========================================================================
# fleet: engines as tenants under the SVFF manager
# ===========================================================================
def _fleet(run, params, policy, **kw):
    return ServeFleet(run, params, num_engines=2, devices=token_devices(4),
                      policy=policy, slots=2, max_len=48, paged=True,
                      page_size=8, workdir=tempfile.mkdtemp(), **kw)


@pytest.mark.parametrize("policy", ["first_fit", "best_fit", "fair_share"])
def test_fleet_serves_through_pause_live_and_migrate(setup, policy):
    run, model, params = setup
    fleet = _fleet(run, params, policy)
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, prompt=rng.integers(0, 500,
                                               int(rng.integers(3, 8))),
                    max_new_tokens=4) for i in range(6)]
    for r in reqs[:4]:
        fleet.submit(r)
    for _ in range(2):
        fleet.step()
    t = fleet.pause_live("serve0", rounds=2)      # fires mid-traffic
    assert t.background                           # pre-copy really ran
    for r in reqs[4:]:
        fleet.submit(r)                           # arrivals while paused
    fleet.unpause("serve0")
    fleet.migrate("serve1")
    done = fleet.drain()
    assert sorted(r.rid for r in done) == list(range(6))
    assert all(r.done and not r.error for r in reqs)
    assert fleet.mgr.query()["journal_pending"] == 0


def test_chunked_prefill_works_with_pallas_backend(setup):
    """Regression: attention()'s kernel-dispatch guard bool()'d the traced
    chunk offset (TracerBoolConversionError) under kernel_backend=pallas."""
    run, model, params = setup
    prun = run.replace(kernel_backend="pallas", interpret=True)
    eng = ServeEngine(prun, params, slots=1, max_len=48, prefill_chunk=3)
    req = Request(rid=0, prompt=np.arange(7) % 100, max_new_tokens=2)
    eng.submit(req)
    res = eng.run_until_idle()
    assert res.drained and req.done and len(req.out) == 2


def test_fleet_drain_surfaces_stranded_paused_engine(setup):
    """Regression: drain() on a fleet with a still-paused engine reported
    a partial drain as complete (the bug the run_until_idle satellite
    fixed, reintroduced one level up)."""
    run, model, params = setup
    fleet = _fleet(run, params, "first_fit")
    reqs = [Request(rid=i, prompt=np.arange(4 + i) % 100,
                    max_new_tokens=6) for i in range(4)]
    for r in reqs:
        fleet.submit(r)
    for _ in range(2):
        fleet.step()
    fleet.pause_live("serve0", rounds=1)          # ... and never unpause
    res = fleet.drain()
    assert res.drained is False                   # stranded work surfaced
    assert any(not r.done for r in reqs)
    fleet.unpause("serve0")
    res2 = fleet.drain()
    assert res2.drained is True
    assert all(r.done for r in reqs)


def test_pause_mid_chunked_prefill_requeues_jobs_token_identical(setup):
    """Regression: a pause landing while chunked-prefill jobs are in
    flight must not lose them — suspend re-queues the jobs (no tokens
    emitted yet, prefill deterministic), frees their pages, and the
    post-resume outputs equal an undisturbed run."""
    run, model, params = setup

    def serve(pause_mid_prefill):
        fleet = ServeFleet(run, params, num_engines=1, devices=token_devices(2),
                           slots=2, max_len=48, paged=True, page_size=8,
                           prefill_chunk=3, workdir=tempfile.mkdtemp())
        eng = fleet.tenants["serve0"].engine
        reqs = [Request(rid=i, prompt=(np.arange(8 + i) * 5) % 100,
                        max_new_tokens=4) for i in range(3)]
        for r in reqs:
            fleet.submit(r)
        fleet.step()                      # jobs created, prompts > chunk
        if pause_mid_prefill:
            assert eng._jobs              # a prefill really is in flight
            fleet.pause_live("serve0", rounds=1)
            assert not eng._jobs          # re-queued, not stranded
            assert eng.alloc.check_invariants() is None
            fleet.unpause("serve0")
        res = fleet.drain()
        assert res.drained and all(r.done and not r.error for r in reqs)
        return [r.out for r in reqs]

    assert serve(False) == serve(True)


def test_fleet_slo_rejection_then_retry_completes(setup):
    """Regression: submit used to set ``req.done = True`` and
    ``req.error`` on the SLO-rejection path BEFORE raising, so a caller
    retrying the same Request after backoff submitted an object every
    engine treated as already finished (its loop dropped it on the first
    step, done-with-stale-error). Rejection must be side-effect-free on
    the request — tracked fleet-side only — and the retry must serve
    normally."""
    run, model, params = setup
    fleet = ServeFleet(run, params, num_engines=1, devices=token_devices(2),
                       slots=1,
                       max_len=48, slo_max_load=1,
                       workdir=tempfile.mkdtemp())
    fleet.submit(Request(rid=0, prompt=np.arange(4), max_new_tokens=2))
    over = Request(rid=1, prompt=np.arange(4), max_new_tokens=2)
    with pytest.raises(RequestRejected):
        fleet.submit(over)
    # the request object is UNTOUCHED: the caller owns retry policy
    assert over.done is False and over.error is None and over.out == []
    # the rejection is visible fleet-side instead
    assert len(fleet.rejections) == 1
    assert fleet.rejections[0]["rid"] == 1
    assert fleet.telemetry.rejected["serve0"] == 1
    done = fleet.drain()
    assert sorted(r.rid for r in done) == [0]     # only real completions
    fleet.submit(over)                            # retry after backoff
    done2 = fleet.drain()
    assert [r.rid for r in done2] == [1]
    assert over.done and over.error is None and len(over.out) == 2


def test_fleet_tie_break_is_creation_order_not_lexicographic(setup):
    """Regression: load ties broke on the tid STRING, so a >= 10 engine
    fleet placed round-robin as serve0, serve1, serve10, serve11,
    serve2, ... — placement must follow engine creation index (this
    matters once the autoscaler spawns tenants dynamically)."""
    run, model, params = setup
    fleet = ServeFleet(run, params, num_engines=12, devices=token_devices(12),
                       slots=1, max_len=48, workdir=tempfile.mkdtemp())
    placements = [fleet.submit(Request(rid=i, prompt=np.arange(4) % 50,
                                       max_new_tokens=1))
                  for i in range(12)]
    assert placements == [f"serve{i}" for i in range(12)]


def test_fleet_placement_follows_policy_heterogeneous_pool(setup):
    """fair_share/best_fit placement of serving tenants over a
    heterogeneous VF table (sizes 2,1,4 + 1 occupied -> share 4)."""
    from repro.core import SVFFManager
    from tests.test_scheduler import make_pool
    run, model, params = setup

    def attach_one(policy):
        pool = make_pool()                         # sizes (2, 1, 4) + occ
        mgr = SVFFManager(pool, workdir=tempfile.mkdtemp(),
                          scheduler=policy)
        eng = ServeEngine(run, jax.tree.map(jnp.array, params), slots=1,
                          max_len=48)
        tn = EngineTenant("serveX", eng, placement=policy)
        mgr.attach(tn)
        return len(pool.vfs[tn.vf_id].devices)

    assert attach_one("first_fit") == 2            # PF table order
    assert attach_one("best_fit") == 1             # smallest sufficient
    assert attach_one("fair_share") == 4           # closest to share


def test_make_scheduler_instance_cached_across_managers():
    from repro.core import DevicePool, SVFFManager, make_scheduler
    a = SVFFManager(DevicePool(devices=("x0",)),
                    workdir=tempfile.mkdtemp(), scheduler="best_fit")
    b = SVFFManager(DevicePool(devices=("x1",)),
                    workdir=tempfile.mkdtemp(), scheduler="best_fit")
    assert a.scheduler is b.scheduler              # stateless + cached
    assert a.scheduler is make_scheduler("best_fit")


# ===========================================================================
# I10 in the scenario simulator
# ===========================================================================
def test_sim_i10_regression_seeds():
    """Checked-in regression seeds: serve traffic + pause/pause_live/
    migrate interleavings stay token-deterministic (I10), replay-stable,
    across all three placement policies."""
    from repro.sim import ScenarioConfig, ScenarioRunner
    for policy in ("first_fit", "best_fit", "fair_share"):
        cfg = ScenarioConfig(seed=3, policy=policy, serve_rate=0.35,
                             num_ops=30)
        res = ScenarioRunner(cfg).run()
        assert res.fingerprint() == ScenarioRunner(cfg).run().fingerprint()
        kinds = {r.op.kind for r in res.ops}
        assert "serve_submit" in kinds


def test_sim_serve_tenant_oracle_catches_corruption():
    """I10 has teeth: flipping one byte of live paged KV diverges the
    token stream from the no-reconfiguration oracle."""
    from repro.sim import SimServeTenant

    class _VF:
        mesh_shape = (1, 1)
        mesh_axes = ("data", "model")
        devices = ("d0",)
        vf_id = "vf1"
        emulated: dict = {}

    tn = SimServeTenant("sv0", seed=3)
    tn.bind(_VF())
    tn.submit_burst(3)
    tn.run_steps(2)
    req = next(r for r in tn.requests if r.out and not r.done)
    tn.pages[tn.tables[0][0], 0] += 1              # corrupt one cell
    tn.run_steps(1)
    want = tn.expected_output(tn.seed, req.rid)
    assert list(req.out) != want[:len(req.out)]


@pytest.mark.slow
def test_sim_i10_sweep_all_policies():
    from repro.sim import ScenarioConfig, ScenarioRunner
    for policy in ("first_fit", "best_fit", "fair_share"):
        for seed in range(10):
            ScenarioRunner(ScenarioConfig(
                seed=seed, policy=policy, serve_rate=0.35,
                num_ops=28)).run()


def test_engine_eos_stops_early(setup):
    run, model, params = setup
    # discover the first greedy token, then use it as the EOS id
    probe = Request(rid=0, prompt=np.arange(4) % 50, max_new_tokens=2)
    eng = ServeEngine(run, params, slots=1, max_len=48)
    eng.submit(probe)
    while eng.step() or eng.queue:
        pass
    eos = probe.out[0]
    req = Request(rid=1, prompt=np.arange(4) % 50, max_new_tokens=10,
                  eos_id=eos)
    eng2 = ServeEngine(run, params, slots=1, max_len=48)
    eng2.submit(req)
    while eng2.step() or eng2.queue:
        pass
    assert req.done and len(req.out) == 1 and req.out[0] == eos
