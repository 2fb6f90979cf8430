"""Refcounted copy-on-write prefix sharing: allocator trie/refcount
semantics, engine-level CoW + lazy growth + preemption, the I12 refcount
invariant, and the allocator-hardening bugfixes (typed double-free,
defragment-before-backoff, dead `extend` wired as lazy decode growth)."""
import tempfile

import jax
import numpy as np
import pytest

from repro.configs import make_run_config
from repro.core import DevicePool, SVFFManager, StagingEngine
from repro.core.pool import token_devices
from repro.models.model import build_model
from repro.serve.engine import Request, ServeEngine
from repro.serve.paged import (BlockAllocator, CacheExhausted,
                               DoubleFreeError, RequestRejected,
                               UnknownRequestError)
from repro.sim.invariants import InvariantViolation, check_invariants


@pytest.fixture(scope="module")
def setup():
    run = make_run_config("qwen3-0.6b", "decode_32k", smoke=True)
    model = build_model(run)
    params = model.init(jax.random.key(0))
    return run, model, params


def naive_generate(model, params, prompt, n, max_len=48):
    import jax.numpy as jnp
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


# ===========================================================================
# allocator: trie sharing + refcounts
# ===========================================================================
def _alloc_with_prompt(alloc, rid, tokens, extra=0):
    """Allocate rid's prompt pages (+extra) and register them for sharing,
    mirroring the engine's allocate-at-admit / register-at-place split."""
    n = alloc.pages_needed(len(tokens)) + extra
    pages = alloc.allocate(rid, n, tokens=tokens)
    alloc.register_prefix(rid)
    return pages


def test_full_page_prefix_shares_physical_pages():
    alloc = BlockAllocator(16, 4)
    sys_prompt = tuple(range(8))                      # two full pages
    p0 = _alloc_with_prompt(alloc, 0, sys_prompt)
    p1 = alloc.allocate(1, 2, tokens=sys_prompt)
    assert p1 == p0                                   # same physical pages
    assert alloc.shared_count(1) == 2
    assert alloc.refcount(p0[0]) == alloc.refcount(p0[1]) == 2
    assert alloc.pages_in_use == 2                    # counted once
    # divergent second page -> only the first page shares
    p2 = alloc.allocate(2, 2, tokens=sys_prompt[:4] + (90, 91, 92, 93))
    assert p2[0] == p0[0] and p2[1] not in p0
    assert alloc.shared_count(2) == 1
    alloc.check_invariants()


def test_partial_page_shares_only_on_exact_prefix_rest():
    alloc = BlockAllocator(16, 4)
    reg = tuple(range(6))                  # 1 full page + rest (4, 5)
    p0 = _alloc_with_prompt(alloc, 0, reg)
    # sharer's leftover (4,) is a PREFIX of the registered (4, 5): both
    # pages shared — the longer registered tail sits past the sharer's
    # position and is masked by the decode kernel
    p1 = alloc.allocate(1, 2, tokens=tuple(range(5)))
    assert p1 == p0 and alloc.shared_count(1) == 2
    # leftover (4, 7) is NOT a prefix: only the full page shares
    p2 = alloc.allocate(2, 2, tokens=(0, 1, 2, 3, 4, 7))
    assert p2[0] == p0[0] and p2[1] != p0[1]
    # leftover longer than the registered rest: the registered page does
    # not hold the sharer's extra row, so it must not share either
    p3 = alloc.allocate(3, 2, tokens=tuple(range(7)))
    assert p3[0] == p0[0] and p3[1] != p0[1]
    alloc.check_invariants()


def test_free_keeps_shared_pages_live_for_siblings():
    alloc = BlockAllocator(16, 4)
    prompt = tuple(range(8))
    p0 = _alloc_with_prompt(alloc, 0, prompt)
    alloc.allocate(1, 2, tokens=prompt)
    alloc.free(0)                          # registrant finishes first
    assert alloc.refcount(p0[0]) == 1      # sibling keeps the pages live
    assert alloc.pages_in_use == 2
    # the trie entry survives with the page: a third request still hits
    p2 = alloc.allocate(2, 2, tokens=prompt)
    assert p2 == p0 and alloc.shared_count(2) == 2
    alloc.free(1)
    alloc.free(2)
    assert alloc.pages_in_use == 0         # last owner returned them
    # and the trie let go: a fresh request gets fresh pages, no stale hit
    assert alloc.allocate(3, 2, tokens=prompt) and alloc.shared_count(3) == 0
    alloc.check_invariants()


def test_double_free_raises_typed_error():
    alloc = BlockAllocator(8, 4)
    with pytest.raises(DoubleFreeError):
        alloc.free(7)                      # never allocated
    alloc.allocate(0, 2)
    alloc.free(0)
    with pytest.raises(DoubleFreeError):
        alloc.free(0)                      # double free
    assert issubclass(DoubleFreeError, RuntimeError)
    alloc.check_invariants()


def test_cow_splits_one_page_and_respects_guards():
    alloc = BlockAllocator(16, 4)
    prompt = tuple(range(8))
    p0 = _alloc_with_prompt(alloc, 0, prompt)
    alloc.allocate(1, 2, tokens=prompt)
    old, new = alloc.cow(1, 1)             # rid 1 writes into page idx 1
    assert old == p0[1] and new not in p0
    assert alloc.pages_of(1) == [p0[0], new]
    assert alloc.pages_of(0) == p0         # sharer's chain untouched
    assert alloc.refcount(old) == 1 and alloc.refcount(new) == 1
    with pytest.raises(ValueError):
        alloc.cow(1, 1)                    # already private
    alloc.check_invariants()


def test_cow_exhaustion_is_typed_and_side_effect_free():
    alloc = BlockAllocator(4, 4)           # capacity 3
    prompt = tuple(range(8))
    _alloc_with_prompt(alloc, 0, prompt)
    alloc.allocate(1, 2, tokens=prompt)
    alloc.allocate(2, 1)                   # last free page gone
    before = alloc.pages_of(1)
    with pytest.raises(CacheExhausted):
        alloc.cow(1, 0)
    assert alloc.pages_of(1) == before     # refcounts untouched
    alloc.check_invariants()


def test_extend_grows_chain_with_private_pages():
    alloc = BlockAllocator(8, 4)
    prompt = tuple(range(4))
    _alloc_with_prompt(alloc, 0, prompt)
    chain0 = alloc.pages_of(0)
    (new,) = alloc.extend(0, 1)
    assert alloc.pages_of(0) == chain0 + [new]
    assert alloc.refcount(new) == 1
    # decode-grown pages are never offered for sharing
    p1 = alloc.allocate(1, 2, tokens=prompt + (9, 9, 9, 9))
    assert new not in p1
    with pytest.raises(UnknownRequestError):
        alloc.extend(42, 1)                # unknown rid
    with pytest.raises(CacheExhausted):
        alloc.extend(0, 99)
    alloc.check_invariants()


def test_defragment_moves_shared_pages_once_and_remaps_trie():
    alloc = BlockAllocator(32, 4)
    prompt = tuple(range(8))
    alloc.allocate(0, 3)                   # filler to push pages up
    p1 = _alloc_with_prompt(alloc, 1, prompt, extra=1)
    alloc.allocate(2, 2, tokens=prompt)
    alloc.free(0)                          # hole below the shared pages
    moves = alloc.defragment()             # runs check_invariants itself
    assert moves
    c1, c2 = alloc.pages_of(1), alloc.pages_of(2)
    assert c1[:2] == c2[:2]                # sharing survives compaction
    assert c1[:2] != p1[:2]                # and the pages really moved
    assert alloc.refcount(c1[0]) == 2
    # the trie remapped with the pages: a post-defrag admit still hits
    p3 = alloc.allocate(3, 2, tokens=prompt)
    assert p3 == c2 and alloc.shared_count(3) == 2


def test_allocator_self_check_catches_seeded_over_decref():
    alloc = BlockAllocator(16, 4)
    prompt = tuple(range(8))
    pages = _alloc_with_prompt(alloc, 0, prompt)
    alloc.allocate(1, 2, tokens=prompt)
    alloc.check_invariants()               # sane baseline
    alloc._decref(pages[0])                # seeded bug: one decref too many
    with pytest.raises(AssertionError, match="refcount drift"):
        alloc.check_invariants()


# ===========================================================================
# engine: bit-identical outputs, CoW splits, lazy growth, preemption
# ===========================================================================
def _drain(eng, limit=300):
    steps = 0
    while (eng.step() or eng.queue or eng._jobs) and steps < limit:
        steps += 1
    return steps


def test_share_prefix_outputs_bit_identical_and_fewer_pages(setup):
    """Four residents on one prompt: sharing must not change a single
    token (I10 vs both the naive oracle and a no-sharing engine) while
    holding strictly fewer unique pages at equal residency."""
    run, model, params = setup
    prompt = np.arange(32) % 100
    want = naive_generate(model, params, prompt, 4)
    peaks = {}
    outs = {}
    for share in (False, True):
        eng = ServeEngine(run, params, slots=4, max_len=48, paged=True,
                          page_size=16, share_prefix=share)
        reqs = [Request(rid=i, prompt=prompt, max_new_tokens=4)
                for i in range(4)]
        for r in reqs:
            eng.submit(r)
        peak = 0
        steps = 0
        while (eng.step() or eng.queue) and steps < 100:
            peak = max(peak, eng.alloc.pages_in_use)
            steps += 1
        peaks[share] = peak
        outs[share] = [r.out for r in reqs]
        assert all(r.done for r in reqs)
        assert eng.alloc.pages_in_use == 0          # everything returned
        eng.alloc.check_invariants()
    assert outs[True] == outs[False] == [want] * 4
    assert peaks[True] < peaks[False]
    # 2 shared prompt pages x 3 sharing residents
    assert peaks[False] - peaks[True] >= 4


def test_cow_splits_exactly_one_page_on_mid_page_divergence(setup):
    """Two requests share a 12-token prompt (page_size 8: one full page +
    a partial). The first decode write lands mid-page in the shared
    partial page -> exactly ONE CoW split (the writer goes private; the
    remaining owner writes in place at refcount 1)."""
    run, model, params = setup
    prompt = (np.arange(12) * 3) % 100
    want = naive_generate(model, params, prompt, 4)
    eng = ServeEngine(run, params, slots=2, max_len=48, paged=True,
                      page_size=8, share_prefix=True)
    reqs = [Request(rid=i, prompt=prompt, max_new_tokens=4)
            for i in range(2)]
    for r in reqs:
        eng.submit(r)
    _drain(eng)
    assert [r.out for r in reqs] == [want, want]
    assert eng.stats["shared_page_hits"] == 2       # full + partial hit
    assert eng.stats["cow_splits"] == 1
    eng.alloc.check_invariants()


def test_sibling_finish_keeps_shared_pages_live(setup):
    """A short request finishing must not free the shared prompt pages
    its long-running sibling still reads through."""
    run, model, params = setup
    prompt = np.arange(16) % 100
    want = naive_generate(model, params, prompt, 8)
    eng = ServeEngine(run, params, slots=2, max_len=48, paged=True,
                      page_size=16, share_prefix=True)
    long_r = Request(rid=0, prompt=prompt, max_new_tokens=8)
    short_r = Request(rid=1, prompt=prompt, max_new_tokens=2)
    eng.submit(long_r)
    eng.submit(short_r)
    while not short_r.done:
        eng.step()
    # sibling gone; the long request still owns the shared prompt page
    assert eng.alloc.refcount(eng.alloc.pages_of(0)[0]) == 1
    eng.alloc.check_invariants()
    _drain(eng)
    assert long_r.out == want and short_r.out == want[:2]


def test_defragment_with_refcounted_pages_mid_decode(setup):
    """Production defragment (the _admit retry path calls this) while
    shared refcount>1 pages are live mid-decode: chains, tables, and the
    trie all follow the moved pages; outputs stay bit-identical."""
    run, model, params = setup
    prompt = np.arange(32) % 100
    want = naive_generate(model, params, prompt, 6)
    eng = ServeEngine(run, params, slots=3, max_len=48, paged=True,
                      page_size=16, share_prefix=True)
    filler = Request(rid=9, prompt=(np.arange(8) * 7) % 100,
                     max_new_tokens=1)     # finishes at prefill -> a hole
    reqs = [Request(rid=i, prompt=prompt, max_new_tokens=6)
            for i in range(2)]
    eng.submit(filler)
    for r in reqs:
        eng.submit(r)
    eng.step()                             # all admitted, filler done
    assert filler.done and not reqs[0].done
    moves = eng.defragment()
    chain = eng.alloc.pages_of(0)
    assert eng.alloc.refcount(chain[0]) == 2       # sharing survived
    assert list(eng.tables[0][:len(chain)]) == chain
    eng.alloc.check_invariants()
    _drain(eng)
    assert [r.out for r in reqs] == [want, want]
    assert moves is not None               # the path ran (may be {})


def test_lazy_extend_grows_pages_on_demand(setup):
    """Satellite: admission reserves only PROMPT pages; decode grows the
    chain one page at a time toward max_new_tokens."""
    run, model, params = setup
    prompt = np.arange(16) % 100
    want = naive_generate(model, params, prompt, 20)
    eng = ServeEngine(run, params, slots=1, max_len=48, paged=True,
                      page_size=16)
    req = Request(rid=0, prompt=prompt, max_new_tokens=20)
    eng.submit(req)
    seen_pages = []
    while not req.done:
        eng.step()
        seen_pages.append(eng.alloc.pages_in_use)
    assert req.out == want
    # grew 1 -> 2 -> 3 pages on demand instead of reserving 3 up front
    assert seen_pages[0] == 2 and max(seen_pages) == 3
    assert eng.stats["lazy_extends"] == 2
    assert eng.alloc.pages_in_use == 0


def test_impossible_request_rejected_despite_lazy_growth(setup):
    """The full-need capacity check stays at admission: a request whose
    TOTAL footprint exceeds the pool must reject typed up front, not
    live-lock in an endless extend/preempt cycle mid-decode."""
    run, model, params = setup
    eng = ServeEngine(run, params, slots=1, max_len=48, paged=True,
                      page_size=8, num_pages=3)     # capacity 2
    bad = Request(rid=0, prompt=np.arange(8) % 100, max_new_tokens=16)
    eng.submit(bad)
    eng.step()
    assert bad.done and bad.error and "capacity" in bad.error
    assert eng.alloc.pages_in_use == 0


def test_preemption_replay_is_token_identical(setup):
    """CoW/extend exhaustion preempts a slot (free pages + requeue); the
    replay from scratch must emit exactly the same tokens (I10)."""
    run, model, params = setup
    prompt = np.arange(8) % 100
    want = naive_generate(model, params, prompt, 10)
    eng = ServeEngine(run, params, slots=2, max_len=48, paged=True,
                      page_size=8, num_pages=4)     # capacity 3
    reqs = [Request(rid=i, prompt=prompt, max_new_tokens=10)
            for i in range(2)]
    for r in reqs:
        eng.submit(r)
    _drain(eng)
    # both fit at admission (1 prompt page each) but the pool cannot hold
    # both requests' full 3-page footprints -> one slot preempted
    assert eng.stats["preemptions"] >= 1
    assert [r.out for r in reqs] == [want, want]
    assert eng.alloc.pages_in_use == 0
    eng.alloc.check_invariants()


def test_exhaustion_defragments_once_and_counts_pressure(setup):
    """Satellite: CacheExhausted at admission triggers one production
    defragment() pass and both events land in engine stats (the fleet
    pumps them into MetricsBus for the autoscaler)."""
    run, model, params = setup
    eng = ServeEngine(run, params, slots=2, max_len=48, paged=True,
                      page_size=8, num_pages=4)     # capacity 3
    first = Request(rid=0, prompt=np.arange(8) % 100, max_new_tokens=10)
    second = Request(rid=1, prompt=np.arange(16) % 100, max_new_tokens=4)
    eng.submit(first)
    eng.step()                             # rid 0 resident, 1 page
    eng.submit(second)                     # needs 2 prompt pages; only
    _drain(eng)                            # fits once rid 0 progresses
    assert first.done and second.done
    assert eng.stats["cache_exhausted"] >= 1
    assert eng.stats["defrag_events"] >= 1
    assert eng.stats["cache_exhausted"] >= eng.stats["defrag_events"]


def test_fleet_exposes_cache_pressure_to_autoscaler(setup):
    """The telemetry path end-to-end: engine stats -> MetricsBus ->
    EngineStats fields the autoscaler policy reads."""
    from repro.serve.fleet import ServeFleet
    run, _, params = setup
    fleet = ServeFleet(run, params, num_engines=1, devices=token_devices(2),
                       slots=2, max_len=48, paged=True, page_size=16,
                       share_prefix=True,
                       workdir=tempfile.mkdtemp())
    prompt = np.arange(16) % 100
    for i in range(2):
        fleet.submit(Request(rid=i, prompt=prompt, max_new_tokens=3))
    fleet.drain()
    snap = fleet.telemetry_snapshot()
    st = snap.engines[0]
    assert st.pages_free > 0 and st.pages_in_use == 0
    assert st.cache_exhausted == 0 and st.defrag_events == 0
    eng = fleet.tenants["serve0"].engine
    assert eng.stats["shared_page_hits"] >= 1
    assert "cache_exhausted" in fleet.telemetry.describe().get(
        "serve0", {"cache_exhausted": 0})


# ===========================================================================
# I12: refcount accounting == live block-table references
# ===========================================================================
class _VF:
    mesh_shape = (1, 1)
    mesh_axes = ("data", "model")
    devices = ("d0",)
    vf_id = "vf1"
    emulated: dict = {}


def _serve_system(tmp_path):
    from repro.sim.tenant import SimServeTenant
    pool = DevicePool(devices=tuple(f"d{i}" for i in range(4)))
    mgr = SVFFManager(pool, workdir=str(tmp_path),
                      staging=StagingEngine(num_queues=1),
                      scheduler="first_fit")
    tn = SimServeTenant("sv0", seed=2)
    mgr.init(num_vfs=2, tenants=[tn], devices_per_vf=2)
    tn.submit_burst(6)
    tn.run_steps(2)                        # pages held, sharing live
    assert tn.alloc.pages_in_use > 0
    return mgr, tn


def test_i12_catches_seeded_over_decref(tmp_path):
    """The acceptance bug: one decref too many on a shared page frees a
    page a sibling still reads through. I12 must catch it."""
    mgr, tn = _serve_system(tmp_path)
    check_invariants(mgr)                  # sane baseline
    page = tn.alloc.pages_of(
        next(r for r in tn.active if r is not None).rid)[0]
    tn.alloc._decref(page)                 # seeded over-decref
    with pytest.raises(InvariantViolation, match="I12"):
        check_invariants(mgr)


def test_i12_catches_table_chain_divergence(tmp_path):
    """A CoW that repoints the allocator chain but not the block-table
    row (or vice versa) must fail I12's table==chain cross-check."""
    mgr, tn = _serve_system(tmp_path)
    check_invariants(mgr)
    slot = next(s for s, r in enumerate(tn.active) if r is not None)
    tn.tables[slot, 0] = (tn.tables[slot, 0] % (tn.num_pages - 1)) + 1
    with pytest.raises(InvariantViolation, match="I12"):
        check_invariants(mgr)


def test_sim_i10_regression_seed_with_sharing():
    """Checked-in regression seed: serve traffic with prefix sharing ON
    (the sim tenant always shares) stays token-deterministic and replay-
    stable, and the run actually exercised sharing."""
    from repro.sim import ScenarioConfig, ScenarioRunner
    for policy in ("first_fit", "best_fit"):
        cfg = ScenarioConfig(seed=3, policy=policy, serve_rate=0.35,
                             num_ops=30)
        r1, r2 = ScenarioRunner(cfg), ScenarioRunner(cfg)
        assert r1.run().fingerprint() == r2.run().fingerprint()
        shared = sum(getattr(tn, "shared_hits", 0)
                     for tn in r1.tenants.values())
        assert shared > 0, "scenario never hit the prefix trie"
