"""Request live migration: journaled KV block shipping between engines.

Covers the fleet-level surface of the migration tentpole on REAL
engines — mid-decode token identity, clean aborts that leave the Request
untouched (retry-safe), prefix-shared/CoW chains, scale-in that drains a
busy engine by migrating its work, engine-crash re-homing — plus the
sim-level scenario op and the I13 single-ownership invariant. The
crash-window matrix for the migration op lives in test_chaos.py (the
``CRASH_POINTS`` parametrization picks up the four migrate_* windows
automatically).
"""
import dataclasses
import tempfile

import jax
import numpy as np
import pytest

from repro.configs import make_run_config
from repro.core.autoscaler import (AutoscaleAction, AutoscaleConfig,
                                   EngineStats, TelemetrySnapshot,
                                   justify_action)
from repro.core import ManagerError, SVFFManager
from repro.core.pool import DevicePool, token_devices
from repro.core.staging import StagingEngine
from repro.models.model import build_model
from repro.serve.engine import Request, ServeEngine
from repro.serve.fleet import ServeFleet
from repro.serve.paged import CacheExhausted
from repro.sim.invariants import InvariantViolation, check_invariants
from repro.sim.tenant import SimServeTenant


@pytest.fixture(scope="module")
def setup():
    run = make_run_config("qwen3-0.6b", "decode_32k", smoke=True)
    model = build_model(run)
    params = model.init(jax.random.key(0))
    return run, model, params


def _fleet(run, params, **kw):
    kw.setdefault("num_engines", 2)
    kw.setdefault("devices", token_devices(4))
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 48)
    kw.setdefault("paged", True)
    kw.setdefault("page_size", 8)
    return ServeFleet(run, params, workdir=tempfile.mkdtemp(), **kw)


def _reference(run, params, specs, **engine_kw):
    """Token oracle: the same requests served by one undisturbed engine."""
    engine_kw.setdefault("slots", max(2, len(specs)))
    engine_kw.setdefault("max_len", 48)
    engine_kw.setdefault("paged", True)
    engine_kw.setdefault("page_size", 8)
    eng = ServeEngine(run, params, **engine_kw)
    reqs = [Request(rid=rid, prompt=np.array(p), max_new_tokens=n)
            for rid, p, n in specs]
    for r in reqs:
        eng.submit(r)
    res = eng.run_until_idle()
    assert res.drained
    return {r.rid: list(r.out) for r in reqs}


# ===========================================================================
# mid-decode migration: token identity + telemetry
# ===========================================================================
def test_mid_decode_migration_is_token_identical(setup):
    run, model, params = setup
    specs = [(0, (np.arange(6) * 5 + 2) % 100, 6),
             (1, (np.arange(9) * 3) % 100, 5)]
    want = _reference(run, params, specs)
    fleet = _fleet(run, params)
    reqs = [Request(rid=rid, prompt=np.array(p), max_new_tokens=n)
            for rid, p, n in specs]
    placed = [fleet.submit(r) for r in reqs]
    assert placed == ["serve0", "serve1"]
    for _ in range(2):
        fleet.step()
    victim = reqs[0]
    assert victim.out and not victim.done          # genuinely mid-decode
    res = fleet.migrate_request("serve0", "serve1", victim.rid)
    assert res is not None and res["rid"] == victim.rid
    assert res["blocks"] >= 1                      # KV pages really shipped
    assert fleet.tenants["serve1"].owns_request(victim.rid)
    assert not fleet.tenants["serve0"].owns_request(victim.rid)
    assert fleet.tenants["serve0"].engine._migrating == {}
    assert fleet.mgr.query()["journal_pending"] == 0
    done = fleet.drain()
    assert res is not None and sorted(r.rid for r in done) == [0, 1]
    for r in reqs:
        assert r.done and not r.error
        assert list(r.out) == want[r.rid], (r.rid, r.out, want[r.rid])
    # the hand-off is visible in fleet telemetry, attributed to the source
    desc = fleet.telemetry.describe()["serve0"]
    assert desc["migrations_attempted"] == 1
    assert desc["migrations_completed"] == 1
    assert desc["migrations_aborted"] == 0
    assert desc["migration_blocks"] == res["blocks"]
    snap = fleet.telemetry_snapshot()
    stats = {e.tid: e for e in snap.engines}
    assert stats["serve0"].migrations_completed == 1
    assert stats["serve0"].migration_blocks_shipped == res["blocks"]


def test_aborted_migration_is_side_effect_free_and_retryable(setup):
    """Satellite regression: a target-side CacheExhausted must leave the
    Request object untouched (no done/error flags, tokens intact, still
    decoding on the source) so the SAME migration can retry later and
    complete token-identically."""
    run, model, params = setup
    specs = [(0, (np.arange(8) * 7 + 1) % 100, 8)]
    want = _reference(run, params, specs)
    # 5 pages (page 0 reserved -> 4 usable) per engine: two 2-page
    # residents fill serve1's pool AND both its slots
    fleet = _fleet(run, params, num_pages=5)
    victim = Request(rid=0, prompt=np.array(specs[0][1]), max_new_tokens=8)
    fleet.tenants["serve0"].engine.submit(victim)
    blockers = [Request(rid=10 + i, prompt=(np.arange(12) * (i + 3)) % 100,
                        max_new_tokens=6) for i in range(2)]
    for b in blockers:
        fleet.tenants["serve1"].engine.submit(b)
    for _ in range(2):
        fleet.step()
    assert victim.out and not victim.done
    before = list(victim.out)
    with pytest.raises(CacheExhausted):
        fleet.mgr.migrate_request(fleet.tenants["serve0"],
                                  fleet.tenants["serve1"], victim.rid)
    # clean abort: journal rolled back, request untouched on the source
    assert victim.done is False and victim.error is None
    assert list(victim.out) == before
    assert fleet.tenants["serve0"].owns_request(victim.rid)
    assert not fleet.tenants["serve1"].owns_request(victim.rid)
    assert fleet.tenants["serve0"].engine._migrating == {}
    assert fleet.mgr.query()["journal_pending"] == 0
    # the wrapper's bounded retries also abort while the target is full
    assert fleet.migrate_request("serve0", "serve1", victim.rid) is None
    assert fleet.telemetry.migrations_aborted["serve0"] >= 1
    assert fleet.telemetry.migrations_completed["serve0"] == 0
    # free the target, retry the SAME request: completes, token-identical
    fleet.tenants["serve1"].engine.run_until_idle()
    assert not victim.done
    res = fleet.migrate_request("serve0", "serve1", victim.rid)
    assert res is not None
    assert fleet.tenants["serve1"].owns_request(victim.rid)
    fleet.drain()
    assert victim.done and not victim.error
    assert list(victim.out) == want[0]


# ===========================================================================
# prefix sharing / CoW across migration
# ===========================================================================
def test_migrating_prefix_shared_requests_reshare_on_target(setup):
    run, model, params = setup
    base = (np.arange(16) * 3 + 1) % 100           # two FULL shared pages
    pa = np.concatenate([base, (np.arange(4) * 7) % 100])
    pb = np.concatenate([base, (np.arange(4) * 11 + 5) % 100])
    specs = [(0, pa, 5), (1, pb, 5)]
    want = _reference(run, params, specs, share_prefix=True)
    fleet = _fleet(run, params, share_prefix=True)
    ra = Request(rid=0, prompt=pa, max_new_tokens=5)
    rb = Request(rid=1, prompt=pb, max_new_tokens=5)
    src = fleet.tenants["serve0"].engine
    dst = fleet.tenants["serve1"].engine
    src.submit(ra)
    src.submit(rb)
    for _ in range(2):
        fleet.step()
    assert ra.out and rb.out
    head = src.alloc.pages_of(ra.rid)[0]
    assert src.alloc.refcount(head) == 2           # really sharing
    # migrate rb away: the source's shared head pages drop to refcount 1
    assert fleet.migrate_request("serve0", "serve1", rb.rid) is not None
    assert src.alloc.refcount(head) == 1
    assert src.alloc.check_invariants() is None    # I12 on the source
    assert dst.alloc.check_invariants() is None    # I12 on the target
    # migrate ra too: its full prompt pages RE-SHARE against the prefix
    # rb registered on the target (the partial tail page ships copied)
    assert fleet.migrate_request("serve0", "serve1", ra.rid) is not None
    assert dst.alloc.shared_count(ra.rid) == 2
    assert dst.alloc.refcount(dst.alloc.pages_of(ra.rid)[0]) == 2
    assert src.alloc.check_invariants() is None
    assert dst.alloc.check_invariants() is None
    fleet.drain()
    for r in (ra, rb):
        assert r.done and not r.error
        assert list(r.out) == want[r.rid], (r.rid, r.out, want[r.rid])


# ===========================================================================
# scale_in under load drains by migration
# ===========================================================================
def test_scale_in_under_load_migrates_work_to_siblings(setup):
    run, model, params = setup
    rng = np.random.default_rng(17)
    specs = [(i, rng.integers(0, 100, int(rng.integers(4, 9))), 6)
             for i in range(4)]
    want = _reference(run, params, specs)
    fleet = _fleet(run, params, slots=4)
    reqs = [Request(rid=rid, prompt=np.array(p), max_new_tokens=n)
            for rid, p, n in specs]
    for r in reqs:
        fleet.submit(r)
    for _ in range(2):
        fleet.step()
    busy = fleet.tenants["serve1"]
    assert busy.load > 0                           # scale_in of a BUSY engine
    fleet.scale_in("serve1")
    assert busy.status == "detached"
    for r in reqs:
        assert fleet.tenants["serve0"].owns_request(r.rid)
    assert fleet.mgr.query()["journal_pending"] == 0
    done = fleet.drain()
    assert sorted(r.rid for r in done) == [0, 1, 2, 3]
    for r in reqs:
        assert r.done and not r.error
        assert list(r.out) == want[r.rid], (r.rid, r.out, want[r.rid])


def test_scale_in_refuses_typed_when_no_sibling_has_capacity(setup):
    run, model, params = setup
    fleet = _fleet(run, params, num_engines=1)
    req = Request(rid=0, prompt=np.arange(6) % 100, max_new_tokens=6)
    fleet.submit(req)
    fleet.step()
    with pytest.raises(ManagerError, match="no running sibling"):
        fleet.scale_in("serve0")
    # the refusal stranded nothing: the engine still serves the request
    assert fleet.tenants["serve0"].owns_request(req.rid)
    fleet.drain()
    assert req.done and not req.error


# ===========================================================================
# engine crash: live requests re-home onto siblings
# ===========================================================================
def test_engine_crash_rehomes_live_requests_zero_loss(setup):
    run, model, params = setup
    rng = np.random.default_rng(23)
    specs = [(i, rng.integers(0, 100, int(rng.integers(4, 8))), 5)
             for i in range(4)]
    want = _reference(run, params, specs)
    fleet = _fleet(run, params, slots=4)
    reqs = [Request(rid=rid, prompt=np.array(p), max_new_tokens=n)
            for rid, p, n in specs]
    for r in reqs:
        fleet.submit(r)
    for _ in range(2):
        fleet.step()
    crashed = [r for r in reqs
               if fleet.tenants["serve0"].owns_request(r.rid)]
    assert crashed                                 # the crash hits live work
    out = fleet.recover_engine("serve0")
    assert sorted(rid for rid, _ in out["rehomed"]) == \
        sorted(r.rid for r in crashed if not r.done)
    assert fleet.tenants["serve0"].load == 0
    assert fleet.tenants["serve0"].status == "running"
    done = fleet.drain()
    assert {r.rid for r in done} >= {r.rid for r in crashed}
    for r in reqs:
        assert r.done and not r.error
        # recompute is bit-identical: same prompt, same seeded sampler
        assert list(r.out) == want[r.rid], (r.rid, r.out, want[r.rid])


def test_engine_crash_recovery_refuses_without_capacity(setup):
    run, model, params = setup
    fleet = _fleet(run, params, num_engines=1)
    req = Request(rid=0, prompt=np.arange(5) % 100, max_new_tokens=6)
    fleet.submit(req)
    fleet.step()
    before = list(req.out)
    with pytest.raises(ManagerError, match="no sibling"):
        fleet.recover_engine("serve0")
    # refusal happened BEFORE any mutation: nothing was reset or cleared
    assert list(req.out) == before
    assert fleet.tenants["serve0"].owns_request(req.rid)


# ===========================================================================
# control plane: in-flight load justifies a rebalance
# ===========================================================================
def test_rebalance_justified_by_inflight_only_load():
    hot = EngineStats(tid="a", index=0, status="running", load=6,
                      queue_depth=0, inflight=6)
    cold = EngineStats(tid="b", index=1, status="running", load=0)
    snap = TelemetrySnapshot(epoch=1, slo_max_load=6, engines=(hot, cold))
    cfg = AutoscaleConfig(rebalance_gap=4)
    act = AutoscaleAction("rebalance", snap, victim="a", target="b")
    assert justify_action(act, cfg) is None
    # nothing queued AND nothing in flight still fails justification
    idle_hot = dataclasses.replace(hot, queue_depth=0, inflight=0)
    snap2 = TelemetrySnapshot(epoch=2, slo_max_load=6,
                              engines=(idle_hot, cold))
    act2 = AutoscaleAction("rebalance", snap2, victim="a", target="b")
    assert "nothing queued or in flight" in justify_action(act2, cfg)


# ===========================================================================
# sim plane: scenario op + I13
# ===========================================================================
def _sim_mgr(workdir, tenants):
    pool = DevicePool(devices=tuple(f"d{i}" for i in range(8)), max_vfs=4)
    mgr = SVFFManager(pool, workdir=str(workdir),
                      staging=StagingEngine(num_queues=2),
                      scheduler="first_fit")
    mgr.init(len(tenants), tenants, devices_per_vf=2)
    return mgr


def test_scenario_traffic_with_migrations_holds_invariants(tmp_path):
    from repro.sim.harness import ScenarioRunner
    from repro.sim.scenario import ScenarioConfig, generate_scenario

    # default streams are byte-identical with the knob at 0
    assert generate_scenario(ScenarioConfig(seed=3)) == \
        generate_scenario(ScenarioConfig(seed=3, migrate_rate=0.0))
    cfg = ScenarioConfig(seed=1, num_ops=40, serve_rate=0.5,
                         migrate_rate=0.25, autoscale_rate=0.1)
    ops = generate_scenario(cfg)
    assert any(o.kind == "migrate_request" for o in ops)
    runner = ScenarioRunner(cfg)
    runner.run()                    # invariants (incl. I13) run per-op
    migrated = sum(getattr(tn, "migrations_in", 0)
                   for tn in runner.tenants.values())
    assert migrated > 0             # migrations actually executed


def test_i13_catches_request_live_on_two_engines(tmp_path):
    sv0 = SimServeTenant("sv0", seed=5)
    sv1 = SimServeTenant("sv1", seed=6)
    mgr = _sim_mgr(tmp_path, [sv0, sv1])
    sv0.submit_burst(3)
    for _ in range(6):
        sv0.run_steps(1)
        if sv0.peek_migratable() is not None:
            break
    assert sv0.peek_migratable() is not None
    check_invariants(mgr)                          # healthy before
    # corrupt: admit on the target WITHOUT releasing the source
    payload = sv0.extract_request()
    sv1.admit_migrated(payload, payload["state"])
    with pytest.raises(InvariantViolation, match="I13"):
        check_invariants(mgr)
    # roll the target admission back: healthy again (abort really is
    # side-effect-free on shared ownership state)
    sv1.abort_incoming(payload["rid"])
    sv0.abort_migration(payload["rid"])
    check_invariants(mgr)
