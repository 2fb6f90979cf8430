"""Elastic SLO control plane: policy-loop unit behaviour (hysteresis,
cooldown, justification), fleet-level scale-out/in/rebalance through the
journaled manager ops, and the sim's autoscale op with invariant I11."""
import tempfile

import numpy as np
import pytest

from repro.core.pool import token_devices
from repro.core.autoscaler import (Autoscaler, AutoscaleAction,
                                   AutoscaleConfig, EngineStats,
                                   TelemetrySnapshot, justify_action)
from repro.sim import (InvariantViolation, ScenarioConfig, ScenarioRunner,
                       check_autoscale, generate_scenario)


@pytest.fixture(scope="module")
def setup():
    import jax
    from repro.configs import make_run_config
    from repro.models.model import build_model
    run = make_run_config("qwen3-0.6b", "decode_32k", smoke=True)
    model = build_model(run)
    params = model.init(jax.random.key(0))
    return run, model, params


def snap(loads, *, epoch=0, slo=8, free_vfs=1, grow=0, queued=None,
         jobs=None, widths=None, wmax=None, bubbles=None):
    """Synthetic telemetry: engine i running at loads[i]; widths/wmax/
    bubbles optionally give each engine a pipeline-gang shape."""
    queued = queued if queued is not None else loads
    jobs = jobs or [0] * len(loads)
    widths = widths or [1] * len(loads)
    wmax = wmax or widths
    bubbles = bubbles or [0.0] * len(loads)
    return TelemetrySnapshot(
        epoch=epoch, slo_max_load=slo,
        engines=tuple(
            EngineStats(tid=f"e{i}", index=i, status="running",
                        load=loads[i], queue_depth=queued[i],
                        prefill_jobs=jobs[i], stage_width=widths[i],
                        stage_width_max=wmax[i], bubble_frac=bubbles[i])
            for i in range(len(loads))),
        free_vfs=free_vfs, grow_budget=grow)


# ===========================================================================
# policy loop
# ===========================================================================
def test_scale_out_needs_hot_engine_and_capacity():
    a = Autoscaler(AutoscaleConfig(hysteresis=1, cooldown=0))
    assert a.observe(snap([2])) is None            # below threshold
    act = a.observe(snap([8]))
    assert act is not None and act.kind == "scale_out"
    assert justify_action(act, a.cfg) is None
    # no capacity -> no action even when hot
    b = Autoscaler(AutoscaleConfig(hysteresis=1, cooldown=0))
    assert b.observe(snap([8], free_vfs=0, grow=0)) is None


def test_hysteresis_requires_sustained_condition():
    a = Autoscaler(AutoscaleConfig(hysteresis=3, cooldown=0))
    assert a.observe(snap([8])) is None            # streak 1
    assert a.observe(snap([8])) is None            # streak 2
    assert a.observe(snap([0])) is None            # streak reset
    assert a.observe(snap([8])) is None
    assert a.observe(snap([8])) is None
    assert a.observe(snap([8])).kind == "scale_out"


def test_cooldown_suppresses_flapping_on_oscillating_load():
    """Load oscillating hot/idle every epoch must not produce an action
    per epoch: after each action the loop is silent for ``cooldown``
    epochs, and scale_in additionally needs an idle STREAK, which the
    oscillation keeps resetting."""
    cfg = AutoscaleConfig(hysteresis=1, cooldown=4, min_engines=1)
    a = Autoscaler(cfg)
    actions = []
    for epoch in range(32):
        hot = epoch % 2 == 0
        s = snap([9 if hot else 0, 1], epoch=epoch)
        act = a.observe(s)
        if act:
            actions.append((epoch, act.kind))
    # one action per (1 + cooldown) epochs at most
    assert len(actions) <= 32 // (1 + cfg.cooldown) + 1
    for (e1, _), (e2, _) in zip(actions, actions[1:]):
        assert e2 - e1 > cfg.cooldown
    # steady load produces NO actions at all once balanced
    b = Autoscaler(cfg)
    assert all(b.observe(snap([3, 3], epoch=i)) is None
               for i in range(10))


def test_scale_in_only_when_idle_and_above_floor():
    cfg = AutoscaleConfig(hysteresis=2, cooldown=0, min_engines=1)
    a = Autoscaler(cfg)
    assert a.observe(snap([0, 0])) is None         # idle streak 1
    act = a.observe(snap([0, 0]))                  # idle streak 2
    assert act is not None and act.kind == "scale_in"
    assert act.victim == "e1"                      # newest idle engine
    assert justify_action(act, cfg) is None
    # at the floor: never
    b = Autoscaler(AutoscaleConfig(hysteresis=1, cooldown=0,
                                   min_engines=1))
    assert b.observe(snap([0])) is None
    assert b.observe(snap([0])) is None


def test_rebalance_preferred_over_scale_out_when_cold_engine_exists():
    cfg = AutoscaleConfig(hysteresis=1, cooldown=0, rebalance_gap=4)
    act = Autoscaler(cfg).observe(snap([9, 0]))
    assert act.kind == "rebalance"
    assert act.victim == "e0" and act.target == "e1"
    assert justify_action(act, cfg) is None


def test_justification_catches_unjustified_actions():
    """I11 has teeth: actions forged against a snapshot that does not
    support them are named violations."""
    cfg = AutoscaleConfig()
    cold = snap([0, 0])
    for bogus, needle in (
            (AutoscaleAction("scale_out", cold), "no engine at load"),
            (AutoscaleAction("scale_in", snap([5, 5]), victim="e1"),
             "busy engine"),
            (AutoscaleAction("rebalance", snap([3, 2]), victim="e0",
                             target="e1"), "without imbalance"),
            (AutoscaleAction("warp", cold), "unknown action")):
        err = justify_action(bogus, cfg)
        assert err is not None and needle in err
        with pytest.raises(InvariantViolation, match="I11"):
            check_autoscale(bogus, cfg)


# ===========================================================================
# the width dimension: grow/shrink reshape in the policy loop
# ===========================================================================
def test_grow_reshape_only_when_engines_maxed():
    """With engine-count headroom a hot fleet scales OUT; only once
    ``max_engines`` is hit does the planner widen the hottest gang —
    and then only if a free VF exists and the gang has template room."""
    cfg = AutoscaleConfig(hysteresis=1, cooldown=0, max_engines=1)
    act = Autoscaler(cfg).observe(
        snap([9], widths=[2], wmax=[4], free_vfs=1))
    assert act is not None and act.kind == "reshape"
    assert act.victim == "e0" and act.width == 3
    assert justify_action(act, cfg) is None
    # engine headroom -> scale_out wins over widening
    roomy = AutoscaleConfig(hysteresis=1, cooldown=0, max_engines=4)
    act = Autoscaler(roomy).observe(
        snap([9], widths=[2], wmax=[4], free_vfs=1))
    assert act is not None and act.kind == "scale_out"
    # no free VF -> nothing to widen with
    assert Autoscaler(cfg).observe(
        snap([9], widths=[2], wmax=[4], free_vfs=0)) is None
    # at the template ceiling -> no grow either
    assert Autoscaler(cfg).observe(
        snap([9], widths=[4], wmax=[4], free_vfs=1)) is None


def test_shrink_reshape_on_measured_bubble():
    """A gang whose measured schedule bubble crosses ``reshape_bubble``
    is narrowed before any engine is parked; a busy low-bubble gang is
    left alone."""
    cfg = AutoscaleConfig(hysteresis=1, cooldown=0, min_engines=1)
    act = Autoscaler(cfg).observe(
        snap([2, 3], widths=[3, 1], wmax=[4, 1], bubbles=[0.7, 0.0]))
    assert act is not None and act.kind == "reshape"
    assert act.victim == "e0" and act.width == 2
    assert justify_action(act, cfg) is None
    assert Autoscaler(cfg).observe(
        snap([2, 3], widths=[3, 1], wmax=[4, 1],
             bubbles=[0.2, 0.0])) is None


def test_justification_catches_unjustified_reshapes():
    """I11 covers the width dimension: reshape actions the snapshot does
    not support are named violations."""
    cfg = AutoscaleConfig()
    for bogus, needle in (
            (AutoscaleAction("reshape", snap([9]), victim="e9", width=2),
             "not running"),
            (AutoscaleAction("reshape", snap([9], widths=[2], wmax=[4]),
                             victim="e0", width=2), "to width 2 from 2"),
            (AutoscaleAction("reshape", snap([9], widths=[2], wmax=[2]),
                             victim="e0", width=3), "template ceiling"),
            (AutoscaleAction("reshape", snap([1], widths=[2], wmax=[4]),
                             victim="e0", width=3), "hot threshold"),
            (AutoscaleAction("reshape",
                             snap([9], widths=[2], wmax=[4], free_vfs=0),
                             victim="e0", width=3), "free VF"),
            (AutoscaleAction("reshape",
                             snap([5], widths=[2], wmax=[4],
                                  bubbles=[0.1]),
                             victim="e0", width=1), "busy")):
        err = justify_action(bogus, cfg)
        assert err is not None and needle in err
        with pytest.raises(InvariantViolation, match="I11"):
            check_autoscale(bogus, cfg)


# ===========================================================================
# real fleet: scale-out / scale-in / rebalance through the manager
# ===========================================================================
def test_fleet_vf_cap_follows_device_budget_and_scales_out(setup):
    """Regression: ``DevicePool(max_vfs=max(num_engines, 1))`` froze the
    VF count at the initial engine count, so ANY reconfiguration to more
    VFs was silently impossible. The cap must be the device budget, and
    scale-out past the initial fleet size must serve traffic on the new
    engine (grow path: the full reconf cycle carves one more VF)."""
    from repro.serve import Request, ServeFleet
    run, model, params = setup
    fleet = ServeFleet(run, params, num_engines=1, devices=token_devices(4),
                       slots=2,
                       max_len=48, workdir=tempfile.mkdtemp())
    assert fleet.pool.max_vfs == 4                  # device budget, not 1
    tid = fleet.scale_out()                         # past the initial size
    assert tid == "serve1"
    assert sum(1 for tn in fleet.tenants.values()
               if tn.status == "running") == 2
    assert len(fleet.pool.vfs) == 2
    reqs = [Request(rid=i, prompt=np.arange(4) % 50, max_new_tokens=2)
            for i in range(4)]
    placed = {fleet.submit(r) for r in reqs}
    assert placed == {"serve0", "serve1"}           # both engines serve
    res = fleet.drain()
    assert res.drained and all(r.done and not r.error for r in reqs)
    assert fleet.mgr.query()["journal_pending"] == 0


def test_fleet_precarved_vfs_make_scale_out_pause_free(setup):
    """With spare VFs pre-carved at init (num_vfs > num_engines), a
    scale-out is a plain attach: no engine is ever paused for it."""
    from repro.serve import ServeFleet
    run, model, params = setup
    fleet = ServeFleet(run, params, num_engines=1, devices=token_devices(4),
                       slots=2,
                       max_len=48, num_vfs=2, workdir=tempfile.mkdtemp())
    assert len(fleet.pool.vfs) == 2
    fleet.scale_out()
    ops = [e["op"] for e in fleet.mgr.journal.entries()]
    assert ops.count("attach") == 2 and "pause" not in ops


def test_fleet_scale_in_refuses_inflight_prefill_then_parks(setup):
    """Satellite edge case: scale-in must refuse while the victim holds
    in-flight chunked-prefill jobs (they would strand), and succeed once
    drained — parking the engine's state on disk with its VF detached."""
    from repro.core import ManagerError
    from repro.serve import Request, ServeFleet
    run, model, params = setup
    fleet = ServeFleet(run, params, num_engines=1, devices=token_devices(2),
                       slots=2,
                       max_len=48, prefill_chunk=3,
                       workdir=tempfile.mkdtemp())
    eng = fleet.tenants["serve0"].engine
    fleet.submit(Request(rid=0, prompt=(np.arange(8) * 5) % 100,
                         max_new_tokens=2))
    fleet.step()
    assert eng._jobs                                # prefill in flight
    with pytest.raises(ManagerError, match="busy"):
        fleet.scale_in("serve0")
    assert fleet.tenants["serve0"].status == "running"   # refusal atomic
    res = fleet.drain()
    assert res.drained
    fleet.scale_in("serve0")
    assert fleet.tenants["serve0"].status == "detached"
    vf = next(iter(fleet.pool.vfs.values()))
    assert vf.owner is None and vf.devices          # devices reusable


def test_fleet_rebalance_moves_queue_and_keeps_tokens(setup):
    """Rebalance steals queued requests hot -> cold and migrates the hot
    victim; outputs equal an undisturbed run (queued requests have
    emitted nothing, in-flight ones survive the migrate bit-exactly)."""
    from repro.serve import Request, ServeFleet
    run, model, params = setup

    def serve(rebalance):
        fleet = ServeFleet(run, params, num_engines=2, devices=token_devices(4),
                           slots=1, max_len=48,
                           workdir=tempfile.mkdtemp())
        reqs = [Request(rid=i, prompt=(np.arange(4) * (i + 2)) % 100,
                        max_new_tokens=3) for i in range(5)]
        # force the pile-up onto serve0 via direct engine submission
        for r in reqs:
            fleet.tenants["serve0"].engine.submit(r)
        fleet.step()
        if rebalance:
            moved = fleet.rebalance("serve0", "serve1")
            assert moved >= 1
            assert fleet.tenants["serve1"].engine.queue
        res = fleet.drain()
        assert res.drained and all(r.done and not r.error for r in reqs)
        assert fleet.mgr.query()["journal_pending"] == 0
        return [r.out for r in reqs]

    assert serve(False) == serve(True)


# ===========================================================================
# sim: the autoscale op + I11 after every action
# ===========================================================================
def test_generator_autoscale_rate_zero_is_byte_identical():
    base = ScenarioConfig(seed=7, serve_rate=0.35, num_ops=30)
    with_field = ScenarioConfig(seed=7, serve_rate=0.35, num_ops=30,
                                autoscale_rate=0.0)
    assert generate_scenario(base) == generate_scenario(with_field)


@pytest.mark.parametrize("arrival", ["ramp", "spike", "diurnal"])
def test_sim_autoscale_scenarios_hold_invariants(arrival):
    """Randomized serve + autoscale histories stay replay-stable with
    I1-I11 checked after every op, across arrival patterns."""
    took = []
    for seed in (1, 2, 4, 7):
        cfg = ScenarioConfig(seed=seed, serve_rate=0.45,
                             autoscale_rate=0.3, num_ops=40,
                             arrival=arrival)
        r = ScenarioRunner(cfg)
        res = r.run()
        assert res.fingerprint() == ScenarioRunner(cfg).run().fingerprint()
        took.extend(a.kind for a in r.autoscaler.history)
    assert "scale_out" in took       # the plane actually acts


def test_sim_i11_catches_seeded_unjustified_action(monkeypatch):
    """Seeded-bug demonstration: a planner that scales out on a COLD
    snapshot must be caught by I11 inside the harness, tagged with the
    reproducing seed/op#."""
    def bad_observe(self, s):
        return AutoscaleAction("scale_out", s, reason="seeded bug")
    monkeypatch.setattr(Autoscaler, "observe", bad_observe)
    cfg = ScenarioConfig(seed=1, serve_rate=0.45, autoscale_rate=0.3,
                         num_ops=40)
    with pytest.raises(InvariantViolation, match="I11"):
        ScenarioRunner(cfg).run()


def test_sim_crash_mid_scale_out_recovers_consistent(tmp_path):
    """PR-3 crashpoint fired mid-scale-out (inside the journaled attach
    the autoscaler's action executes through): recovery must leave an
    I8-clean journal/pool and be idempotent (I9 is asserted inside
    recover_manager)."""
    from repro.core.fault import InjectedCrash, crash_plane
    from repro.sim import check_invariants, recover_manager

    from repro.sim.harness import REJECTIONS

    cfg = ScenarioConfig(seed=2, serve_rate=0.45, autoscale_rate=0.3,
                         num_ops=40, arrival="ramp")
    r = ScenarioRunner(cfg, workdir=str(tmp_path))
    r._wd = str(tmp_path)              # _apply is driven without run()
    ops = generate_scenario(cfg)
    # drive the scenario; every autoscale op runs with the attach-window
    # crash point armed, so the FIRST scale_out the policy takes dies
    # mid-attach (scale_in/rebalance don't traverse the window)
    crashed = False
    try:
        for op in ops:
            if op.kind == "autoscale":
                crash_plane.arm("mid_record_write")
                try:
                    r._apply(op)
                except InjectedCrash:
                    crashed = True
                    break
                finally:
                    crash_plane.disarm()
            else:
                try:
                    r._apply(op)
                except REJECTIONS:
                    pass               # chaos ops are meant to be rejected
    finally:
        crash_plane.disarm()
    assert crashed, "no scale_out materialized for this seed"
    # the manager died mid-attach; rebuild and verify I1-I9
    r.mgr = recover_manager(r.mgr, r.tenants, policy=cfg.policy,
                            workdir=str(tmp_path), num_queues=2)
    check_invariants(r.mgr)
    assert r.mgr.query()["journal_pending"] == 0
