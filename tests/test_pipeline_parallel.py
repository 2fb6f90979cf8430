"""Pipeline-parallel wrapper: GPipe schedule == sequential composition —
plus the elastic pipeline-serving gang: stage templates, the K-VF
PipelineServeEngine vs the single-stage oracle, live reshape / VF-loss
fallback bit-identity (I10+I14), atomic gang admission, and the
gang-aware scale-out budget."""
import dataclasses
import json
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

from repro.core.pool import token_devices
from repro.runtime.pipeline import (bubble_fraction, schedule_stats,
                                    serve_schedule)
from repro.serve.stages import (build_templates, check_partition,
                                pipeline_supported)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_bubble_fraction():
    assert bubble_fraction(1, 4) == pytest.approx(3 / 4)
    assert bubble_fraction(16, 4) == pytest.approx(3 / 19)
    assert bubble_fraction(8, 1) == 0.0


@pytest.mark.slow
def test_pipeline_matches_sequential():
    prog = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, jax.numpy as jnp
import numpy as np
from repro.runtime.pipeline import pipeline_apply

mesh = jax.make_mesh((4,), ("pipe",))
S, M, mb, D = 4, 8, 2, 16
k = jax.random.key(0)
W = jax.random.normal(k, (S, D, D)) * 0.3
b = jax.random.normal(jax.random.key(1), (S, D)) * 0.1
x = jax.random.normal(jax.random.key(2), (M, mb, D))

def stage(params, h):
    w, bb = params
    return jnp.tanh(h @ w + bb)

want = x
for s in range(S):
    want = stage((W[s], b[s]), want.reshape(M * mb, D)).reshape(M, mb, D)

got = jax.jit(lambda p, xx: pipeline_apply(stage, p, xx, mesh))((W, b), x)
err = float(jnp.max(jnp.abs(got - want)))
print(json.dumps({"err": err}))
"""
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["err"] < 1e-5, res


# ===========================================================================
# stage templates (I14's vocabulary)
# ===========================================================================
def test_build_templates_every_width_partitions():
    tpls = build_templates(12, 4)
    assert sorted(tpls) == [1, 2, 3, 4]
    for k, t in tpls.items():
        check_partition(t.bounds, 12)          # raises on a bad partition
        widths = [hi - lo for lo, hi in zip(t.bounds, t.bounds[1:])]
        assert len(widths) == k and sum(widths) == 12
        assert max(widths) - min(widths) <= 1   # balanced
    # width is capped at the period count — never an empty stage
    assert sorted(build_templates(2, 5)) == [1, 2]


def test_check_partition_rejects_bad_bounds():
    with pytest.raises(ValueError):
        check_partition((0, 2, 2, 4), 4)        # empty stage
    with pytest.raises(ValueError):
        check_partition((1, 4), 4)              # does not start at 0
    with pytest.raises(ValueError):
        check_partition((0, 3), 4)              # does not cover the stack


def test_serve_schedule_order_and_stats():
    items = list(serve_schedule(3, 2))
    # every (s, m) exactly once, dependencies (s-1,m) and (s,m-1) first
    assert sorted(items) == [(s, m) for s in range(2) for m in range(3)]
    seen = set()
    for s, m in items:
        assert s == 0 or (s - 1, m) in seen
        assert m == 0 or (s, m - 1) in seen
        seen.add((s, m))
    # uniform walls reduce to the analytic bubble fraction
    st = schedule_stats([[1.0] * 4 for _ in range(2)])
    assert st.makespan == pytest.approx(5.0)
    assert st.bubble == pytest.approx(bubble_fraction(4, 2))
    assert st.stage_busy == (4.0, 4.0)


# ===========================================================================
# the K-VF engine vs the single-stage oracle (bit-identity, I10)
# ===========================================================================
@pytest.fixture(scope="module")
def dsetup():
    """A deepseek-67b-class config (untied embeddings, all-attn pattern)
    shrunk to smoke size but DEEPENED to 4 layers so K=4 templates exist.
    scan_layers=False matches what the pipeline engine forces, so oracle
    and gang run the byte-identical unrolled XLA program."""
    import jax
    from repro.configs import make_run_config
    from repro.models.model import build_model
    run = make_run_config("deepseek-67b", "decode_32k", smoke=True)
    run = dataclasses.replace(
        run,
        model=dataclasses.replace(run.model, num_layers=4),
        sharding=dataclasses.replace(run.sharding, scan_layers=False))
    ok, why = pipeline_supported(run.model)
    assert ok, why
    model = build_model(run)
    params = model.init(jax.random.key(0))
    return run, params


def _drive(eng, reqs, hook=None):
    for r in reqs:
        eng.submit(r)
    steps = 0
    while (eng.step() or eng.queue) and steps < 200:
        steps += 1
        if hook is not None:
            hook(steps)
    assert all(r.done for r in reqs), [r.rid for r in reqs if not r.done]
    return [list(r.out) for r in reqs]


def _mkreqs(n=3, max_new=6):
    from repro.serve.engine import Request
    prompts = [np.arange(4) % 97, (np.arange(7) * 3) % 97,
               (np.arange(5) * 5 + 2) % 97, (np.arange(6) * 7 + 1) % 97]
    return [Request(rid=i, prompt=np.asarray(prompts[i % 4], np.int32),
                    max_new_tokens=max_new) for i in range(n)]


@pytest.mark.slow
def test_pipeline_k4_serves_deepseek_class_bit_identical(dsetup):
    from repro.serve.engine import ServeEngine
    from repro.serve.pipeline_engine import PipelineServeEngine
    run, params = dsetup
    oracle = ServeEngine(run, params, slots=3, max_len=64, paged=True)
    want = _drive(oracle, _mkreqs())
    gang = PipelineServeEngine(run, params, stages=4, microbatches=2,
                               slots=3, max_len=64)
    assert gang.stage_width == 4 and gang.max_stage_width == 4
    got = _drive(gang, _mkreqs())
    assert got == want
    # measured telemetry accumulated over the decode schedule
    loads = gang.stage_loads()
    assert len(loads) == 4 and all(0.0 <= x <= 1.0 for x in loads)
    assert 0.0 <= gang.measured_bubble < 1.0
    assert gang.sched_ticks > 0


@pytest.mark.slow
def test_live_reshape_k4_to_k3_bit_identical(dsetup):
    """A K=4 -> K=3 width change mid-decode leaves every token stream
    exactly equal to the single-stage oracle's (the acceptance bar for
    the reshape path: pure re-layout, no state rebuild)."""
    from repro.serve.engine import ServeEngine
    from repro.serve.pipeline_engine import PipelineServeEngine
    run, params = dsetup
    oracle = ServeEngine(run, params, slots=3, max_len=64, paged=True)
    want = _drive(oracle, _mkreqs(max_new=8))
    gang = PipelineServeEngine(run, params, stages=4, microbatches=2,
                               slots=3, max_len=64)

    def shrink_mid_flight(step):
        if step == 3:
            gang.apply_reshape(3)
        elif step == 6:
            gang.apply_reshape(2)
    got = _drive(gang, _mkreqs(max_new=8), hook=shrink_mid_flight)
    assert got == want
    assert gang.stage_width == 2 and gang.reshape_count == 2
    assert gang.stage_bounds() == gang.templates[2].bounds


# ===========================================================================
# gang management: atomic admission, crash windows, fleet fallback
# ===========================================================================
def test_gang_admission_error_is_atomic(tmp_path):
    """A gang that cannot be placed whole is refused TYPED and
    side-effect-free: no member attached, no VF claimed, no pending
    journal entry — then the same gang attaches fine once room exists."""
    from repro.core import GangPlacementError, SVFFManager
    from repro.core.pool import DevicePool
    from repro.core.staging import StagingEngine
    from repro.sim.invariants import check_invariants
    from repro.sim.tenant import SimPipelineTenant, SimTenant

    pool = DevicePool(devices=tuple(f"d{i}" for i in range(4)), max_vfs=2)
    mgr = SVFFManager(pool, workdir=str(tmp_path),
                      staging=StagingEngine(num_queues=2),
                      scheduler="first_fit")
    vm0 = SimTenant("vm0", seed=1)
    mgr.init(2, [vm0])                    # 1 free VF, gang needs 2
    lead = SimPipelineTenant("pg0", seed=2, width=2, max_width=2)
    with pytest.raises(GangPlacementError):
        mgr.attach_group(lead)
    assert lead.status == "created"
    assert all(sh.status == "created" for sh in lead.gang_shells)
    assert all(vf.owner in (None, "vm0") for vf in pool.vfs.values())
    assert not [e for e in mgr.journal.entries()
                if e["status"] == "pending"]
    check_invariants(mgr)
    mgr.detach(vm0)                       # room appears: attach succeeds
    mgr.attach_group(lead)
    assert lead.status == "running"
    assert sum(1 for sh in lead.gang_shells
               if sh.status == "running") == 1
    check_invariants(mgr)


@pytest.mark.chaos
def test_gang_crash_windows_recover():
    """The PR's crash windows: mid-gang-attach rolls the whole gang back
    (I8/I9-clean), before-commit rolls it forward; reshape crashes land
    on exactly the old or the new width, never between (I14)."""
    from repro.sim.chaos import run_crash_case
    for point in ("gang_mid_member", "gang_before_commit",
                  "reshape_mid_members", "reshape_before_commit"):
        for seed in (0, 1):
            assert run_crash_case(point, seed)["ok"]


@pytest.fixture(scope="module")
def qsetup():
    """The fleet-level gang config: qwen3-0.6b smoke (2 layers -> K up
    to 2), scan_layers=False to match the pipeline engine's program."""
    import jax
    from repro.configs import make_run_config
    from repro.models.model import build_model
    run = make_run_config("qwen3-0.6b", "decode_32k", smoke=True)
    run = dataclasses.replace(
        run, sharding=dataclasses.replace(run.sharding,
                                          scan_layers=False))
    model = build_model(run)
    params = model.init(jax.random.key(0))
    return run, params


@pytest.mark.slow
def test_fleet_vf_loss_fallback_and_stage_telemetry(qsetup):
    """A shell VF dies mid-serving: the fleet sheds exactly that stage
    (journaled reshape K=2 -> K=1) and every request still matches the
    single-stage oracle token-for-token. Per-stage telemetry surfaces
    through EngineStats and the MetricsBus on the way."""
    from repro.serve.engine import ServeEngine
    from repro.serve.fleet import ServeFleet
    run, params = qsetup
    oracle = ServeEngine(run, params, slots=2, max_len=48, paged=True)
    want = _drive(oracle, _mkreqs(n=3))
    with tempfile.TemporaryDirectory() as wd:
        fleet = ServeFleet(run, params, num_engines=1, devices=token_devices(4),
                           stages=2, slots=2, max_len=48, workdir=wd)
        tn = fleet.tenants["serve0"]
        assert tn.stage_width == 2
        reqs = _mkreqs(n=3)
        for r in reqs:
            fleet.submit(r)
        for _ in range(3):
            fleet.step()
        snap = fleet.telemetry_snapshot()
        e = next(s for s in snap.engines if s.tid == "serve0")
        assert e.stage_width == 2 and e.stage_width_max == 2
        assert len(e.stage_loads) == 2
        assert 0.0 <= e.bubble_frac <= 1.0
        desc = fleet.telemetry.describe()["serve0"]
        assert len(desc["stage_loads"]) == 2
        # the fallback: shed the dead shell's stage, keep serving at K=1
        shell = tn.gang_shells[0]
        assert shell.status == "running"
        info = fleet.handle_vf_loss("serve0", shell.vf_id)
        assert info["k_new"] == 1 and info["dropped"] == [shell.tid]
        assert tn.stage_width == 1 and shell.status == "detached"
        assert fleet.drain().drained
        assert [list(r.out) for r in reqs] == want
        assert not [ent for ent in fleet.mgr.journal.entries()
                    if ent["status"] == "pending"]


@pytest.mark.slow
def test_fleet_scale_out_gang_budget(qsetup):
    """Satellite bugfix: scale_out's VF-cap math counts the K VFs a
    whole gang needs. 3 devices with one K=2 gang live -> a second gang
    (4 VFs) is refused typed, nothing half-carved; with 4 devices the
    same scale-out reconfs to 4 VFs and gang-attaches whole."""
    from repro.core import ManagerError
    from repro.serve.fleet import ServeFleet
    run, params = qsetup
    with tempfile.TemporaryDirectory() as wd:
        fleet = ServeFleet(run, params, num_engines=1, devices=token_devices(3),
                           stages=2, slots=2, max_len=48, workdir=wd)
        with pytest.raises(ManagerError, match="device budget"):
            fleet.scale_out()
        assert len(fleet.pool.vfs) == 2         # partition untouched
        assert sorted(fleet.tenants) == ["serve0"]   # no leaked tenant
    with tempfile.TemporaryDirectory() as wd:
        fleet = ServeFleet(run, params, num_engines=1, devices=token_devices(4),
                           stages=2, slots=2, max_len=48, workdir=wd)
        tid = fleet.scale_out()
        tn = fleet.tenants[tid]
        assert tn.status == "running" and tn.stage_width == 2
        assert sum(1 for s in tn.gang_shells
                   if s.status == "running") == 1
