"""SVFF core behaviour tests: VF state machine, pool invariants, pause
transparency (the paper's §IV-B1 semantics), manager reconf, QMP, records,
fault recovery. Multi-device tests run in a subprocess with a forced
8-device CPU pool (XLA locks the device count at first init)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

import jax

from repro.configs import make_run_config
from repro.core import (DevicePool, PoolError, VFState, VFTransitionError,
                        VirtualFunction)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# ---------------------------------------------------------------------------
# single-device unit tests
# ---------------------------------------------------------------------------
def test_vf_state_machine():
    vf = VirtualFunction(vf_id="0000:03:00.1")
    vf.assign_devices(jax.devices()[:1], (1, 1))
    with pytest.raises(VFTransitionError):
        vf.transition(VFState.PAUSED)          # detached -> paused illegal
    vf.transition(VFState.ATTACHED)
    vf.transition(VFState.PAUSED)
    with pytest.raises(VFTransitionError):
        vf.transition(VFState.DETACHED)        # paused -> detached illegal
    vf.transition(VFState.ATTACHED)
    vf.transition(VFState.DETACHED)


def test_pool_set_num_vfs_blocks_attached():
    """The SR-IOV limitation (paper §IV-B1): #VF can't change while VFs
    are attached — but paused VFs don't block it."""
    pool = DevicePool(devices=jax.devices())
    pool.set_num_vfs(1, devices_per_vf=1)
    vf = list(pool.vfs.values())[0]
    vf.owner = "vm0"
    vf.transition(VFState.ATTACHED)
    with pytest.raises(PoolError):
        pool.set_num_vfs(0)
    vf.transition(VFState.PAUSED)
    vf.release_devices()
    pool.set_num_vfs(1, devices_per_vf=1)      # paused VF survives
    assert vf.vf_id in pool.vfs


def test_pool_isolation_invariant():
    pool = DevicePool(devices=jax.devices())
    pool.set_num_vfs(1, devices_per_vf=1)
    rogue = VirtualFunction(vf_id="0000:03:00.9")
    rogue.assign_devices(jax.devices()[:1], (1, 1))
    pool.vfs[rogue.vf_id] = rogue
    with pytest.raises(PoolError):
        pool._check_invariants()               # same device, two VFs


def test_max_vfs_limit():
    pool = DevicePool(devices=jax.devices(), max_vfs=4)
    with pytest.raises(PoolError):
        pool.set_num_vfs(5)


def test_tenant_step_times_keep_the_last_64():
    """The straggler monitor reads the last step time; the tenant keeps a
    bounded window of them, however long it runs."""
    from repro.core import Tenant
    tn = Tenant("vm0", make_run_config("svff-bench", "train_4k", smoke=True),
                local_batch=2, seq_len=16)
    for i in range(100):
        tn.step_times.append(float(i))
    assert len(tn.step_times) == 64
    assert tn.step_times[-1] == 99.0 and tn.step_times[0] == 36.0


# ---------------------------------------------------------------------------
# multi-device behaviour (subprocess with 8 CPU devices)
# ---------------------------------------------------------------------------
def run_in_pool_subprocess(body: str) -> dict:
    """Run `body` with an 8-device pool; it must print a JSON result."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count=8"
        import json
        import jax
        import numpy as np
        from repro.configs import make_run_config
        from repro.core import (ControlPlane, DevicePausedError, DevicePool,
                                SVFFManager, StagingEngine, Supervisor,
                                Tenant, VFState)
        import tempfile
        WORKDIR = tempfile.mkdtemp(prefix='svff_test_')
        run = make_run_config('svff-bench', 'train_4k', smoke=True)
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_pause_transparency_and_state_preservation(tmp_path):
    """The paper's central claim: pausing detaches from the host but not
    the guest; after unpause the tenant continues with bit-identical state
    and no re-'realize' (executable cache hit)."""
    res = run_in_pool_subprocess("""
        pool = DevicePool()
        mgr = SVFFManager(pool, workdir=WORKDIR)
        tn = Tenant('vm0', run, local_batch=2, seq_len=16)
        mgr.init(num_vfs=2, tenants=[tn], devices_per_vf=4)
        tn.run_steps(2)
        before = jax.tree.leaves(tn.export_state()['params'])[1]
        before = np.asarray(before).copy()
        nexec = len(tn._exec_cache)

        mgr.pause(tn)
        visible = tn.query()                    # guest still sees device
        blocked = False
        try:
            tn.run_steps(1)
        except DevicePausedError:
            blocked = True
        vf = pool.find(tn.vf_id)
        mgr.unpause(tn)
        after = np.asarray(jax.tree.leaves(tn.export_state()['params'])[1])
        tn.run_steps(1)
        print(json.dumps({
            'visible_while_paused': visible['status'] == 'paused',
            'vf_kept_identity': visible['vf'] is not None,
            'io_blocked': blocked,
            'devices_released': True,
            'state_identical': bool((before == after).all()),
            'exec_cache_hit': len(tn._exec_cache) == nexec,
            'steps_after': tn.steps_done,
        }))
    """)
    assert res == {"visible_while_paused": True, "vf_kept_identity": True,
                   "io_blocked": True, "devices_released": True,
                   "state_identical": True, "exec_cache_hit": True,
                   "steps_after": 3}


@pytest.mark.slow
def test_reconf_grows_pool_without_disturbing_live_tenants():
    """Paper's headline scenario: attach additional VFs to new VMs without
    affecting devices already attached to other VMs."""
    res = run_in_pool_subprocess("""
        pool = DevicePool()
        mgr = SVFFManager(pool, workdir=WORKDIR)
        a = Tenant('vmA', run, local_batch=2, seq_len=16, seed=1)
        mgr.init(num_vfs=1, tenants=[a], devices_per_vf=8)
        a.run_steps(2)
        sA = np.asarray(jax.tree.leaves(a.export_state()['params'])[1]).copy()
        # grow to 2 VFs (each 4 devices) and attach a new tenant
        b = Tenant('vmB', run, local_batch=2, seq_len=16, seed=2)
        mgr.tenants['vmB'] = b
        t = mgr.reconf(num_vfs=2, new_tenants=[b], devices_per_vf=4)
        a.run_steps(1); b.run_steps(1)
        sA2 = np.asarray(jax.tree.leaves(a.export_state()['params'])[1])
        print(json.dumps({
            'timings_keys': sorted(t.keys()),
            'a_steps': a.steps_done, 'b_steps': b.steps_done,
            'a_continued': bool(sA2.shape == sA.shape),
            'a_mesh': list(pool.find(a.vf_id).mesh_shape),
        }))
    """)
    assert res["timings_keys"] == ["add_vf", "change_num_vf", "remove_vf",
                                   "rescan", "total"]
    assert res["a_steps"] == 3 and res["b_steps"] == 1
    assert res["a_continued"]


@pytest.mark.slow
def test_elastic_reshard_on_unpause():
    """Unpause onto a different slice size: state is resharded, training
    continues — elastic scaling through the pause mechanism."""
    res = run_in_pool_subprocess("""
        pool = DevicePool()
        mgr = SVFFManager(pool, workdir=WORKDIR)
        tn = Tenant('vm0', run, local_batch=2, seq_len=16)
        mgr.init(num_vfs=2, tenants=[tn], devices_per_vf=2)
        tn.run_steps(1)
        mgr.pause(tn)
        vf = pool.find(tn.vf_id)
        pool.set_num_vfs(1, devices_per_vf=8)   # repartition under pause
        mgr.unpause(tn, num_devices=8)
        tn.run_steps(1)
        print(json.dumps({
            'new_mesh': list(pool.find(tn.vf_id).mesh_shape),
            'steps': tn.steps_done,
        }))
    """)
    assert res["steps"] == 2
    import math
    assert math.prod(res["new_mesh"]) == 8


@pytest.mark.slow
def test_detach_attach_roundtrip_via_disk():
    res = run_in_pool_subprocess("""
        pool = DevicePool()
        mgr = SVFFManager(pool, workdir=WORKDIR)
        tn = Tenant('vm0', run, local_batch=2, seq_len=16)
        mgr.init(num_vfs=2, tenants=[tn], devices_per_vf=4)
        tn.run_steps(2)
        w = np.asarray(jax.tree.leaves(tn.export_state()['params'])[1]).copy()
        mgr.detach(tn)
        detached = tn.status == 'detached' and tn.vf_id is None
        mgr.attach(tn)
        w2 = np.asarray(jax.tree.leaves(tn.export_state()['params'])[1])
        tn.run_steps(1)
        print(json.dumps({
            'detached': detached,
            'state_identical': bool((w == w2).all()),
            'steps': tn.steps_done,
        }))
    """)
    assert res == {"detached": True, "state_identical": True, "steps": 3}


@pytest.mark.slow
def test_qmp_socket_and_fault_recovery():
    res = run_in_pool_subprocess("""
        import socket
        pool = DevicePool()
        mgr = SVFFManager(pool, workdir=WORKDIR)
        t0 = Tenant('vm0', run, local_batch=2, seq_len=16)
        mgr.init(num_vfs=2, tenants=[t0], devices_per_vf=4)
        cp = ControlPlane(mgr)
        cp.serve_unix(WORKDIR + '/qmp.sock')
        s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        s.connect(WORKDIR + '/qmp.sock')
        f = s.makefile('rw')
        greeting = json.loads(f.readline())
        f.write(json.dumps({'execute': 'query-vfs'}) + '\\n'); f.flush()
        vfs = json.loads(f.readline())
        f.write(json.dumps({'execute': 'device_pause',
                            'arguments': {'id': 'vm0'}}) + '\\n'); f.flush()
        pz = json.loads(f.readline())
        f.write(json.dumps({'execute': 'device_pause',
                            'arguments': {'id': 'vm0', 'pause': False}})
                + '\\n'); f.flush()
        upz = json.loads(f.readline())
        cp.shutdown()
        # fault injection -> supervisor migrates
        sup = Supervisor(mgr)
        t0.inject_failure()
        sup.run_round(1)
        t0.run_steps(1)
        print(json.dumps({
            'greeting': 'QMP' in greeting,
            'nvfs': vfs['return']['num_vfs'],
            'pause_ok': 'return' in pz, 'unpause_ok': 'return' in upz,
            'events': [e['kind'] for e in sup.events],
            'recovered_steps': t0.steps_done,
        }))
    """)
    assert res["greeting"] and res["nvfs"] == 2
    assert res["pause_ok"] and res["unpause_ok"]
    assert res["events"] == ["failure", "migrated"]
    assert res["recovered_steps"] >= 1
