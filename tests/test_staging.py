"""Pipelined descriptor engine: per-tenant memo lifecycle, dirty
tracking (identity + digest), live-pause stall accounting, and the
multi-device restore paths (NamedSharding + quantized leaves) that the
pause/unpause cycle exercises on a real mesh."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DevicePool, StagingEngine, SVFFManager, pause_vf,
                        pause_vf_live, unpause_vf)
from repro.core.vf import VFState, VirtualFunction
from repro.sim import (ServeSimTenant, SimTenant, check_invariants,
                       check_pause_timings)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# ---------------------------------------------------------------------------
# memo lifecycle (satellite: bound StagingEngine._memo)
# ---------------------------------------------------------------------------
def _tree(seed=0, n=2048):
    rng = np.random.default_rng(seed)
    return {"w": jnp.asarray(rng.standard_normal((n,)), jnp.float32),
            "b": jnp.asarray(rng.standard_normal((4, 8)), jnp.float32)}


def test_memo_scoped_per_tenant_and_cleared():
    eng = StagingEngine(num_queues=2, incremental=True)
    ta, tb = _tree(1), _tree(2)
    eng.save(ta, tenant="vmA")
    eng.save(tb, tenant="vmB")
    assert eng.memo_size("vmA") == 2 and eng.memo_size("vmB") == 2
    assert eng.memo_size() == 4
    eng.save(ta, tenant="vmA")
    assert eng.last_stats.bytes_moved == 0        # hit within scope
    eng.save(tb, tenant="vmA")                    # other tenant's tree: miss
    assert eng.last_stats.bytes_moved > 0
    eng.clear("vmA")
    assert eng.memo_size("vmA") == 0 and eng.memo_size("vmB") == 2
    eng.save(ta, tenant="vmA")
    assert eng.last_stats.bytes_moved > 0         # memo really gone
    eng.clear()
    assert eng.memo_size() == 0


def test_manager_detach_clears_tenant_memo(tmp_path):
    pool = DevicePool(devices=tuple(f"d{i}" for i in range(4)))
    staging = StagingEngine(num_queues=1, incremental=True)
    mgr = SVFFManager(pool, workdir=str(tmp_path), staging=staging)
    tn = SimTenant("vm0", seed=0)
    mgr.init(num_vfs=2, tenants=[tn], devices_per_vf=1)
    tn.run_steps(1)
    staging.save(tn.export_state(), tenant=tn.tid)
    # SimTenant state is numpy (identity mode memoizes only jax arrays),
    # so plant a sentinel to prove detach really empties the scope
    staging._memo_for(tn.tid)["sentinel"] = object()
    assert staging.memo_size(tn.tid) == 1
    mgr.detach(tn)
    assert staging.memo_size(tn.tid) == 0            # emptied on detach
    check_invariants(mgr)


def test_pause_clears_tenant_memo(tmp_path):
    pool = DevicePool(devices=tuple(f"d{i}" for i in range(4)))
    staging = StagingEngine(num_queues=1, incremental=True)
    mgr = SVFFManager(pool, workdir=str(tmp_path), staging=staging)
    tn = SimTenant("vm0", seed=0)
    mgr.init(num_vfs=2, tenants=[tn], devices_per_vf=1)
    mgr.pause(tn)
    assert staging.memo_size(tn.tid) == 0
    mgr.unpause(tn)
    check_invariants(mgr)


# ---------------------------------------------------------------------------
# dirty tracking
# ---------------------------------------------------------------------------
def test_digest_dirty_tracking_skips_equal_content():
    eng = StagingEngine(num_queues=2, incremental=True, dirty="digest")
    tree = _tree(3)
    eng.save(tree, tenant="t")
    clone = {k: v * 1.0 for k, v in tree.items()}    # new objects, = bytes
    eng.save(clone, tenant="t")
    assert eng.last_stats.bytes_moved == 0
    assert eng.last_stats.skipped_bytes > 0
    changed = dict(clone)
    changed["w"] = clone["w"] + 1.0
    eng.save(changed, tenant="t")
    assert eng.last_stats.bytes_moved == changed["w"].nbytes


def test_identity_dirty_tracking_requires_same_object():
    eng = StagingEngine(num_queues=2, incremental=True)
    tree = _tree(4)
    eng.save(tree, tenant="t")
    clone = {k: v * 1.0 for k, v in tree.items()}
    eng.save(clone, tenant="t")
    assert eng.last_stats.bytes_moved > 0            # identity can't prove


# ---------------------------------------------------------------------------
# stage 3: raw-byte placement of stream chunks into one buffer per leaf
# ---------------------------------------------------------------------------
def _leaf(dtype, shape, seed=0):
    """A device leaf of ``dtype`` whose bytes are random (0/1 for bool)."""
    dt = np.dtype(dtype)
    rng = np.random.default_rng(seed)
    if dt == np.bool_:
        return jnp.asarray(rng.integers(0, 2, shape).astype(np.bool_))
    raw = rng.integers(0, 256, shape + (dt.itemsize,), dtype=np.uint8)
    return jnp.asarray(raw.view(dt).reshape(shape))


class _Fetches:
    """Wraps ``jax.device_get`` to keep every buffer it hands back."""

    def __init__(self, monkeypatch):
        self.got = []
        real = jax.device_get

        def device_get(x):
            out = real(x)
            self.got.extend(np.asarray(h) for h in jax.tree.leaves(out))
            return out
        monkeypatch.setattr(jax, "device_get", device_get)


def _u8(a):
    return np.ascontiguousarray(np.asarray(a)).reshape(-1).view(np.uint8)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32", "int8", "bool",
                                   "float8_e4m3fn"])
@pytest.mark.parametrize("shape,parts", [((13, 5, 24), 7),
                                         ((13, 5, 24), 1),
                                         ((1, 96), 4)],
                         ids=["uneven_rows", "single_chunk", "one_row"])
def test_stream_save_assembles_bit_identical_leaves(monkeypatch, dtype,
                                                    shape, parts):
    x = _leaf(dtype, shape)
    eng = StagingEngine(num_queues=3, transport="stream",
                        chunk_bytes=x.nbytes // parts + 1)
    fetches = _Fetches(monkeypatch)
    snap = eng.save({"x": x})["x"]
    want = np.asarray(x)
    assert snap.dtype == want.dtype and snap.shape == want.shape
    assert snap.flags["C_CONTIGUOUS"]
    np.testing.assert_array_equal(_u8(snap), _u8(want))
    chunked = len(fetches.got) > 1
    assert chunked == (parts > 1 and shape[0] > 1)
    assert eng.last_stats.assembled_bytes == (x.nbytes if chunked else 0)
    if chunked:
        # one buffer that owns its bytes, apart from every fetched chunk
        assert sum(h.shape[0] for h in fetches.got) == want.size // shape[-1]
        assert not any(np.shares_memory(snap, h) for h in fetches.got)
    else:
        (only,) = fetches.got                    # kept as fetched, no copy
        assert np.shares_memory(snap, only) and snap.shape == shape
    back = eng.restore({"x": snap})["x"]
    assert back.dtype == x.dtype and back.shape == x.shape
    np.testing.assert_array_equal(_u8(back), _u8(want))


def _chunked_tree(seed):
    return {"kv": _leaf("bfloat16", (64, 128), seed),      # 16 KiB: 4 chunks
            "small": _leaf("float32", (4, 8), seed + 1)}   # one chunk


def test_assembled_bytes_counts_chunked_plain_leaves():
    tree = _chunked_tree(0)
    eng = StagingEngine(num_queues=2, transport="stream", chunk_bytes=4096,
                        incremental=True)
    eng.save(tree, tenant="t")
    assert eng.last_stats.num_descriptors == 5
    assert eng.last_stats.assembled_bytes == tree["kv"].nbytes
    eng.save(tree, tenant="t")                   # every leaf hits the memo
    assert eng.last_stats.skipped_bytes == (tree["kv"].nbytes
                                            + tree["small"].nbytes)
    assert eng.last_stats.assembled_bytes == 0
    borrow = StagingEngine(num_queues=2, transport="borrow",
                           chunk_bytes=4096)
    borrow.save(tree)
    assert borrow.last_stats.assembled_bytes == 0


def _memo_state(eng, tenant):
    return {k: (e.host, _u8(e.host).copy())
            for k, e in eng._memo_for(tenant).items()}


@pytest.mark.parametrize("fault", ["crashpoint", "placement"])
def test_failed_save_leaves_memo_and_host_copies(monkeypatch, fault):
    from repro.core.fault import InjectedCrash, crash_plane
    eng = StagingEngine(num_queues=2, transport="stream", chunk_bytes=4096,
                        incremental=True)
    eng.save(_chunked_tree(0), tenant="t")
    before = _memo_state(eng, "t")
    if fault == "crashpoint":
        crash_plane.arm("mid_pipeline_chunk")
        err = InjectedCrash
    else:
        # a queue thread dies after it has placed part of the leaf
        calls = [0]
        place = StagingEngine._place

        def flaky(d):
            calls[0] += 1
            if calls[0] == 3:
                raise RuntimeError("host copy failed")
            place(d)
        monkeypatch.setattr(StagingEngine, "_place", staticmethod(flaky))
        err = RuntimeError
    try:
        with pytest.raises(err):
            eng.save(_chunked_tree(7), tenant="t")
    finally:
        crash_plane.disarm()
    after = _memo_state(eng, "t")
    assert eng.memo_size("t") == 2 and after.keys() == before.keys()
    for k, (host, raw) in before.items():
        assert after[k][0] is host
        np.testing.assert_array_equal(after[k][1], raw)


# ---------------------------------------------------------------------------
# live pause (unit level; the sim covers it op-by-op)
# ---------------------------------------------------------------------------
def _attached_vf(tid, vid="0000:0a:00.1"):
    vf = VirtualFunction(vf_id=vid)
    vf.assign_devices(jax.devices()[:1], (1, 1))
    vf.transition(VFState.ATTACHED)
    vf.owner = tid
    return vf


def _mini_tenant(tid="vm0"):
    return ServeSimTenant(jnp.arange(4096, dtype=jnp.float32),
                          jnp.zeros((8,), jnp.float32), tid=tid)


def test_pause_vf_live_precopy_accounting_and_bit_identity():
    pool = DevicePool(devices=jax.devices())
    tn = _mini_tenant()
    vf = _attached_vf(tn.tid)
    tn.vf_id = vf.vf_id
    staging = StagingEngine(num_queues=2, incremental=True)
    tn.step()
    want_params = np.asarray(tn.params).copy()
    stepped = [0]

    def live_step():
        tn.step()
        stepped[0] += 1
    snap, t = pause_vf_live(pool, vf, tn, staging, rounds=2,
                            step_fn=live_step)
    check_pause_timings(t, live=True)
    assert stepped[0] == 2                       # kept working during rounds
    assert t.background == {"precopy_0", "precopy_1"}
    assert t.stop_s < t.total
    assert snap.precopy_rounds == 2
    assert snap.steps_done == tn.steps_done == 3
    # final payload reflects post-round state; params untouched
    vf.assign_devices(jax.devices()[:1], (1, 1))
    unpause_vf(pool, vf, tn, snap, staging)
    np.testing.assert_array_equal(np.asarray(tn.params), want_params)
    np.testing.assert_array_equal(np.asarray(tn.cache),
                                  np.full((8,), 3.0, np.float32))
    # params moved in the background rounds, not in the stop-and-copy
    assert snap.stats.skipped_bytes >= want_params.nbytes


def test_pause_vf_stop_equals_total():
    pool = DevicePool(devices=jax.devices())
    tn = _mini_tenant("vm1")
    vf = _attached_vf(tn.tid, "0000:0a:00.2")
    tn.vf_id = vf.vf_id
    snap, t = pause_vf(pool, vf, tn, StagingEngine(num_queues=1))
    check_pause_timings(t, live=False)
    assert t.background == set()
    assert abs(t.stop_s - t.total) < 1e-12


# ---------------------------------------------------------------------------
# _scale_sharding + restore(shardings=...) on a 2-device mesh (subprocess:
# XLA pins the host device count at first init)
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_restore_quantized_with_named_sharding_on_mesh():
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count=2"
        import json
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.core import StagingEngine
        from repro.core.staging import _scale_sharding

        mesh = Mesh(np.array(jax.devices()).reshape(2, 1), ("dp", "mp"))
        sh = NamedSharding(mesh, P("dp", None))
        rep = NamedSharding(mesh, P())
        # _scale_sharding maps any NamedSharding to full replication
        ssh = _scale_sharding(sh)
        assert isinstance(ssh, NamedSharding) and ssh.spec == P(), ssh
        assert _scale_sharding(None) is None
        assert _scale_sharding(object()) is None

        rng = np.random.default_rng(0)
        tree = {
            "big": jax.device_put(jnp.asarray(
                rng.standard_normal((16, 512)), jnp.float32), sh),
            "odd": jax.device_put(jnp.asarray(
                rng.standard_normal((7, 33)), jnp.float32), rep),
            "idx": jax.device_put(jnp.asarray(
                rng.integers(0, 50, (6,)), jnp.int32), rep),
        }
        shardings = {"big": sh, "odd": rep, "idx": rep}
        results = {}
        for name, kw in (
                ("plain", {}),
                ("stream", {"transport": "stream", "chunk_bytes": 2048}),
                ("int8", {"compression": "int8", "min_quant_size": 1024}),
                ("int8_stream", {"compression": "int8",
                                 "min_quant_size": 1024,
                                 "transport": "stream",
                                 "chunk_bytes": 2048})):
            eng = StagingEngine(num_queues=2, **kw)
            staged = eng.save(tree)
            out = eng.restore(staged, shardings=shardings)
            jax.block_until_ready(out)
            # quantized restore computes through qdma_unpack, so only
            # assert target shardings on the directly-placed leaves there
            ok_shard = out["odd"].sharding.is_equivalent_to(rep, 2)
            if "int8" not in name:
                ok_shard = (ok_shard and
                            out["big"].sharding.is_equivalent_to(sh, 2))
            exact = all(
                np.array_equal(np.asarray(tree[k]), np.asarray(out[k]))
                for k in ("odd", "idx"))
            if "int8" in name:
                a = np.asarray(tree["big"]); b = np.asarray(out["big"])
                big_ok = bool(np.abs(a - b).max() <= np.abs(a).max() / 64)
            else:
                big_ok = bool(np.array_equal(np.asarray(tree["big"]),
                                             np.asarray(out["big"])))
            results[name] = bool(ok_shard and exact and big_ok)
        print(json.dumps(results))
    """)
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res == {"plain": True, "stream": True, "int8": True,
                   "int8_stream": True}, res
