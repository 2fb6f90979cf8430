"""Paper claim (1): 'native performance ... no performance loss' — a
pause/unpause cycle must not change the tenant's steady-state step time
(the guest driver never reloads, executables stay cached). Also measures
the staging engine's snapshot bandwidth with and without qdma_pack int8
compression (the beyond-paper pause-path optimization)."""
import os
if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8")

import argparse
import json
import statistics
import sys


def bench(steps: int = 20) -> dict:
    import tempfile
    import jax  # noqa: F401
    from repro.configs import make_run_config
    from repro.core import DevicePool, SVFFManager, StagingEngine, Tenant

    run = make_run_config("svff-bench", "train_4k", smoke=True)
    pool = DevicePool()
    mgr = SVFFManager(pool, workdir=tempfile.mkdtemp(prefix="svff_tp_"))
    tn = Tenant("vm0", run, local_batch=4, seq_len=64)
    mgr.init(num_vfs=2, tenants=[tn], devices_per_vf=4)
    tn.run_steps(5)                         # warmup
    tn.step_times.clear()
    tn.run_steps(steps)
    before = statistics.median(tn.step_times)

    mgr.pause(tn)
    mgr.unpause(tn)
    tn.run_steps(2)
    tn.step_times.clear()
    tn.run_steps(steps)
    after = statistics.median(tn.step_times)

    out = {"step_ms_before_pause": before * 1000,
           "step_ms_after_unpause": after * 1000,
           "pause_cycle_overhead_pct": 100 * (after - before) / before}

    # snapshot bandwidth, plain vs qdma_pack int8
    state = tn.export_state()
    for comp in ("none", "int8"):
        eng = StagingEngine(compression=comp, min_quant_size=1024)
        staged = eng.save(state)
        st = eng.last_stats
        out[f"snapshot_{comp}_bytes"] = st.bytes_moved
        out[f"snapshot_{comp}_ms"] = st.seconds * 1000
        out[f"snapshot_{comp}_gbps"] = st.bytes_moved / st.seconds / 1e9
    out["compression_ratio"] = (out["snapshot_none_bytes"] /
                                out["snapshot_int8_bytes"])
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    row = bench(args.steps)
    print(json.dumps(row))
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(row, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
