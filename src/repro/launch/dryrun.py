"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input-shape) cell, lower + compile the real step
function (train_step / prefill / serve_step) against ShapeDtypeStruct
inputs on the production mesh — 16x16 single-pod and 2x16x16 multi-pod —
and extract memory_analysis / cost_analysis / collective bytes for the
roofline (EXPERIMENTS.md §Dry-run, §Roofline).

No arrays are ever allocated at production shapes; the 512 placeholder
devices exist only inside this process.
"""
# The VERY FIRST lines — before ANY other import — jax locks the device
# count on first init. Do NOT set this globally (tests see 1 device).
import os
if "--real-devices" not in __import__("sys").argv:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=512")

import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import (SHAPES, get_model_config, list_archs,
                           make_run_config, shape_applicable)
from repro.launch.mesh import make_production_mesh, mesh_config
from repro.models.model import build_model
from repro.runtime.hlo import collective_stats, scan_op_counts
from repro.runtime.partitioning import ShardingRules, sharding_scope
from repro.runtime.roofline import Roofline, model_flops_estimate
from repro.train.step import (batch_specs, make_train_step,
                              train_state_shapes, train_state_specs)

RESULTS_DIR = os.path.join(os.path.dirname(__file__),
                           "../../../results/dryrun")

ASSIGNED_ARCHS = [
    "arctic-480b", "olmoe-1b-7b", "qwen3-0.6b", "llama3-8b", "deepseek-67b",
    "phi3-mini-3.8b", "seamless-m4t-medium", "xlstm-350m",
    "jamba-1.5-large-398b", "internvl2-1b",
]


def _cell_path(arch, shape, mesh_name, out_dir):
    os.makedirs(out_dir, exist_ok=True)
    return os.path.join(out_dir, f"{arch}__{shape}__{mesh_name}.json")


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               overrides: dict | None = None, model_override=None):
    """Build and lower one cell; returns (lowered, run, rules, meta)."""
    import dataclasses as _dc
    mcfg = mesh_config(multi_pod=multi_pod)
    run = make_run_config(arch, shape_name, mesh=mcfg,
                          kernel_backend="reference",
                          **(overrides or {}))
    if model_override is not None:
        run = _dc.replace(run, model=model_override)
    model = build_model(run)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = ShardingRules(mcfg, run, mesh)
    shape_cfg = SHAPES[shape_name]
    kind = shape_cfg.kind

    with mesh:
        with sharding_scope(rules):
            if kind == "train":
                step = make_train_step(run, rules)
                sshapes = train_state_shapes(run)
                sspecs = rules.named(train_state_specs(run, rules))
                bspecs = rules.named(batch_specs(run, rules))
                bshapes = model.input_specs()
                lowered = jax.jit(
                    step, in_shardings=(sspecs, bspecs),
                    donate_argnums=(0,)).lower(sshapes, bshapes)
            elif kind == "prefill":
                pshapes = model.param_shapes()
                pspecs = rules.named(rules.param_specs(pshapes))
                bspecs = rules.named(batch_specs(run, rules))
                bshapes = model.input_specs()

                def prefill(params, batch):
                    with sharding_scope(rules):
                        return model.prefill(params, batch)
                lowered = jax.jit(
                    prefill, in_shardings=(pspecs, bspecs)).lower(
                        pshapes, bshapes)
            else:  # decode
                pshapes = model.param_shapes()
                pspecs = rules.named(rules.param_specs(pshapes))
                cshapes = model.cache_specs()
                cspecs = rules.named(cache_partition_specs(rules, cshapes))
                ishapes = model.input_specs()
                from jax.sharding import PartitionSpec as P
                tok_spec = rules.named(P(
                    rules._fit(ishapes["tokens"].shape[0], rules.dp_axes),
                    None))
                pos_spec = rules.named(P())

                def serve_step(params, cache, tokens, pos):
                    with sharding_scope(rules):
                        return model.decode_step(params, cache, tokens, pos)
                lowered = jax.jit(
                    serve_step,
                    in_shardings=(pspecs, cspecs, tok_spec, pos_spec),
                    donate_argnums=(1,)).lower(
                        pshapes, cshapes, ishapes["tokens"], ishapes["pos"])
    return lowered, run, rules


def cache_partition_specs(rules: ShardingRules, cache_shapes):
    """Decode-cache specs: kv leaves get the kv_cache rule (seq sharding),
    recurrent states shard on batch. Leading dim is the period stack."""
    from jax.sharding import PartitionSpec as P

    def one(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else ""
        shape = leaf.shape
        if name in ("k", "v", "xk", "xv"):
            inner = rules.spec("kv_cache", shape[1:])
            return P(None, *inner)
        inner = rules.spec("state", shape[1:])
        return P(None, *inner)
    return jax.tree_util.tree_map_with_path(one, cache_shapes)


# ---------------------------------------------------------------------------
# True-cost extraction.
#
# XLA's cost_analysis counts a while-loop body ONCE, not x trip-count, so a
# scanned layer stack under-reports flops/bytes/collectives by ~num_periods.
# Fix: compile two UNROLLED variants of the same cell at full width with
# P=1 and P=2 pattern-periods; every metric is linear in P
# (metric = a + b*P), so   b = m2 - m1,  a = m1 - b,  total = a + nper*b.
# The full scanned compile still provides memory_analysis (true buffer
# allocation) and proves the production mesh compiles.
#
# xLSTM blocks contain *inner* time scans (mLSTM chunk loop, sLSTM step
# loop) that stay while-loops even in the unrolled variants; their missing
# trips are added analytically (first-order formulas below).
# ---------------------------------------------------------------------------
def _inner_scan_correction(model_cfg, shape_cfg, kind: str) -> dict:
    """Analytic add-on flops/bytes for inner time scans (xlstm only)."""
    from repro.configs.base import MLSTM, SLSTM
    from repro.models.params import mlstm_dims, slstm_dims
    B, S = shape_cfg.global_batch, shape_cfg.seq_len
    if kind == "decode":
        return {"flops": 0.0, "bytes": 0.0}
    mult = 4.0 if kind == "train" else 1.0      # fwd + remat replay + 2x bwd
    plen = len(model_cfg.block_pattern)
    nper = model_cfg.num_layers // plen
    flops = 0.0
    for j, bk in enumerate(model_cfg.block_pattern):
        if bk == MLSTM:
            di, H = mlstm_dims(model_cfg)
            hd = model_cfg.xlstm.head_dim
            Q = min(model_cfg.xlstm.chunk, S)
            nc = S // Q
            body = B * H * (4 * Q * Q * hd + 8 * Q * hd * hd)
            flops += (nc - 1) * body * mult * nper
        elif bk == SLSTM:
            heads, dh, d_up = slstm_dims(model_cfg)
            D = model_cfg.d_model
            body = B * (8 * D * D + 8 * D * dh + 20 * D)
            flops += (S - 1) * body * mult * nper
    return {"flops": flops, "bytes": flops / 16.0}  # ~AI of these blocks


def _cost_of(lowered) -> dict:
    compiled = lowered.compile()
    cost = compiled.cost_analysis() or {}
    coll = collective_stats(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll_bytes": float(coll.total_bytes),
            "coll_by_op": dict(coll.bytes_by_op)}


def true_costs(arch: str, shape_name: str, multi_pod: bool, run,
               overrides: dict | None = None) -> dict:
    """Extrapolated per-device costs for the full layer count."""
    import dataclasses as _dc
    base = run.model
    plen = len(base.block_pattern)
    nper = base.num_layers // plen
    var_overrides = dict(overrides or {})
    var_overrides.setdefault("sharding", run.sharding)
    var_overrides["sharding"] = _dc.replace(var_overrides["sharding"],
                                            scan_layers=False,
                                            unroll_microbatch=True)
    var_overrides["precision"] = run.precision
    var_overrides["optimizer"] = run.optimizer
    ms = []
    for P in (1, 2):
        mc = _dc.replace(
            base, num_layers=plen * P,
            num_encoder_layers=(plen * P if base.num_encoder_layers else 0))
        lowered, _, _ = lower_cell(arch, shape_name, multi_pod,
                                   overrides=var_overrides,
                                   model_override=mc)
        ms.append(_cost_of(lowered))
    out = {}
    for key in ("flops", "bytes", "coll_bytes"):
        b = ms[1][key] - ms[0][key]
        a = ms[0][key] - b
        out[key] = max(a + nper * b, 0.0)
    by_op = {}
    for op in set(ms[0]["coll_by_op"]) | set(ms[1]["coll_by_op"]):
        b = ms[1]["coll_by_op"].get(op, 0) - ms[0]["coll_by_op"].get(op, 0)
        a = ms[0]["coll_by_op"].get(op, 0) - b
        v = a + nper * b
        if v > 0:
            by_op[op] = v
    out["coll_by_op"] = by_op
    corr = _inner_scan_correction(base, run.shape, run.shape.kind)
    out["flops"] += corr["flops"] / (512 if multi_pod else 256)
    out["bytes"] += corr["bytes"] / (512 if multi_pod else 256)
    out["inner_scan_corr_flops"] = corr["flops"]
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str = RESULTS_DIR, force: bool = False,
             tag: str = "", overrides: dict | None = None) -> dict:
    mesh_name = ("multi" if multi_pod else "single") + (f"-{tag}" if tag
                                                        else "")
    path = _cell_path(arch, shape_name, mesh_name, out_dir)
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    model_cfg = get_model_config(arch)
    ok, why = shape_applicable(model_cfg, SHAPES[shape_name])
    result = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
              "chips": 512 if multi_pod else 256}
    if not ok:
        result.update({"status": "skipped", "reason": why})
        with open(path, "w") as f:
            json.dump(result, f, indent=1)
        return result

    try:
        from repro.configs import ShardingConfig
        overrides = dict(overrides or {})
        overrides.setdefault("sharding", ShardingConfig(remat="full"))

        t0 = time.perf_counter()
        lowered, run, rules = lower_cell(arch, shape_name, multi_pod,
                                         overrides)
        t_lower = time.perf_counter() - t0

        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
        coll = collective_stats(hlo)

        # true per-device costs via unrolled 1/2-period extrapolation
        tc = true_costs(arch, shape_name, multi_pod, run, overrides)

        chips = result["chips"]
        rf = Roofline(
            arch=arch, shape=shape_name, mesh=mesh_name, chips=chips,
            hlo_flops=tc["flops"],
            hlo_bytes=tc["bytes"],
            collective_bytes=tc["coll_bytes"],
            collective_detail={"bytes_by_op": tc["coll_by_op"]},
            model_flops=model_flops_estimate(run.model, run.shape))

        result.update({
            "status": "ok",
            "lower_s": t_lower, "compile_s": t_compile,
            "memory": {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
                "output_bytes": getattr(mem, "output_size_in_bytes", 0),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
                "code_bytes": getattr(mem, "generated_code_size_in_bytes", 0),
            },
            "cost_scanned_raw": {k: float(v) for k, v in cost.items()
                                 if isinstance(v, (int, float))
                                 and "{" not in k},
            "cost_extrapolated": {k: v for k, v in tc.items()
                                  if k != "coll_by_op"},
            "collectives_scanned_raw": coll.describe(),
            "collectives": {"bytes_by_op": tc["coll_by_op"],
                            "total_bytes": sum(tc["coll_by_op"].values())},
            "hlo_ops": scan_op_counts(hlo),
            "roofline": rf.row(),
        })
    except Exception as e:                                    # noqa: BLE001
        result.update({"status": "error", "error": repr(e),
                       "traceback": traceback.format_exc()[-4000:]})
    with open(path, "w") as f:
        json.dump(result, f, indent=1, default=str)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="one arch (default: all)")
    ap.add_argument("--shape", default=None, choices=list(SHAPES),
                    help="one shape (default: all four)")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default=RESULTS_DIR)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--real-devices", action="store_true",
                    help="skip the 512-device override (debug)")
    args = ap.parse_args(argv)

    if args.list:
        for a in ASSIGNED_ARCHS:
            print(a)
        return 0

    archs = [args.arch] if args.arch else ASSIGNED_ARCHS
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                r = run_cell(arch, shape, mp, out_dir=args.out,
                             force=args.force)
                status = r["status"]
                line = f"{arch:24s} {shape:12s} {r['mesh']:7s} {status}"
                if status == "ok":
                    rf = r["roofline"]
                    line += (f"  bound={rf['bound']:10s}"
                             f" step={rf['step_s']*1e3:8.2f}ms"
                             f" compile={r['compile_s']:6.1f}s")
                    mb = (r['memory']['argument_bytes'] +
                          r['memory']['temp_bytes']) / 2**30
                    line += f" mem/dev={mb:7.2f}GiB"
                elif status == "error":
                    failures += 1
                    line += f"  {r['error'][:80]}"
                print(line, flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
