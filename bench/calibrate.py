"""Readings that set a cell's correctness limit: for each seed, one whole run
of the cell (set-up, window, check) in this process, with the control read
on the same sample: the reference with fp8 weights in the program's place.

    python3 bench/calibrate.py --workload qwen3-chat --seeds 11,12,13 --seconds 15

Prints one JSON line per seed: the program's widest logit gap (the lower
reading is the largest over sound seeds), the control's (the upper reading
is the smallest) and whether the control was judged correct (it has to
read false), the served tokens compared, and the run's metrics. The
benchmark's own runs never read the control.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    import jax
    from bench import harness, roofline
    from bench.run import enable_cache
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform}", file=sys.stderr)
        return 2
    enable_cache()
    peaks = roofline.peaks_for(devices[0].device_kind)
    bench = harness.Bench(ROOT)
    counter = harness.CompileCounter().install()
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run_cell(bench, args.workload, seed, args.seconds,
                               False, devices=devices, peaks=peaks,
                               counter=counter, t_start=time.perf_counter(),
                               control=True, log=lambda m: None)
        prog = out["program_checks"]
        print(json.dumps({
            "seed": seed, "control_correct": out["correct"],
            "max_logit_gap": prog["max_logit_gap"]["value"],
            "control_max_logit_gap": out["checks"]["max_logit_gap"]["value"],
            "tokens_checked": prog["tokens_checked"]["value"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
            "peak_bytes": out["device"]["memory_peak_bytes"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]
    sys.exit(main())
