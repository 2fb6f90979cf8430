"""Run one cell of the benchmark on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout (the directory of ``BENCHMARK.json``). The
last line of standard output is the result object; with ``--trace 0`` its
metrics are the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics, read from a profiler trace of part of the window. The last lines of
standard error give each number compared against the reference beside its
limit. ``--control 1`` judges the control's tokens in place of the
program's, by the same limit. A platform other than the TPU, fewer chips than the cell asks for,
or a device kind missing from ``bench/peaks.json`` exits non-zero before
any work.
"""
import time

T_START = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="judge the control's tokens (the reference with "
                         "fp8 weights) in place of the program's; its "
                         "`correct` has to read false")
    return ap.parse_args(argv)


def enable_cache():
    """The program's persistent compilation cache (``JAX_COMPILATION_CACHE_DIR``
    where set, else ``<checkout>/.jax_cache``), holding every program however
    quickly it compiled, so that a second run of a cell compiles nothing."""
    import jax
    from repro.launch.cache import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return enable_compile_cache()


def print_checks(checks: dict) -> None:
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)


def main(argv=None) -> int:
    args = parse(argv)
    import jax
    from bench import harness, roofline
    bench = harness.Bench(ROOT)
    cell = bench.cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform}", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"{args.workload} needs {cell['chips']} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    try:
        peaks = roofline.peaks_for(devices[0].device_kind)
    except roofline.UnknownDevice as e:
        print(e, file=sys.stderr)
        return 2
    print(f"cache {enable_cache()}; device {devices[0].device_kind} "
          f"x{len(devices)}", flush=True)
    counter = harness.CompileCounter().install()
    out = harness.run_cell(bench, args.workload, args.seed, args.seconds,
                           bool(args.trace), devices=devices, peaks=peaks,
                           counter=counter, t_start=T_START,
                           control=bool(args.control))
    print(json.dumps(out), flush=True)
    print_checks(out["checks"])
    return 0


if __name__ == "__main__":
    # the checkout root in place of this script's directory (whose module
    # names would shadow the standard library's), and the program's sources
    sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]
    sys.exit(main())
