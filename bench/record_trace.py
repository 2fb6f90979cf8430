"""Record a short profiler trace of a cell on the chip, for the trace
reduction's test: set-up as in a run, a handful of the mix's requests, then
``--steps`` fleet steps inside the harness's annotations.

    python3 bench/record_trace.py --workload qwen3-chat --steps 6 --out DIR

Writes the ``.xplane.pb`` under ``--out`` and prints what the reduction
reads from it.
"""
import argparse
import glob
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--out", required=True)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)
    import jax
    from bench import harness, trace
    from bench.run import enable_cache
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform}", file=sys.stderr)
        return 2
    enable_cache()
    bench = harness.Bench(ROOT)
    cell = bench.cell(args.workload)
    cfg = bench.config(cell["config"])
    mix = bench.mix(cell["traffic"])
    workdir = tempfile.mkdtemp(prefix="bench_record_")
    try:
        fleet, dims = harness.build(bench, cfg, mix, args.seed, devices[:1],
                                    workdir, print)
        traffic = bench.generator(mix).Traffic(mix, args.seed, 10.0,
                                               dims.vocab)
        for a in traffic.due(10.0)[:cfg["serve"]["slots"]]:
            fleet.submit(harness._request(a, 1.0))
        for _ in range(8):
            fleet.step()
        with tempfile.TemporaryDirectory() as td:
            jax.profiler.start_trace(td)
            with jax.profiler.TraceAnnotation("bench.window"):
                for _ in range(args.steps):
                    with jax.profiler.TraceAnnotation("bench.step"):
                        fleet.step()
            jax.profiler.stop_trace()
            os.makedirs(args.out, exist_ok=True)
            for p in glob.glob(os.path.join(args.out, "*.xplane.pb")):
                os.remove(p)
            shutil.copy(trace.find_xplane(td), os.path.join(
                args.out, f"{args.workload.replace('-', '_')}_steps"
                ".xplane.pb"))
        t = trace.load(args.out)
        print(json.dumps({"window_s": t.window_s, "busy_s": t.busy_s,
                          "paged_decode_s": t.kernel_s("paged_decode"),
                          "breakdown": t.breakdown()}))
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]
    sys.exit(main())
