"""Find an open-loop cell's knee: the highest arrival rate at which the
requests due but not yet admitted when the window closes number at most the
engine's slot count. One set-up, then one window per rate, each drained
before the next.

    python3 bench/sweep.py --workload qwen3-chat --rates 3,4,5,6 --seconds 20

Prints one line per rate: backlog at the window's close, TTFT and ITL
tails. The knee found is written into the cell's mix file by hand (a cell
offers a fixed rate; the benchmark never searches for one).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import jax
    from bench import harness, readers
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
    counter = harness.CompileCounter().install()
    workdir = tempfile.mkdtemp(prefix="bench_sweep_")
    try:
        fleet, dims = harness.build(bench, cfg, mix, args.seed,
                                    devices[:1], workdir, print)
        print(f"setup {time.perf_counter() - T_START:.1f} s", flush=True)
        for rate in (float(r) for r in args.rates.split(",")):
            m = dict(mix, rate_per_s=rate, events=[])
            rec = harness.Record(cell=args.workload, cfg=cfg, mix=m,
                                 dims=dims, peaks={}, seconds=args.seconds)
            traffic = bench.generator(m).Traffic(m, args.seed, args.seconds,
                                                 dims.vocab)
            harness.serve_window(fleet, traffic, rec, bench, trace_dir=None,
                                 counter=counter)
            backlog = sum(1 for s in rec.due_in_window()
                          if not s.req.t_tok or s.req.t_tok[0] > rec.t1)
            print(json.dumps({
                "rate_per_s": rate, "due": len(rec.due_in_window()),
                "backlog_at_close": backlog, "slots": cfg["serve"]["slots"],
                "ttft_p95_ms": readers.ttft_p95_ms(rec),
                "itl_p95_ms": readers.itl_p95_ms(rec),
                "tokens_per_s": readers.tokens_per_s(rec),
                "compiles_in_window": rec.info["compiles_in_window"]}),
                flush=True)
            fleet.drain()
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]
    sys.exit(main())
