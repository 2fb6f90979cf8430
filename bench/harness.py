"""The benchmark harness: one cell of ``BENCHMARK.json`` from set-up to the
result line.

Everything that belongs to one configuration, traffic mix, event or metric
is a file of its own that the harness finds by name:

  configuration   the ``file`` that BENCHMARK.json names for it; its
                  ``family`` names ``bench/families/<family>.py`` (run
                  config and weights) and its ``reference`` names
                  ``bench/references/<reference>.py`` (the plain reference)
  traffic mix     ``bench/traffic/<mix>.json``; its ``kind`` names the
                  generator ``bench/traffic/<kind>.py``
  event           an entry of the mix's ``events``; its ``op`` names
                  ``bench/events/<op>.py``
  metric          ``bench/metrics/<name>.py``, whose ``read(record)``
                  returns the value or None when it has nothing to read

A run: weights from the seed on the device, the fleet built, every program
the mix can reach warmed up (``setup_s`` ends here), the measured window,
then the check against the reference once the fleet is freed.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Optional

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
#: how long after the window the harness keeps serving so that every
#: request due in the window gets its first token (open loop only)
SETTLE_S = 60.0
#: a fleet step longer than this is listed on an info line, with the
#: engines' counters that moved in it and the tokens it emitted
SLOW_STEP_S = 0.25


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------
def _load(path: str, name: str):
    if not os.path.isfile(path):
        raise FileNotFoundError(f"benchmark file {path} does not exist")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod          # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """``BENCHMARK.json`` at ``root`` and the files it names under
    ``bench_dir``."""

    def __init__(self, root: str, bench_dir: str = BENCH_DIR):
        self.root, self.dir = root, bench_dir
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.spec = json.load(f)
        self._mods: dict = {}

    def _module(self, sub: str, name: str):
        key = (sub, name)
        if key not in self._mods:
            self._mods[key] = _load(os.path.join(self.dir, sub, name + ".py"),
                                    f"bench_{sub}_{name}".replace(".", "_")
                                    .replace("-", "_"))
        return self._mods[key]

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                with open(os.path.join(self.root, c["file"])) as f:
                    cfg = json.load(f)
                cfg["name"] = name
                return cfg
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def mix(self, name: str) -> dict:
        with open(os.path.join(self.dir, "traffic", name + ".json")) as f:
            return json.load(f)

    def family(self, cfg: dict):
        return self._module("families", cfg["family"])

    def reference(self, cfg: dict):
        return self._module("references", cfg["reference"])

    def generator(self, mix: dict):
        return self._module("traffic", mix["kind"])

    def event(self, op: str):
        return self._module("events", op)

    def reader(self, metric: str):
        return self._module("metrics", metric).read

    def metrics(self, cell: str, trace: bool) -> list:
        """The metric entries this cell reports: end-to-end ones without
        tracing, per-layer ones with it."""
        group = self.spec["per_layer" if trace else "end_to_end"]
        return [m for m in group
                if "workloads" not in m or cell in m["workloads"]]


# ---------------------------------------------------------------------------
# counting compiles
# ---------------------------------------------------------------------------
class CompileCounter:
    """Backend compiles and persistent-cache loads, from JAX's monitoring
    events. Register once per process."""

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_loads = 0

    def install(self):
        import jax

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_loads += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        return self

    def reading(self) -> tuple:
        return (self.compiles, self.cache_loads)


# ---------------------------------------------------------------------------
# the record the metric readers read
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Sent:
    """One request as the harness sent it."""
    arrival: object                  # bench.workload.Arrival
    req: object                      # the program's Request
    due: float                       # absolute due time (host clock)
    sent: float                      # when submit was called
    engine: Optional[str] = None     # fleet engine it was routed to
    rejected: Optional[str] = None   # rejection message

    @property
    def plen(self) -> int:
        return len(self.arrival.prompt)


@dataclasses.dataclass
class Record:
    cell: str
    cfg: dict
    mix: dict
    dims: object
    peaks: dict
    seconds: float
    setup_s: float = 0.0
    t0: float = 0.0                  # window start (host clock)
    t1: float = 0.0                  # window end
    sent: list = dataclasses.field(default_factory=list)
    steps: list = dataclasses.field(default_factory=list)   # (start, end)
    #: traced window only: (start, end, [ctx per decoded request], slots,
    #: table width) for each fleet step
    decode_log: list = dataclasses.field(default_factory=list)
    events: list = dataclasses.field(default_factory=list)
    trace: object = None             # bench.trace.Trace
    trace_host: tuple = (0.0, 0.0)   # traced window on the host clock
    info: dict = dataclasses.field(default_factory=dict)
    slow_steps: list = dataclasses.field(default_factory=list)
    open_loop: bool = True

    def in_window(self, t: float) -> bool:
        return self.t0 <= t <= self.t1

    def due_in_window(self) -> list:
        return [s for s in self.sent if s.due < self.t1]


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def _annotate(enabled: bool):
    if not enabled:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


def _options():
    """Device ops and the harness's annotations; no Python function events
    (they made the trace ten times larger and its write take ~20 s)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


class GcPauses:
    """Python garbage collections and their pauses, from ``gc.callbacks``."""

    def __init__(self):
        self.pauses: list = []          # (generation, seconds)
        self._t = None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))
            self._t = None


def _table_width(pos: int, page: int, maxp: int) -> int:
    need = pos // page + 1
    return min(1 << max(0, (need - 1).bit_length()), maxp)


def warm_up(fleet, cfg: dict, mix: dict, vocab: int, log) -> None:
    """Compile every program the mix can reach, from the mix's parameters
    alone: one request at each prompt length the mix can draw (prefill,
    chunked prefill, KV admission, first decode), then a walk through any
    decode table width that those leave out."""
    from repro.serve import Request
    from bench.workload import possible_lengths
    rng = np.random.default_rng(0)
    serve = cfg["serve"]
    page, max_len = serve["page_size"], serve["max_len"]
    maxp = -(-max_len // page)
    lengths = possible_lengths(mix["prompt"])
    max_out = int(mix["output"]["max"])
    rid = iter(range(1_000_000_000, 2_000_000_000))

    def one(plen, new):
        greedy = plen % 32 == 0
        fleet.submit(Request(
            rid=next(rid), prompt=rng.integers(0, vocab, plen,
                                               dtype=np.int32),
            max_new_tokens=new, temperature=0.0 if greedy else 0.8,
            top_k=0 if greedy else 50, seed=1))
        fleet.drain()

    covered = set()
    for plen in lengths:
        one(plen, 2)
        covered.add(_table_width(plen, page, maxp))
    need = {_table_width(p, page, maxp)
            for p in range(lengths[0], lengths[-1] + max_out)}
    if need - covered:
        one(lengths[-1], max_out)
    log(f"warm-up: {len(lengths)} prompt lengths {lengths[0]}..{lengths[-1]},"
        f" decode table widths {sorted(need)}")


def _request(arrival, due_abs: float):
    from repro.serve import Request
    return Request(rid=arrival.rid, prompt=arrival.prompt,
                   max_new_tokens=arrival.max_new,
                   temperature=arrival.temperature, top_k=arrival.top_k,
                   seed=arrival.seed, t_submit=due_abs)


def serve_window(fleet, traffic, rec: Record, bench: Bench, *, trace_dir,
                 counter: CompileCounter) -> None:
    """The measured window: offer the mix's load for ``rec.seconds``,
    firing the mix's events on schedule; then (open loop) keep serving
    until every request due in the window has its first token."""
    import jax
    from repro.serve.paged import RequestRejected
    tracing = trace_dir is not None
    annotate = _annotate(tracing)
    events = sorted(rec.mix.get("events", []), key=lambda e: e["at_s"])
    tw = rec.mix.get("trace_window_s", [2.0, 8.0])
    trace_from, trace_to = tw[0], min(tw[1], rec.seconds)
    live: list[Sent] = []
    traced = None            # the open bench.window annotation
    page = rec.cfg["serve"]["page_size"]
    slots = rec.cfg["serve"]["slots"]
    maxp = -(-rec.cfg["serve"]["max_len"] // page)

    def submit(arrival, now_abs):
        due = rec.t0 + arrival.due_s
        s = Sent(arrival=arrival, req=_request(arrival, due), due=due,
                 sent=now_abs)
        with annotate("bench.submit"):
            try:
                s.engine = fleet.submit(s.req)
            except RequestRejected as e:
                s.rejected = str(e)
        rec.sent.append(s)
        if s.rejected is None:
            live.append(s)

    def step(log_decode: bool):
        before = [len(s.req.out) for s in live] if log_decode else None
        counts = _engine_counts(fleet)
        t = time.perf_counter()
        with annotate("bench.step"):
            fleet.step()
        e = time.perf_counter()
        rec.steps.append((t, e))
        if rec.in_window(e):
            pages.append(_pool_pages(fleet))
        if e - t > SLOW_STEP_S:
            moved = _engine_counts(fleet)
            moved.subtract(counts)
            rec.slow_steps.append({
                "at_s": round(t - rec.t0, 3), "ms": round(1e3 * (e - t), 1),
                "first_tokens": sum(1 for s in live if s.req.t_tok
                                    and t <= s.req.t_tok[0] <= e),
                "tokens": sum(1 for s in live for x in s.req.t_tok
                              if t <= x <= e),
                "counters": {k: v for k, v in moved.items() if v}})
        if log_decode:
            ctxs, top = [], 0
            for s, n in zip(live, before):
                for i in range(max(n, 1), len(s.req.out)):
                    ctxs.append(s.plen + i)        # decode of token i
                    top = max(top, s.plen + i - 1)
            rec.decode_log.append((t, e, ctxs, slots,
                                   _table_width(top, page, maxp)))
        return e

    def retire(now_abs):
        keep = []
        for s in live:
            if s.req.done:
                traffic.completed(s.arrival, now_abs - rec.t0)
            else:
                keep.append(s)
        live[:] = keep

    pages: list = []          # (in use, capacity) after each step
    c0 = counter.reading()
    pauses = GcPauses()
    gc.callbacks.append(pauses)
    rec.t0 = time.perf_counter()
    rec.t1 = rec.t0 + rec.seconds
    while True:
        now = time.perf_counter()
        rel = now - rec.t0
        if rel >= rec.seconds:
            break
        for a in traffic.due(rel):
            submit(a, now)
        if events and rel >= events[0]["at_s"]:
            ev = events.pop(0)
            rec.events.append(bench.event(ev["op"]).fire(fleet, ev, annotate))
            continue
        if tracing and traced is None and trace_from <= rel < trace_to:
            jax.profiler.start_trace(trace_dir, profiler_options=_options())
            traced = jax.profiler.TraceAnnotation("bench.window")
            traced.__enter__()
            rec.trace_host = (time.perf_counter(), 0.0)
        if traced is not None and rel >= trace_to:
            rec.trace_host = (rec.trace_host[0], time.perf_counter())
            traced.__exit__(None, None, None)
            jax.profiler.stop_trace()
            traced, tracing = None, False
        if not live:
            nxt = traffic.next_due()
            if nxt is not None:
                time.sleep(max(0.0, min(nxt - rel, 0.002)))
            continue
        end = step(traced is not None)
        retire(end)
    if traced is not None:
        rec.trace_host = (rec.trace_host[0], time.perf_counter())
        traced.__exit__(None, None, None)
        jax.profiler.stop_trace()
    gc.callbacks.remove(pauses)
    c1 = counter.reading()
    rec.info["gc_collections_in_window"] = len(pauses.pauses)
    rec.info["gc_max_pause_ms"] = 1e3 * max((p for _, p in pauses.pauses),
                                            default=0.0)
    rec.info["max_step_ms"] = 1e3 * max((e - s for s, e in rec.steps),
                                        default=0.0)
    if pages:
        cap = max(c for _, c in pages)
        rec.info["pool_pages_in_use_mean"] = sum(u for u, _ in pages) / len(
            pages)
        rec.info["pool_pages_in_use_max"] = max(u for u, _ in pages)
        rec.info["pool_pages_capacity"] = cap
    rec.info["compiles_in_window"] = c1[0] - c0[0]
    rec.info["cache_loads_in_window"] = c1[1] - c0[1]
    rec.open_loop = traffic.open_loop
    if traffic.open_loop:
        waiting = [s for s in rec.due_in_window()
                   if s.rejected is None and not s.req.out]
        limit = time.perf_counter() + SETTLE_S
        while waiting and time.perf_counter() < limit:
            step(False)
            waiting = [s for s in waiting if not s.req.out]
        rec.info["settle_s"] = time.perf_counter() - rec.t1
    late = [s.sent - s.due for s in rec.sent]
    if late:
        rec.info["generator_late_ms_p50"] = 1e3 * float(np.median(late))
        rec.info["generator_late_ms_max"] = 1e3 * max(late)


def _engine_counts(fleet) -> collections.Counter:
    """The engines' own counters (admissions, preemptions, defragments,
    ...), summed over the fleet."""
    total = collections.Counter()
    for tn in fleet.tenants.values():
        total.update(tn.engine.stats)
    return total


def _pool_pages(fleet) -> tuple:
    """KV pages in use and the pools' capacity, over the paged engines."""
    used = cap = 0
    for tn in fleet.tenants.values():
        alloc = getattr(tn.engine, "alloc", None)
        if alloc is not None:
            used, cap = used + alloc.pages_in_use, cap + alloc.capacity
    return used, cap


def free_fleet(fleet) -> None:
    """Drop the fleet's device state so the reference has the chip."""
    fleet.mgr.staging.close()
    for tn in fleet.tenants.values():
        tn.engine.params = None
        tn.engine._cache = None
    fleet.tenants.clear()
    gc.collect()


def build(bench: Bench, cfg: dict, mix: dict, seed: int, devs, workdir: str,
          log):
    """Set-up: the weights for ``seed``, the fleet on ``devs``, and every
    program the mix (and its events) can reach, compiled."""
    import jax
    from repro.serve import Request, ServeFleet
    fam = bench.family(cfg)
    dims = fam.dims(cfg)
    # made on the device, handed over from host memory: the fleet keeps
    # its caller's tree as the source of every engine's copy, so a device
    # source would hold the chip's memory twice
    weights = jax.device_get(fam.make_weights(cfg, seed))
    fleet = ServeFleet(fam.run_config(cfg), weights, devices=devs,
                       num_engines=1, workdir=workdir, **cfg["serve"])
    del weights
    warm_up(fleet, cfg, mix, dims.vocab, log)
    rng = np.random.default_rng(1)

    def request(i):
        return Request(rid=1_500_000_000 + i,
                       prompt=rng.integers(0, dims.vocab,
                                           int(mix["prompt"]["max"]),
                                           dtype=np.int32),
                       max_new_tokens=int(mix["output"]["max"]), seed=1)
    for ev in mix.get("events", []):
        bench.event(ev["op"]).warm(fleet, ev, request, _annotate(False))
    return fleet, dims


def run_cell(bench: Bench, cell_name: str, seed: int, seconds: float,
             trace: bool, *, devices, peaks: dict, counter: CompileCounter,
             t_start: float, control: bool = False, log=None) -> dict:
    """One run of a cell; returns the result object. With ``control`` the
    control's tokens are the ones judged (``correct``, ``checks``), and the
    program's checks are kept under ``program_checks``."""
    from bench import check
    log = log or (lambda msg: print(msg, flush=True))
    cell = bench.cell(cell_name)
    cfg = bench.config(cell["config"])
    mix = bench.mix(cell["traffic"])
    devs = list(devices)[:cell["chips"]]
    workdir = tempfile.mkdtemp(prefix="bench_fleet_")
    try:
        fleet, dims = build(bench, cfg, mix, seed, devs, workdir, log)
        rec = Record(cell=cell_name, cfg=cfg, mix=mix, dims=dims,
                     peaks=peaks, seconds=float(seconds))
        traffic = bench.generator(mix).Traffic(mix, seed, seconds,
                                               dims.vocab)
        rec.setup_s = time.perf_counter() - t_start
        log(f"setup_s {rec.setup_s:.3f} (compiles {counter.compiles}, "
            f"{counter.compile_s:.1f} s; cache loads {counter.cache_loads})")
        with tempfile.TemporaryDirectory(prefix="bench_trace_") as td:
            serve_window(fleet, traffic, rec, bench,
                         trace_dir=td if trace else None, counter=counter)
            if trace:
                from bench import trace as btrace
                rec.trace = btrace.load(td)
        rec.info["peak_bytes_in_use"] = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in devs)
        free_fleet(fleet)
        del fleet
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    metrics = {}
    for m in bench.metrics(cell_name, trace):
        v = bench.reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    judged = check.run(bench, rec, seed, control=control, log=log)
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(list(devices)),
              "memory_peak_bytes": int(rec.info["peak_bytes_in_use"])}
    attempted = rec.due_in_window() if rec.open_loop else [
        s for s in rec.sent if s.sent <= rec.t1]
    which = "control_" if control else ""
    out = {"correct": judged[which + "correct"],
           "attempted": len(attempted),
           "failed": sum(1 for s in attempted if failed(s, rec.open_loop)),
           "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = rec.trace.busy_s
        device["window_s"] = rec.trace.window_s
        out["breakdown"] = rec.trace.breakdown()
    _info_lines(rec, log)
    if control:
        out["program_checks"] = judged["checks"]
    out["checks"] = judged[which + "checks"]
    return out


def failed(s: Sent, open_loop: bool) -> bool:
    """Rejected, failed by the program, or (open loop) never served: a
    closed-loop request sent just before the window closed is in flight."""
    return bool(s.rejected is not None or s.req.error
                or (open_loop and not s.req.out))


def _info_lines(rec: Record, log) -> None:
    due = rec.due_in_window()
    log("info requests due_in_window=%d sent=%d rejected=%d "
        "first_token=%d completed=%d" % (
            len(due), len(rec.sent),
            sum(s.rejected is not None for s in rec.sent),
            sum(bool(s.req.out) for s in due),
            sum(bool(s.req.done and not s.req.error) for s in rec.sent)))
    steps = [b - a for a, b in rec.steps if rec.in_window(b)]
    log("info steps_in_window=%d mean_step_ms=%.3f tokens_in_window=%d" % (
        len(steps), 1e3 * sum(steps) / max(len(steps), 1),
        sum(1 for s in rec.sent for t in s.req.t_tok if rec.in_window(t))))
    if rec.open_loop:
        from bench.readers import ttft_p95_ms
        log(f"info ttft_p95_ms={ttft_p95_ms(rec)}")
    for k, v in sorted(rec.info.items()):
        log(f"info {k}={v}")
    for st in sorted(rec.slow_steps, key=lambda x: -x["ms"])[:5]:
        log("info slow_step " + json.dumps(st))
    for ev in rec.events:
        log("info event " + json.dumps(
            {k: v for k, v in ev.items() if not k.startswith("t_")},
            default=str))
