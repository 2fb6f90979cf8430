"""The program's own spans in a profiler trace, and the per-layer numbers
they give.

The program marks its layers with ``svff.*`` annotations
(``repro.runtime.spans``): ``svff.fleet.step``, ``svff.engine.step`` and
its parts (``admit``, ``prefill``, ``place``, ``prepare``, ``decode``,
``readback``, ``bookkeep``), the pause and unpause phases
(``svff.pause.precopy_0``, ...), and the staging engine's calls and
transfers (``svff.staging.save``, ``svff.staging.d2h`` with a ``bytes``
stat, ...). A child is a span whose interval lies inside its parent's:
spans of the transfer threads may share a line with the main thread in the
profiler's output, so nesting is read from the intervals, not the lines.

The readers take the spans of the traced window and return None when it
holds none of theirs. ``bench/trace.py`` does not keep the spans yet, so
no metric file reads them; until it does, this script runs a cell as
``bench/run.py --trace 1`` does and prints what they read, with the idle
gaps named by the innermost program span as well:

    python3 bench/spans.py --workload qwen3-chat --seed <n> --seconds <s>

The last line of its output is ``{"spans": {...}, "span_count_s": {...},
"idle_gaps": [...]}``.
"""
from __future__ import annotations

import collections
import json
import os
import sys

if __package__ in (None, ""):
    # run as a script: the checkout root in place of this script's
    # directory, and the program's sources
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[0:1] = [_ROOT, os.path.join(_ROOT, "src")]

from bench import trace  # noqa: E402

PREFIX = "svff."

Span = collections.namedtuple("Span", "name start end stats")


def collect(planes, window_ns) -> list:
    """Every ``svff.*`` event of the host planes that lies inside the
    window, ordered by start."""
    w0, w1 = window_ns
    out = []
    for plane in planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith(PREFIX):
                    continue
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if w0 <= s and e <= w1:
                    out.append(Span(ev.name, s, e, dict(ev.stats)))
    return sorted(out, key=lambda sp: (sp.start, -sp.end))


def _named(spans, name):
    return [s for s in spans if s.name == PREFIX + name]


def _inside(spans, parent):
    return [s for s in spans if s is not parent
            and parent.start <= s.start and s.end <= parent.end]


def _covered(intervals) -> float:
    return sum(e - s for s, e in trace._union(intervals))


def innermost(spans, t):
    """The shortest program span open at ``t``, or None."""
    best = None
    for s in spans:
        if s.start <= t <= s.end and (best is None or
                                      s.end - s.start < best.end - best.start):
            best = s
    return best


def idle_gaps(planes, window_ns, spans, k: int = 10) -> list:
    """The ``k`` longest idle gaps of the devices inside the window, as
    ``[label, seconds]``: the label of ``bench/trace.py`` (the innermost
    harness span), followed by ``/<program span>`` where a program span
    is open at the gap's midpoint."""
    planes = list(planes)           # a ProfileData's planes iterate once
    w0, w1 = window_ns
    harness = trace._host_spans(planes)
    gaps = []
    for plane in planes:
        if not plane.name.startswith("/device:") or "CPU" in plane.name:
            continue
        ivals = [(max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1))
                 for line in plane.lines if line.name == trace.OPS_LINE
                 for ev in line.events]
        merged = trace._union([(s, e) for s, e in ivals if e > s])
        if not merged:
            continue
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                mid = (gs + ge) / 2
                label = trace._span_at(harness, mid)
                inner = innermost(spans, mid)
                if inner is not None:
                    label += "/" + inner.name
                gaps.append((ge - gs, label))
    gaps.sort(key=lambda g: -g[0])
    return [[name, ns / 1e9] for ns, name in gaps[:k]]


# -- readers -----------------------------------------------------------------
def engine_host_ms(spans):
    """Mean over the ``svff.engine.step`` spans of the span's length less
    the union of its ``svff.engine.readback`` children: host time per step
    not spent waiting on the chip, ms."""
    readbacks = _named(spans, "engine.readback")
    steps = _named(spans, "engine.step")
    if not steps:
        return None
    host = [(st.end - st.start)
            - _covered([(r.start, r.end) for r in _inside(readbacks, st)])
            for st in steps]
    return sum(host) / len(host) / 1e6


def prefill_step_share(spans):
    """Share of the ``svff.engine.step`` spans that hold an
    ``svff.engine.prefill`` child, %."""
    prefills = _named(spans, "engine.prefill")
    steps = _named(spans, "engine.step")
    if not steps:
        return None
    return 100.0 * sum(1 for st in steps
                       if _inside(prefills, st)) / len(steps)


def pause_d2h_gbps(spans):
    """Bytes of the ``svff.staging.d2h`` spans over the union of their
    intervals: the rate at which the pause fetches device state, GB/s."""
    d2h = _named(spans, "staging.d2h")
    ns = _covered([(s.start, s.end) for s in d2h])
    if not ns:
        return None
    return sum(s.stats.get("bytes", 0) for s in d2h) / ns


def pause_staging_host_ms(spans):
    """Summed length of the ``svff.staging.save`` and
    ``svff.staging.restore`` spans less the part of each that its
    ``svff.staging.d2h`` / ``svff.staging.h2d`` children cover: staging's
    host self time (slice dispatch, digests, assembly), ms."""
    calls = _named(spans, "staging.save") + _named(spans, "staging.restore")
    if not calls:
        return None
    moves = _named(spans, "staging.d2h") + _named(spans, "staging.h2d")
    ns = sum((c.end - c.start)
             - _covered([(m.start, m.end) for m in _inside(moves, c)])
             for c in calls)
    return ns / 1e6


READERS = {"engine_host_ms.chat": engine_host_ms,
           "prefill_step_share.chat": prefill_step_share,
           "pause_d2h_gbps": pause_d2h_gbps,
           "pause_staging_host_ms": pause_staging_host_ms}


def report(planes, window_ns) -> dict:
    """What the readers read from a trace's planes, with the mean
    ``svff.fleet.step`` (the program's own view of the harness's step),
    the count and summed seconds of each span name, and the idle gaps
    named down to the program span."""
    planes = list(planes)
    spans = collect(planes, window_ns)
    steps = _named(spans, "fleet.step")
    out = {name: read(spans) for name, read in READERS.items()}
    out["fleet_step_span_ms"] = (
        sum(s.end - s.start for s in steps) / len(steps) / 1e6
        if steps else None)
    by_name = collections.defaultdict(lambda: [0, 0.0])
    for s in spans:
        by_name[s.name][0] += 1
        by_name[s.name][1] += (s.end - s.start) / 1e9
    return {"spans": out, "span_count_s": dict(sorted(by_name.items())),
            "idle_gaps": idle_gaps(planes, window_ns, spans)}


def main(argv=None) -> int:
    from bench import run           # first: set-up is counted from here
    from jax.profiler import ProfileData
    kept = {}
    load = trace.load

    def load_and_read(log_dir):
        t = load(log_dir)
        planes = ProfileData.from_file(trace.find_xplane(log_dir)).planes
        kept.update(report(planes, t.window_ns))
        return t
    trace.load = load_and_read
    args = list(sys.argv[1:] if argv is None else argv)
    rc = run.main(args + ["--trace", "1"])
    print(json.dumps(kept), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
