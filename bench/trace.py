"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: the traced window, device busy time, time per device op,
the kernels' time, and the idle gaps named by the harness span that was
open on the host.

The harness marks the window with a ``bench.window`` annotation and each
call into the program with a ``bench.<call>`` annotation
(``jax.profiler.TraceAnnotation``), so host spans and device ops share the
profiler's clock. Device ops are the events of a device plane's
"XLA Ops" line, named there by their HLO text ("%paged_decode.6 = ...");
an op is keyed by its HLO name ("paged_decode.6"), and a kernel by that
name without its numeric suffix ("paged_decode"). Control-flow ops
("while", "conditional", "call") enclose the ops of their bodies: they
count towards busy time but not towards any op's own time.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
PARENTS = ("while", "conditional", "call")


@dataclasses.dataclass
class Trace:
    window_ns: tuple            # (start, end) of the bench.window span
    busy_ns: float              # union of device-op intervals, per chip
    op_ns: dict                 # op name -> summed device time, all chips
    gaps: list                  # [(length_ns, host span name)] per chip
    chips: int

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9

    def kernel_s(self, kernel: str) -> float | None:
        """Summed device time of every op of ``kernel`` (its HLO name
        without the numeric suffix); None when the trace holds none."""
        hit = [n for n in self.op_ns if base_name(n) == kernel]
        if not hit:
            return None
        return sum(self.op_ns[n] for n in hit) / 1e9

    def breakdown(self, k: int = 10) -> dict:
        ops = sorted(self.op_ns.items(), key=lambda kv: -kv[1])[:k]
        gaps = sorted(self.gaps, key=lambda g: -g[0])[:k]
        return {"device_ops": [[n, ns / 1e9] for n, ns in ops],
                "idle_gaps": [[name, ns / 1e9] for ns, name in gaps]}


def op_name(event_name: str) -> str:
    """"%paged_decode.6 = bf16[...] custom-call(...)" -> "paged_decode.6"."""
    return event_name.split(" = ", 1)[0].split(" ", 1)[0].lstrip("%")


def base_name(name: str) -> str:
    return re.sub(r"\.\d+$", "", name)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _host_spans(planes):
    """Every ``bench.*`` annotation on the host planes: (start, end, name)."""
    spans = []
    for plane in planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    return spans


def _span_at(spans, t):
    """The innermost harness span (other than the window) open at ``t``."""
    best = None
    for s, e, name in spans:
        if name != WINDOW and s <= t <= e:
            if best is None or e - s < best[1] - best[0]:
                best = (s, e, name)
    return best[2] if best else "no harness span"


def reduce(planes) -> Trace:
    """Reduce the planes of a ``jax.profiler.ProfileData``."""
    planes = list(planes)
    spans = _host_spans(planes)
    wins = [(s, e) for s, e, n in spans if n == WINDOW]
    if not wins:
        raise ValueError(f"trace holds no {WINDOW!r} annotation")
    w0, w1 = wins[0]
    busy = 0.0
    op_ns = collections.Counter()
    gaps = []
    chips = 0
    for plane in planes:
        if not plane.name.startswith("/device:") or "CPU" in plane.name:
            continue
        ivals = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s = max(ev.start_ns, w0)
                e = min(ev.start_ns + ev.duration_ns, w1)
                if e <= s:
                    continue
                ivals.append((s, e))
                name = op_name(ev.name)
                if base_name(name) not in PARENTS:
                    op_ns[name] += e - s
        if not ivals:
            continue
        chips += 1
        merged = _union(ivals)
        busy += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                gaps.append((ge - gs, _span_at(spans, (gs + ge) / 2)))
    if chips == 0:
        raise ValueError("trace holds no device op inside the window")
    return Trace(window_ns=(w0, w1), busy_ns=busy / chips, op_ns=dict(op_ns),
                 gaps=gaps, chips=chips)


def load(log_dir: str) -> Trace:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(find_xplane(log_dir)).planes)
