"""Arithmetic shared by the metric readers under ``bench/metrics``. Each
function takes the run's ``harness.Record`` and returns the metric's value,
or None when the run holds nothing for it to read."""
from __future__ import annotations

from bench import roofline
from bench.stats import percentile


# -- end to end (host clock) -------------------------------------------------
def ttft_p95_ms(rec):
    """95th percentile (nearest rank) over every request due in the window
    of first-token time minus due time; a rejected or failed request, or
    one that never got a token, counts as +inf."""
    due = rec.due_in_window()
    if not due:
        return None
    vals = [(s.req.t_tok[0] - s.due) * 1e3
            if s.rejected is None and not s.req.error and s.req.t_tok
            else float("inf") for s in due]
    return percentile(vals, 0.95)


def itl_p95_ms(rec):
    """95th percentile over every gap between consecutive tokens of every
    request, for the gaps that end in the window."""
    gaps = [(b - a) * 1e3 for s in rec.sent
            for a, b in zip(s.req.t_tok, s.req.t_tok[1:])
            if rec.in_window(b)]
    return percentile(gaps, 0.95) if gaps else None


def tokens_per_s(rec):
    """Output tokens emitted in the window over the window's length."""
    n = sum(1 for s in rec.sent for t in s.req.t_tok if rec.in_window(t))
    return n / rec.seconds


def _pause(rec):
    return next((e for e in rec.events if e["op"] == "pause_unpause"), None)


def reconf_stall_ms(rec):
    """From the pause_live call to the first token the paused engine emits
    after unpause returns."""
    ev = _pause(rec)
    if ev is None:
        return None
    after = [t for s in rec.sent if s.engine == ev["engine"]
             for t in s.req.t_tok if t > ev["t_return"]]
    return (min(after) - ev["t_call"]) * 1e3 if after else None


# -- per layer (traced run) --------------------------------------------------
def _traced_steps(rec):
    a, b = rec.trace_host
    return [(s, e) for s, e in rec.steps if s >= a and e <= b]


def fleet_step_ms(rec):
    """Mean host time of one ``ServeFleet.step`` call in the traced
    window, from the harness's spans around the calls."""
    steps = _traced_steps(rec)
    if rec.trace is None or not steps:
        return None
    return 1e3 * sum(e - s for s, e in steps) / len(steps)


def device_idle_share(rec):
    """Share of the traced window in which no op ran on the device, %."""
    if rec.trace is None:
        return None
    return 100.0 * (1.0 - rec.trace.busy_s / rec.trace.window_s)


#: the HLO name of ``kernels/paged_decode``'s Pallas call in the trace
PAGED_DECODE = "paged_decode"


def paged_decode_roofline(rec):
    """Least time of every ``paged_decode`` call in the traced window (from
    each decoded request's valid positions) over the kernel's summed
    device time, %."""
    if rec.trace is None:
        return None
    kernel_s = rec.trace.kernel_s(PAGED_DECODE)
    if not kernel_s:
        return None
    m = rec.dims
    least = 0.0
    for _, _, ctxs, slots, width in rec.decode_log:
        if ctxs:
            f, b = roofline.paged_decode_call(m, ctxs, slots, width)
            least += m.layers * roofline.least_seconds(f, b, rec.peaks)
    return 100.0 * least / kernel_s if least else None


def mfu(rec):
    """Model FLOPs of the prompt and output tokens processed in the traced
    window, over the window and the chip's bf16 peak, %. A prompt counts
    where its first token lands in the window; an output token where it is
    emitted."""
    if rec.trace is None:
        return None
    a, b = rec.trace_host
    m = rec.dims
    flops = 0.0
    for s in rec.sent:
        for i, t in enumerate(s.req.t_tok):
            if a <= t <= b:
                flops += (roofline.prompt_flops(m, s.plen) if i == 0
                          else roofline.token_flops(m, s.plen + i))
    if not flops:
        return None
    return 100.0 * flops / ((b - a) * rec.peaks["bf16_flops_per_s"])


def pause_stop_ms(rec):
    """The stop-and-copy time ``pause_live`` reports (``stop_ms``)."""
    ev = _pause(rec)
    return None if ev is None else ev["stop_ms"]


def pause_restore_ms(rec):
    """The whole of ``unpause``, as its phase timings report it."""
    ev = _pause(rec)
    return None if ev is None else ev["restore_ms"]
