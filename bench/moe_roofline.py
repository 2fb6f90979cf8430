"""Operations and bytes of the sparse-expert, sliding-window stacks'
kernels (``bench/families/moe.py``), and the readers of their roofline
shares. Kept with the benchmark so that no change to the program can move
them; the chip's peaks are ``bench/roofline.py``'s.

``paged_decode`` at a layer of window W reads the K and V rows of
min(ctx, W) positions of each decoded request, at a full layer ctx.

``grouped_ffn`` (one call per layer of every decode step and prefill
chunk) needs 6·D·F FLOPs per (token, held expert) pair, the weights of
every held expert that a pair reaches, and the pairs' rows in (bf16) and
out (float32). The harness hands no engine counter to a reader, so the
pairs and the experts reached are their expectations for routing spread
evenly over the experts: a token reaches a held expert with probability
k / E, so T tokens make T·k·Eh/E pairs and reach Eh·(1 - (1 - k/E)^T)
held experts. Only the decoded or prefilled tokens count, not the idle
slots and chunk padding the kernel also computes.
"""
from __future__ import annotations

import math

from bench import roofline


def decode_least_s(m, ctxs, slots: int, width: int, peaks: dict) -> float:
    """Least time of one decode step's ``paged_decode`` calls, one per
    layer, each bounded by its own FLOPs or bytes."""
    total = 0.0
    for w in m.windows:
        seen = [min(c, w) if w else c for c in ctxs]
        f, b = roofline.paged_decode_call(m, seen, slots, width)
        total += roofline.least_seconds(f, b, peaks)
    return total * m.layers / m.period


def expert_ffn_call(m, tokens: int):
    """(FLOPs, bytes) of one ``grouped_ffn`` call over ``tokens`` tokens."""
    share = m.top_k / m.experts
    pairs = tokens * m.top_k * m.experts_held / m.experts
    reached = m.experts_held * (1.0 - (1.0 - share) ** tokens)
    flops = 6.0 * m.d * m.expert_ff * pairs
    nbytes = reached * 3 * m.d * m.expert_ff * 2 + pairs * m.d * (2 + 4)
    return flops, nbytes


def _traced_steps(rec):
    a, b = rec.trace_host
    return [(s, e) for s, e in rec.steps if s >= a and e <= b]


def chunk_tokens(rec) -> list:
    """Tokens of every prefill chunk that ran in the traced window. The
    engine runs one chunk a step, the oldest prompt's first, so a prompt's
    chunks are the steps that end with the one that gave its first token."""
    C = rec.cfg["serve"]["prefill_chunk"]
    a, b = rec.trace_host
    steps = sorted(rec.steps)
    starts = [s for s, _ in steps]
    out = []
    for s in rec.sent:
        if not s.req.t_tok:
            continue
        t0 = s.req.t_tok[0]
        i = next((k for k in range(len(steps))
                  if steps[k][0] <= t0 <= steps[k][1]), None)
        if i is None:
            continue
        n = math.ceil(s.plen / C)
        for c in range(n):
            k = i - (n - 1 - c)
            if k >= 0 and starts[k] >= a and steps[k][1] <= b:
                out.append(min(C, s.plen - c * C))
    return out


def paged_decode_roofline(rec):
    """Least time of every ``paged_decode`` call in the traced window over
    the kernel's summed device time, %."""
    if rec.trace is None:
        return None
    kernel_s = rec.trace.kernel_s("paged_decode")
    if not kernel_s:
        return None
    least = sum(decode_least_s(rec.dims, ctxs, slots, width, rec.peaks)
                for _, _, ctxs, slots, width in rec.decode_log if ctxs)
    return 100.0 * least / kernel_s if least else None


def expert_ffn_roofline(rec):
    """Least time of every ``grouped_ffn`` call in the traced window (the
    decode steps' and the prefill chunks') over the kernel's summed device
    time, %."""
    if rec.trace is None:
        return None
    kernel_s = rec.trace.kernel_s("grouped_ffn")
    if not kernel_s:
        return None
    m = rec.dims
    calls = [len(ctxs) for _, _, ctxs, _, _ in rec.decode_log if ctxs]
    calls += chunk_tokens(rec)
    least = sum(roofline.least_seconds(*expert_ffn_call(m, t), rec.peaks)
                for t in calls) * m.layers
    return 100.0 * least / kernel_s if least else None
