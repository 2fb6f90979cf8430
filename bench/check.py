"""Whether what the timed path served is correct.

Once the window has closed and the fleet is freed, a sample of the requests
the run finished is drawn from the seed: always the longest greedy and the
longest sampled one, for each event the requests whose streams span it,
then others until the sample holds ``min_tokens`` served tokens. The
configuration's plain reference runs once over each prompt with its served
tokens (float32, weights made again from the seed), and the number compared
is the widest gap by which a served token lies from what the serving rule
picks on the reference's logits (``bench.gumbel.token_gaps``): for a greedy
token, how far its logit lies below the best; for a sampled one, how far it
lies outside the top-k, or how far its logit perturbed by the request's
Gumbel noise lies below the best perturbed logit. A server that computes
what the model says only differs where two logits are within its rounding;
a wrong cache, kernel, head, restore, temperature or top-k lies far off.

With ``control`` the same prompts and tokens are read again with the
control in the program's place (the reference with fp8 weights, serving by
the same rule), and the control's tokens are judged by the same limit.
"""
from __future__ import annotations

import time

import numpy as np


def sample(rec, seed: int) -> list:
    chk = rec.cfg["check"]
    # a sampled request without a top-k is not compared: its competitors
    # would be the whole vocabulary (no mix sends one)
    cands = [s for s in rec.sent
             if s.rejected is None and s.req.done and not s.req.error
             and s.req.out
             and (s.arrival.temperature <= 0 or s.arrival.top_k > 0)]
    if not cands:
        return []
    rng = np.random.default_rng([seed, 3])
    chosen = []
    for greedy in (True, False):
        kind = [s for s in cands if (s.arrival.temperature <= 0) == greedy]
        if kind:
            chosen.append(max(kind, key=lambda s: (s.plen + len(s.req.out),
                                                   s.arrival.rid)))
    for ev in rec.events:
        span = [s for s in cands if s not in chosen
                and any(t < ev["t_call"] for t in s.req.t_tok)
                and any(t > ev["t_return"] for t in s.req.t_tok)]
        for i in rng.permutation(len(span))[:2]:
            chosen.append(span[int(i)])
    rest = [s for s in cands if s not in chosen]
    for i in rng.permutation(len(rest)):
        if (sum(len(s.req.out) for s in chosen) >= chk["min_tokens"]
                or len(chosen) >= chk["max_requests"]):
            break
        chosen.append(rest[int(i)])
    return chosen


def _block(block: list, batch: int, seq_len: int, probes: int) -> dict:
    """The reference's inputs for up to ``batch`` requests: each prompt with
    its served tokens, the positions whose next token was served, and at
    each the token, sampling parameters and Gumbel key."""
    b = {"tokens": np.zeros((batch, seq_len), np.int32),
         "pos": np.zeros((batch, probes), np.int32),
         "tok": np.zeros((batch, probes), np.int32),
         "temp": np.zeros((batch, probes), np.float32),
         "top_k": np.ones((batch, probes), np.int32),
         "keys": np.zeros((batch, probes, 3), np.uint32),
         "valid": np.zeros((batch, probes), bool)}
    for j, s in enumerate(block):
        out = np.asarray(s.req.out, np.int64)
        seq = np.concatenate([s.arrival.prompt, out[:-1]])
        n = len(out)
        b["tokens"][j, :len(seq)] = seq
        b["pos"][j, :n] = s.plen - 1 + np.arange(n)
        b["tok"][j, :n] = np.clip(out, -1, np.iinfo(np.int32).max)
        b["temp"][j, :n] = max(float(s.arrival.temperature), 0.0)
        b["top_k"][j, :n] = max(int(s.arrival.top_k), 1)
        b["keys"][j, :n, 0] = s.arrival.seed & 0xFFFFFFFF
        b["keys"][j, :n, 1] = s.arrival.rid & 0xFFFFFFFF
        b["keys"][j, :n, 2] = np.arange(n)
        b["valid"][j, :n] = True
    return b


def readings(ref, weights, dims, chosen: list, chk: dict, seq_len: int,
             probes: int, control: bool) -> dict:
    """Widest gap of the served tokens (and of the control's) over the
    sample, and how many served tokens of each kind were compared."""
    batch = chk["reference_batch"]
    kmax = max([1] + [int(s.arrival.top_k) for s in chosen])
    margin = float(chk["max_logit_gap"])
    worst, worst_ctl, greedy, sampled = -np.inf, -np.inf, 0, 0
    for i in range(0, len(chosen), batch):
        b = _block(chosen[i:i + batch], batch, seq_len, probes)
        args = (b["tokens"], b["pos"])
        rule = (b["temp"], b["top_k"], b["keys"])
        probe = [b["tok"]]
        if control:
            probe.append(np.asarray(ref.pick(weights, *args, *rule, m=dims,
                                             quant="fp8", kmax=kmax)))
        g = np.asarray(ref.gaps(weights, *args, np.stack(probe, -1), *rule,
                                m=dims, kmax=kmax, margin=margin))
        v = b["valid"]
        worst = max(worst, float(g[..., 0][v].max()))
        if control:
            worst_ctl = max(worst_ctl, float(g[..., 1][v].max()))
        greedy += int((v & (b["temp"] <= 0)).sum())
        sampled += int((v & (b["temp"] > 0)).sum())
    out = {"max_logit_gap": worst, "tokens_checked": greedy + sampled,
           "greedy_tokens": greedy, "sampled_tokens": sampled}
    if control:
        out["control_max_logit_gap"] = worst_ctl
    return out


def verdict(gap, tokens: int, chk: dict) -> tuple:
    """``correct`` and the numbers compared, each beside its limit."""
    checks = {"max_logit_gap": {"value": gap, "limit": chk["max_logit_gap"]},
              "tokens_checked": {"value": tokens, "limit": 1}}
    correct = tokens >= 1 and gap is not None and gap <= chk["max_logit_gap"]
    # a reading of inf (a served id outside the vocabulary) is not JSON
    if gap is not None and not np.isfinite(gap):
        checks["max_logit_gap"]["value"] = str(gap)
    return bool(correct), checks


def run(bench, rec, seed: int, *, control: bool = False, log=print) -> dict:
    """``correct`` and ``checks`` of the program's tokens; with ``control``
    also ``control_correct`` and ``control_checks``, the same verdict on
    the control's tokens."""
    chk = rec.cfg["check"]
    chosen = sample(rec, seed)
    t = time.perf_counter()
    r = {"max_logit_gap": None, "tokens_checked": 0, "greedy_tokens": 0,
         "sampled_tokens": 0, "control_max_logit_gap": None}
    if chosen:
        weights = bench.family(rec.cfg).make_weights(rec.cfg, seed)
        r.update(readings(bench.reference(rec.cfg), weights, rec.dims,
                          chosen, chk, rec.cfg["serve"]["max_len"],
                          int(rec.mix["output"]["max"]), control))
        del weights
    log(f"info reference: {len(chosen)} requests, {r['greedy_tokens']} "
        f"greedy and {r['sampled_tokens']} sampled tokens, "
        f"{time.perf_counter() - t:.2f} s")
    correct, checks = verdict(r["max_logit_gap"], r["tokens_checked"], chk)
    out = {"correct": correct, "checks": checks}
    if control:
        out["control_correct"], out["control_checks"] = verdict(
            r["control_max_logit_gap"], r["tokens_checked"], chk)
    return out
