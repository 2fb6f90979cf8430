"""§Elastic: static vs autoscaler-driven fleets under shaped traffic.

The claim under test: closing the loop from observed load back into VF
reconfiguration (scale-out / scale-in / rebalance through the journaled
manager ops) beats a static fleet on SLO-miss rate and rejection rate
under non-stationary traffic, without taxing inter-token latency.

Protocol (see EXPERIMENTS.md §Elastic): one STATIC fleet (1 engine, no
control plane) and one ELASTIC fleet (1 engine + 3 warm parked standbys
on pre-carved spare VFs, ``AutoscaleConfig(max_engines=4)``) serve the
same four traffic traces —

  steady    constant light load (the baseline; both fleets cope)
  ramp      arrivals grow linearly 0 -> ~3x one engine's service rate
  spike     light baseline with a short burst of ~3x slo_max_load
  diurnal   one sinusoid period, peak ~2.5x one engine's service rate

— one request wave + one fleet step per tick; the elastic fleet runs one
``autoscale_step`` per tick. Rejected requests are dropped and counted.

Latency is measured in TICKS (fleet steps), not wall time: on real
hardware every VF's engine steps in parallel on its own devices, whereas
this host steps them sequentially, so wall time would charge scale-out
for concurrency the hardware provides for free. Tick-space is the
hardware-independent measure (the same convention the pause-path
hillclimb uses for the zero-copy CPU grid); wall-clock percentiles are
still reported per row as context. SLOs: first token within
``SLO_TTFT_TICKS`` of submission, mean inter-token gap <=
``SLO_ITL_TICKS``. A rejected request counts as an SLO miss (it got no
conformant service), so shedding load cannot fake a good miss rate.

Acceptance gates (committed BENCH_elastic.json):
  * spike & ramp: elastic slo_miss_rate AND rejection_rate strictly
    below static;
  * every elastic trace's itl_ticks_p95 <= 1.1x the static steady-state
    itl_ticks_p95 (elasticity must not tax serving cadence).
CI reruns a reduced trace on PRs with the same gates.
"""
import argparse
import json
import math
import sys
import time

SLO_TTFT_TICKS = 4       # first token within ~half a slot-generation
SLO_ITL_TICKS = 1.5      # sustained decode cadence: ~a token per tick


def make_traces(ticks: int, peak: int) -> dict:
    """Per-tick arrival counts, deterministic."""
    third = max(1, ticks // 3)
    return {
        "steady": [1 if t % 2 == 0 else 0 for t in range(ticks)],
        "ramp": [round(peak * t / (ticks - 1)) for t in range(ticks)],
        "spike": [1 if t % 2 == 0 else 0 for t in range(ticks)][:third]
                 + [peak * 4] * 2
                 + [1 if t % 2 == 0 else 0
                    for t in range(ticks - third - 2)],
        "diurnal": [round(peak * 0.8 * (0.5 - 0.5 * math.cos(
            2 * math.pi * t / (ticks - 1)))) for t in range(ticks)],
    }


def pct(xs, q):
    from repro.serve import percentile
    return percentile(xs, q)


class _Rids:
    def __init__(self):
        self.n = 0

    def take(self):
        self.n += 1
        return self.n


def make_request(rng, vocab, rid, max_new):
    from repro.serve import Request
    # fixed prompt length: ONE prefill executable per engine, so warming
    # stays cheap even with 4 engines x 2 fleets
    return Request(rid=rid, prompt=rng.integers(0, vocab, 8),
                   max_new_tokens=max_new)


def warm_fleet(fleet, vocab, max_new):
    """Compile every executable each engine (attached AND parked) will
    need: one prefill at the fixed prompt length + one decode crossing a
    page boundary."""
    from repro.serve import Request
    import numpy as np
    rng = np.random.default_rng(99)
    for tn in fleet.tenants.values():
        eng = tn.engine
        eng.submit(Request(rid=900_000 + fleet._order[tn.tid],
                           prompt=rng.integers(0, vocab, 8),
                           max_new_tokens=max(max_new, 24)))
        eng.unpause()
        eng.run_until_idle()


def drive(fleet, trace, rng, vocab, rids, *, max_new, elastic,
          max_drain_ticks=2000):
    """Run one trace; returns per-request tick/wall stats. The tick
    counter keeps advancing through the post-trace drain, so queue debt
    built during the trace is paid on the record."""
    from repro.serve import RequestRejected
    live, finished, actions = [], [], []
    offered = rejected = 0
    t0 = time.perf_counter()

    def poll(tick):
        for rec in list(live):
            r = rec["req"]
            if rec["first_tick"] is None and r.out:
                rec["first_tick"] = tick
            if r.done:
                rec["done_tick"] = tick
                rec["tokens"] = len(r.out)
                finished.append(rec)
                live.remove(rec)

    tick = 0
    for tick, n in enumerate(trace):
        for _ in range(n):
            r = make_request(rng, vocab, rids.take(), max_new)
            offered += 1
            r.t_submit = time.perf_counter()
            try:
                fleet.submit(r)
                live.append({"req": r, "submit_tick": tick,
                             "first_tick": None})
            except RequestRejected:
                rejected += 1          # dropped: the caller's retry policy
        if elastic:
            act = fleet.autoscale_step()
            if act is not None:
                actions.append({"tick": tick, "kind": act.kind,
                                "reason": act.reason})
        fleet.step()
        poll(tick)
    while live and tick < len(trace) + max_drain_ticks:
        tick += 1
        if elastic:
            act = fleet.autoscale_step()
            if act is not None:
                actions.append({"tick": tick, "kind": act.kind,
                                "reason": act.reason})
        fleet.step()
        poll(tick)
    assert not live, "trace left stranded work"
    res = fleet.drain()
    assert res.drained
    return finished, offered, rejected, actions, time.perf_counter() - t0


def row_for(name, mode, recs, offered, rejected, wall, actions):
    ttft_t = [rec["first_tick"] - rec["submit_tick"] for rec in recs]
    itl_t = [(rec["done_tick"] - rec["first_tick"])
             / max(rec["tokens"] - 1, 1) for rec in recs]
    ttft_w, itl_w = [], []
    for rec in recs:
        r = rec["req"]
        if r.t_tok:
            ttft_w.append(r.t_tok[0] - r.t_submit)
            itl_w.extend(b - a for a, b in zip(r.t_tok, r.t_tok[1:]))
    # SLO accounting over the OFFERED load: rejected = missed
    miss = rejected + sum(
        1 for tt, it in zip(ttft_t, itl_t)
        if tt > SLO_TTFT_TICKS or it > SLO_ITL_TICKS)
    return {"trace": name, "mode": mode, "offered": offered,
            "completed": len(recs), "rejected": rejected,
            "rejection_rate": round(rejected / max(offered, 1), 4),
            "slo_miss_rate": round(miss / max(offered, 1), 4),
            "ttft_ticks_p50": pct(ttft_t, 0.5),
            "ttft_ticks_p95": pct(ttft_t, 0.95),
            "itl_ticks_p50": round(pct(itl_t, 0.5), 3),
            "itl_ticks_p95": round(pct(itl_t, 0.95), 3),
            "ttft_p95_ms": round(pct(ttft_w, 0.95) * 1e3, 3),
            "itl_p95_ms": round(pct(itl_w, 0.95) * 1e3, 3),
            "wall_s": round(wall, 3), "actions": actions}


def reset_elastic(fleet, min_engines):
    """Between traces: park extra engines and forget control-plane state,
    so each trace starts from the same 1-engine fleet."""
    from repro.core.autoscaler import Autoscaler
    from repro.serve.telemetry import MetricsBus
    running = sorted(
        (tn for tn in fleet.tenants.values() if tn.status == "running"),
        key=lambda tn: fleet._order[tn.tid])
    for tn in running[min_engines:]:
        fleet.scale_in(tn.tid)
    if fleet.autoscaler is not None:
        fleet.autoscaler = Autoscaler(fleet.autoscale_config)
    fleet.telemetry = MetricsBus()
    fleet.rejections.clear()
    fleet.rejected_total = 0


def bench(ticks=60, peak=3, max_new=8, slots=8, slo_max_load=16,
          seed=0):
    import tempfile
    import jax
    import numpy as np
    from repro.configs import make_run_config
    from repro.core.autoscaler import AutoscaleConfig
    from repro.models.model import build_model
    from repro.core.pool import token_devices
    from repro.serve import ServeFleet

    run = make_run_config("qwen3-0.6b", "decode_32k", smoke=True)
    model = build_model(run)
    params = model.init(jax.random.key(0))
    vocab = run.model.vocab_size
    kw = dict(devices=token_devices(8), slots=slots, max_len=256, paged=True,
              page_size=16, slo_max_load=slo_max_load)
    static = ServeFleet(run, params, num_engines=1,
                        workdir=tempfile.mkdtemp(prefix="svff_el_s_"),
                        **kw)
    # 3 warm standbys + 3 pre-carved spare VFs: scale-out is a pause-free
    # attach (the reconf grow path stays covered by tests); a lower hot
    # threshold + short cooldown reacts within ~2 ticks of a burst
    elastic = ServeFleet(run, params, num_engines=1, spare_engines=3,
                         num_vfs=4,
                         autoscale=AutoscaleConfig(
                             scale_out_load=0.5, hysteresis=1, cooldown=1,
                             rebalance_gap=6, max_engines=4,
                             min_engines=1, rebalance_migrate=False),
                         workdir=tempfile.mkdtemp(prefix="svff_el_e_"),
                         **kw)
    warm_fleet(static, vocab, max_new)
    warm_fleet(elastic, vocab, max_new)

    rows = [{"name": "protocol", "ticks": ticks, "peak_per_tick": peak,
             "max_new": max_new, "slots": slots,
             "slo_max_load": slo_max_load,
             "slo_ttft_ticks": SLO_TTFT_TICKS,
             "slo_itl_ticks": SLO_ITL_TICKS}]
    print(json.dumps(rows[0]))

    rids = _Rids()
    traces = make_traces(ticks, peak)
    by = {}
    for name, trace in traces.items():
        for mode, fleet in (("static", static), ("elastic", elastic)):
            rng = np.random.default_rng(seed + 7)   # same arrivals
            recs, offered, rejected, actions, wall = drive(
                fleet, trace, rng, vocab, rids, max_new=max_new,
                elastic=(mode == "elastic"))
            row = row_for(name, mode, recs, offered, rejected, wall,
                          actions)
            rows.append(row)
            by[(name, mode)] = row
            print(json.dumps(row))
            if mode == "elastic":
                reset_elastic(fleet, 1)

    # guard ONLY the degenerate no-sample case (p95 == 0.0); a real
    # sub-1.0 steady p95 must stay the gate's denominator, or the 1.1x
    # target would be silently loosened
    st = by[("steady", "static")]["itl_ticks_p95"]
    steady_itl = st if st > 0 else 1.0
    summary = {"name": "summary",
               "static_steady_itl_ticks_p95": steady_itl,
               "itl_ratio_target": 1.1}
    gates = []
    for name in ("spike", "ramp"):
        s, e = by[(name, "static")], by[(name, "elastic")]
        summary[f"{name}_rejection_static"] = s["rejection_rate"]
        summary[f"{name}_rejection_elastic"] = e["rejection_rate"]
        summary[f"{name}_slo_miss_static"] = s["slo_miss_rate"]
        summary[f"{name}_slo_miss_elastic"] = e["slo_miss_rate"]
        gates.append(e["rejection_rate"] < s["rejection_rate"])
        gates.append(e["slo_miss_rate"] < s["slo_miss_rate"])
    ratios = {name: round(by[(name, "elastic")]["itl_ticks_p95"]
                          / steady_itl, 3)
              for name in traces}
    summary["elastic_itl_ticks_p95_vs_static_steady"] = ratios
    summary["actions_per_trace"] = {
        name: [a["kind"] for a in by[(name, "elastic")]["actions"]]
        for name in traces}
    summary["elastic_beats_static_spike_ramp"] = all(gates)
    summary["itl_within_target"] = (
        max(ratios.values()) <= summary["itl_ratio_target"])
    rows.append(summary)
    print(json.dumps(summary))
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ticks", type=int, default=60)
    ap.add_argument("--peak", type=int, default=3,
                    help="requests/tick at the ramp's end")
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--slo-max-load", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    rows = bench(ticks=args.ticks, peak=args.peak, max_new=args.max_new,
                 slots=args.slots, slo_max_load=args.slo_max_load,
                 seed=args.seed)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
        print(f"wrote {args.out}")
    summary = rows[-1]
    ok = (summary["elastic_beats_static_spike_ramp"]
          and summary["itl_within_target"])
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
