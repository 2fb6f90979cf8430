"""Smoke run of the SVFF serve path on a TPU.

Serves qwen3-0.6b at its full registered width (random weights made from
a seed) through ``ServeFleet``: one engine tenant on a VF under the
``SVFFManager``, paged KV cache, fused sampling. The engine is paused
live mid-stream, resumed, and drained. Checks:

  (a) every request completes;
  (b) every stream is bit-identical to the same requests on the same
      fleet with no pause (invariant I10);
  (c) the compiled decode step runs the Pallas kernels
      (``tpu_custom_call`` in its HLO);
  (d) the last-prompt-position and first-decode logits of the Pallas
      path match ``kernel_backend="reference"`` within ``LOGIT_RTOL``;
  (e) the engine's params and KV cache sit on its VF's device.

``--chips 4`` runs only the four-chip path and what it is compared with:
the requests on one engine on one chip, then on three engines (one chip
each, a fourth chip free) with one engine migrated onto the free chip
mid-stream. Greedy streams must equal the one-chip run's, every engine's
leaves must sit on its own chip, and the migrated engine's on the new one.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips

The last line of stdout is ``{"ok": true, "device": {...}}``; a failed
check exits non-zero before it. ``main`` refuses every platform but the
TPU. The phase functions take the run config (which carries the explicit
``interpret`` flag) and the devices, so a CPU test runs them at smoke size.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import make_run_config  # noqa: E402
from repro.models.model import build_model  # noqa: E402
from repro.serve import Request, ServeFleet  # noqa: E402
from repro.serve.paged import admit_kv, init_paged_cache  # noqa: E402
from repro.train.step import (make_decode_step,  # noqa: E402
                              make_serve_steps)

ARCH = "qwen3-0.6b"
#: relative L2 error allowed between Pallas and reference logits. Both
#: compute in bfloat16 (8 mantissa bits, eps 2^-8 ~ 0.4%); summation order
#: differs inside attention, and the rounding differences compound over
#: the layers. A wrong kernel (a dropped head, page or mask) is off by
#: tens of percent.
LOGIT_RTOL = 2e-2


@dataclasses.dataclass(frozen=True)
class Spec:
    smoke: bool            # the registered smoke model instead of full width
    requests: int
    prompt_lens: tuple     # cycled over request pairs (greedy, sampled)
    new_tokens: int
    slots: int
    max_len: int
    pause_after: int       # fleet steps before the live pause / migrate
    page_size: int = 16
    seed: int = 0


FULL = Spec(smoke=False, requests=16, prompt_lens=(32, 64, 128, 256),
            new_tokens=32, slots=8, max_len=512, pause_after=12)
SMOKE = Spec(smoke=True, requests=8, prompt_lens=(4, 8, 12, 16),
             new_tokens=6, slots=4, max_len=32, pause_after=3)


class CheckFailed(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    print(f"check {'PASS' if ok else 'FAIL'}: {what}", flush=True)
    if not ok:
        raise CheckFailed(what)


def build(spec: Spec, *, interpret: bool, kernel_backend: str = "auto"):
    """(run config, random params) for the spec's model."""
    run = make_run_config(ARCH, "decode_32k", smoke=spec.smoke,
                          kernel_backend=kernel_backend, interpret=interpret,
                          seed=spec.seed)
    params = build_model(run).init(jax.random.key(run.seed))
    return run, params


def make_requests(spec: Spec, vocab: int) -> list:
    """The same request set from the spec's seed, every call: pairs of one
    greedy and one sampled request (temperature 0.8, top-k 50) per
    prompt length."""
    rng = np.random.default_rng(spec.seed)
    reqs = []
    for i in range(spec.requests):
        plen = spec.prompt_lens[(i // 2) % len(spec.prompt_lens)]
        greedy = i % 2 == 0
        reqs.append(Request(
            rid=i, prompt=rng.integers(0, vocab, plen).astype(np.int32),
            max_new_tokens=spec.new_tokens, seed=spec.seed,
            temperature=0.0 if greedy else 0.8, top_k=0 if greedy else 50))
    return reqs


def build_fleet(run, params, devices, spec: Spec, *, num_engines: int,
                workdir: str) -> ServeFleet:
    return ServeFleet(run, params, devices=devices, num_engines=num_engines,
                      paged=True, fused_sampling=True, slots=spec.slots,
                      max_len=spec.max_len, page_size=spec.page_size,
                      workdir=workdir)


def chip_of(fleet: ServeFleet, tid: str):
    return fleet.pool.find(fleet.tenants[tid].vf_id).devices[0]


def leaves_on(engine, device) -> bool:
    """Every params and KV-cache leaf of ``engine`` lives on ``device``."""
    leaves = jax.tree.leaves(engine.params) + jax.tree.leaves(engine._cache)
    return bool(leaves) and all(x.devices() == {device} for x in leaves)


def all_done(reqs) -> bool:
    return all(r.done and not r.error
               and len(r.out) == r.max_new_tokens for r in reqs)


def phase_pause(run, params, devices, spec: Spec, workdir: str) -> dict:
    """One engine on ``devices[0]``: the requests once without a pause,
    then again with a live pause mid-stream. Checks (a), (b), (e)."""
    fleet = build_fleet(run, params, devices[:1], spec, num_engines=1,
                        workdir=workdir)
    vocab = run.model.vocab_size
    t0 = time.perf_counter()
    steady = make_requests(spec, vocab)
    for r in steady:
        fleet.submit(r)
    res = fleet.drain()
    first_s = time.perf_counter() - t0
    check(res.drained and all_done(steady),
          f"(a) unpaused run: {len(steady)} requests complete")

    paused = make_requests(spec, vocab)
    for r in paused:
        fleet.submit(r)
    for _ in range(spec.pause_after):
        fleet.step()
    tn = fleet.tenants["serve0"]
    inflight = sum(r is not None for r in tn.engine.active)
    check(inflight > 0 and not all(r.done for r in paused),
          f"pause lands mid-stream ({inflight} slots decoding)")
    t1 = time.perf_counter()
    timings = fleet.pause_live("serve0")
    staged = fleet.mgr.snapshots["serve0"].stats
    fleet.unpause("serve0")
    resume_s = time.perf_counter() - t1
    res = fleet.drain()
    check(res.drained and all_done(paused),
          f"(a) paused run: {len(paused)} requests complete")
    same = [a.out == b.out for a, b in zip(steady, paused)]
    check(all(same), f"(b) {sum(same)}/{len(same)} streams bit-identical "
          "to the unpaused run (I10)")
    chip = chip_of(fleet, "serve0")
    check(chip == devices[0] and leaves_on(tn.engine, chip),
          f"(e) params and KV cache on the VF's device {chip}")
    return {"fleet": fleet, "first_pass_s": first_s,
            "tokens": sum(len(r.out) for r in steady + paused),
            "stop_ms": timings.stop_ms, "pause_unpause_s": resume_s,
            "staged_bytes": staged.bytes_moved,
            "skipped_bytes": staged.skipped_bytes,
            "transport": staged.transport}


def decode_step_hlo(engine) -> str:
    """Compiled HLO of the engine's decode step at full table width."""
    B = engine.slots
    args = (engine.params, engine._cache, jnp.zeros((B, 1), jnp.int32),
            jnp.full((B,), -1, jnp.int32), jnp.asarray(engine.tables),
            jnp.zeros((B,), bool), jnp.zeros((B,), jnp.float32),
            jnp.zeros((B,), jnp.int32), jnp.zeros((B, 3), jnp.int32))
    return engine._decode.lower(*args).compile().as_text()


def first_logits(run, params, prompt, spec: Spec):
    """(last-prompt-position logits, first-decode logits) of one request
    through prefill and one paged decode step."""
    model = build_model(run)
    prefill, _ = make_serve_steps(run)
    req_cache, last = jax.jit(prefill)(params,
                                       {"tokens": jnp.asarray(prompt)[None]})
    plen, page = len(prompt), spec.page_size
    maxp = math.ceil(spec.max_len / page)
    shape = dataclasses.replace(run.shape, seq_len=spec.max_len,
                                global_batch=1)
    cache = init_paged_cache(model, shape, 1 + maxp, page)
    pages = list(range(1, 1 + math.ceil((plen + 1) / page)))
    cache = admit_kv(cache, req_cache, pages, page, slot=0)
    tables = np.zeros((1, maxp), np.int32)
    tables[0, :len(pages)] = pages
    tok = jnp.argmax(last[0]).astype(jnp.int32).reshape(1, 1)
    decode = jax.jit(make_decode_step(run, paged=True))
    logits, _ = decode(params, cache, tok, jnp.asarray([plen], jnp.int32),
                       jnp.asarray(tables), jnp.asarray([True]))
    return (np.asarray(last[0], np.float32),
            np.asarray(logits[0], np.float32))


def phase_logits(run, params, spec: Spec) -> dict:
    """Check (d): the Pallas path against the reference on one prompt."""
    prompt = make_requests(spec, run.model.vocab_size)[-1].prompt
    got = first_logits(run, params, prompt, spec)
    want = first_logits(run.replace(kernel_backend="reference"), params,
                        prompt, spec)
    out = {}
    for name, a, b in zip(("prefill", "decode"), got, want):
        err = float(np.linalg.norm(a - b) / np.linalg.norm(b))
        out[name] = {"rel_l2": err,
                     "max_abs": float(np.max(np.abs(a - b))),
                     "argmax_equal": bool(np.argmax(a) == np.argmax(b))}
        check(np.isfinite(a).all() and err <= LOGIT_RTOL,
              f"(d) {name} logits: Pallas vs reference rel L2 {err:.3e} "
              f"<= {LOGIT_RTOL}")
    return out


def phase_four_chips(run, params, devices, spec: Spec, workdir: str) -> dict:
    """Three engines, one chip each, and one free chip; the requests are
    routed across engines and ``serve0`` migrates onto the free chip
    mid-stream. Compared with the same requests on one engine."""
    devs = list(devices[:4])
    check(len(set(devs)) == 4, f"four distinct devices {devs}")
    vocab = run.model.vocab_size
    one = build_fleet(run, params, devs[:1], spec, num_engines=1,
                      workdir=os.path.join(workdir, "one"))
    want = make_requests(spec, vocab)
    for r in want:
        one.submit(r)
    res = one.drain()
    check(res.drained and all_done(want), "one-chip run completes")
    del one, res
    gc.collect()    # free the one-chip engine's params before three more

    fleet = build_fleet(run, params, devs, spec, num_engines=3,
                        workdir=os.path.join(workdir, "four"))
    reqs = make_requests(spec, vocab)
    for r in reqs:
        fleet.submit(r)
    for _ in range(spec.pause_after):
        fleet.step()
    tids = sorted(fleet.tenants)
    chips = {tid: chip_of(fleet, tid) for tid in tids}
    check(len(set(chips.values())) == 3 and all(
        leaves_on(fleet.tenants[t].engine, chips[t]) for t in tids),
        f"each engine's leaves on its own chip {chips}")
    old = chips["serve0"]
    mig = fleet.migrate("serve0")
    new = chip_of(fleet, "serve0")
    check(new == devs[3] and new != old
          and leaves_on(fleet.tenants["serve0"].engine, new),
          f"serve0 migrated {old} -> {new}, its leaves on {new}")
    res = fleet.drain()
    check(res.drained and all_done(reqs), "four-chip run completes")
    greedy = [a.out == b.out for a, b in zip(want, reqs)
              if a.temperature <= 0]
    check(all(greedy), f"{sum(greedy)}/{len(greedy)} greedy streams equal "
          "the one-chip run's")
    sampled = [a.out == b.out for a, b in zip(want, reqs)
               if a.temperature > 0]
    return {"chips": {t: str(c) for t, c in chips.items()},
            "migrated_to": str(new), "migrate_s": mig["migrate_s"],
            "sampled_equal": f"{sum(sampled)}/{len(sampled)}"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips}: JAX found {len(devices)} devices",
              file=sys.stderr)
        return 2
    from repro.launch.cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    seen = {"compile_s": 0.0, "compile_cache_hits": 0}

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen["compile_s"] += secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            seen["compile_cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    print(f"device {devices[0].device_kind} x{len(devices)}; compile "
          f"cache {cache_dir}", flush=True)

    t0 = time.perf_counter()
    run, params = build(FULL, interpret=False)
    print(f"{ARCH}: {run.model.num_layers} layers, d_model "
          f"{run.model.d_model}, vocab {run.model.vocab_size}, "
          f"{sum(x.size for x in jax.tree.leaves(params)) / 1e6:.1f}M "
          f"params ({run.kernels} kernels)", flush=True)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as wd:
        if args.chips == 4:
            out = phase_four_chips(run, params, devices, FULL, wd)
        else:
            out = phase_pause(run, params, devices, FULL, wd)
            hlo = decode_step_hlo(out.pop("fleet").tenants["serve0"].engine)
            check("tpu_custom_call" in hlo,
                  "(c) compiled decode step holds tpu_custom_call")
            out["logits"] = phase_logits(run, params, FULL)
    stats = devices[0].memory_stats() or {}
    out.update(seen, wall_s=time.perf_counter() - t0,
               peak_bytes_in_use=stats.get("peak_bytes_in_use",
                                           "not reported"))
    print(json.dumps(out, default=str), flush=True)
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
