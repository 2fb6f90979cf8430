"""A live pause of one engine and its unpause, issued back to back through
the fleet's public entry points (``ServeFleet.pause_live`` then
``ServeFleet.unpause``). The record keeps the host clock at the call and
at each return, the phase timings both calls return, and the bytes the
stop-and-copy staged."""
from __future__ import annotations

import time


def fire(fleet, event: dict, annotate) -> dict:
    tid = event["engine"]
    rec = {"op": "pause_unpause", "engine": tid,
           "t_call": time.perf_counter()}
    with annotate("bench.pause_live"):
        paused = fleet.pause_live(tid)
    rec["t_paused"] = time.perf_counter()
    snap = fleet.mgr.snapshots.get(tid)
    if snap is not None and snap.stats is not None:
        rec["staged_bytes"] = snap.stats.bytes_moved
        rec["skipped_bytes"] = snap.stats.skipped_bytes
    with annotate("bench.unpause"):
        restored = fleet.unpause(tid)
    rec["t_return"] = time.perf_counter()
    rec["stop_ms"] = paused.stop_ms
    rec["pause_phases_s"] = dict(paused.phases)
    rec["restore_ms"] = restored.total * 1e3
    rec["restore_phases_s"] = dict(restored.phases)
    return rec


def warm(fleet, event: dict, make_request, annotate) -> None:
    """Run the same pause once in set-up, with the engine decoding, so that
    every program of the staging path is compiled before the window."""
    tid = event["engine"]
    eng = fleet.tenants[tid].engine
    for i in range(eng.slots):
        fleet.submit(make_request(i))
    for _ in range(64):
        fleet.step()
        if all(r is not None for r in eng.active):
            break
    fire(fleet, event, annotate)
    fleet.drain()
