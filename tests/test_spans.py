"""Program spans (``repro.runtime.spans``) in a profiler trace: the serve
engine's step and its parts, the pause and unpause phases, and the staging
engine's transfers, read back from the ``.xplane.pb`` a CPU trace writes."""
import collections
import glob
import os
import tempfile

import jax
import numpy as np
import pytest

from repro.configs import make_run_config
from repro.core.pause import PhaseTimings
from repro.core.pool import token_devices
from repro.models.model import build_model
from repro.runtime.spans import PREFIX, span
from repro.serve.engine import Request, ServeEngine
from repro.serve.fleet import ServeFleet

Span = collections.namedtuple("Span", "name start end stats")
PHASES = ("admit_ns", "prefill_ns", "place_ns", "prepare_ns", "decode_ns",
          "readback_ns", "bookkeep_ns", "step_ns")


def traced(fn):
    """Run ``fn`` under the profiler; its result and every ``svff.*``
    event of the host planes, ordered by start."""
    from jax.profiler import ProfileData
    with tempfile.TemporaryDirectory() as td:
        jax.profiler.start_trace(td)
        try:
            out = fn()
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(td, "**", "*.xplane.pb"),
                            recursive=True)
        planes = ProfileData.from_file(path).planes
        spans = [Span(ev.name[len(PREFIX):], ev.start_ns,
                      ev.start_ns + ev.duration_ns, dict(ev.stats))
                 for p in planes if p.name.startswith("/host")
                 for line in p.lines for ev in line.events
                 if ev.name.startswith(PREFIX)]
    return out, sorted(spans, key=lambda s: (s.start, -s.end))


def inside(child, parent):
    return parent.start <= child.start and child.end <= parent.end


def children(parent, spans, name):
    return [s for s in spans if s.name == name and s is not parent
            and inside(s, parent)]


@pytest.fixture(scope="module")
def setup():
    run = make_run_config("qwen3-0.6b", "decode_32k", smoke=True)
    params = build_model(run).init(jax.random.key(0))
    return run, params


@pytest.fixture(scope="module")
def engine_trace(setup):
    """A paged engine with chunked prefill: a 20-token prompt in chunks of
    8 beside a 5-token prompt prefilled whole, decoding together, then a
    6-token prompt admitted into the slot the 5-token one frees; every
    traced step decodes."""
    run, params = setup
    eng = ServeEngine(run, params, slots=2, max_len=48, paged=True,
                      page_size=8, prefill_chunk=8, fused_sampling=True)
    eng.submit(Request(rid=11, prompt=np.arange(20) % 100, max_new_tokens=4))
    eng.submit(Request(rid=12, prompt=np.arange(5) % 100, max_new_tokens=4))
    eng.step()                               # compile outside the trace
    before = collections.Counter(eng.stats)
    eng.submit(Request(rid=13, prompt=np.arange(6) % 100, max_new_tokens=2))
    _, spans = traced(lambda: [eng.step() for _ in range(4)])
    return eng, before, spans


def test_engine_step_spans_hold_their_parts(engine_trace):
    _, _, spans = engine_trace
    steps = [s for s in spans if s.name == "engine.step"]
    assert len(steps) == 4
    for st in steps:
        assert len(children(st, spans, "engine.admit")) == 1
        decodes = children(st, spans, "engine.decode")
        assert len(decodes) == 1
        assert decodes[0].stats["slots"] >= 1
        assert decodes[0].stats["width"] >= 1
        # a readback after the dispatch, then the per-slot bookkeeping
        (book,) = children(st, spans, "engine.bookkeep")
        assert any(r.start >= decodes[0].end and r.end <= book.start
                   for r in children(st, spans, "engine.readback"))
    # the chunked prompt advances one chunk per step: offsets 8 and 16
    chunks = [s for s in spans if s.name == "engine.prefill"
              and s.stats.get("rid") == 11]
    assert [c.stats["offset"] for c in chunks] == [8, 16]
    assert all(c.stats["plen"] == 20 for c in chunks)
    assert any(inside(c, st) for c in chunks for st in steps)


def test_prefill_place_and_readback_carry_the_request_id(engine_trace):
    _, _, spans = engine_trace
    # rid 13 is prefilled whole inside admission, then placed
    (admit,) = [s for s in spans if s.name == "engine.admit"
                and children(s, spans, "engine.prefill")
                and children(s, spans, "engine.prefill")[0].stats["rid"]
                == 13]
    (pre,) = children(admit, spans, "engine.prefill")
    assert pre.stats["plen"] == 6
    (rb,) = children(pre, spans, "engine.readback")
    assert rb.stats["rid"] == 13
    (place,) = children(admit, spans, "engine.place")
    assert place.stats["rid"] == 13 and place.start >= pre.end
    # the last chunk of rid 11 reads its logits back and is placed
    last = [s for s in spans if s.name == "engine.prefill"
            and s.stats.get("offset") == 16][0]
    assert children(last, spans, "engine.readback")[0].stats["rid"] == 11
    assert any(p.stats["rid"] == 11 for p in spans
               if p.name == "engine.place")


def test_engine_phase_counters_grow(engine_trace):
    eng, before, spans = engine_trace
    for key in PHASES:
        assert eng.stats[key] > before[key], key
    # the step counter is the summed length of the traced steps (it is
    # measured on another clock, and the first step came before the trace)
    traced_ns = sum(s.end - s.start for s in spans
                    if s.name == "engine.step")
    assert eng.stats["step_ns"] - before["step_ns"] == pytest.approx(
        traced_ns, rel=0.05)
    assert eng.stats["step_ns"] >= eng.stats["decode_ns"]


def _device_bytes(tree) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(tree)
               if isinstance(x, jax.Array))


@pytest.fixture(scope="module")
def pause_trace(setup):
    """A live pause and unpause of one engine of a fleet, mid-decode."""
    run, params = setup
    fleet = ServeFleet(run, params, num_engines=1, devices=token_devices(1),
                       slots=2, max_len=48, paged=True, page_size=8,
                       workdir=tempfile.mkdtemp())
    rng = np.random.default_rng(5)
    for i in range(2):
        fleet.submit(Request(rid=i, prompt=rng.integers(0, 500, 6),
                             max_new_tokens=8))
    for _ in range(2):
        fleet.step()
    eng = fleet.tenants["serve0"].engine
    fetched = _device_bytes({"params": eng.params, "cache": eng._cache})

    def cycle():
        paused = fleet.pause_live("serve0", rounds=2)
        restored = fleet.unpause("serve0")
        return paused, restored, fleet.mgr.staging.last_stats
    (paused, restored, restore_stats), spans = traced(cycle)
    return paused, restored, restore_stats, fetched, spans


def test_pause_and_unpause_phases_are_spans(pause_trace):
    paused, restored, _, _, spans = pause_trace
    names = {s.name for s in spans}
    for ph in paused.phases:
        assert f"pause.{ph}" in names
    for ph in restored.phases:
        assert f"unpause.{ph}" in names
    assert {"pause.precopy_0", "pause.precopy_1", "pause.save_config_space",
            "pause.unregister_pci", "pause.unregister_vfio",
            "unpause.restore_io", "unpause.restore_config"} <= names
    assert all(s.stats["tenant"] == "serve0" for s in spans
               if s.name.startswith(("pause.", "unpause.")))
    # one save per pre-copy round and one in the stop-and-copy
    saves = [s for s in spans if s.name == "staging.save"]
    assert len(saves) == 3
    assert "staging.d2h" in names and "staging.h2d" in names


def test_phase_timings_are_their_spans(pause_trace):
    paused, restored, _, _, spans = pause_trace
    for op, t in (("pause", paused), ("unpause", restored)):
        for ph, secs in t.phases.items():
            (s,) = [s for s in spans if s.name == f"{op}.{ph}"]
            # timed inside its own span: longer by the span's entry and
            # exit alone
            assert -1e-6 <= (s.end - s.start) / 1e9 - secs < 1e-3, (op, ph)


def test_d2h_bytes_add_up_to_the_fetched_device_leaves(pause_trace):
    _, _, restore_stats, fetched, spans = pause_trace
    # the first pre-copy round meets an empty memo: it fetches every device
    # leaf (params and KV cache); the host leaves (pos, tables, ...) are
    # copied on the host, not fetched
    (first,) = children([s for s in spans if s.name == "pause.precopy_0"][0],
                        spans, "staging.save")
    d2h = children(first, spans, "staging.d2h")
    assert len(d2h) >= 2                     # bursts over the queues
    assert sum(s.stats["bytes"] for s in d2h) == fetched
    # the restore puts every staged leaf back
    (restore,) = [s for s in spans if s.name == "staging.restore"]
    h2d = children(restore, spans, "staging.h2d")
    assert sum(s.stats["bytes"] for s in h2d) == restore_stats.bytes_moved
    assert children(restore, spans, "staging.ready")


def test_span_counter_and_phase_without_a_profiler():
    c = collections.Counter()
    with span("engine.admit", c, rid=3):
        pass
    with span("engine.admit", c):
        pass
    assert set(c) == {"admit_ns"} and c["admit_ns"] > 0
    t = PhaseTimings(op="detach", tenant="vm0")
    with t.phase("unbind"):
        pass
    with pytest.raises(RuntimeError):
        with t.phase("snapshot_disk"):
            raise RuntimeError("a failed phase records nothing")
    assert list(t.phases) == ["unbind"] and t.stop_s == t.total
