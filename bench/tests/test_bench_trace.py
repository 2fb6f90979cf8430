"""The reduction from a profiler trace to busy time, kernel time and the
breakdown: on hand-made planes, and on a trace recorded on a TPU v5 lite
(four fleet steps of qwen3-chat with 16 slots decoding, recorded by
``bench/record_trace.py`` and committed gzipped under ``data/``)."""
import gzip
import os
import types

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "qwen3_chat_steps.xplane.pb.gz")


def ev(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=[])


def plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=n, events=e) for n, e in lines.items()])


def synthetic():
    host = plane("/host:CPU", {"python": [
        ev("bench.window", 100, 1000),
        ev("bench.step", 100, 400), ev("bench.submit", 520, 40),
        ev("bench.step", 600, 450), ev("other", 0, 2000)]})
    dev = plane("/device:TPU:0", {
        "XLA Ops": [
            ev("%while.3 = (s32[]) while(...)", 150, 200),
            ev("%paged_decode.6 = bf16[16,16,128] custom-call(...)", 160, 50),
            ev("%paged_decode.6 = bf16[16,16,128] custom-call(...)", 260, 50),
            ev("%fusion.9 = f32[4] fusion(...)", 340, 30),
            ev("%fusion.9 = f32[4] fusion(...)", 700, 100),
            ev("%copy.1 = f32[4] copy(...)", 1050, 100)],
        "Async XLA Ops": [ev("%copy-start = ...", 0, 2000)]})
    return [host, dev]


def test_busy_kernels_and_gaps_on_synthetic_planes():
    t = trace.reduce(synthetic())
    assert t.window_ns == (100, 1100)
    assert t.window_s == pytest.approx(1e-6)
    # while [150, 350] and fusion [340, 370] merge; [700, 800]; the copy is
    # clipped to the window's end [1050, 1100]
    assert t.busy_ns == 220 + 100 + 50
    assert t.kernel_s("paged_decode") == pytest.approx(100e-9)
    assert t.kernel_s("flash_attention") is None
    assert "while.3" not in t.op_ns
    gaps = sorted(t.gaps, reverse=True)
    assert gaps[0] == (330, "bench.submit")      # (370, 700), midpoint 535
    assert (250, "bench.step") in gaps           # (800, 1050)
    assert (50, "bench.step") in gaps            # (100, 150)
    b = t.breakdown()
    assert b["device_ops"][0] == ["fusion.9", 130e-9]
    assert len(b["idle_gaps"]) == 3


def test_a_trace_without_the_window_is_refused():
    host = plane("/host:CPU", {"python": [ev("bench.step", 0, 10)]})
    with pytest.raises(ValueError):
        trace.reduce([host])


def test_recorded_chip_trace():
    from jax.profiler import ProfileData
    with open(RECORDED, "rb") as f:
        data = ProfileData.from_serialized_xspace(gzip.decompress(f.read()))
    t = trace.reduce(data.planes)
    assert t.chips == 1
    # what the reduction read when the trace was recorded
    assert t.window_s == pytest.approx(0.123473885)
    assert t.busy_s == pytest.approx(0.095908771)
    assert t.kernel_s("paged_decode") == pytest.approx(0.023872848)
    assert t.kernel_s("fused_sample") > 0
    assert t.kernel_s("flash_attention") > 0
    b = t.breakdown()
    assert b["device_ops"][0] == ["paged_decode.6", pytest.approx(0.023872848)]
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert {n for n, _ in b["idle_gaps"]} == {"bench.step"}
