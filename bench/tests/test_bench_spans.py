"""The program's ``svff.*`` spans in a trace (``bench/spans.py``): nesting
by interval across the main and transfer threads, the four readers, and
the idle gaps named down to the program span, on hand-made planes and on a
trace recorded on a TPU v5 lite (four fleet steps of qwen3-chat with the
program's spans, recorded by ``bench/record_trace.py`` and committed
gzipped under ``data/``)."""
import gzip
import os
import types

import pytest

from bench import spans, trace

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "qwen3_chat_spans.xplane.pb.gz")


def ev(name, start, end, **stats):
    return types.SimpleNamespace(name=name, start_ns=start,
                                 duration_ns=end - start,
                                 stats=list(stats.items()))


def plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=n, events=e) for n, e in lines.items()])


def synthetic():
    """Two engine steps (the first with a prefill), then a live pause of
    two pre-copy rounds whose transfers run on two queue threads, and an
    unpause; one program span outside the window."""
    main = [
        ev("bench.window", 0, 10000),
        ev("bench.step", 100, 2100),
        ev("svff.fleet.step", 110, 2090),
        ev("svff.engine.step", 120, 2080),
        ev("svff.engine.admit", 130, 300),
        ev("svff.engine.prefill", 300, 900, rid=7, plen=1024, offset=256),
        ev("svff.engine.readback", 700, 850, rid=7),
        ev("svff.engine.decode", 1000, 1100, slots=16, width=64),
        ev("svff.engine.readback", 1100, 1900),
        ev("svff.engine.bookkeep", 1900, 2000),
        ev("bench.step", 2200, 3200),
        ev("svff.fleet.step", 2205, 3195),
        ev("svff.engine.step", 2210, 3190),
        ev("svff.engine.readback", 2500, 3000),
        ev("bench.pause_live", 3300, 9300),
        ev("svff.pause.precopy_0", 3310, 6000, tenant="serve0"),
        ev("svff.staging.save", 3320, 5990),
        ev("svff.staging.dispatch", 3330, 3500),
        ev("svff.pause.precopy_1", 6000, 8000, tenant="serve0"),
        ev("svff.staging.save", 6010, 7990),
        ev("bench.unpause", 9400, 9900),
        ev("svff.unpause.restore_io", 9405, 9895, tenant="serve0"),
        ev("svff.staging.restore", 9410, 9890),
        ev("svff.staging.h2d", 9420, 9800, bytes=1000),
        ev("svff.engine.step", 20000, 20100),
    ]
    host = plane("/host:CPU", {
        "python3": main,
        "qdma_0": [ev("svff.staging.d2h", 3500, 5500, bytes=4000),
                   ev("svff.staging.d2h", 6100, 7900, bytes=3000)],
        "qdma_1": [ev("svff.staging.d2h", 4000, 5800, bytes=2000),
                   ev("jax host event", 4000, 5800)]})
    dev = plane("/device:TPU:0", {"XLA Ops": [
        ev("%fusion.1 = f32[4] fusion(...)", 0, 1000),
        ev("%paged_decode.6 = bf16[16] custom-call(...)", 1950, 2300),
        ev("%fusion.1 = f32[4] fusion(...)", 3400, 3450),
        ev("%copy.2 = f32[4] copy(...)", 9850, 10000)]})
    return [host, dev]


def test_spans_in_the_window_across_threads():
    got = spans.collect(synthetic(), (0, 10000))
    names = [s.name for s in got]
    assert names.count("svff.engine.step") == 2      # not the one outside
    assert names.count("svff.staging.d2h") == 3      # both queue threads
    assert "jax host event" not in names and "bench.step" not in names
    assert [s.start for s in got] == sorted(s.start for s in got)
    d2h = [s for s in got if s.name == "svff.staging.d2h"]
    assert [s.stats["bytes"] for s in d2h] == [4000, 2000, 3000]


def test_readers_on_synthetic_spans():
    got = spans.collect(synthetic(), (0, 10000))
    # step 1: 1960 ns less its readbacks 150 + 800; step 2: 980 less 500
    assert spans.engine_host_ms(got) == pytest.approx((1010 + 480) / 2 / 1e6)
    assert spans.prefill_step_share(got) == pytest.approx(50.0)
    # 9000 bytes over the union of the transfers: 2300 + 1800 ns
    assert spans.pause_d2h_gbps(got) == pytest.approx(9000 / 4100)
    # saves 2670 - 2300 and 1980 - 1800, the restore 480 - 380
    assert spans.pause_staging_host_ms(got) == pytest.approx(650 / 1e6)


def test_readers_read_nothing_without_program_spans():
    host = plane("/host:CPU", {"python3": [ev("bench.window", 0, 1000),
                                           ev("bench.step", 0, 900)]})
    got = spans.collect([host], (0, 1000))
    assert got == []
    for read in spans.READERS.values():
        assert read(got) is None
    # the chat cell's trace holds no staging span, the pause cell's steps
    chat = [s for s in spans.collect(synthetic(), (0, 10000))
            if not s.name.startswith(("svff.staging", "svff.pause",
                                      "svff.unpause"))]
    assert spans.pause_d2h_gbps(chat) is None
    assert spans.pause_staging_host_ms(chat) is None
    assert spans.engine_host_ms(chat) is not None


def test_idle_gaps_name_the_program_span():
    planes = synthetic()
    got = spans.collect(planes, (0, 10000))
    gaps = spans.idle_gaps(planes, (0, 10000), got)
    assert gaps[0] == ["bench.pause_live/svff.staging.d2h", 6400e-9]
    assert ["bench.step/svff.engine.readback", 1100e-9] in gaps
    assert ["bench.step/svff.engine.readback", 950e-9] in gaps
    # the gap lengths are the reduction's own
    base = trace.reduce(planes)
    assert sorted(g[1] for g in gaps) == sorted(ns / 1e9
                                                for ns, _ in base.gaps)
    # with no program span open, the label is the harness's alone
    assert spans.idle_gaps(planes, (0, 10000), []) == [
        [name, ns / 1e9] for ns, name in sorted(base.gaps, reverse=True)]


def test_report_adds_the_fleet_step_and_the_sums():
    out = spans.report(synthetic(), (0, 10000))
    assert out["spans"]["fleet_step_span_ms"] == pytest.approx(
        (1980 + 990) / 2 / 1e6)
    assert out["spans"]["prefill_step_share.chat"] == pytest.approx(50.0)
    n, secs = out["span_count_s"]["svff.staging.d2h"]
    assert n == 3 and secs == pytest.approx(5600e-9)
    assert out["idle_gaps"][0][0] == "bench.pause_live/svff.staging.d2h"


def test_recorded_chip_trace_with_program_spans():
    from jax.profiler import ProfileData
    with open(RECORDED, "rb") as f:
        data = ProfileData.from_serialized_xspace(gzip.decompress(f.read()))
    planes = list(data.planes)
    t = trace.reduce(planes)
    assert t.window_s == pytest.approx(0.214539164)
    assert t.kernel_s("paged_decode") == pytest.approx(0.037012789)
    out = spans.report(planes, t.window_ns)
    counts = {k: n for k, (n, _) in out["span_count_s"].items()}
    assert counts["svff.fleet.step"] == counts["svff.engine.step"] == 4
    assert counts["svff.engine.decode"] == counts["svff.engine.bookkeep"] == 4
    # what the readers read when the trace was recorded: one prompt's last
    # chunks rode on every step, and the last one was placed
    assert counts["svff.engine.place"] == 1
    assert out["spans"]["prefill_step_share.chat"] == pytest.approx(100.0)
    assert out["spans"]["engine_host_ms.chat"] == pytest.approx(11.520747)
    assert out["spans"]["pause_d2h_gbps"] is None
    assert out["idle_gaps"][0] == ["bench.step/svff.engine.prefill",
                                   pytest.approx(0.020598258)]
