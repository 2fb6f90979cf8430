"""The harness at smoke size on the CPU: generators, metric arithmetic,
roofline functions, the peak table, a whole run of each cell, a cell that
exists only as files, and the faults that ``correct`` has to catch."""
import json
import math
import os
import time
import types

import numpy as np
import pytest

from conftest import CPU_PEAKS, ROOT, add_batch_cell, make_root

from bench import harness, readers, roofline
from bench.stats import percentile, spread
from bench.workload import length_values, possible_lengths

CHAT = {"kind": "open_loop", "rate_per_s": 5.0,
        "prompt": {"dist": "lognormal", "median": 384, "sigma": 0.8,
                   "min": 16, "max": 768, "round_to": 16},
        "output": {"dist": "lognormal", "median": 96, "sigma": 0.8,
                   "min": 8, "max": 256},
        "greedy_share": 0.5, "temperature": 0.8, "top_k": 50}
BIG_SEED = 2**33 + 5


def generator(kind):
    return harness._load(os.path.join(ROOT, "bench", "traffic",
                                      kind + ".py"), "gen_" + kind)


# -- generators -------------------------------------------------------------
def test_open_loop_same_seed_same_requests():
    gen = generator("open_loop")
    a, b = (gen.Traffic(CHAT, BIG_SEED, 20.0, 1000) for _ in range(2))
    assert len(a.arrivals) == 100
    for x, y in zip(a.arrivals, b.arrivals):
        assert x.due_s == y.due_s and x.max_new == y.max_new
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.temperature, x.top_k, x.seed) == (y.temperature, y.top_k,
                                                    y.seed)
    due = [x.due_s for x in a.arrivals]
    assert due == sorted(due) and due[0] == 0.0 and due[-1] < 20.0


def test_open_loop_seeds_share_the_work_in_another_order():
    gen = generator("open_loop")
    a = gen.Traffic(CHAT, 1, 20.0, 1000).arrivals
    b = gen.Traffic(CHAT, 2, 20.0, 1000).arrivals
    sizes = [[(len(x.prompt), x.max_new) for x in t] for t in (a, b)]
    assert sizes[0] != sizes[1]
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt)
                                                      for x in b)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in b)
    gaps = [np.round(np.diff([x.due_s for x in t]), 9) for t in (a, b)]
    assert not np.array_equal(gaps[0], gaps[1])
    # n arrivals use n - 1 of the n stratified gaps
    assert len(set(gaps[0]) & set(gaps[1])) >= len(gaps[0]) - 1
    assert not np.array_equal(a[0].prompt, b[0].prompt)
    assert [x.temperature for x in a] != [x.temperature for x in b]
    plens = [len(x.prompt) for x in a]
    assert all(p % 16 == 0 and 16 <= p <= 768 for p in plens)
    assert set(plens) <= set(possible_lengths(CHAT["prompt"]))
    assert sum(x.temperature == 0 for x in a) == 50


def test_length_values_are_quantiles():
    v = length_values({"median": 100, "sigma": 0.5, "min": 10,
                       "max": 1000, "round_to": 1}, 101)
    assert v[50] == 100 and v == sorted(v)
    assert possible_lengths({"min": 16, "max": 64, "round_to": 16}) == \
        [16, 32, 48, 64]
    assert possible_lengths({"min": 20, "max": 50, "round_to": 16}) == \
        [32, 48, 50]


def test_closed_loop_refills_the_client_that_finished():
    gen = generator("closed_loop")
    mix = dict(CHAT, kind="closed_loop", clients=3, pool=4)
    t = gen.Traffic(mix, BIG_SEED, 10.0, 1000)
    first = t.due(0.0)
    assert [a.client for a in first] == [0, 1, 2]
    assert [a.rid for a in first] == [0, 1, 2]
    assert t.due(1.0) == []
    t.completed(first[1], 2.5)
    (nxt,) = t.due(3.0)
    assert (nxt.client, nxt.rid, nxt.due_s) == (1, 3, 3.0)
    sizes = [(int(p), int(o)) for p, o, _ in t.pool]
    assert (len(first[0].prompt), first[0].max_new) == sizes[0]
    assert (len(nxt.prompt), nxt.max_new) == sizes[3]


# -- metric arithmetic --------------------------------------------------------
def test_percentile_nearest_rank_and_inf():
    xs = list(range(1, 20)) + [math.inf]
    assert percentile(xs, 0.95) == 19
    assert percentile(xs[:-2] + [math.inf, math.inf], 0.95) == math.inf
    assert percentile([3.0, 1.0, 2.0, 4.0], 0.5) == 2.0
    assert math.isnan(percentile([], 0.95))
    assert spread([1.0, 2.0, 3.0, 4.0, 5.0]) == pytest.approx(
        (4.5 - 1.5) / 3.0)


def _sent(plen, due, toks, engine="serve0", rejected=None, greedy=True):
    arrival = types.SimpleNamespace(prompt=np.zeros(plen, np.int32),
                                    temperature=0.0 if greedy else 0.8,
                                    rid=int(due * 1000))
    req = types.SimpleNamespace(out=[1] * len(toks), t_tok=list(toks),
                                done=True, error=None)
    return harness.Sent(arrival=arrival, req=req, due=due, sent=due,
                        engine=engine, rejected=rejected)


def _record(sent, t0=100.0, seconds=10.0, **kw):
    rec = harness.Record(cell="c", cfg={}, mix={}, dims=None,
                         peaks=CPU_PEAKS, seconds=seconds, t0=t0,
                         t1=t0 + seconds, **kw)
    rec.sent = sent
    return rec


def test_ttft_counts_misses_as_inf():
    sent = [_sent(16, 100.0 + 0.5 * i, [100.0 + 0.5 * i + 0.01 * (i + 1)])
            for i in range(19)]
    sent.append(_sent(16, 105.5, [], rejected="full"))
    sent.append(_sent(16, 111.0, [111.1]))            # due after the window
    rec = _record(sent)
    assert readers.ttft_p95_ms(rec) == pytest.approx(190.0)
    sent[0].rejected = "full"
    assert readers.ttft_p95_ms(rec) == math.inf


def test_itl_over_all_gaps_ending_in_window():
    a = _sent(16, 100.0, [99.0, 100.5, 100.6, 100.8])   # gap ending at 100.5
    b = _sent(16, 101.0, [109.0, 109.9, 110.5])         # last ends outside
    rec = _record([a, b])
    gaps = [1500.0, 100.0, 200.0, 900.0]
    assert readers.itl_p95_ms(rec) == pytest.approx(percentile(gaps, 0.95))
    assert readers.tokens_per_s(rec) == pytest.approx(5 / 10.0)


def test_reconf_stall_spans_call_to_first_token_after_unpause():
    a = _sent(16, 100.0, [101.0, 102.0, 110.25, 110.5])
    b = _sent(16, 100.0, [101.0, 110.4], engine="serve1")
    ev = {"op": "pause_unpause", "engine": "serve0", "t_call": 102.5,
          "t_return": 110.0, "stop_ms": 7.0, "restore_ms": 3.0}
    rec = _record([a, b], events=[ev])
    assert readers.reconf_stall_ms(rec) == pytest.approx(7750.0)
    assert readers.pause_stop_ms(rec) == 7.0
    assert readers.pause_restore_ms(rec) == 3.0
    assert readers.reconf_stall_ms(_record([a])) is None


# -- roofline and peaks -------------------------------------------------------
def _dims(name):
    from bench.families import dense
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return dense.dims(json.load(f))


def test_flops_and_bytes_at_known_shapes():
    m = _dims("qwen3-0.6b")
    per_layer = 1024 * (2 * 2048 + 2 * 1024) + 3 * 1024 * 3072
    assert roofline.matmul_params(m) == 28 * per_layer + 1024 * 151936
    assert roofline.token_flops(m, 10) == pytest.approx(
        2 * roofline.matmul_params(m) + 4 * 10 * 16 * 128 * 28)
    assert roofline.prompt_flops(m, 3) == pytest.approx(
        sum(roofline.token_flops(m, c) for c in (1, 2, 3)))
    f, b = roofline.paged_decode_call(m, [100, 200], slots=16,
                                      table_width=32)
    assert f == 4 * 300 * 16 * 128
    assert b == 2 * 300 * 8 * 128 * 2 + 2 * 16 * 16 * 128 * 2 \
        + 16 * 32 * 4 + 16 * 4
    peaks = roofline.peaks_for("TPU v5 lite")
    assert roofline.least_seconds(f, b, peaks) == pytest.approx(b / 819e9)
    p = _dims("phi3-mini-3.8b-l16")
    assert (p.head_dim, p.vocab_padded, p.tied) == (96, 32128, False)


def test_peak_table_refuses_an_unknown_device():
    assert roofline.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(roofline.UnknownDevice):
        roofline.peaks_for("cpu")


def test_main_refuses_a_platform_other_than_tpu(capsys):
    from bench import run
    assert run.main(["--workload", "qwen3-chat", "--seed", "1",
                     "--seconds", "1"]) == 2
    assert "{" not in capsys.readouterr().out


# -- whole runs at smoke size ---------------------------------------------------
def _run(root, cell, counter, seconds=3.0, seed=2**31 + 11, **kw):
    import jax
    bench = harness.Bench(root, os.path.join(root, "bench"))
    return harness.run_cell(bench, cell, seed, seconds, False,
                            devices=jax.devices(), peaks=CPU_PEAKS,
                            counter=counter, t_start=time.perf_counter(),
                            log=lambda m: None, **kw)


@pytest.mark.parametrize("cell,metrics", [
    ("qwen3-chat", {"itl_p95_ms", "setup_s"}),
    ("qwen3-chat-pause", {"reconf_stall_ms", "setup_s"}),
    ("phi3-batch", {"tokens_per_s", "setup_s"}),
])
def test_run_prints_the_contract_keys(tiny_root, counter, cell, metrics):
    if cell == "phi3-batch":        # staged: its files are there
        add_batch_cell(tiny_root)
    out = _run(tiny_root, cell, counter)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == metrics
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    assert out["checks"]["tokens_checked"]["value"] >= 1
    json.dumps(out)


def test_a_cell_mix_and_metric_that_exist_only_as_files(tmp_path, counter):
    root = make_root(tmp_path)
    traffic = os.path.join(root, "bench", "traffic")
    with open(os.path.join(traffic, "chat.json")) as f:
        mix = json.load(f)
    mix["rate_per_s"] = 2.0
    with open(os.path.join(traffic, "chat-slow.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "bench", "metrics",
                           "served_requests.py"), "w") as f:
        f.write("def read(rec):\n"
                "    return sum(1 for s in rec.sent if s.req.done)\n")
    spec_path = os.path.join(root, "BENCHMARK.json")
    with open(spec_path) as f:
        spec = json.load(f)
    spec["workloads"].append({"name": "qwen3-chat-slow",
                              "config": "qwen3-0.6b", "traffic": "chat-slow",
                              "chips": 1, "why": "a new cell"})
    spec["end_to_end"].append({"name": "served_requests", "unit": "count",
                               "better": "higher", "bound": 0.1,
                               "source": "host_clock",
                               "workloads": ["qwen3-chat-slow"]})
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    out = _run(root, "qwen3-chat-slow", counter)
    assert out["correct"] is True
    assert out["attempted"] == 6
    assert out["metrics"]["served_requests"]["value"] >= 6
    assert "itl_p95_ms" not in out["metrics"]


# -- faults that correct has to catch -------------------------------------------
def test_a_token_altered_where_produced_is_not_correct(tiny_root, counter,
                                                       monkeypatch):
    from repro.serve import engine
    orig = engine.ServeEngine._finish_token

    def altered(self, req, tok):
        if req.rid < 1_000_000_000 and len(req.out) == 3:
            tok = (tok + 1) % self.run.model.vocab_size
        return orig(self, req, tok)
    monkeypatch.setattr(engine.ServeEngine, "_finish_token", altered)
    out = _run(tiny_root, "qwen3-chat", counter)
    assert out["correct"] is False
    assert out["checks"]["max_logit_gap"]["value"] > 0.02


def test_a_decode_step_that_returns_its_cache_unchanged_is_not_correct(
        tiny_root, counter, monkeypatch):
    from repro.train import step
    orig = step.make_decode_step

    def stale(*a, **kw):
        fn = orig(*a, **kw)

        def decode(params, cache, *rest):
            out, _ = fn(params, cache, *rest)
            return out, cache
        return decode
    monkeypatch.setattr(step, "make_decode_step", stale)
    out = _run(tiny_root, "qwen3-chat", counter)
    assert out["correct"] is False


@pytest.mark.parametrize("fault", ["top_k", "temperature"])
def test_a_sampler_that_ignores_its_top_k_or_temperature_is_not_correct(
        tiny_root, counter, monkeypatch, fault):
    """Fused sampling with the filter or the scale dropped: the greedy
    tokens stay right, the sampled ones do not. A temperature error moves
    a sampled token by about (1/T - 1) times the spread of the top logits,
    so the mix samples at 0.25 here, where dropping it shows at smoke
    size."""
    if fault == "temperature":
        path = os.path.join(tiny_root, "bench", "traffic", "chat.json")
        with open(path) as f:
            mix = json.load(f)
        with open(path, "w") as f:
            json.dump(dict(mix, temperature=0.25), f)
    from repro.kernels import sampling
    orig = sampling.prepare_rows

    def broken(logits, temp, top_k, **kw):
        import jax.numpy as jnp
        if fault == "top_k":
            top_k = jnp.zeros_like(jnp.asarray(top_k))
        else:
            temp = jnp.where(jnp.asarray(temp) > 0, 1.0, temp)
        return orig(logits, temp, top_k, **kw)
    import jax
    monkeypatch.setattr(sampling, "prepare_rows", broken)
    jax.clear_caches()              # the sampler traced with the fault
    try:
        out = _run(tiny_root, "qwen3-chat", counter)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert out["correct"] is False
