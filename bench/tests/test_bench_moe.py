"""The sparse-expert, sliding-window configuration at smoke size: one
period of 3 sliding (window 8) + 1 full YaRN layers, 4 of 16 experts held,
top 4; its cell served end to end on the CPU and judged by its reference
(kernels in interpret mode), its fp8 control judged not correct, and the
roofline readers of its kernels on a synthetic record."""
import json
import os

import pytest

from conftest import CPU_PEAKS, make_root
from test_bench_harness import _run

CELL = "mellum2-code-long"
FILE = "bench/configs/mellum2-12b-a2.5b-ep8.json"


def _tiny_moe(root, interpret):
    path = os.path.join(root, FILE)
    with open(path) as f:
        cfg = json.load(f)
    types = ["sliding_attention"] * 3 + ["full_attention"]
    cfg.update(num_hidden_layers=4, layer_types=types,
               mlp_layer_types=["sparse"] * 4, sliding_window=8,
               num_experts=4, router_experts=16, held_offset=4,
               num_experts_per_tok=4, moe_intermediate_size=32,
               interpret=interpret)
    cfg["rope_parameters"]["full_attention"].update(
        original_max_position_embeddings=32, factor=4,
        attention_factor=1.1386)
    with open(path, "w") as f:
        json.dump(cfg, f)


@pytest.fixture
def moe_root(tmp_path):
    root = make_root(tmp_path, interpret=True)
    _tiny_moe(root, True)
    return root


def test_the_cell_is_served_correctly_and_its_control_is_not(moe_root,
                                                            counter):
    out = _run(moe_root, CELL, counter, seconds=3.0, control=True)
    prog = out["program_checks"]
    limit = prog["max_logit_gap"]["limit"]
    assert prog["max_logit_gap"]["value"] <= limit
    assert prog["tokens_checked"]["value"] >= 1
    assert out["failed"] == 0
    assert out["correct"] is False
    assert set(out["metrics"]) == {"itl_p95_ms", "setup_s"}


def test_the_kernel_rooflines_on_a_synthetic_record():
    """Window-bounded decode bytes and the grouped FFN's expected bytes:
    a share is least time over kernel time, so it halves when the kernel
    takes twice as long."""
    import types
    from bench import moe_roofline as mr
    from bench.families.moe import Dims
    m = Dims(layers=28, d=2304, heads=32, kv_heads=4, head_dim=128,
             vocab=98304, vocab_padded=98304, experts=64, experts_held=8,
             held_offset=0, top_k=8, norm_topk=True, expert_ff=896,
             qk_norm=True, eps=1e-6, dtype="bfloat16",
             windows=(1024, 1024, 1024, 0), ropes=((5e5, 0, 0, 0, 0, 1),) * 4)
    least = mr.decode_least_s(m, [8000], 16, 512, CPU_PEAKS)
    full = 28 * (2 * 8000 * 4 * 128 * 2) / CPU_PEAKS["hbm_bytes_per_s"]
    assert least < 0.4 * full         # 21 of 28 layers read 1024 rows
    f, b = mr.expert_ffn_call(m, 16)
    assert f == pytest.approx(6 * 2304 * 896 * 16)
    assert 0.8 * 8 * 3 * 2304 * 896 * 2 < b < 8 * 3 * 2304 * 896 * 2
    trace = types.SimpleNamespace(kernel_s=lambda k: 1.0)
    rec = types.SimpleNamespace(trace=trace, dims=m, peaks=CPU_PEAKS,
                                decode_log=[(0, 1, [8000] * 4, 16, 512)],
                                steps=[(0, 1)], sent=[],
                                trace_host=(0, 2),
                                cfg={"serve": {"prefill_chunk": 256}})
    one = mr.expert_ffn_roofline(rec)
    trace.kernel_s = lambda k: 2.0
    assert mr.expert_ffn_roofline(rec) == pytest.approx(one / 2)
    assert mr.paged_decode_roofline(rec) > 0
