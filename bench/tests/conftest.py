"""CPU fixtures for the benchmark's own tests: a copy of the benchmark at
smoke size (2 layers, 64 wide, 500-token vocabulary) under a temporary
checkout root, with every mix scaled down to prompts of 16-64 tokens.

    python -m pytest bench/tests -q
"""
import json
import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

#: smoke widths of every configuration (names keep the published keys)
TINY = {"hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "num_hidden_layers": 2, "vocab_size": 500}
TINY_SERVE = {"slots": 4, "max_len": 128, "prefill_chunk": 32}
#: CPU peaks for readers that need a table row; never a device's
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def make_root(tmp_path, *, interpret=False) -> str:
    """A checkout root holding BENCHMARK.json and a copy of ``bench/`` at
    smoke size; returns the root."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for c in spec["configs"]:
        _tiny_config(str(root / c["file"]), interpret)
    for mix_path in (root / "bench" / "traffic").glob("*.json"):
        mix = json.loads(mix_path.read_text())
        mix["prompt"].update(median=24, min=16, max=64, round_to=16)
        mix["output"].update(median=8, min=4, max=32)
        mix["top_k"] = 20
        if "rate_per_s" in mix:
            mix["rate_per_s"] = 4.0
        for ev in mix.get("events", []):
            ev["at_s"] = 1.0
        mix_path.write_text(json.dumps(mix))
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return str(root)


def add_batch_cell(root: str) -> None:
    """Put the staged ``phi3-batch`` cell (closed loop on
    phi3-mini-3.8b-l16) into the checkout's BENCHMARK.json, at smoke
    size: entries only, its files are already there."""
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    file = "bench/configs/phi3-mini-3.8b-l16.json"
    spec["configs"].append({"name": "phi3-mini-3.8b-l16",
                            "source": "https://huggingface.co/microsoft/"
                                      "Phi-3-mini-4k-instruct",
                            "file": file, "reduced": ["num_hidden_layers"],
                            "why": "staged"})
    spec["workloads"].append({"name": "phi3-batch",
                              "config": "phi3-mini-3.8b-l16",
                              "traffic": "batch", "chips": 1,
                              "why": "staged"})
    spec["end_to_end"].append({"name": "tokens_per_s", "unit": "tokens/s",
                               "better": "higher", "bound": 0.05,
                               "source": "host_clock",
                               "workloads": ["phi3-batch"]})
    with open(path, "w") as f:
        json.dump(spec, f)
    _tiny_config(os.path.join(root, file), False)


def _tiny_config(path: str, interpret: bool) -> None:
    with open(path) as f:
        cfg = json.load(f)
    cfg.update(TINY, interpret=interpret)
    cfg["serve"].update(TINY_SERVE)
    cfg["check"].update(min_tokens=48, max_requests=4, reference_batch=2,
                        max_logit_gap=0.02)
    with open(path, "w") as f:
        json.dump(cfg, f)


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)


@pytest.fixture(scope="session")
def counter():
    from bench.harness import CompileCounter
    return CompileCounter().install()
