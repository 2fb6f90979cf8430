"""End-to-end behaviour tests for the full system: the training driver
(incl. crash/restart fault tolerance), the serving driver, and a
reduced-mesh dry-run through the real dryrun entry point."""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(argv, timeout=900, extra_env=None):
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    if extra_env:
        env.update(extra_env)
    return subprocess.run([sys.executable] + argv, capture_output=True,
                          text=True, env=env, timeout=timeout)


@pytest.mark.slow
def test_train_driver_learns(tmp_path):
    out = run_py(["-m", "repro.launch.train", "--arch", "qwen3-0.6b",
                  "--smoke", "--steps", "30", "--batch", "8", "--seq", "64",
                  "--lr", "3e-3", "--warmup", "5", "--workdir",
                  str(tmp_path), "--checkpoint-every", "10"])
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(l) for l in
             open(tmp_path / "metrics.jsonl").read().splitlines()]
    assert lines[-1]["step"] == 30
    assert lines[-1]["loss"] < lines[0]["loss"] - 0.3, (
        lines[0]["loss"], lines[-1]["loss"])
    # checkpoints exist
    assert any(d.startswith("step_") for d in os.listdir(tmp_path / "ckpt"))


@pytest.mark.slow
def test_train_crash_restart_bit_identical(tmp_path):
    """Kill the driver mid-run; --resume must produce the same final loss
    as an uninterrupted run (determinism + crash consistency)."""
    common = ["-m", "repro.launch.train", "--arch", "qwen3-0.6b", "--smoke",
              "--steps", "20", "--batch", "4", "--seq", "32", "--lr", "1e-2",
              "--warmup", "2", "--checkpoint-every", "5"]
    ref = run_py(common + ["--workdir", str(tmp_path / "a")])
    assert ref.returncode == 0, ref.stderr[-2000:]
    ref_last = json.loads(ref.stdout.strip().splitlines()[-1])

    crash = run_py(common + ["--workdir", str(tmp_path / "b"),
                             "--crash-at", "10"])
    assert crash.returncode == 17          # simulated hard crash
    resume = run_py(common + ["--workdir", str(tmp_path / "b"), "--resume"])
    assert resume.returncode == 0, resume.stderr[-2000:]
    res_last = json.loads(resume.stdout.strip().splitlines()[-1])
    assert res_last["loss"] == pytest.approx(ref_last["loss"], abs=1e-5)


@pytest.mark.slow
def test_serve_driver_completes():
    out = run_py(["-m", "repro.launch.serve", "--arch", "qwen3-0.6b",
                  "--smoke", "--requests", "5", "--slots", "2",
                  "--new-tokens", "4", "--max-len", "32"])
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["completed"] == 5
    assert res["generated_tokens"] == 20


@pytest.mark.slow
def test_dryrun_entrypoint_reduced_mesh(tmp_path):
    """The real dryrun.py cell path on a reduced (8-device) mesh: lower +
    compile + roofline JSON for one cell. (The full 512-device sweep's
    committed results are validated by test_full_sweep_results_complete.)"""
    prog = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=8'\n"
        "import repro.configs.base as B\n"
        "import repro.launch.mesh as M\n"
        "import jax\n"
        "B.SINGLE_POD_MESH = B.MeshConfig((4, 2), ('data', 'model'))\n"
        "M.make_production_mesh = "
        "lambda *, multi_pod=False: M.auto_mesh((4, 2), "
        "('data', 'model'))\n"
        "from repro.launch.dryrun import run_cell\n"
        f"r = run_cell('qwen3-0.6b', 'train_4k', False, "
        f"out_dir='{tmp_path}', force=True)\n"
        "assert r['status'] == 'ok', r.get('error')\n"
        "print(r['status'], r['roofline']['bound'])\n"
    )
    out = run_py(["-c", prog])
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1].startswith("ok")


@pytest.mark.sweep
def test_full_sweep_results_complete():
    """The committed dry-run sweep must cover all 40 cells x 2 meshes with
    no errors (skips only where DESIGN.md §4 documents them). Gated at
    COLLECTION time (conftest deselects ``sweep`` tests in checkouts
    without the committed results; ``SVFF_FULL_SWEEP=1`` forces them on)
    so the suite reports a deselection, never a silent runtime skip."""
    d = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun")
    assert os.path.isdir(d), f"no committed sweep results at {d}"
    statuses = {}
    for fn in os.listdir(d):
        if not fn.endswith(".json"):
            continue
        mesh_part = fn.rsplit("__", 1)[-1].replace(".json", "")
        if mesh_part not in ("single", "multi"):
            continue                     # tagged perf-iteration cells
        r = json.load(open(os.path.join(d, fn)))
        statuses[fn] = r["status"]
    assert len(statuses) == 80
    errors = {k: v for k, v in statuses.items() if v == "error"}
    assert not errors, errors
    skips = [k for k, v in statuses.items() if v == "skipped"]
    assert all("long_500k" in k for k in skips)
    assert len(skips) == 16                  # 8 full-attention archs x 2
