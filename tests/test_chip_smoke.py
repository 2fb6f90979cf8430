"""``chip_smoke.py``'s phases at smoke size on the CPU, Pallas kernels in
interpret mode: the same code the chip runs at full width, minus the
platform check in ``main``."""
import importlib.util
import os
import shutil
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE_PY = os.path.join(ROOT, "chip_smoke.py")


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE_PY)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod          # dataclasses look it up there
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    cs = _load()
    run, params = cs.build(cs.SMOKE, interpret=True)
    assert run.kernels == "pallas"
    return cs, run, params


def test_pause_phase_streams_identical_and_placed(smoke, tmp_path):
    """Checks (a), (b) and (e) on the one CPU device: a live pause
    mid-stream leaves every stream bit-identical, and params and KV cache
    come back on the VF's device."""
    cs, run, params = smoke
    out = cs.phase_pause(run, params, jax.devices(), cs.SMOKE, str(tmp_path))
    assert out["tokens"] == 2 * cs.SMOKE.requests * cs.SMOKE.new_tokens
    # the pre-copy rounds staged the params; the stop-and-copy skips them
    assert out["staged_bytes"] > 0 and out["skipped_bytes"] > 0
    assert 0 <= out["stop_ms"]


def test_logits_phase_pallas_matches_reference(smoke):
    """Check (d): interpret-mode kernels against the jnp reference."""
    cs, run, params = smoke
    out = cs.phase_logits(run, params, cs.SMOKE)
    for name in ("prefill", "decode"):
        assert out[name]["rel_l2"] <= cs.LOGIT_RTOL


def test_main_refuses_a_host_without_tpu(smoke, capsys):
    cs, _, _ = smoke
    assert cs.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_fails(tmp_path):
    """Outside the repo (no ``src/``) the script fails before any result."""
    shutil.copy(SMOKE_PY, tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_four_chip_phase_on_virtual_devices(tmp_path):
    """The ``--chips 4`` path on four CPU devices: three engines on their
    own devices, one migrated onto the free fourth, greedy streams equal
    to the one-device run's."""
    prog = (
        "import jax, sys\n"
        f"sys.path.insert(0, {ROOT!r})\n"
        "import chip_smoke as cs\n"
        "run, params = cs.build(cs.SMOKE, interpret=True)\n"
        "out = cs.phase_four_chips(run, params, jax.devices(), cs.SMOKE,\n"
        f"                          {str(tmp_path)!r})\n"
        "print(out['migrated_to'])\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "TFRT_CPU_3"


def test_compile_cache_location(monkeypatch, tmp_path):
    """The entry points' cache: ``JAX_COMPILATION_CACHE_DIR`` where it is
    set (JAX reads it; nothing is overridden), else a fixed directory in
    the checkout."""
    from repro.launch import cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert cache.enable_compile_cache() == os.path.join(ROOT,
                                                            ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == cache.DEFAULT_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
