"""The serve path's Pallas kernels compile for a TPU v5e at qwen3-0.6b
widths (H=16, K=8, hd=128, page 16, V=151936), and the sliding-window and
grouped expert kernels at Mellum2's (H=32, K=4, window 1024, D=2304,
expert width 896, 8 experts held).

Nothing runs: each test compiles for a described, not attached, chip —
what the TPU compiler refuses (block shapes off the (8, 128) tiling, too
much VMEM) fails here instead of on the chip. The topology is described
inside a fixture, so a process that cannot load the TPU library skips
these tests and every worker still collects the same ones.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops as kops

B, H, K, HD, PAGE, VOCAB = 8, 16, 8, 128, 16, 151936
MAX_LEN = 512
NP = MAX_LEN // PAGE
P = 1 + B * NP


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs to /tmp
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def spec(one_chip):
    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _assert_kernel(lowered):
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_paged_decode_compiles_for_v5e(spec, kv_dtype):
    q = spec((B, 1, H, HD), jnp.bfloat16)
    pages = spec((P, PAGE, K, HD), jnp.dtype(kv_dtype))
    tables, pos = spec((B, NP), jnp.int32), spec((B,), jnp.int32)
    if kv_dtype == "int8":
        scale = spec((P, PAGE, K), jnp.float32)
        lowered = kops.paged_decode_quant.lower(
            q, pages, pages, scale, scale, tables, pos, backend="pallas")
    else:
        lowered = kops.paged_decode.lower(q, pages, pages, tables, pos,
                                          backend="pallas")
    _assert_kernel(lowered)


def test_fused_sample_compiles_for_v5e(spec):
    _assert_kernel(kops.fused_sample.lower(
        spec((B, VOCAB), jnp.float32), spec((B,), jnp.float32),
        spec((B,), jnp.int32), spec((B, 3), jnp.int32),
        vocab_size=VOCAB, backend="pallas"))


@pytest.mark.parametrize("q_len", [MAX_LEN, 64])
def test_flash_attention_compiles_for_v5e(spec, q_len):
    """Whole-prompt prefill (S=512) and a chunked-prefill continuation
    (a 64-row chunk at a traced offset against 512 cached rows)."""
    q = spec((1, q_len, H, HD), jnp.bfloat16)
    kv = spec((1, MAX_LEN, K, HD), jnp.bfloat16)
    args = (q, kv, kv) if q_len == MAX_LEN else (q, kv, kv,
                                                 spec((), jnp.int32))
    _assert_kernel(kops.flash_attention.lower(*args, backend="pallas"))


@pytest.mark.parametrize("q_len", [256, 4096])
def test_windowed_flash_attention_compiles_for_v5e(spec, q_len):
    """A chunk against its staging ring (window 1024 + chunk 256) and a
    whole windowed prompt, at Mellum2's heads."""
    q = spec((1, q_len, 32, HD), jnp.bfloat16)
    T = 1280 if q_len == 256 else q_len
    kv = spec((1, T, 4, HD), jnp.bfloat16)
    off = spec((), jnp.int32)
    _assert_kernel(kops.flash_attention.lower(q, kv, kv, off, off,
                                              window=1024,
                                              backend="pallas"))


def test_windowed_paged_decode_compiles_for_v5e(spec):
    q = spec((16, 1, 32, HD), jnp.bfloat16)
    pages = spec((1 + 16 * 80, PAGE, 4, HD), jnp.bfloat16)
    _assert_kernel(kops.paged_decode.lower(
        q, pages, pages, spec((16, 1024), jnp.int32), spec((16,), jnp.int32),
        window=1024, backend="pallas"))


@pytest.mark.parametrize("rows", [16 * 8, 256 * 8])
def test_grouped_ffn_compiles_for_v5e(spec, rows):
    """A decode step's and a prefill chunk's (token, expert) pairs."""
    x = spec((rows, 2304), jnp.bfloat16)
    w = spec((8, 2304, 896), jnp.bfloat16)
    _assert_kernel(kops.grouped_ffn.lower(
        x, w, w, spec((8, 896, 2304), jnp.bfloat16), spec((8,), jnp.int32),
        backend="pallas"))
