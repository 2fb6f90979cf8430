"""The serve path's Pallas kernels compile for a TPU v5e at qwen3-0.6b
widths (H=16, K=8, hd=128, page 16, V=151936).

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
