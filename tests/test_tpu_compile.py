"""The serve path's Pallas kernels compile for a TPU v5e at qwen3-0.6b
widths (H=16, K=8, hd=128, page 16, V=151936), and the sliding-window and
grouped expert kernels at Mellum2's (H=32, K=4, window 1024, D=2304,
expert width 896, 8 experts held). The whole paged decode step compiles
at both, and it and an admission update the KV pools in place.

Nothing runs: each test compiles for a described, not attached, chip —
what the TPU compiler refuses (block shapes off the (8, 128) tiling, too
much VMEM) fails here instead of on the chip. The topology is described
inside a fixture, so a process that cannot load the TPU library skips
these tests and every worker still collects the same ones.
"""
import dataclasses
import os
import re

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
    """Over the pools of 28 layers stacked, at a traced layer index."""
    q = spec((B, 1, H, HD), jnp.bfloat16)
    pages = spec((28, P, PAGE, K, HD), jnp.dtype(kv_dtype))
    tables, pos = spec((B, NP), jnp.int32), spec((B,), jnp.int32)
    layer = spec((), jnp.int32)
    if kv_dtype == "int8":
        scale = spec((28, P, PAGE, K), jnp.float32)
        lowered = kops.paged_decode_quant.lower(
            q, pages, pages, scale, scale, tables, pos, layer,
            backend="pallas")
    else:
        lowered = kops.paged_decode.lower(q, pages, pages, tables, pos,
                                          layer, backend="pallas")
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
    pages = spec((7, 1 + 16 * 80, PAGE, 4, HD), jnp.bfloat16)
    _assert_kernel(kops.paged_decode.lower(
        q, pages, pages, spec((16, 1024), jnp.int32), spec((16,), jnp.int32),
        spec((), jnp.int32), window=1024, backend="pallas"))


@pytest.mark.parametrize("rows", [16 * 8, 256 * 8])
def test_grouped_ffn_compiles_for_v5e(spec, rows):
    """A decode step's and a prefill chunk's (token, expert) pairs."""
    x = spec((rows, 2304), jnp.bfloat16)
    w = spec((8, 2304, 896), jnp.bfloat16)
    _assert_kernel(kops.grouped_ffn.lower(
        x, w, w, spec((8, 896, 2304), jnp.bfloat16), spec((8,), jnp.int32),
        backend="pallas"))


# ---------------------------------------------------------------------------
# the whole paged decode step and admission: the KV pools stay in place
# ---------------------------------------------------------------------------
#: ops that may not have a pool-sized result: a copy of a pool in another
#: layout, or a layer's pool sliced out of the stack and written back
COPY_OPS = ("reshape", "copy", "transpose", "dynamic-slice",
            "dynamic-update-slice")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.+?) ([a-z][\w\-]*)\(")


def pool_sized_ops(hlo: str, pages) -> list:
    """(opcode, name) of each instruction of compiled HLO text whose result
    has a dimension in ``pages``: the page count of a KV pool."""
    found = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m and any(int(d) in pages
                     for dims in re.findall(r"\w+\[([\d,]+)\]", m.group(2))
                     for d in dims.split(",")):
            found.append((m.group(3), m.group(1)))
    return found


def pool_copies(hlo: str, pages) -> list:
    """The pool-sized ops of ``COPY_OPS``, and the fusions named after
    them (``dynamic-slice_bitcast_fusion``, ...)."""
    return [(op, name) for op, name in pool_sized_ops(hlo, pages)
            if op in COPY_OPS or (op == "fusion" and any(
                c in name for c in COPY_OPS))]


def assert_in_place(compiled, pages, layer_pool: int, pools: int):
    """No pool-sized copy, temporaries under one layer's pool, and the
    output aliasing at least the pools' bytes of the donated input."""
    hlo = compiled.as_text()
    copies = pool_copies(hlo, pages)
    assert not copies, copies
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < layer_pool, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes >= pools, (mem.alias_size_in_bytes,
                                              pools)


def _serve_run(cfg, max_len):
    from repro.configs import PrecisionConfig, RunConfig
    from repro.configs.base import ShapeConfig
    return RunConfig(model=cfg, shape=ShapeConfig("serve", "decode", max_len,
                                                  16),
                     precision=PrecisionConfig("bfloat16", "bfloat16",
                                               "float32"),
                     kernel_backend="pallas")


def _pool_specs(spec, run, pages, ring_pages=0):
    from repro.models.model import build_model
    from repro.serve.paged import init_paged_cache
    model = build_model(run)
    cache = jax.eval_shape(lambda: init_paged_cache(
        model, run.shape, pages, PAGE, ring_pages=ring_pages))
    return model, jax.tree.map(lambda s: spec(s.shape, s.dtype), cache)


def _compile_decode_step(spec, cfg, max_len, pages, width, ring_pages=0):
    """The serve engine's paged, fused decode program, built by the
    engine's own factory (with its donation), for 16 slots of
    ``max_len``, compiled for one described v5e chip."""
    from repro.serve.engine import decode_program
    slots = 16
    run = _serve_run(cfg, max_len)
    model, cache = _pool_specs(spec, run, pages, ring_pages)
    params = jax.tree.map(lambda s: spec(s.shape, s.dtype),
                          model.param_shapes())
    step = decode_program(run, paged=True, fused=True,
                          counters=cfg.moe is not None)
    return step.lower(
        params, cache, spec((slots, 1), jnp.int32), spec((slots,), jnp.int32),
        spec((slots, width), jnp.int32), spec((slots,), jnp.bool_),
        spec((slots,), jnp.float32), spec((slots,), jnp.int32),
        spec((slots, 3), jnp.int32)).compile(), cache


def _pools_bytes(cache) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))


@pytest.mark.parametrize("model", ["qwen3-0.6b", "mellum2-ep8"])
def test_paged_decode_step_reads_pools_in_place_for_v5e(spec, model):
    """qwen3-0.6b at 2 layers (16 slots of 2,048, 2,049 pages, table width
    64) and Mellum2's share at one sliding and one full block twice over
    (6,657 full pages, rings of 80 pages a slot, width 1,024): the layer
    scan carries the stacked pools, writes each layer's new rows where
    they lie, and ``paged_decode`` reads the stack at the layer's index.
    So no pool is sliced out of the stack and written back, none is
    viewed in another layout, the temporaries stay under one layer's K
    pool, and the donated pools come back in the same buffers."""
    from repro.configs import ATTN
    from repro.configs import mellum2_12b_a2_5b as mellum2
    from repro.configs import qwen3_0_6b
    # the page counts of a pool, and of the stack of 2 that the kernel
    # reads as one pool
    if model == "qwen3-0.6b":
        cfg = dataclasses.replace(qwen3_0_6b.full(), num_layers=2)
        pages = {2049, 2 * 2049}
        k_pool = 2049 * PAGE * K * HD * 2
        compiled, cache = _compile_decode_step(spec, cfg, 2048, 2049, 64)
    else:
        cfg = dataclasses.replace(
            mellum2.ep_share(8, 0), num_layers=4, block_pattern=(ATTN,) * 2,
            attn_kinds=(mellum2.SLIDING, mellum2.FULL))
        pages = {6657, 2 * 6657, 1 + 16 * 80, 2 * (1 + 16 * 80)}
        k_pool = 6657 * PAGE * 4 * HD * 2
        compiled, cache = _compile_decode_step(spec, cfg, 16384, 6657, 1024,
                                               ring_pages=80)
    assert "paged_decode" in compiled.as_text()
    assert_in_place(compiled, pages, k_pool, _pools_bytes(cache))


def test_admit_kv_writes_pools_in_place_for_v5e(spec):
    """One admission of 64 pages (a 1,024-row prompt) into qwen3-0.6b's
    pools at 2 layers (2,049 pages): the rows are scattered into the
    donated pools, which come back in the same buffers."""
    from repro.configs import qwen3_0_6b
    from repro.serve.paged import _admit_kv_jit
    cfg = dataclasses.replace(qwen3_0_6b.full(), num_layers=2)
    run = _serve_run(cfg, 2048)
    _, cache = _pool_specs(spec, run, 2049)
    req = {"block0": {n: spec((2, 1, 64 * PAGE, K, HD), jnp.bfloat16)
                      for n in ("k", "v")}}
    compiled = _admit_kv_jit.lower(
        cache, req, spec((64,), jnp.int32), spec((), jnp.int32),
        page_size=PAGE, skip=0).compile()
    assert_in_place(compiled, {2049, 2 * 2049}, 2049 * PAGE * K * HD * 2,
                    _pools_bytes(cache))
