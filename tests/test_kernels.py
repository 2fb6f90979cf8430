"""Per-kernel sweeps: shapes x dtypes, assert_allclose vs the ref.py
oracles (kernels run in interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode
from repro.kernels.paged_decode import paged_decode, paged_decode_quant
from repro.kernels.qdma_pack import qdma_pack, qdma_unpack
from repro.kernels.ssm_scan import ssm_scan


def rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,K,hd,causal", [
    (1, 128, 4, 4, 64, True),        # MHA causal
    (2, 256, 4, 2, 64, True),        # GQA
    (1, 256, 8, 1, 128, True),       # MQA, wide head
    (2, 128, 2, 2, 64, False),       # bidirectional (encoder)
    (1, 384, 6, 3, 64, True),        # non-pow2 grid
])
def test_flash_attention_sweep(B, S, H, K, hd, causal, dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    q = rand(ks[0], (B, S, H, hd), dtype)
    k = rand(ks[1], (B, S, K, hd), dtype)
    v = rand(ks[2], (B, S, K, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("blocks", [(64, 64), (128, 32)])
def test_flash_attention_block_shapes(blocks):
    bq, bk = blocks
    ks = jax.random.split(jax.random.key(1), 3)
    q = rand(ks[0], (1, 256, 2, 64), jnp.float32)
    k = rand(ks[1], (1, 256, 2, 64), jnp.float32)
    v = rand(ks[2], (1, 256, 2, 64), jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,T,H,K,hd,pos", [
    (1, 256, 4, 4, 64, 255),
    (2, 512, 4, 2, 64, 17),          # pos inside first block
    (1, 1024, 8, 2, 128, 700),
    (2, 256, 2, 1, 64, 0),           # single valid position
])
def test_flash_decode_sweep(B, T, H, K, hd, pos, dtype):
    ks = jax.random.split(jax.random.key(2), 3)
    q = rand(ks[0], (B, 1, H, hd), dtype)
    k = rand(ks[1], (B, T, K, hd), dtype)
    v = rand(ks[2], (B, T, K, hd), dtype)
    out = flash_decode(q, k, v, pos, interpret=True)
    want = ref.flash_decode_ref(q, k, v, pos)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


def test_flash_decode_matches_flash_attention_last_row():
    """decode(pos=S-1) == last row of causal flash_attention."""
    ks = jax.random.split(jax.random.key(3), 3)
    S = 256
    q = rand(ks[0], (1, S, 4, 64), jnp.float32)
    k = rand(ks[1], (1, S, 2, 64), jnp.float32)
    v = rand(ks[2], (1, S, 2, 64), jnp.float32)
    full = flash_attention(q, k, v, causal=True, interpret=True)
    dec = flash_decode(q[:, -1:], k, v, S - 1, interpret=True)
    np.testing.assert_allclose(np.asarray(dec[:, 0]), np.asarray(full[:, -1]),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
@pytest.mark.parametrize("B,H,K,hd,page,NP,P,positions,nper,layer", [
    (1, 4, 4, 64, 16, 4, 8, (63,), 1, 0),          # MHA, full view valid
    (3, 4, 2, 64, 8, 4, 16, (5, -1, 31), 1, 0),    # GQA, one inactive slot
    (2, 8, 2, 128, 32, 2, 5, (0, 40), 1, 0),       # MQA-ish wide head
    (2, 2, 1, 64, 8, 8, 17, (-1, -1), 1, 0),       # all slots inactive
    (3, 16, 8, 128, 16, 4, 12, (15, -1, 48), 1, 0),  # qwen3 heads; page ends
    (2, 32, 4, 128, 16, 3, 7, (16, 47), 1, 0),     # Mellum2 heads; page bounds
    (3, 16, 8, 128, 16, 4, 12, (15, -1, 48), 3, 1),  # stack: a middle layer
    (2, 32, 4, 128, 16, 3, 7, (16, 47), 3, 2),     # stack: the last layer
])
def test_paged_decode_sweep(B, H, K, hd, page, NP, P, positions, nper, layer,
                            dtype):
    """The kernel reads each page of layer ``layer`` of the stacked pools
    as the pool stores it, (page, K, hd), and equals the oracle on that
    layer; an int8 pool (``paged_decode_quant``, float32 queries) equals
    the oracle over the same quantized pool."""
    ks = jax.random.split(jax.random.key(4), 3)
    quant = dtype == jnp.int8
    fdt = jnp.float32 if quant else dtype
    q = rand(ks[0], (B, 1, H, hd), fdt)
    kp = rand(ks[1], (nper, P, page, K, hd), fdt)
    vp = rand(ks[2], (nper, P, page, K, hd), fdt)
    rng = np.random.default_rng(0)
    # arbitrary page ids (reads may alias; page 0 stays reserved for writes)
    tables = jnp.asarray(rng.integers(1, P, (B, NP)), jnp.int32)
    pos = jnp.asarray(positions, jnp.int32)
    one = slice(layer, layer + 1)            # the layer read, alone
    if quant:
        kq, k_scale = ref.kv_quant_ref(kp)
        vq, v_scale = ref.kv_quant_ref(vp)
        out = paged_decode_quant(q, kq, vq, k_scale, v_scale, tables, pos,
                                 layer, interpret=True)
        want = ref.paged_decode_quant_ref(q, kq, vq, k_scale, v_scale,
                                          tables, pos, layer)
        alone = ref.paged_decode_quant_ref(q, kq[one], vq[one],
                                           k_scale[one], v_scale[one],
                                           tables, pos, 0)
    else:
        out = paged_decode(q, kp, vp, tables, pos, layer, interpret=True)
        want = ref.paged_decode_ref(q, kp, vp, tables, pos, layer)
        alone = ref.paged_decode_ref(q, kp[one], vp[one], tables, pos, 0)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(alone))
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=TOL[fdt], rtol=TOL[fdt])
    # inactive slots produce exactly-zero rows in BOTH implementations
    for b, p in enumerate(positions):
        if p < 0:
            assert np.all(np.asarray(out[b]) == 0)
            assert np.all(np.asarray(want[b]) == 0)


def test_paged_decode_matches_flash_decode_contiguous_view():
    """With an identity-ordered table the paged kernel equals flash_decode
    over the gathered contiguous cache."""
    ks = jax.random.split(jax.random.key(5), 3)
    page, NP, P, K, hd = 32, 4, 9, 2, 64
    q = rand(ks[0], (1, 1, 4, hd), jnp.float32)
    kp = rand(ks[1], (1, P, page, K, hd), jnp.float32)
    vp = rand(ks[2], (1, P, page, K, hd), jnp.float32)
    tables = jnp.asarray([[3, 1, 8, 5]], jnp.int32)
    pos = 77
    out = paged_decode(q, kp, vp, tables, jnp.asarray([pos], jnp.int32), 0,
                       interpret=True)
    k = kp[0, tables[0]].reshape(1, NP * page, K, hd)
    v = vp[0, tables[0]].reshape(1, NP * page, K, hd)
    want = flash_decode(q, k, v, pos, block_k=page, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,hd,N,chunk", [
    (1, 128, 2, 32, 16, 32),
    (2, 256, 4, 64, 64, 64),
    (1, 512, 1, 128, 16, 128),
])
def test_ssm_scan_sweep(B, S, H, hd, N, chunk, dtype):
    ks = jax.random.split(jax.random.key(4), 4)
    xdt = rand(ks[0], (B, S, H, hd), dtype)
    Bv = rand(ks[1], (B, S, N), dtype)
    Cv = rand(ks[2], (B, S, N), dtype)
    la = -jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))
    y, hf = ssm_scan(xdt, Bv, Cv, la, chunk=chunk, interpret=True)
    yr, hfr = ref.ssm_scan_sequential_ref(xdt, Bv, Cv, la)
    tol = 5e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=tol,
                               rtol=tol)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(hfr), atol=tol,
                               rtol=tol)


def test_ssm_scan_matches_chunked_ref():
    ks = jax.random.split(jax.random.key(5), 4)
    B, S, H, hd, N = 2, 256, 3, 32, 16
    xdt = rand(ks[0], (B, S, H, hd), jnp.float32)
    Bv = rand(ks[1], (B, S, N), jnp.float32)
    Cv = rand(ks[2], (B, S, N), jnp.float32)
    la = -jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))
    y, hf = ssm_scan(xdt, Bv, Cv, la, chunk=64, interpret=True)
    yr, hfr = ref.ssm_scan_ref(xdt, Bv, Cv, la, chunk=64)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-4,
                               rtol=2e-4)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape,block", [
    ((4, 512), 256), ((3, 7, 512), 128), ((128, 256), 256), ((2, 1024), 512),
])
def test_qdma_pack_sweep(shape, block, dtype):
    x = rand(jax.random.key(6), shape, dtype)
    q, s = qdma_pack(x, block=block, interpret=True)
    qr, sr = ref.qdma_pack_ref(x, block=block)
    assert q.dtype == jnp.int8 and q.shape == x.shape
    # identical up to round-to-nearest ties on values landing exactly on a
    # quantization boundary (last-ulp division-order differences)
    diff = np.abs(np.asarray(q, np.int32) - np.asarray(qr, np.int32))
    assert diff.max() <= 1 and (diff > 0).mean() < 1e-3
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    # round-trip error bounded by the quantization step
    xx = qdma_unpack(q, s, dtype="float32", interpret=True)
    step = np.asarray(s)[..., :, None] * np.ones((1,) * s.ndim + (block,))
    err = np.abs(np.asarray(xx) -
                 np.asarray(x, np.float32).reshape(xx.shape))
    assert (err <= 0.5 * step.reshape(err.shape) + 1e-6).all()


def test_qdma_pack_preserves_zeros_and_extremes():
    x = jnp.zeros((4, 512), jnp.float32).at[0, 0].set(1000.0)
    q, s = qdma_pack(x, block=256, interpret=True)
    xx = qdma_unpack(q, s, interpret=True)
    assert float(xx[0, 0]) == pytest.approx(1000.0, rel=1e-2)
    assert float(jnp.abs(xx[1:]).max()) == 0.0


# ---------------------------------------------------------------------------
# sliding windows and the grouped expert FFN
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("S,window,q_offset,k_offset,blocks", [
    (256, 64, 0, 0, (64, 64)),         # whole prompt, window < block span
    (128, 100, 0, 0, (32, 32)),        # window not a multiple of the block
    (64, 48, 200, 104, (32, 32)),      # ring view: keys start before q
    (64, 48, 16, -48, (32, 32)),       # ring view with negative positions
])
def test_flash_attention_window(S, window, q_offset, k_offset, blocks):
    """Windowed flash attention (and its skipped key blocks) == the masked
    oracle; keys carry their own offset, negative positions masked."""
    ks = jax.random.split(jax.random.key(11), 3)
    T = S if not k_offset else q_offset + S - k_offset
    q = rand(ks[0], (1, S, 4, 64), jnp.float32)
    k = rand(ks[1], (1, T, 2, 64), jnp.float32)
    v = rand(ks[2], (1, T, 2, 64), jnp.float32)
    out = flash_attention(q, k, v, causal=True, q_offset=q_offset,
                          k_offset=k_offset, window=window,
                          block_q=blocks[0], block_k=blocks[1],
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, q_offset=q_offset,
                                   window=window, k_offset=k_offset)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("K,ring,positions,nper,layer", [
    (2, 0, (5, 70, 127), 1, 0),
    (2, 0, (-1, 31, 64), 1, 0),
    (4, 4, (36, -1, 100), 1, 0),     # windows that wrap round the ring
    (8, 4, (7, 39, 56), 1, 0),       # a clipped window; page bounds
    (4, 4, (36, -1, 100), 3, 1),     # the ring of a middle layer
])
def test_paged_decode_window(K, ring, positions, nper, layer):
    """A windowed paged decode reads only its window's pages of layer
    ``layer`` and equals the oracle, over random tables or per-slot rings
    of ``ring`` pages (logical page i of slot b at 1 + b * ring + i %
    ring, as ``serve.paged`` lays them out); with the window wider than
    the context it is the plain decode."""
    ks = jax.random.split(jax.random.key(12), 3)
    page, NP, P, hd, W = 8, 16, 40, 64, 20
    q = rand(ks[0], (3, 1, 2 * K, hd), jnp.float32)
    kp = rand(ks[1], (nper, P, page, K, hd), jnp.float32)
    vp = rand(ks[2], (nper, P, page, K, hd), jnp.float32)
    if ring:
        tables = (1 + jnp.arange(3)[:, None] * ring
                  + jnp.arange(NP)[None, :] % ring).astype(jnp.int32)
    else:
        tables = jnp.asarray(
            np.random.default_rng(1).integers(1, P, (3, NP)), jnp.int32)
    pos = jnp.asarray(positions, jnp.int32)
    out = paged_decode(q, kp, vp, tables, pos, layer, window=W,
                       interpret=True)
    want = ref.paged_decode_ref(q, kp, vp, tables, pos, layer, window=W)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5,
                               rtol=2e-5)
    wide = paged_decode(q, kp, vp, tables, pos, layer, window=NP * page,
                        interpret=True)
    plain = paged_decode(q, kp, vp, tables, pos, layer, interpret=True)
    np.testing.assert_allclose(np.asarray(wide), np.asarray(plain),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("sizes,block_m", [
    ((10, 0, 15, 5), 16),            # an empty group, tiles shared
    ((0, 0, 0, 0), 16),              # nothing routed here
    ((40, 0, 0, 0), 128),            # one group, one tile
    ((3, 3, 3, 3), 8),               # every tile holds two groups
])
def test_grouped_ffn_matches_ragged_dot(sizes, block_m):
    """The grouped expert FFN kernel == ragged_dot SwiGLU on the rows the
    groups own (rows past them are the caller's to mask)."""
    from repro.kernels.grouped_ffn import grouped_ffn
    ks = jax.random.split(jax.random.key(13), 4)
    M, D, F, G = 48, 128, 256, 4
    x = rand(ks[0], (M, D), jnp.bfloat16)
    wg = (rand(ks[1], (G, D, F), jnp.float32) / 11).astype(jnp.bfloat16)
    wi = (rand(ks[2], (G, D, F), jnp.float32) / 11).astype(jnp.bfloat16)
    wo = (rand(ks[3], (G, F, D), jnp.float32) / 16).astype(jnp.bfloat16)
    gs = jnp.asarray(sizes, jnp.int32)
    n = sum(sizes)
    out = grouped_ffn(x, wg, wi, wo, gs, block_m=block_m, interpret=True)
    want = ref.grouped_ffn_ref(x, wg, wi, wo, gs)
    np.testing.assert_allclose(np.asarray(out[:n]), np.asarray(want[:n]),
                               atol=2e-3, rtol=2e-3)
