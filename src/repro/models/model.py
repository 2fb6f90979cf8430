"""Model orchestration: block dispatch, period-scan over the layer stack,
train/prefill/decode entry points, loss, and ShapeDtypeStruct specs for the
dry-run. One code path serves all 10 assigned architectures; family
differences are entirely data-driven from ModelConfig.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (ATTN, MAMBA, MLSTM, SLSTM, ModelConfig,
                                RunConfig, ShapeConfig)
from repro.models import params as P
from repro.models.attention import (attention, decode_attention,
                                    paged_decode_attention)
from repro.models.layers import apply_rope, embed_lookup, rms_norm, swiglu
from repro.models.moe import EXPERT_COUNTERS, moe_ffn, moe_ffn_dropless
from repro.models.ssm import mamba_block
from repro.models.xlstm import mlstm_block, slstm_block
from repro.runtime.partitioning import constrain

_BLOCK_FNS = {MAMBA: mamba_block, MLSTM: mlstm_block, SLSTM: slstm_block}


def _dt(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[name]


# ===========================================================================
# attention mixer
# ===========================================================================
def _ring_tables(tables, ring_pool_pages: int):
    """Block tables of a sliding-window block's per-slot page ring: slot b
    owns pages 1 + b*RP .. (b+1)*RP of its pool (page 0 is the garbage
    page) and logical page i of its sequence lives in ring page i mod RP.
    A pure function of the slot and the table width, so the host keeps no
    table for the ring."""
    B, W = tables.shape
    rp = (ring_pool_pages - 1) // B
    return (1 + jnp.arange(B, dtype=jnp.int32)[:, None] * rp
            + jnp.arange(W, dtype=jnp.int32)[None, :] % rp)


def _ring_chunk(cache, k, v, pos, window):
    """Chunked prefill of a sliding-window block into its (1, R) staging
    ring: the chunk's rows land at ``pos % R`` (R a multiple of the chunk,
    at least window + chunk), and the ring is returned as a view in
    position order, ending at the chunk's last row, with the position of
    its first row."""
    R, C = cache["k"].shape[1], k.shape[1]
    assert R % C == 0 and R >= window + C, (R, C, window)
    at = pos % R
    kc = jax.lax.dynamic_update_slice(
        cache["k"], k.astype(cache["k"].dtype), (0, at, 0, 0))
    vc = jax.lax.dynamic_update_slice(
        cache["v"], v.astype(cache["v"].dtype), (0, at, 0, 0))
    start = (at + C) % R

    def view(ring):
        two = jnp.concatenate([ring, ring], axis=1)
        return jax.lax.dynamic_slice_in_dim(two, start, R, axis=1)
    return kc, vc, view(kc), view(vc), pos + C - R


def _attn_mixer(cfg: ModelConfig, p: dict, x, cdt, mode, cache, positions,
                pos, backend, interpret, causal=True, tables=None,
                active=None, akind=None, layer=None):
    B, S, D = x.shape
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    akind = akind or cfg.attn_kind(0)
    W = akind.window
    h = rms_norm(x, p["ln1"], cfg.norm_eps).astype(cdt)
    if mode != "decode":
        h = constrain(h, "hidden_full")   # SP: gather seq for TP qkv
    q = (h @ p["wq"].astype(cdt)).reshape(B, S, H, hd)
    k = (h @ p["wk"].astype(cdt)).reshape(B, S, K, hd)
    v = (h @ p["wv"].astype(cdt)).reshape(B, S, K, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, akind.rope.theta, akind.rope)
    k = apply_rope(k, positions, akind.rope.theta, akind.rope)
    if mode != "decode":
        q = constrain(q, "attn_q")
        k = constrain(k, "attn_kv")
        v = constrain(v, "attn_kv")

    new_cache = None
    if mode == "decode" and tables is not None:
        # paged KV: the cache leaves are the stacked page pools
        # (nper, P, page, K, hd) that the layer scan carries, and ``layer``
        # is this block's period: the new token's row is written where it
        # lies, and the kernel reads the same stack at ``layer``. Slot b's
        # new token lands in page tables[b, pos//page] at offset pos%page.
        # Inactive slots (active[b] False) are redirected to the reserved
        # garbage page 0, so an idle slot's pages stay untouched and its
        # attention (pos[b] = -1 -> zero valid tokens) reads none.
        posa = jnp.asarray(pos)
        page = cache["k"].shape[2]
        posw = jnp.maximum(posa, 0)
        rows = jnp.arange(B)
        if W:
            tables = _ring_tables(tables, cache["k"].shape[1])
        pids = tables[rows, posw // page]
        offs = posw % page
        if active is not None:
            pids = jnp.where(active, pids, 0)
        at = (layer, pids, offs)
        if "k_scale" in cache:
            # int8 pool (kv_dtype='int8'): quantize the new token's row
            # on write — per-(slot,head) symmetric scale over hd — and
            # land scale + int8 payload at the same (page, offset)
            from repro.serve.paged import kv_quantize
            kq, ks = kv_quantize(k[:, 0])
            vq, vs = kv_quantize(v[:, 0])
            new_cache = {"k": cache["k"].at[at].set(kq),
                         "v": cache["v"].at[at].set(vq),
                         "k_scale": cache["k_scale"].at[at].set(ks),
                         "v_scale": cache["v_scale"].at[at].set(vs)}
            o = paged_decode_attention(
                q, new_cache["k"], new_cache["v"], tables, posa, layer,
                k_scale=new_cache["k_scale"], v_scale=new_cache["v_scale"],
                backend=backend, interpret=interpret)
        else:
            new_cache = {
                "k": cache["k"].at[at].set(k[:, 0].astype(cache["k"].dtype)),
                "v": cache["v"].at[at].set(v[:, 0].astype(cache["v"].dtype))}
            o = paged_decode_attention(q, new_cache["k"], new_cache["v"],
                                       tables, posa, layer, window=W,
                                       backend=backend, interpret=interpret)
    elif mode == "decode":
        posa = jnp.asarray(pos)
        if posa.ndim == 0:       # uniform position: dynamic_update_slice
            kc = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, pos, 0, 0))
            vc = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, pos, 0, 0))
        else:                    # per-slot positions (continuous batching)
            rows = jnp.arange(B)
            posw = jnp.maximum(posa, 0)
            knew = k[:, 0].astype(cache["k"].dtype)
            vnew = v[:, 0].astype(cache["v"].dtype)
            if active is not None:
                # masked scatter: an inactive slot writes back the bytes it
                # already holds, so its cache rows are bit-untouched (and
                # nothing lands at position 0 for an empty slot)
                knew = jnp.where(active[:, None, None],
                                 knew, cache["k"][rows, posw])
                vnew = jnp.where(active[:, None, None],
                                 vnew, cache["v"][rows, posw])
            kc = cache["k"].at[rows, posw].set(knew)
            vc = cache["v"].at[rows, posw].set(vnew)
        kc = constrain(kc, "kv_cache")
        vc = constrain(vc, "kv_cache")
        o = decode_attention(q, kc, vc, pos, backend=backend, window=W,
                             interpret=interpret)
        new_cache = {"k": kc, "v": vc}
    elif mode == "prefill_chunk" and W:
        kc, vc, kv_k, kv_v, k0 = _ring_chunk(cache, k, v, pos, W)
        o = attention(q, kv_k, kv_v, causal=True, q_offset=pos, window=W,
                      k_offset=k0, backend=backend, interpret=interpret)
        new_cache = {"k": kc, "v": vc}
    elif mode == "prefill_chunk":
        # chunked-prefill continuation: append this chunk's KV at offset
        # ``pos`` and attend causally against everything cached so far
        # (kv_len masks the not-yet-written tail, incl. any chunk padding)
        kc = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, pos, 0, 0))
        vc = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, pos, 0, 0))
        o = attention(q, kc, vc, causal=True, q_offset=pos,
                      backend=backend, interpret=interpret)
        new_cache = {"k": kc, "v": vc}
    else:
        o = attention(q, k, v, causal=causal, backend=backend,
                      window=W if causal else 0, interpret=interpret)
        if mode == "prefill":
            new_cache = {"k": constrain(k, "kv_cache"),
                         "v": constrain(v, "kv_cache")}
        # reshard the (bf16) attention output explicitly — otherwise GSPMD
        # may place the seq->replicated gather inside downstream fp32 norm
        # internals, doubling the bytes (§Perf HC2)
        o = constrain(o, "attn_q")
    out = o.reshape(B, S, H * hd).astype(cdt) @ p["wo"].astype(cdt)
    if mode != "decode":
        out = constrain(out, "hidden")
    return out, new_cache


def _cross_mixer(cfg: ModelConfig, p: dict, x, cdt, mode, cache, memory,
                 backend, interpret):
    """Encoder-decoder cross attention. memory: (B, Te, D) or None if the
    projected memory (xk/xv) is already in the cache (decode)."""
    B, S, D = x.shape
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    h = rms_norm(x, p["ln_x"], cfg.norm_eps).astype(cdt)
    q = (h @ p["xq"].astype(cdt)).reshape(B, S, H, hd)
    if mode == "decode":
        xk, xv = cache["xk"], cache["xv"]
    else:
        m = memory.astype(cdt)
        Te = m.shape[1]
        xk = (m @ p["xk"].astype(cdt)).reshape(B, Te, K, hd)
        xv = (m @ p["xv"].astype(cdt)).reshape(B, Te, K, hd)
    o = attention(q, xk, xv, causal=False, backend=backend,
                  interpret=interpret)
    out = o.reshape(B, S, H * hd).astype(cdt) @ p["xo"].astype(cdt)
    new_cache = {"xk": xk, "xv": xv} if mode in ("prefill", "decode") else None
    return out, new_cache


def _apply_ffn(cfg: ModelConfig, p: dict, x, cdt, backend="reference",
               interpret=False):
    aux = {}
    if "ln2" not in p:
        return x, aux
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    y = jnp.zeros_like(x)
    if "ffn" in p:
        # SP mode: gather the sequence (bf16) exactly here for the TP
        # matmuls; the reduce-scatter back happens at the block-boundary
        # "hidden" constraint (Megatron-SP placement, §Perf HC2 it.3)
        hf = constrain(h, "hidden_full")
        y = y + swiglu(hf, p["ffn"]["wi"], p["ffn"]["wg"], p["ffn"]["wo"],
                       cdt)
    if "moe" in p:
        if cfg.moe.dropless:
            ym, aux = moe_ffn_dropless(h, p["moe"], cfg.moe, cdt,
                                       backend=backend, interpret=interpret)
        else:
            ym, aux = moe_ffn(h, p["moe"], cfg.moe, cdt)
        y = y + ym
    return x + y.astype(x.dtype), aux


def _apply_block(cfg, run: RunConfig, kind: str, p, x, mode, cache_j,
                 positions, pos, memory, causal=True, cross=False,
                 tables=None, active=None, akind=None, layer=None):
    cdt = _dt(run.precision.compute)
    # the kernels are forward-only (no VJP): training differentiates the
    # reference, serving (prefill / chunk / decode) runs the kernels
    backend = "reference" if mode == "train" else run.kernels
    interpret = run.interpret
    new_cache = {}
    if kind == ATTN:
        out, nc = _attn_mixer(cfg, p, x, cdt, mode, cache_j, positions, pos,
                              backend, interpret, causal=causal,
                              tables=tables, active=active, akind=akind,
                              layer=layer)
        x = x + out
        if nc:
            new_cache.update(nc)
        if cross:
            out, ncx = _cross_mixer(cfg, p, x, cdt, mode, cache_j, memory,
                                    backend, interpret)
            x = x + out
            if ncx:
                new_cache.update(ncx)
    else:
        out, nc = _BLOCK_FNS[kind](cfg, p, x, cdt, mode=mode, cache=cache_j,
                                   backend=backend, interpret=interpret)
        if nc and active is not None and mode == "decode":
            # recurrent per-slot state: an inactive slot's cells must stay
            # bit-untouched (its row would otherwise integrate garbage)
            nc = jax.tree.map(
                lambda n, o: jnp.where(
                    active.reshape(active.shape + (1,) * (n.ndim - 1)),
                    n, o),
                nc, {k_: cache_j[k_] for k_ in nc})
        x = x + out
        if nc:
            new_cache.update(nc)
    x, aux = _apply_ffn(cfg, p, x, cdt, backend, interpret)
    x = constrain(x, "hidden")
    return x, (new_cache or None), aux


# ===========================================================================
# layer-stack scan
# ===========================================================================
ZERO_AUX = {"load_balance": 0.0, "router_z": 0.0}


def zero_aux(cfg: ModelConfig) -> dict:
    """The stack's aux accumulators: the losses, and the dropless expert
    layer's counters where the config has one."""
    aux = dict(ZERO_AUX)
    if cfg.moe is not None and cfg.moe.dropless:
        aux.update({k: 0.0 for k in EXPERT_COUNTERS})
    return aux


def run_stack(cfg: ModelConfig, run: RunConfig, layers: dict, x, mode,
              cache=None, positions=None, pos=None, memory=None,
              is_encoder=False, tables=None, active=None):
    """Scan the (period-stacked) layer stack.

    layers: {"block{j}": tree stacked over periods}
    cache: same structure (or None); returned updated for prefill/decode.
    tables/active: paged-KV block tables + active-slot mask (decode only;
    see ``Model.decode_step``) — layer-invariant, so threaded by closure.

    A paged decode (``tables`` given) carries the whole stacked cache
    through the stack with the period index: each block writes its new
    rows into the stacked pools where they lie and the kernel reads the
    stack at that index. Every other mode scans the cache as ``xs`` and
    restacks it as ``ys``, which slices each period's cache out of the
    stack and writes it back: a copy of every pool, each step.
    """
    pattern = (ATTN,) if is_encoder else cfg.block_pattern
    plen = len(pattern)
    nper = (cfg.num_encoder_layers if is_encoder else cfg.num_layers) // plen
    causal = not is_encoder
    cross = cfg.is_encoder_decoder and not is_encoder
    with_cache = (mode in ("prefill", "prefill_chunk", "decode")
                  and not is_encoder)
    carried = with_cache and mode == "decode" and tables is not None

    def period_fn(x, aux_in, period_params, period_cache, layer=None):
        """``layer`` (carried caches only): ``period_cache`` is the whole
        stack and ``layer`` this period's index in it."""
        aux_acc = dict(aux_in)
        new_caches = dict(period_cache) if carried else {}
        for j in range(plen):
            name = f"block{j}"
            cj = period_cache.get(name) if period_cache else None
            # a recurrent block's per-slot state is small: sliced out of
            # the stack and written back
            sliced = carried and pattern[j] != ATTN
            if sliced:
                cj = jax.tree.map(lambda l: l[layer], cj)
            x, nc, aux = _apply_block(
                cfg, run, pattern[j], period_params[name], x, mode,
                cj, positions, pos, memory, causal=causal, cross=cross,
                tables=tables, active=active,
                akind=None if is_encoder else cfg.attn_kind(j), layer=layer)
            if nc is not None:
                if sliced:
                    nc = jax.tree.map(lambda l, n: l.at[layer].set(n),
                                      period_cache[name], nc)
                new_caches[name] = nc
            for k_, v_ in aux.items():
                aux_acc[k_] = aux_acc[k_] + v_
        return x, aux_acc, (new_caches if with_cache else None)

    remat = run.sharding.remat
    if remat != "none" and mode == "train":
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if remat == "dots" else
                  jax.checkpoint_policies.nothing_saveable)
        period_fn = jax.checkpoint(period_fn, policy=policy,
                                   static_argnums=())

    scan = run.sharding.scan_layers and nper > 1
    if carried and scan:
        def body(carry, xs):
            x, aux, c = carry
            pp, i = xs
            return period_fn(x, aux, pp, c, i), None
        (x, aux, ncache), _ = jax.lax.scan(
            body, (x, zero_aux(cfg), cache),
            (layers, jnp.arange(nper, dtype=jnp.int32)))
    elif carried:
        aux, ncache = zero_aux(cfg), cache
        for i in range(nper):
            pp = jax.tree.map(lambda l: l[i], layers)
            x, aux, ncache = period_fn(x, aux, pp, ncache, i)
    elif scan:
        def body(carry, xs):
            x, aux = carry
            pp, pc = xs
            x, aux, ncache = period_fn(x, aux, pp, pc)
            return (x, aux), ncache
        # None is an empty pytree, so (layers, None) is a valid xs when no
        # cache flows through the stack.
        (x, aux), ncache = jax.lax.scan(body, (x, zero_aux(cfg)),
                                        (layers, cache))
    else:
        aux = zero_aux(cfg)
        ncache = {} if with_cache else None
        for i in range(nper):
            pp = jax.tree.map(lambda l: l[i], layers)
            pc = jax.tree.map(lambda l: l[i], cache) if cache else None
            x, aux, nc = period_fn(x, aux, pp, pc)
            if with_cache:
                ncache[i] = nc
        if with_cache:
            ncache = jax.tree.map(lambda *ls: jnp.stack(ls),
                                  *[ncache[i] for i in range(nper)])
    return x, aux, ncache


# ===========================================================================
# the Model
# ===========================================================================
class Model:
    """Functional model bound to a RunConfig (mesh-agnostic; sharding comes
    from the active ``sharding_scope``)."""

    def __init__(self, run: RunConfig):
        self.run = run
        self.cfg = run.model

    # -- params -------------------------------------------------------------
    def init(self, rng: jax.Array) -> dict:
        return P.init_params(self.cfg, rng, _dt(self.run.precision.params))

    def param_shapes(self) -> dict:
        return P.param_shapes(self.cfg, _dt(self.run.precision.params))

    # -- embedding / head ----------------------------------------------------
    def _embed(self, params, tokens, cdt):
        return embed_lookup(params["embed"]["tok"], tokens, cdt)

    def _logits(self, params, x):
        ldt = _dt(self.run.precision.logits)
        if self.cfg.tie_embeddings:
            w = params["embed"]["tok"]
            out = jnp.einsum("bsd,vd->bsv", x.astype(ldt), w.astype(ldt))
        else:
            out = x.astype(ldt) @ params["lm_head"].astype(ldt)
        return constrain(out, "logits")

    def _encode(self, params, frames, cdt):
        x = frames.astype(cdt)
        x = constrain(x, "hidden")
        pos = jnp.arange(x.shape[1])
        x, _, _ = run_stack(self.cfg, self.run, params["encoder"]["layers"],
                            x, "train", positions=pos, is_encoder=True)
        return rms_norm(x, params["encoder"]["final_norm"], self.cfg.norm_eps)

    # -- forward (train / prefill) -------------------------------------------
    def forward(self, params, batch, mode="train"):
        cfg, run = self.cfg, self.run
        cdt = _dt(run.precision.compute)
        x = self._embed(params, batch["tokens"], cdt)
        memory = None
        if cfg.frontend.kind == "vision":
            x = jnp.concatenate([batch["patches"].astype(cdt), x], axis=1)
        if cfg.is_encoder_decoder:
            memory = self._encode(params, batch["frames"], cdt)
        x = constrain(x, "hidden")
        positions = jnp.arange(x.shape[1])
        x, aux, cache = run_stack(cfg, run, params["decoder"]["layers"], x,
                                  mode, positions=positions, memory=memory)
        x = rms_norm(x, params["decoder"]["final_norm"], cfg.norm_eps)
        logits = self._logits(params, x)
        return logits, aux, cache

    # -- loss -----------------------------------------------------------------
    def loss(self, params, batch):
        cfg = self.cfg
        logits, aux, _ = self.forward(params, batch, mode="train")
        if cfg.frontend.kind == "vision":          # text positions only
            logits = logits[:, cfg.frontend.num_patches:]
        labels = batch["labels"]
        Vp = logits.shape[-1]
        # mask the padded vocab tail
        vmask = (jnp.arange(Vp) < cfg.vocab_size)
        logits = jnp.where(vmask, logits, -1e30)
        valid = labels >= 0
        safe = jnp.clip(labels, 0, cfg.vocab_size - 1)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        # gold logit via one-hot contraction, NOT take_along_axis: a gather
        # over the vocab(-TP-sharded) dim makes SPMD all-gather the full
        # fp32 logits; the masked reduction keeps everything local and the
        # partitioner emits only a tiny (B,S) all-reduce.  §Perf iteration 1.
        onehot = (jnp.arange(Vp)[None, None, :] == safe[..., None])
        gold = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
        ce = jnp.where(valid, lse - gold, 0.0)
        ntok = jnp.maximum(jnp.sum(valid), 1)
        ce_mean = jnp.sum(ce) / ntok
        aux_total = sum(v for k, v in aux.items() if k in ZERO_AUX)
        loss = ce_mean + aux_total
        metrics = {"loss": loss, "ce": ce_mean, "ntok": ntok, **aux}
        return loss, metrics

    # -- serving ---------------------------------------------------------------
    def prefill(self, params, batch):
        """Returns (cache, last_logits)."""
        logits, _, cache = self.forward(params, batch, mode="prefill")
        return cache, logits[:, -1]

    def decode_step(self, params, cache, tokens, pos, *, tables=None,
                    active=None, counters=False):
        """tokens: (B,1) int32; pos: scalar int32 (uniform) or (B,) int32
        (per-slot, continuous batching) — the slot the new token occupies
        (attends to <= pos). Returns (logits (B,V), new_cache).

        active: optional (B,) bool — False rows are masked OUT of the
        decode: their cache bytes (KV rows / recurrent state) stay
        bit-untouched and their attention reads zero tokens (pos[b] must
        be < 0 for them). Their logits are garbage and must be discarded.

        tables: optional (B,NP) int32 paged-KV block tables. When given,
        attention-cache leaves are page pools (nper, P, page, K, hd) —
        see ``repro.serve.paged`` — updated where they lie (the layer
        scan carries them), and ``pos`` is per-slot logical position;
        page 0 is reserved as the garbage page.

        counters: also return the stack's counters (``EXPERT_COUNTERS``
        summed over layers), as a third value."""
        cfg, run = self.cfg, self.run
        cdt = _dt(run.precision.compute)
        x = self._embed(params, tokens, cdt)
        x = constrain(x, "hidden")
        posa = jnp.asarray(pos)
        if posa.ndim == 0:
            positions = jnp.reshape(pos, (1,))
        else:
            # rope positions must be in-range even for inactive (-1) slots
            positions = jnp.maximum(posa, 0)[:, None]
        x, aux, cache = run_stack(cfg, run, params["decoder"]["layers"], x,
                                  "decode", cache=cache,
                                  positions=positions, pos=pos,
                                  tables=tables, active=active)
        x = rms_norm(x, params["decoder"]["final_norm"], cfg.norm_eps)
        logits = self._logits(params, x)
        if counters:
            return logits[:, 0], cache, {k: aux[k] for k in EXPERT_COUNTERS
                                         if k in aux}
        return logits[:, 0], cache

    def prefill_chunk(self, params, cache, tokens, offset):
        """One chunk of a chunked prefill: process ``tokens`` (B,C) at
        absolute positions [offset, offset+C), appending KV into the dense
        staging ``cache`` and attending causally against every earlier
        chunk. Returns (cache, logits (B,C,V)) — the caller picks the
        logits row of the last REAL token (trailing chunk padding yields
        garbage rows that are never used, and the padded KV tail is
        overwritten by decode before it can ever be attended).

        Only attention-pattern stacks support this (recurrent blocks would
        need their chunk-boundary state threaded); callers gate on
        ``cfg.attention_free`` / ``block_pattern``."""
        cfg, run = self.cfg, self.run
        cdt = _dt(run.precision.compute)
        x = self._embed(params, tokens, cdt)
        x = constrain(x, "hidden")
        positions = offset + jnp.arange(x.shape[1])
        x, _, cache = run_stack(cfg, run, params["decoder"]["layers"], x,
                                "prefill_chunk", cache=cache,
                                positions=positions, pos=offset)
        x = rms_norm(x, params["decoder"]["final_norm"], cfg.norm_eps)
        return cache, self._logits(params, x)

    # =========================================================================
    # specs (dry-run: ShapeDtypeStructs, no allocation)
    # =========================================================================
    def input_specs(self, shape: Optional[ShapeConfig] = None) -> dict:
        cfg = self.cfg
        shape = shape or self.run.shape
        B, S = shape.global_batch, shape.seq_len
        cdt = _dt(self.run.precision.compute)
        i32 = jnp.int32

        def sd(shp, dt):
            return jax.ShapeDtypeStruct(shp, dt)

        if shape.kind == "train":
            specs = {"tokens": sd((B, S), i32), "labels": sd((B, S), i32)}
        elif shape.kind == "prefill":
            specs = {"tokens": sd((B, S), i32)}
        else:  # decode: one new token against a cache of length S
            return {"tokens": sd((B, 1), i32), "pos": sd((), i32)}
        if cfg.frontend.kind == "vision":
            specs["patches"] = sd((B, cfg.frontend.num_patches, cfg.d_model),
                                  cdt)
        if cfg.is_encoder_decoder:
            Te = S // cfg.frontend.frame_ratio
            specs["frames"] = sd((B, Te, cfg.d_model), cdt)
        return specs

    def cache_specs(self, shape: Optional[ShapeConfig] = None,
                    ring: int = 0) -> dict:
        """Decode-cache ShapeDtypeStructs: (periods, B, ...) per block.
        ``ring`` > 0: sliding-window blocks hold a ring of that many
        positions (chunked prefill's staging) instead of ``seq_len``."""
        cfg = self.cfg
        shape = shape or self.run.shape
        B, S = shape.global_batch, shape.seq_len
        cdt = _dt(self.run.precision.compute)
        plen = len(cfg.block_pattern)
        nper = cfg.num_layers // plen
        K, hd = cfg.num_kv_heads, cfg.head_dim
        D = cfg.d_model

        def sd(shp, dt=cdt):
            return jax.ShapeDtypeStruct((nper,) + shp, dt)

        tree = {}
        for j, kind in enumerate(cfg.block_pattern):
            if kind == ATTN:
                T = ring if ring and cfg.attn_kind(j).window else S
                c = {"k": sd((B, T, K, hd)), "v": sd((B, T, K, hd))}
                if cfg.is_encoder_decoder:
                    Te = S // cfg.frontend.frame_ratio
                    c["xk"] = sd((B, Te, K, hd))
                    c["xv"] = sd((B, Te, K, hd))
            elif kind == MAMBA:
                di, nh, _, ch = P.mamba_dims(cfg)
                c = {"conv": sd((B, cfg.ssm.conv_dim - 1, ch)),
                     "ssm": sd((B, nh, cfg.ssm.head_dim, cfg.ssm.d_state),
                               jnp.float32)}
            elif kind == MLSTM:
                di, nh = P.mlstm_dims(cfg)
                hdm = cfg.xlstm.head_dim
                c = {"mlstm": {"C": sd((B, nh, hdm, hdm), jnp.float32),
                               "n": sd((B, nh, hdm), jnp.float32),
                               "m": sd((B, nh), jnp.float32)}}
            elif kind == SLSTM:
                c = {"slstm": {k_: sd((B, D), jnp.float32)
                               for k_ in ("h", "c", "n", "m")}}
            tree[f"block{j}"] = c
        return tree

    def init_cache(self, shape: Optional[ShapeConfig] = None,
                   ring: int = 0) -> dict:
        def one(path, s):
            name = path[-1].key if hasattr(path[-1], "key") else ""
            if name == "m":       # exp-gate stabilizers start at -inf-ish
                return jnp.full(s.shape, -1e30, s.dtype)
            return jnp.zeros(s.shape, s.dtype)
        return jax.tree_util.tree_map_with_path(
            one, self.cache_specs(shape, ring=ring))


def build_model(run: RunConfig) -> Model:
    return Model(run)
