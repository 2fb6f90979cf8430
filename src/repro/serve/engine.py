"""Serving engine: continuous batching over decode_step, dense or paged KV.

Requests are admitted into slots of a batched decode state and decoded
together; finished slots are recycled without stopping the batch. Two
cache layouts:

  dense (default)   per-slot ring of ``max_len`` KV rows — simple, but
                    every slot pays for its worst case and decode walks the
                    whole allocation
  paged             block-granular paged KV (``repro.serve.paged``): slots
                    borrow fixed-size pages from a shared pool via a
                    ``BlockAllocator``, decode is block-table-indirected
                    (``kernels/paged_decode``) and costs only the pages a
                    request has actually written — the vLLM-shaped layout
                    that lets 16+ concurrent requests share the storage a
                    dense ring would burn on 4

Prefill is chunked when ``prefill_chunk > 0`` (attention-pattern stacks):
one prompt chunk is processed per engine step, interleaved with the
running batch's decode, so admitting a long prompt no longer stalls
in-flight requests. Sampling is per-request temperature / top-k with a
counter-seeded RNG — a request's tokens are a pure function of
(request, logits), so a pause/migrate mid-request cannot change its
output (invariant I10).

The engine runs as a Tenant workload under the SVFF manager (see
``repro.serve.fleet``), so it can be paused/unpaused mid-serving —
requests queue while paused; the guest keeps its 'device'.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import RunConfig
from repro.models.model import Model, build_model
from repro.runtime.spans import span
from repro.serve.paged import (BlockAllocator, CacheExhausted,
                               RequestRejected, admit_kv, apply_page_moves,
                               copy_page, extract_kv, init_paged_cache,
                               paged_cache_supported, reset_slot_state)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (len,) int32
    max_new_tokens: int = 16
    eos_id: int = -1                   # -1: never stops early
    temperature: float = 0.0           # 0: greedy argmax (<= 0 likewise)
    top_k: int = 0                     # 0: no top-k filter
    seed: int = 0                      # sampling stream (with rid)
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    error: Optional[str] = None        # set when admission rejected it
    t_submit: float = 0.0              # set by ServeEngine.submit
    t_tok: list = dataclasses.field(default_factory=list)  # per-token wall

    def __post_init__(self):
        # a positive-but-denormal temperature is always a caller bug: it
        # asks for near-greedy noise but 1/T overflows the f32 logits to
        # inf. The old sampler hid this with a silent max(T, 1e-6) clamp
        # that changed the requested distribution — reject it loudly at
        # construction instead (temperature <= 0 stays the greedy switch)
        if 0 < self.temperature < 1e-6:
            raise ValueError(
                f"request {self.rid}: temperature {self.temperature} is "
                "positive but below 1e-6; use 0 for greedy or a "
                "temperature >= 1e-6")


class DrainResult(list):
    """``run_until_idle``'s return value: the finished requests, plus
    ``drained`` — False when the engine stopped with work still pending
    (paused with a non-empty queue / live slots, or max_steps ran out)."""

    def __init__(self, items=(), drained: bool = True):
        super().__init__(items)
        self.drained = drained


@dataclasses.dataclass
class _PrefillJob:
    """An in-progress chunked prefill occupying a slot (not yet decoding)."""
    req: Request
    slot: int
    cache: dict                        # dense (B=1) staging cache
    plen: int
    offset: int = 0
    pages: Optional[list] = None       # paged: pages reserved at admission


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


class ServeEngine:
    def __init__(self, run: RunConfig, params, *, slots: int = 4,
                 max_len: int = 256, rules=None, paged: bool = False,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 prefill_chunk: int = 0, share_prefix: bool = False,
                 kv_dtype: Optional[str] = None,
                 fused_sampling: bool = False):
        self.run = run
        self.model = build_model(run)
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.queue: collections.deque[Request] = collections.deque()
        self.active: list[Optional[Request]] = [None] * slots
        self.pos = np.full((slots,), -1, np.int64)      # last written index
        self.last_token = np.zeros((slots,), np.int32)
        self.paused = False
        self._finished: list[Request] = []              # completed requests
        self._jobs: dict[int, _PrefillJob] = {}         # slot -> prefill job
        #: rid -> slot frozen by an in-flight outbound migration. A frozen
        #: slot keeps its Request/pages/KV (extraction copies, never
        #: moves), is skipped by decode, and thaws on release (commit) or
        #: abort — which is why an aborted migration is side-effect-free.
        self._migrating: dict[int, int] = {}
        #: cache-pressure / sharing counters, pumped into the MetricsBus
        #: by ServeFleet so the autoscaler sees cache pressure, not just
        #: queue depth; and the host nanoseconds spent in each part of a
        #: step (``step_ns``, ``admit_ns``, ``prefill_ns``, ``place_ns``,
        #: ``prepare_ns``, ``decode_ns``, ``readback_ns``, ``bookkeep_ns``:
        #: the ``engine.*`` spans, nested ones counted in their parents
        #: too). Cumulative over the engine's lifetime.
        self.stats = collections.Counter()
        # per-step dirty set: which export_state keys changed since the
        # last export. Informational for drivers (and asserted in tests);
        # the byte-level skipping itself happens in StagingEngine's
        # identity/digest memo — params stay the same jax objects across
        # exports, so a live pause's stop-and-copy moves them 0 times.
        self._dirty = {"params", "cache", "pos", "last_token"}

        cfg = run.model
        self.paged = paged
        if kv_dtype is not None and not paged:
            raise ValueError("kv_dtype requires the paged cache layout")
        self.kv_dtype = kv_dtype
        #: fused sampling: temperature/top-k Gumbel sampling runs inside
        #: the jitted decode step (kernels/sampling) and only token ids
        #: come back to the host — bit-identical to the host ``_sample``
        #: path (both draw the same portable counter-hash noise), so I10
        #: holds across the knob
        self.fused_sampling = bool(fused_sampling)
        if paged:
            ok, why = paged_cache_supported(cfg)
            if not ok:
                raise ValueError(f"paged KV for {cfg.name}: {why}")
            self.page_size = page_size
            maxp = math.ceil(max_len / page_size)
            self.num_pages = (num_pages if num_pages is not None
                              else 1 + slots * maxp)
            self.alloc = BlockAllocator(self.num_pages, page_size)
            self.tables = np.zeros((slots, maxp), np.int32)
            self._dirty.add("tables")
        # prefix sharing keys on prompt tokens alone, so it is gated to
        # token-only frontends (vision patch rows precede the token rows
        # and differ per request)
        self.share_prefix = (paged and share_prefix
                             and cfg.frontend.kind == "none")
        # chunked prefill needs per-chunk attention continuation, which only
        # the attention-pattern stacks support (recurrent blocks would need
        # their chunk-boundary state threaded through)
        chunkable = (all(b == "attn" for b in cfg.block_pattern)
                     and not cfg.is_encoder_decoder
                     and cfg.frontend.kind == "none")
        self.prefill_chunk = prefill_chunk if chunkable else 0

        from repro.train.step import (make_decode_step, make_prefill_chunk,
                                      make_serve_steps)
        prefill, _ = make_serve_steps(run, rules)
        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(make_decode_step(
            run, rules, paged=paged, fused=self.fused_sampling))
        self._chunk = jax.jit(make_prefill_chunk(run, rules))
        self._cache = None                              # lazy batched cache
        #: the chip this engine's params and KV cache live on (its VF's
        #: device, see ``place``); None: wherever jax puts them
        self.device = None

    def place(self, device) -> None:
        """Pin params and KV cache to ``device``; every step then runs
        there (the step's host-made inputs follow the committed params)."""
        self.device = device
        if device is None:
            return
        self.params = jax.device_put(self.params, device)
        if self._cache is not None:
            self._cache = jax.device_put(self._cache, device)

    # -- cache plumbing -------------------------------------------------------
    def _ensure_cache(self):
        if self._cache is None:
            shape = dataclasses.replace(self.run.shape, seq_len=self.max_len,
                                        global_batch=self.slots)
            if self.paged:
                self._cache = init_paged_cache(self.model, shape,
                                               self.num_pages,
                                               self.page_size,
                                               kv_dtype=self.kv_dtype)
            else:
                self._cache = self.model.init_cache(shape)
            if self.device is not None:
                self._cache = jax.device_put(self._cache, self.device)

    def _insert(self, slot: int, req_cache):
        """Write a (1, prefill_len, ...) request cache into batch slot."""
        def one(path, batch_leaf, req_leaf):
            name = path[-1].key if hasattr(path[-1], "key") else ""
            if name in ("k", "v", "xk", "xv"):
                return jax.lax.dynamic_update_slice(
                    batch_leaf, req_leaf.astype(batch_leaf.dtype),
                    (0, slot, 0, 0, 0))
            return jax.lax.dynamic_update_slice(
                batch_leaf, req_leaf.astype(batch_leaf.dtype),
                (0, slot) + (0,) * (batch_leaf.ndim - 2))
        self._cache = jax.tree_util.tree_map_with_path(
            one, self._cache, req_cache)

    # -- public API -----------------------------------------------------------
    def submit(self, req: Request):
        if not req.t_submit:
            req.t_submit = time.perf_counter()
        self.queue.append(req)

    def pause(self):
        self.paused = True

    def unpause(self):
        self.paused = False

    # -- admission ------------------------------------------------------------
    def _validate(self, req: Request):
        cfg = self.run.model
        npatch = (cfg.frontend.num_patches
                  if cfg.frontend.kind == "vision" else 0)
        need = npatch + len(req.prompt) + req.max_new_tokens
        if len(req.prompt) < 1:
            raise RequestRejected(f"request {req.rid}: empty prompt")
        if need > self.max_len:
            raise RequestRejected(
                f"request {req.rid}: prompt {len(req.prompt)} + "
                f"max_new {req.max_new_tokens} exceeds max_len "
                f"{self.max_len}")
        return npatch, need

    def _reject(self, req: Request, err: Exception):
        req.done = True
        req.error = str(err)
        self._finished.append(req)

    def _paged_admit(self, req: Request, npatch: int, need: int) -> list:
        """Reserve pages for admission: only the PROMPT rows up front —
        decode pages grow lazily (``extend`` in ``_ensure_writable``), so
        reserved-but-never-written pages stop inflating pool pressure.
        The full need is still validated against pool capacity here: a
        request that could never complete must be rejected at admission,
        not discovered mid-decode as an endless preempt/replay cycle."""
        if self.alloc.pages_needed(need) > self.alloc.capacity:
            raise RequestRejected(
                f"request {req.rid} needs {self.alloc.pages_needed(need)} "
                f"pages; pool capacity is {self.alloc.capacity} "
                f"(page_size={self.page_size})")
        tokens = None
        if self.share_prefix and npatch == 0:
            tokens = tuple(int(t) for t in req.prompt)
        return self.alloc.allocate(
            req.rid, self.alloc.pages_needed(npatch + len(req.prompt)),
            tokens=tokens)

    def _admit(self):
        """Fill free slots from the queue. A request that is rejected or
        finishes at prefill does NOT consume the slot — it is re-offered
        to the next queued request in the same pass."""
        for s in range(self.slots):
            if self.active[s] is not None or s in self._jobs:
                continue
            while self.queue:
                req = self.queue.popleft()
                try:
                    npatch, need = self._validate(req)
                except RequestRejected as e:
                    self._reject(req, e)
                    continue                      # slot still free
                pages = None
                if self.paged:
                    try:
                        pages = self._paged_admit(req, npatch, need)
                    except RequestRejected as e:
                        self._reject(req, e)
                        continue
                    except CacheExhausted:
                        # transient. One defragment pass before backing
                        # off: compaction keeps block tables dense and
                        # the counters give the autoscaler a cache-
                        # pressure signal distinct from queue depth
                        self.stats["cache_exhausted"] += 1
                        self.defragment()
                        self.stats["defrag_events"] += 1
                        try:
                            pages = self._paged_admit(req, npatch, need)
                        except CacheExhausted:
                            # back off, keep arrival order
                            self.queue.appendleft(req)
                            return
                self._ensure_cache()
                if self.prefill_chunk and len(req.prompt) > \
                        self.prefill_chunk:
                    self._start_job(s, req, pages)
                    break                         # slot taken by the job
                if self._prefill_full(s, req, npatch, pages):
                    break                         # slot now decoding
                # finished at prefill: slot re-offered to the next request

    def _prefill_full(self, slot: int, req: Request, npatch: int,
                      pages) -> bool:
        """B=1 whole-prompt prefill. Returns True if the slot is occupied
        (request entered the decode batch), False if it finished at
        prefill (slot stays free — nothing was written into it)."""
        plen = len(req.prompt)
        with span("engine.prefill", self.stats, rid=req.rid, plen=plen):
            batch = {"tokens": jnp.asarray(req.prompt, jnp.int32)[None]}
            cfg = self.run.model
            if cfg.frontend.kind == "vision":
                batch["patches"] = jnp.zeros(
                    (1, cfg.frontend.num_patches, cfg.d_model), jnp.bfloat16)
            if cfg.is_encoder_decoder:
                Te = max(1, plen // cfg.frontend.frame_ratio)
                batch["frames"] = jnp.zeros((1, Te, cfg.d_model),
                                            jnp.bfloat16)
            req_cache, last_logits = self._prefill(self.params, batch)
            with span("engine.readback", self.stats, rid=req.rid):
                row = np.asarray(last_logits[0])
            tok = self._emit(req, row)
        if req.done:
            if pages is not None:
                self.alloc.free(req.rid)
            return False
        self._place(slot, req, req_cache, npatch + plen, pages)
        self.last_token[slot] = tok
        return True

    # -- chunked prefill ------------------------------------------------------
    def _start_job(self, slot: int, req: Request, pages):
        C = self.prefill_chunk
        plen = len(req.prompt)
        cap = C * _next_pow2(math.ceil(plen / C))   # bucketed staging len
        shape = dataclasses.replace(self.run.shape, seq_len=cap,
                                    global_batch=1)
        self._jobs[slot] = _PrefillJob(
            req=req, slot=slot, cache=self.model.init_cache(shape),
            plen=plen, pages=pages)

    def _advance_prefill(self):
        """Process ONE chunk of the oldest pending prefill job — prefill
        work is batched into the decode schedule instead of stalling it."""
        if not self._jobs:
            return
        slot, job = next(iter(self._jobs.items()))
        C = self.prefill_chunk
        req = job.req
        with span("engine.prefill", self.stats, rid=req.rid, plen=job.plen,
                  offset=job.offset):
            real = min(C, job.plen - job.offset)
            chunk = np.zeros((C,), np.int32)
            chunk[:real] = np.asarray(
                req.prompt[job.offset:job.offset + real], np.int32)
            job.cache, logits = self._chunk(self.params, job.cache,
                                            jnp.asarray(chunk)[None],
                                            jnp.int32(job.offset))
            job.offset += real
            if job.offset < job.plen:
                return
            del self._jobs[slot]
            with span("engine.readback", self.stats, rid=req.rid):
                row = np.asarray(logits[0, real - 1])
            tok = self._emit(req, row)
            if req.done:                         # finished at prefill
                if job.pages is not None:
                    self.alloc.free(req.rid)
                return
            req_cache = self._slice_kv(job.cache, job.plen)
        self._place(slot, req, req_cache, job.plen, job.pages)
        self.last_token[slot] = tok

    @staticmethod
    def _slice_kv(cache: dict, L: int) -> dict:
        def one(path, leaf):
            name = path[-1].key if hasattr(path[-1], "key") else ""
            return leaf[:, :, :L] if name in ("k", "v") else leaf
        return jax.tree_util.tree_map_with_path(one, cache)

    def _place(self, slot: int, req: Request, req_cache, logical_len: int,
               pages):
        """Copy-on-admit: move a prefilled request's cache into the batch
        (paged: into its allocated pages, skipping the trie-shared chain
        head; dense: into its slot ring)."""
        with span("engine.place", self.stats, rid=req.rid):
            if self.paged:
                shared = self.alloc.shared_count(req.rid)
                self.stats["shared_page_hits"] += shared
                self._cache = admit_kv(self._cache, req_cache, pages,
                                       self.page_size, slot,
                                       skip_pages=shared)
                row = self.tables[slot]
                row[:] = 0
                row[:len(pages)] = pages
                self._dirty.add("tables")
                # offer this prompt's pages for sharing only now that
                # their bytes are written (registration at allocate time
                # would let a sibling map onto a still-unwritten chunked
                # prefill)
                if self.share_prefix:
                    self.alloc.register_prefix(req.rid)
            else:
                self._insert(slot, req_cache)
        self.active[slot] = req
        self.pos[slot] = logical_len - 1
        self._dirty |= {"cache", "pos", "last_token"}

    # -- sampling -------------------------------------------------------------
    def _sample(self, req: Request, logits_row: np.ndarray) -> int:
        """THE sampling oracle (invariant I10): every other path —
        pause/migrate replay, preemption-by-recompute, and the fused
        in-kernel sampler (``kernels/sampling``) — must reproduce this
        bit-for-bit. All arithmetic is float32 with portably-exact ops:
        cast, divide, selection (partition), and the shared counter-hash
        Gumbel noise, so host numpy and the device kernel agree on every
        bit. Counter-seeded: token t of request (seed, rid) always draws
        the same noise, so sampling is a pure function of the request."""
        lg = np.asarray(logits_row, np.float32)
        V = self.run.model.vocab_size
        if lg.size > V:
            lg = lg.copy()
            lg[V:] = -np.inf                 # padded vocab tail
        if req.temperature <= 0:
            return int(np.argmax(lg))
        z = lg / np.float32(req.temperature)
        if 0 < req.top_k < V:
            kth = np.partition(z, -req.top_k)[-req.top_k]
            z = np.where(z >= kth, z, -np.inf)
        from repro.kernels.sampling import host_gumbel
        return int(np.argmax(z + host_gumbel(req.seed, req.rid,
                                             len(req.out), z.shape[0])))

    def _emit(self, req: Request, logits_row: np.ndarray) -> int:
        return self._finish_token(req, self._sample(req, logits_row))

    def _finish_token(self, req: Request, tok: int) -> int:
        """Record one sampled token (host- or kernel-sampled) and retire
        the request on EOS / token budget."""
        req.out.append(tok)
        req.t_tok.append(time.perf_counter())
        if tok == req.eos_id or len(req.out) >= req.max_new_tokens:
            req.done = True
            self._finished.append(req)
        return tok

    # -- the decode loop ------------------------------------------------------
    def _table_width(self, pos_new: np.ndarray) -> int:
        """Narrowest pow2 block-table width covering every active slot —
        decode cost follows the tokens actually written, and the pow2
        bucketing keeps the number of compiled variants logarithmic."""
        need = int(np.max(pos_new, initial=-1)) // self.page_size + 1
        return min(_next_pow2(max(need, 1)), self.tables.shape[1])

    def step(self) -> int:
        """One engine iteration: admit + one prefill chunk + one batched
        decode over the ACTIVE slots (inactive slots are masked out: their
        cache bytes stay untouched and they add no attention work).
        Returns number of active slots (0 = idle). No-op while paused.
        Each part is an ``engine.*`` span (``runtime/spans``)."""
        if self.paused:
            return 0
        with span("engine.step", self.stats):
            return self._step()

    def _step(self) -> int:
        with span("engine.admit", self.stats):
            self._admit()
        self._advance_prefill()
        frozen = set(self._migrating.values())
        if frozen:
            # a synchronous migration freezes+thaws within one manager op,
            # so this only ticks when a caller holds the freeze across
            # steps (or a crash did) — the benchmarked migration stall
            self.stats["migration_stall_ticks"] += sum(
                1 for s in frozen if self.active[s] is not None)
        act = [s for s in range(self.slots)
               if self.active[s] is not None and s not in frozen]
        if not act:
            return 0
        with span("engine.prepare", self.stats):
            self._ensure_cache()
            if self.paged:
                # the decode kernel writes each slot's new KV row through
                # its block table, so every write target must be private
                # and allocated BEFORE the batched call: lazily grow the
                # chain (prompt pages were all admission reserved) and
                # CoW-split shared pages; a slot the pool cannot serve is
                # preempted
                act = [s for s in act if self._ensure_writable(s)]
                if not act:
                    return 0
            act_mask = np.zeros((self.slots,), bool)
            act_mask[act] = True
            pos_new = np.where(act_mask, self.pos + 1, -1).astype(np.int32)
            tokens = jnp.asarray(np.where(act_mask, self.last_token, 0),
                                 jnp.int32)[:, None]
            args = [self.params, self._cache, tokens, jnp.asarray(pos_new)]
            W = 0
            if self.paged:
                W = self._table_width(pos_new)
                args.append(jnp.asarray(self.tables[:, :W]))
            args.append(jnp.asarray(act_mask))
            if self.fused_sampling:
                # per-slot sampling params ride into the jitted step; only
                # (slots,) int32 token ids come back — the (B, V) logits
                # never leave the device
                temp = np.zeros((self.slots,), np.float32)
                topk = np.zeros((self.slots,), np.int32)
                keys = np.zeros((self.slots, 3), np.int32)
                for s in act:
                    req = self.active[s]
                    temp[s] = np.float32(req.temperature)
                    topk[s] = req.top_k
                    keys[s] = (req.seed, req.rid, len(req.out))
                args += [jnp.asarray(temp), jnp.asarray(topk),
                         jnp.asarray(keys)]
        with span("engine.decode", self.stats, slots=len(act), width=W):
            out, self._cache = self._decode(*args)
        with span("engine.readback", self.stats):
            out = np.asarray(out)     # token ids (fused) or logits
        with span("engine.bookkeep", self.stats):
            self._dirty |= {"cache", "pos", "last_token"}
            for s in act:
                req = self.active[s]
                self.pos[s] += 1
                if self.fused_sampling:
                    tok = self._finish_token(req, int(out[s]))
                else:
                    tok = self._emit(req, out[s])
                self.last_token[s] = tok
                if not req.done and self.pos[s] + 1 >= self.max_len:
                    req.done = True
                    self._finished.append(req)
                if req.done:
                    self.active[s] = None
                    self._reset_slot(s, rid=req.rid)
        return len(act)

    def _ensure_writable(self, slot: int) -> bool:
        """Make this step's KV write target (position ``pos+1``) safe for
        the decoding slot: extend the chain when the write crosses into
        an unallocated page (lazy growth), CoW-split when it lands in a
        page with refcount > 1. Exhaustion preempts the slot (False)."""
        req = self.active[slot]
        pi = (int(self.pos[slot]) + 1) // self.page_size
        chain = self.alloc.pages_of(req.rid)
        try:
            if pi >= len(chain):
                (new,) = self.alloc.extend(req.rid, 1)
                self.tables[slot, pi] = new
                self.stats["lazy_extends"] += 1
                self._dirty.add("tables")
            elif self.alloc.refcount(chain[pi]) > 1:
                old, new = self.alloc.cow(req.rid, pi)
                self._cache = copy_page(self._cache, old, new)
                self.tables[slot, pi] = new
                self.stats["cow_splits"] += 1
                self._dirty |= {"cache", "tables"}
        except CacheExhausted:
            self.stats["cache_exhausted"] += 1
            self._preempt(slot)
            return False
        return True

    def _preempt(self, slot: int):
        """Preemption-by-recompute, the exhaustion safety valve: drop the
        slot's work, release its pages (guaranteeing pool progress for
        the surviving slots), and requeue the request from scratch at the
        FRONT of the queue. Prefill and sampling are deterministic pure
        functions of the request (counter-seeded RNG — I10), so the
        replay emits exactly the tokens the preempted attempt did."""
        req = self.active[slot]
        self.alloc.free(req.rid)
        req.out.clear()
        req.t_tok.clear()
        self.active[slot] = None
        self.tables[slot, :] = 0
        self.pos[slot] = -1
        self._cache = reset_slot_state(self._cache, slot)
        self.queue.appendleft(req)
        self.stats["preemptions"] += 1
        self._dirty |= {"cache", "pos", "tables"}

    def _reset_slot(self, slot: int, rid: Optional[int] = None):
        """Recycle a finished slot: paged KV pages go back to the
        allocator; dense attn KV is masked by pos so it needs no reset;
        recurrent per-slot state is zeroed either way."""
        if self.paged:
            if rid is not None:
                self.alloc.free(rid)
            self.tables[slot, :] = 0
            self._dirty.add("tables")
        # dense attn KV is masked by pos (paged pages return to the
        # allocator), so only the recurrent per-slot state needs zeroing
        # — one fill-rule implementation for both layouts
        self._cache = reset_slot_state(self._cache, slot)
        self.pos[slot] = -1

    def defragment(self) -> dict:
        """Compact the page pool (allocator + physical pages + tables);
        returns the {old: new} page moves. No-op for dense engines."""
        if not self.paged:
            return {}
        moves = self.alloc.defragment()
        if moves and self._cache is not None:
            self._cache = apply_page_moves(self._cache, moves)
            self._dirty |= {"cache", "tables"}
        for s, req in enumerate(self.active):
            if req is not None:
                pages = self.alloc.pages_of(req.rid)
                self.tables[s, :] = 0
                self.tables[s, :len(pages)] = pages
        for job in self._jobs.values():
            if job.pages is not None:
                job.pages = self.alloc.pages_of(job.req.rid)
        return moves

    def abort_prefill_jobs(self):
        """Push every in-flight chunked-prefill job back onto the queue
        (front, original arrival order) and release its pages. A job has
        emitted NO tokens yet (the first token is sampled at completion)
        and prefill is deterministic, so restarting it after a pause is
        token-identical — this is how a suspend keeps export_state a
        COMPLETE device-state snapshot without staging half-built
        staging caches."""
        for slot, job in reversed(list(self._jobs.items())):
            if job.pages is not None:
                self.alloc.free(job.req.rid)
            self.queue.appendleft(job.req)    # dict is admission-ordered
        self._jobs.clear()

    # -- request migration (KV block shipping) --------------------------------
    # Protocol driven by SVFFManager.migrate_request: peek -> journal ->
    # extract (freeze, copy) -> ship -> admit on target -> release here.
    # Everything before release is non-destructive, so any abort (target
    # CacheExhausted, crash rollback) just thaws the frozen slot and the
    # source keeps serving the request.
    def peek_migratable(self, rid: Optional[int] = None) -> Optional[int]:
        """Pure query: the rid ``extract_request`` would pick — first
        active decoding slot in slot order (or ``rid`` if it is one).
        None when nothing is migratable (dense engine, idle, or already
        mid-migration)."""
        if not self.paged:
            return None
        frozen = set(self._migrating.values())
        for s in range(self.slots):
            req = self.active[s]
            if req is None or s in frozen:
                continue
            if rid is None or req.rid == rid:
                return req.rid
        return None

    def extract_request(self, rid: Optional[int] = None) -> Optional[dict]:
        """Freeze one in-flight request and gather everything the target
        needs to resume it: the Request object, its KV block chain as a
        dense strip (``extract_kv``), its slot's recurrent state, decode
        position and last sampled token, and the prompt tokens recorded
        by the allocator (so the target can re-share trie pages). The
        source keeps its pages — nothing destructive happens here."""
        rid = self.peek_migratable(rid)
        if rid is None:
            return None
        slot = next(s for s in range(self.slots)
                    if self.active[s] is not None
                    and self.active[s].rid == rid)
        self._ensure_cache()
        chain = self.alloc.pages_of(rid)
        state = extract_kv(self._cache, chain, self.page_size, slot)
        self._migrating[rid] = slot
        return {"rid": rid, "req": self.active[slot], "slot": slot,
                "chain_len": len(chain), "page_size": self.page_size,
                "tokens": self.alloc.tokens_of(rid),
                "pos": int(self.pos[slot]),
                "last": int(self.last_token[slot]),
                "state": state}

    def admit_migrated(self, payload: dict, state) -> int:
        """Admit a migrated request into a free slot: allocate a same-
        length chain (re-sharing trie pages for FULL prompt pages only —
        the partly-filled last prompt page may already hold this
        request's decode rows, which a sibling's registered page does
        not), scatter the shipped strip via ``admit_kv`` skipping the
        re-shared head, and resume at the shipped pos/last_token. Raises
        ``CacheExhausted`` (clean, side-effect-free) when no slot or not
        enough pages. Idempotent: re-admitting an owned rid is a no-op
        (recovery roll-forward replays)."""
        rid = payload["rid"]
        if not self.paged:
            raise RequestRejected(
                f"request {rid}: migration target is not a paged engine")
        if self.owns_request(rid):
            return next(s for s, r in enumerate(self.active)
                        if r is not None and r.rid == rid)
        slot = next((s for s in range(self.slots)
                     if self.active[s] is None and s not in self._jobs),
                    None)
        if slot is None:
            raise CacheExhausted(
                f"request {rid}: no free slot on migration target")
        n = payload["chain_len"]
        if n > self.tables.shape[1]:
            raise RequestRejected(
                f"request {rid}: chain of {n} pages exceeds target table "
                f"width {self.tables.shape[1]}")
        if payload["page_size"] != self.page_size:
            raise RequestRejected(
                f"request {rid}: page_size {payload['page_size']} != "
                f"target {self.page_size}")
        tokens = payload.get("tokens")
        share = None
        if self.share_prefix and tokens:
            share = tokens[:self.page_size * (len(tokens)
                                              // self.page_size)] or None
        try:
            pages = self.alloc.allocate(rid, n, tokens=share)
        except CacheExhausted:
            self.stats["cache_exhausted"] += 1
            self.defragment()
            self.stats["defrag_events"] += 1
            pages = self.alloc.allocate(rid, n, tokens=share)
        shared = self.alloc.shared_count(rid)
        self.stats["shared_page_hits"] += shared
        self._ensure_cache()
        self._cache = admit_kv(self._cache,
                               jax.tree.map(jnp.asarray, state), pages,
                               self.page_size, slot, skip_pages=shared)
        row = self.tables[slot]
        row[:] = 0
        row[:len(pages)] = pages
        self.active[slot] = payload["req"]
        self.pos[slot] = payload["pos"]
        self.last_token[slot] = payload["last"]
        if self.share_prefix and share:
            self.alloc.register_prefix(rid)
        self.stats["migrations_in"] += 1
        self.stats["migration_blocks_shipped"] += n - shared
        self._dirty |= {"cache", "pos", "last_token", "tables"}
        return slot

    def release_request(self, rid: int) -> bool:
        """Commit side of an outbound migration: the target owns the
        request now, so free our pages and recycle the frozen slot.
        Idempotent (False when rid is not frozen here) — recovery may
        roll the same release forward twice."""
        slot = self._migrating.pop(rid, None)
        if slot is None:
            return False
        self.active[slot] = None
        self._reset_slot(slot, rid=rid)
        self.stats["migrations_out"] += 1
        self._dirty |= {"cache", "pos", "tables"}
        return True

    def abort_migration(self, rid: int) -> bool:
        """Abort side: thaw the frozen slot. The request never stopped
        being ours (pages, KV, Request object all untouched), so decode
        resumes next step exactly where it froze."""
        return self._migrating.pop(rid, None) is not None

    def abort_incoming(self, rid: int):
        """Target-side rollback: drop any (possibly partial) admission of
        ``rid``. Idempotent no-op when we never admitted it."""
        if not self.paged or rid not in self.alloc.owners():
            return
        for s, req in enumerate(self.active):
            if req is not None and req.rid == rid:
                self.active[s] = None
                self._reset_slot(s, rid=rid)
                return
        self.alloc.free(rid)

    def owns_request(self, rid: int) -> bool:
        """Commit predicate for migration recovery: does this engine hold
        ``rid`` live (an active slot, a prefill job, the queue, or pages
        in its allocator)?"""
        if any(r is not None and r.rid == rid for r in self.active):
            return True
        if any(j.req.rid == rid for j in self._jobs.values()):
            return True
        if any(r.rid == rid for r in self.queue):
            return True
        return self.paged and rid in self.alloc.owners()

    def reset_after_crash(self):
        """Model an engine-process crash: device state (cache, page pool,
        block tables) is lost, every queued/active request is gone. The
        fleet re-homes the victim's requests onto siblings BEFORE calling
        this (``ServeFleet.recover_engine``); afterwards the engine is
        empty but servable again."""
        self.queue.clear()
        self._jobs.clear()
        self._finished.clear()
        self._migrating.clear()
        self.active = [None] * self.slots
        self.pos = np.full((self.slots,), -1, np.int64)
        self.last_token = np.zeros((self.slots,), np.int32)
        self._cache = None
        if self.paged:
            self.alloc = BlockAllocator(self.num_pages, self.page_size)
            self.tables = np.zeros_like(self.tables)
            self._dirty.add("tables")
        self._dirty |= {"params", "cache", "pos", "last_token"}

    def run_until_idle(self, max_steps: int = 10_000) -> DrainResult:
        """Drive the engine until queue and slots drain; returns every
        request completed during the run (prefill-finished ones included),
        in completion order. On a PAUSED engine this returns immediately —
        a paused engine makes no progress, so spinning would only lie
        about the drain; check ``.drained`` to see whether work remains."""
        for _ in range(max_steps):
            if self.paused:
                break
            if self.step() == 0 and not self.queue and not self._jobs:
                break
        pending = (bool(self.queue) or bool(self._jobs)
                   or any(r is not None for r in self.active))
        done, self._finished = self._finished, []
        return DrainResult(done, drained=not pending)

    # -- state for SVFF pause (config-space save) ------------------------------
    def dirty_keys(self) -> set:
        """Top-level export_state keys mutated since the last export —
        a pre-copy pause can skip the clean ones (params, in steady
        state) in its stop-and-copy."""
        return set(self._dirty)

    def export_state(self) -> dict:
        st = {"params": self.params, "cache": self._cache,
              "pos": self.pos.copy(), "last_token": self.last_token.copy()}
        if self.paged:
            st["tables"] = self.tables.copy()
        self._dirty = set()
        return st

    def import_state(self, st: dict):
        if "params" in st:
            self.params = st["params"]
        # restored cache leaves may be host numpy (zero-copy staging
        # transport); admit_kv/reset_slot_state index with .at[], so
        # re-materialize as jax arrays here rather than crashing on the
        # first admission after an unpause
        self._cache = (None if st["cache"] is None else
                       jax.tree.map(jnp.asarray, st["cache"]))
        # restored host arrays may be read-only views (zero-copy staging
        # transport); the engine mutates these in place, so copy
        self.pos = np.array(st["pos"], np.int64)
        self.last_token = np.array(st["last_token"], np.int32)
        if self.paged and "tables" in st:
            self.tables = np.array(st["tables"], np.int32)
        self._dirty = set(st)
