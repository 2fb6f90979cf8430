"""Paged KV cache: block-granular allocation for the serve plane.

The dense per-slot ring allocates ``slots x max_len`` KV rows up front and
decodes against the whole allocation; the paged cache carves the same
physical storage into fixed-size *pages* handed out on demand — the exact
shape of the ``DevicePool`` one layer down (a pool of indivisible resource
units, an owner table, allocate/free/defragment), applied to KV rows
instead of accelerator devices:

  BlockAllocator     the PF analogue: owns the page pool, tracks per-request
                     ownership and per-page refcounts, compacts on
                     ``defragment``
  page 0             reserved garbage page — never allocated; inactive batch
                     slots' masked writes are redirected there, which is how
                     an idle slot's pages stay bit-untouched
  copy-on-admit      a request is prefilled into a private dense staging
                     cache (B=1) and its KV is *copied* into its allocated
                     pages on admission (``admit_kv``), so admission never
                     aliases the running batch's storage
  prefix sharing     requests whose token prefixes match map their block
                     tables onto the SAME physical pages (a prefix trie
                     keyed by token-prefix chains; see below), multiplying
                     effective pool capacity for shared system prompts
  copy-on-write      a decode write landing in a page with refcount > 1
                     splits exactly that page (``cow`` + ``copy_page``):
                     the writer gets a private copy and repoints only its
                     own table row; every other sharer is untouched

Sharing is read-free because ``kernels/paged_decode`` masks reads with
``kpos <= pos``: rows a sharer has not logically reached (another
request's longer prompt tail, or its decode tokens parked in a shared
partial page) are never read, so a page may be shared as long as the rows
BELOW each sharer's position are bit-identical — which the token-prefix
keys guarantee.

The attention-side consumer is ``kernels/paged_decode`` (block-table
indirection, cost proportional to pages actually written).

Sliding-window blocks (``ModelConfig.windows``) keep a second kind of pool:
a per-slot ring. Slot s owns pages 1 + s*RP .. (s+1)*RP of its block's
pool, RP pages of window + one prefill chunk positions, and logical page i
lives in ring page i mod RP; its table is a function of the slot (see
``models/model._ring_tables``), so the allocator knows nothing of it. A
sliding layer thus holds window + chunk positions per request whatever the
context, the decode kernel reads only the window's pages, and the ring
pages never move (defragment, copy-on-write and migration are for the
allocator's pool: prefix sharing and migration refuse windowed stacks).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


class RequestRejected(RuntimeError):
    """Typed admission rejection: the request can NEVER be served by this
    engine (over-long prompt, more pages than the pool holds). The engine
    marks the request done-with-error and keeps serving the batch — one
    bad request must not kill the engine (this replaces a bare ``assert``
    that vanished under ``python -O``)."""


class CacheExhausted(RuntimeError):
    """Transient allocation failure: not enough free pages *right now*.
    Admission backs off (the request stays queued) rather than failing."""


# DoubleFreeError / UnknownRequestError now live in the canonical typed
# error hierarchy (repro.core.errors) so callers can catch them via
# ``from repro.core import ...``; re-exported here because this was their
# historic home (PRs 4-8 call sites / docs name repro.serve.paged).
from repro.core.errors import DoubleFreeError, UnknownRequestError  # noqa: E402,F401


def _is_kv(path) -> bool:
    """Attention-cache leaves that need no slot reset (self-attn KV is
    masked by pos; cross xk/xv only ever appear in DENSE caches — the
    paged layout gates out encoder-decoder stacks entirely)."""
    name = path[-1].key if hasattr(path[-1], "key") else ""
    return name in ("k", "v", "xk", "xv")


def _is_kv_scale(path) -> bool:
    """Per-page quantization-scale siblings of int8 KV pools
    ((nper, P, page, K) fp32 next to (nper, P, page, K, hd) int8) — they
    move with their pages (CoW copies, defragment gathers) but are
    neither scattered from the fp request cache nor slot-reset."""
    name = path[-1].key if hasattr(path[-1], "key") else ""
    return name in ("k_scale", "v_scale", "xk_scale", "xv_scale")


class BlockAllocator:
    """Fixed-size page pool with per-request ownership, per-page
    refcounts, and a prefix trie for copy-on-write page sharing.

    Page ids run [0, num_pages); page 0 is reserved (garbage page), so the
    allocatable capacity is ``num_pages - 1``. Free pages are handed out
    lowest-id first, which keeps block tables deterministic (the serving
    analogue of the scheduler's 'ties break in PF table order').

    Sharing model. A page's KV rows are a function of the ENTIRE token
    prefix up to and including the page's own tokens, so the trie keys
    are token-prefix tuples, not per-page token windows:

      full pages     ``tokens[: page_size * (i+1)]`` -> page of chain
                     index i (registered once, first placement wins)
      partial page   a prompt's last, partly-filled page, keyed under its
                     full-page prefix by the leftover token tuple. A later
                     request may share it only when its own leftover
                     tokens are an exact PREFIX of the registered entry's
                     — its rows are then already present at the right
                     offsets, and any longer registered tail (or the
                     registrant's decode rows parked above it) sits past
                     the sharer's position, masked by the decode kernel

    Registration happens at PLACE time (``register_prefix``), after the
    page bytes are actually written — pages reserved by an in-flight
    chunked prefill are never offered for sharing. Trie entries live
    exactly as long as the page has owners: the last ``free`` decref
    unregisters. Every owner of a page counts one refcount; a decode
    write into a page with refcount > 1 must go through ``cow`` first."""

    def __init__(self, num_pages: int, page_size: int):
        if num_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is reserved)")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self._free = list(range(1, num_pages))     # ascending
        self._owned: dict[int, list[int]] = {}     # rid -> page chain
        self._ref: dict[int, int] = {}             # page -> owner count
        self._shared: dict[int, int] = {}          # rid -> shared chain head
        self._tokens: dict[int, tuple] = {}        # rid -> prompt tokens
        # the trie: full-prefix keys -> page; partial entries grouped
        # under their full-page prefix; _site is the reverse map (one
        # registration per page) used by unregistration and defragment
        self._full: dict[tuple, int] = {}
        self._partial: dict[tuple, list] = {}      # key -> [(rest, page)]
        self._site: dict[int, tuple] = {}

    # -- capacity ------------------------------------------------------------
    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def capacity(self) -> int:
        return self.num_pages - 1

    @property
    def pages_in_use(self) -> int:
        """Unique physical pages currently owned (the sharing win shows
        up here: N requests on one system prompt count its pages once)."""
        return self.capacity - len(self._free)

    def pages_needed(self, tokens: int) -> int:
        return max(1, math.ceil(tokens / self.page_size))

    # -- the prefix trie -----------------------------------------------------
    def _lookup(self, tokens: tuple, n: int) -> list[int]:
        """Longest registered chain prefix for ``tokens``, at most ``n``
        pages: consecutive full-page hits from the root, then (only when
        every full page hit) at most one partial-page hit."""
        P = self.page_size
        shared: list[int] = []
        nfull = len(tokens) // P
        for i in range(min(nfull, n)):
            page = self._full.get(tokens[:P * (i + 1)])
            if page is None:
                return shared
            shared.append(page)
        rest = tokens[P * nfull:]
        if rest and len(shared) == nfull < n:
            for reg_rest, page in self._partial.get(tokens[:P * nfull], ()):
                if rest == reg_rest[:len(rest)]:
                    shared.append(page)
                    break
        return shared

    def register_prefix(self, rid: int) -> int:
        """Offer rid's PROMPT pages (the tokens recorded at allocate) for
        sharing. Idempotent and conflict-safe: a page registers at most
        once, a key keeps its first page. Returns entries added."""
        tokens = self._tokens.get(rid)
        chain = self._owned.get(rid)
        if not tokens or not chain:
            return 0
        P = self.page_size
        added = 0
        nfull = len(tokens) // P
        for i in range(min(nfull, len(chain))):
            key = tokens[:P * (i + 1)]
            page = chain[i]
            if key not in self._full and page not in self._site:
                self._full[key] = page
                self._site[page] = ("full", key)
                added += 1
        rest = tokens[P * nfull:]
        if rest and nfull < len(chain):
            page = chain[nfull]
            key = tokens[:P * nfull]
            node = self._partial.setdefault(key, [])
            if page not in self._site and rest not in [r for r, _ in node]:
                node.append((rest, page))
                self._site[page] = ("partial", key, rest)
                added += 1
        return added

    def _unregister(self, page: int):
        site = self._site.pop(page, None)
        if site is None:
            return
        if site[0] == "full":
            del self._full[site[1]]
        else:
            node = self._partial[site[1]]
            node.remove((site[2], page))
            if not node:
                del self._partial[site[1]]

    # -- allocate / free -----------------------------------------------------
    def allocate(self, rid: int, n: int,
                 tokens: Optional[tuple] = None) -> list[int]:
        """Hand ``rid`` a chain of ``n`` pages. With ``tokens`` (the
        prompt the pages will hold), the chain head reuses registered
        shared pages — only the remainder consumes free pages. The
        exhaustion check runs BEFORE any refcount moves, so a failed
        allocation is side-effect-free."""
        if rid in self._owned:
            raise ValueError(f"request {rid} already holds pages")
        if n > self.capacity:
            raise RequestRejected(
                f"request {rid} needs {n} pages; pool capacity is "
                f"{self.capacity} (page_size={self.page_size})")
        shared = self._lookup(tuple(tokens), n) if tokens else []
        fresh = n - len(shared)
        if fresh > len(self._free):
            raise CacheExhausted(
                f"request {rid} needs {fresh} fresh pages "
                f"({len(shared)} shared), only {len(self._free)} free")
        got, self._free = self._free[:fresh], self._free[fresh:]
        for p in shared:
            self._ref[p] += 1
        for p in got:
            self._ref[p] = 1
        self._owned[rid] = shared + got
        self._shared[rid] = len(shared)
        if tokens is not None:
            self._tokens[rid] = tuple(tokens)
        return list(self._owned[rid])

    def extend(self, rid: int, n: int = 1) -> list[int]:
        """Lazy decode growth: append ``n`` fresh (private) pages to
        rid's chain. Decode-grown pages are never offered for sharing.
        Unknown rid is an ``UnknownRequestError`` — see the class."""
        if rid not in self._owned:
            raise UnknownRequestError(
                f"extend of request {rid}, which holds no pages")
        if n > len(self._free):
            raise CacheExhausted(
                f"request {rid} needs {n} more pages, only "
                f"{len(self._free)} free")
        got, self._free = self._free[:n], self._free[n:]
        for p in got:
            self._ref[p] = 1
        self._owned[rid].extend(got)
        return list(got)

    def shared_count(self, rid: int) -> int:
        """Pages at the head of rid's chain that came from the trie at
        allocate time (the copy-on-admit scatter skips exactly these)."""
        return self._shared.get(rid, 0)

    def refcount(self, page: int) -> int:
        return self._ref.get(page, 0)

    def cow(self, rid: int, idx: int) -> tuple[int, int]:
        """Copy-on-write split: replace the shared page at chain index
        ``idx`` with a fresh private one (caller device-copies the bytes
        via ``copy_page`` and repoints its own table row). Returns
        ``(old_page, new_page)``."""
        if rid not in self._owned:
            raise UnknownRequestError(
                f"cow of request {rid}, which holds no pages")
        chain = self._owned[rid]
        old = chain[idx]
        if self._ref[old] <= 1:
            raise ValueError(
                f"cow of unshared page {old} (rid {rid}, idx {idx})")
        if not self._free:
            raise CacheExhausted(
                f"request {rid} needs 1 page for a CoW split, none free")
        new = self._free.pop(0)
        self._ref[new] = 1
        chain[idx] = new
        self._ref[old] -= 1           # > 0 by the guard above
        return old, new

    def _decref(self, page: int):
        self._ref[page] -= 1
        if self._ref[page] == 0:
            del self._ref[page]
            self._unregister(page)
            self._free.append(page)
            self._free.sort()

    def free(self, rid: int) -> list[int]:
        """Release rid's references. Pages drop to the free list only
        when their LAST owner lets go (a finished request's shared
        system-prompt pages stay live for its siblings). Unknown rid is a
        ``DoubleFreeError`` — see the class docstring."""
        pages = self._owned.pop(rid, None)
        if pages is None:
            raise DoubleFreeError(
                f"free of request {rid}, which holds no pages "
                "(double free, or never allocated)")
        self._shared.pop(rid, None)
        self._tokens.pop(rid, None)
        for p in pages:
            self._decref(p)
        return pages

    def pages_of(self, rid: int) -> list[int]:
        return list(self._owned.get(rid, []))

    def tokens_of(self, rid: int) -> Optional[tuple]:
        """The prompt tokens recorded at allocate time (what migration
        ships so the target allocator can re-share trie pages)."""
        return self._tokens.get(rid)

    def owners(self) -> dict[int, list[int]]:
        return {rid: list(p) for rid, p in self._owned.items()}

    def check_invariants(self):
        """Mirror of DevicePool._check_invariants, refcount-aware: every
        page's refcount equals its live chain references, free+owned is
        an exact partition, and the trie/site maps agree and only name
        live pages."""
        refs: dict[int, int] = {}
        for rid, pages in self._owned.items():
            seen = set()
            for p in pages:
                assert 1 <= p < self.num_pages, (rid, p)
                assert p not in seen, (
                    f"page {p} twice in request {rid}'s chain")
                seen.add(p)
                refs[p] = refs.get(p, 0) + 1
        assert set(refs) == set(self._ref), (
            f"refcount key drift: owned {sorted(refs)} != "
            f"counted {sorted(self._ref)}")
        for p, want in refs.items():
            assert self._ref[p] == want, (
                f"refcount drift: page {p} counted {self._ref[p]}, "
                f"{want} live chain references")
        assert not (set(self._free) & set(refs))
        assert len(self._free) + len(refs) == self.capacity
        for rid, nsh in self._shared.items():
            assert 0 <= nsh <= len(self._owned.get(rid, ())), (rid, nsh)
        for page, site in self._site.items():
            assert page in refs, f"trie entry for freed page {page}"
            if site[0] == "full":
                assert self._full.get(site[1]) == page, site
            else:
                assert (site[2], page) in self._partial.get(site[1], ()), \
                    site
        for key, page in self._full.items():
            assert self._site.get(page) == ("full", key)
        for key, node in self._partial.items():
            for rest, page in node:
                assert self._site.get(page) == ("partial", key, rest)

    # -- defragment ----------------------------------------------------------
    def defragment(self) -> dict[int, int]:
        """Compact owned pages to the lowest ids (request order, then
        chain order, each UNIQUE page re-id'd once — a shared page moves
        once and every sharer's chain follows). Returns the {old: new}
        moves; the caller must apply the same mapping to the physical
        page arrays and any block tables (``apply_page_moves``)."""
        newid: dict[int, int] = {}
        nxt = 1
        for rid in sorted(self._owned):
            for p in self._owned[rid]:
                if p not in newid:
                    newid[p] = nxt
                    nxt += 1
        moves = {old: new for old, new in newid.items() if old != new}
        self._owned = {rid: [newid[p] for p in pages]
                       for rid, pages in self._owned.items()}
        self._ref = {newid[p]: c for p, c in self._ref.items()}
        self._full = {k: newid[p] for k, p in self._full.items()}
        self._partial = {k: [(r, newid[p]) for r, p in node]
                         for k, node in self._partial.items()}
        self._site = {newid[p]: s for p, s in self._site.items()}
        self._free = list(range(nxt, self.num_pages))
        self.check_invariants()
        return moves


def permutation_of(moves: dict[int, int], num_pages: int) -> np.ndarray:
    """(num_pages,) gather indices g with new_pages = pages[g]. Moves from
    ``defragment`` never swap into a still-live source (targets are always
    compacted below their sources), so a single gather applies them all."""
    g = np.arange(num_pages)
    for old, new in moves.items():
        g[new] = old
    return g


# ---------------------------------------------------------------------------
# the paged cache tree
# ---------------------------------------------------------------------------
def paged_cache_supported(cfg) -> tuple[bool, str]:
    if cfg.is_encoder_decoder:
        return False, "encoder-decoder cross-KV is not paged"
    if "attn" not in cfg.block_pattern:
        return False, "attention-free stack has no KV to page"
    return True, ""


def kv_quantize(x):
    """Symmetric int8 quantization of KV rows over the trailing hd axis:
    scale = max|x| / 127 per (..., head) row, q = round(x / scale). The
    max always lands on q = +-127, so dequant -> requant round-trips
    bit-exactly — migration may ship dequantized fp rows and the target
    re-admits them to the identical int8 bytes (I13 stays exact)."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1) / 127.0, 1e-12)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def kv_dequantize(q, scale, dtype=jnp.float32):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def init_paged_cache(model, shape, num_pages: int, page_size: int,
                     kv_dtype: Optional[str] = None,
                     ring_pages: int = 0) -> dict:
    """Build the serve cache tree: attention k/v leaves become shared page
    pools (nper, P, page, K, hd); every other leaf (recurrent state) stays
    dense per-slot (B, ...) exactly as ``init_cache`` makes it. A
    sliding-window block's pool is its per-slot ring instead:
    (nper, 1 + B * ring_pages, page, K, hd).

    ``kv_dtype='int8'`` stores the pools quantized (per-(row,head)
    symmetric scales in fp32 ``k_scale``/``v_scale`` siblings, shape
    (nper, P, page, K)) — page bytes drop ~2x (int8 payload + one fp32
    scale per hd-row vs fp32 payload), so resident requests per pool
    roughly double on top of the prefix-sharing multiplier."""
    ok, why = paged_cache_supported(model.cfg)
    if not ok:
        raise ValueError(f"paged KV unsupported for {model.cfg.name}: {why}")
    if kv_dtype not in (None, "int8"):
        raise ValueError(f"unsupported kv_dtype {kv_dtype!r} "
                         "(None or 'int8')")
    # the dense template only sizes non-KV leaves, so keep its seq dim tiny
    base = model.init_cache(dataclasses.replace(shape, seq_len=1))
    rings = tuple(model.cfg.windows)
    if rings and (kv_dtype is not None or ring_pages < 1):
        raise ValueError("sliding-window blocks need ring_pages and the "
                         "bf16 pool")

    def one(path, leaf):
        if _is_kv(path):
            nper, B, _, K, hd = leaf.shape
            P = 1 + B * ring_pages if _in_ring(path, rings) else num_pages
            return jnp.zeros((nper, P, page_size, K, hd), leaf.dtype)
        return leaf
    tree = jax.tree_util.tree_map_with_path(one, base)
    if kv_dtype == "int8":
        tree = _quantize_tree(tree)
    return tree


def _quantize_tree(node):
    """Recursively convert fp KV page pools to int8 + scale siblings.
    The cache tree is plain nested dicts (see Model.cache_specs)."""
    if not isinstance(node, dict):
        return node
    out = {}
    for name, child in node.items():
        if isinstance(child, dict):
            out[name] = _quantize_tree(child)
        elif name in ("k", "v", "xk", "xv") and child.ndim == 5:
            nper, P, page, K, hd = child.shape
            out[name] = jnp.zeros((nper, P, page, K, hd), jnp.int8)
            out[name + "_scale"] = jnp.zeros((nper, P, page, K),
                                             jnp.float32)
        else:
            out[name] = child
    return out


def _path_key(path) -> tuple:
    return tuple(getattr(p, "key", getattr(p, "idx", None)) for p in path)


def _in_ring(path, ring_blocks) -> bool:
    """A KV leaf of a sliding-window block (its per-slot ring pool)."""
    return bool(ring_blocks) and getattr(path[0], "key", None) in ring_blocks


def ring_order(req_cache: dict, L: int, R: int, ring_blocks) -> dict:
    """A whole-prompt prefill's dense (nper, 1, L, K, hd) KV of the
    sliding-window blocks in ring order: the last R positions, position p
    at row p mod R (what chunked prefill's staging ring holds)."""
    def one(path, leaf):
        if not (_is_kv(path) and _in_ring(path, ring_blocks)):
            return leaf
        if L <= R:
            return jnp.pad(leaf, ((0, 0), (0, 0), (0, R - L), (0, 0),
                                  (0, 0)))
        return jnp.roll(leaf[:, :, L - R:], (L - R) % R, axis=2)
    return jax.tree_util.tree_map_with_path(one, req_cache)


def admit_kv(cache: dict, req_cache: dict, page_ids, page_size: int,
             slot: int, skip_pages: int = 0, ring_blocks=()) -> dict:
    """Copy-on-admit: scatter a prefilled request's (nper, 1, L, K, hd)
    KV into its allocated pages; non-KV leaves (recurrent state) are
    written into batch ``slot`` densely. ``skip_pages`` leading pages of
    the chain are trie-shared and already hold the right rows — writing
    them here would zero-pad over a sibling's live rows, so they are
    excluded from the scatter.

    The pool may be int8 (``kv_dtype='int8'``) while the request cache is
    always the fp dense staging layout — quantization happens here, and
    the scale siblings are filled from the same rows. The two trees then
    have different structures, so this walks the pool's flattened paths
    and looks the fp sources up by path.

    The whole scatter is jit-compiled, keyed by (staging length, page
    count, skip) — the same shape family the prefill executables already
    warm — so an int8 admit costs one fused kernel, not an eager
    quantize-dispatch per cache leaf. ``slot`` rides in as a traced
    scalar: slot churn never retraces. ``cache`` is donated: the rows
    land in its buffers, which the returned tree reuses, and the caller
    drops its reference to the old one.

    The ``ring_blocks``' KV arrives in ring order (``ring_order``), one
    ring of the pool's ring length, and is copied whole into the slot's
    ring pages."""
    skip = int(skip_pages)
    ids = jnp.asarray(page_ids, jnp.int32)[skip:]
    return _admit_kv_jit(cache, req_cache, ids, jnp.int32(slot),
                         page_size=int(page_size), skip=skip,
                         ring_blocks=tuple(ring_blocks))


@functools.partial(jax.jit, static_argnames=("page_size", "skip",
                                             "ring_blocks"),
                   donate_argnums=(0,))
def _admit_kv_jit(cache: dict, req_cache: dict, ids, slot, *,
                  page_size: int, skip: int, ring_blocks=()) -> dict:
    n = int(ids.shape[0])
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
    req = {_path_key(p): leaf
           for p, leaf in jax.tree_util.tree_flatten_with_path(req_cache)[0]}

    def page_rows(req_leaf):
        nper, _, L, K, hd = req_leaf.shape
        r = req_leaf[:, 0, skip * page_size:]
        pad = n * page_size - (L - skip * page_size)
        r = jnp.pad(r, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return r.reshape(nper, n, page_size, K, hd)

    out = []
    for path, pooled in flat:
        key = _path_key(path)
        if _is_kv(path) and _in_ring(path, ring_blocks):
            nper, _, R, K, hd = req[key].shape
            rp = R // page_size
            ring = req[key][:, 0].reshape(nper, rp, page_size, K, hd)
            out.append(jax.lax.dynamic_update_slice(
                pooled, ring.astype(pooled.dtype),
                (0, 1 + slot * rp, 0, 0, 0)))
        elif _is_kv(path):
            if n == 0:                 # whole prompt shared: nothing to copy
                out.append(pooled)
                continue
            r = page_rows(req[key])
            if pooled.dtype == jnp.int8:
                r, _ = kv_quantize(r)
            out.append(pooled.at[:, ids].set(r.astype(pooled.dtype)))
        elif _is_kv_scale(path):
            if n == 0:
                out.append(pooled)
                continue
            r = page_rows(req[key[:-1] + (key[-1][:-len("_scale")],)])
            _, scale = kv_quantize(r)
            out.append(pooled.at[:, ids].set(scale))
        else:
            out.append(jax.lax.dynamic_update_slice(
                pooled, req[key].astype(pooled.dtype),
                (0, slot) + (0,) * (pooled.ndim - 2)))
    return jax.tree_util.tree_unflatten(treedef, out)


def extract_kv(cache: dict, page_ids, page_size: int, slot: int) -> dict:
    """Gather dual of ``admit_kv``, for request migration: pull a
    request's KV block chain out of the page pools into a dense
    (nper, 1, n*page_size, K, hd) request tree, and slice its batch slot
    out of every dense (recurrent-state) leaf, keeping the slot axis.
    The result has exactly the shape ``admit_kv`` scatters, so target-
    side admission IS ``admit_kv(..., skip_pages=n_reshared)``.

    int8 pools are DEQUANTIZED here and scale leaves dropped: a migration
    payload is always the fp dense layout, so source and target engines
    may run different ``kv_dtype`` settings — and since the quantizer's
    row max lands exactly on +-127, a target re-admitting into int8
    reproduces the source's bytes bit-for-bit."""
    ids = jnp.asarray(page_ids, jnp.int32)
    n = int(ids.shape[0])

    def walk(node):
        out = {}
        for name, child in node.items():
            if isinstance(child, dict):
                out[name] = walk(child)
                continue
            if name in ("k_scale", "v_scale", "xk_scale", "xv_scale"):
                continue
            if name in ("k", "v", "xk", "xv") and child.ndim == 5:
                nper, _, P, K, hd = child.shape
                rows = child[:, ids]
                if child.dtype == jnp.int8:
                    rows = kv_dequantize(rows, node[name + "_scale"][:, ids])
                out[name] = rows.reshape(nper, 1, n * P, K, hd)
            else:
                out[name] = jax.lax.dynamic_slice(
                    child, (0, slot) + (0,) * (child.ndim - 2),
                    (child.shape[0], 1) + child.shape[2:])
        return out
    return walk(cache)


def copy_page(cache: dict, src: int, dst: int) -> dict:
    """CoW page split, device side: duplicate one physical page across
    every KV pool (and its quantization scales) so the writer's fresh
    private page starts bit-identical to the shared one it is leaving."""
    def one(path, leaf):
        if _is_kv(path) or _is_kv_scale(path):
            return leaf.at[:, dst].set(leaf[:, src])
        return leaf
    return jax.tree_util.tree_map_with_path(one, cache)


def apply_page_moves(cache: dict, moves: dict[int, int],
                     ring_blocks=()) -> dict:
    """Apply a ``defragment`` move map to the physical page pools
    (quantization scales ride along — their axis 1 is the same page id);
    the ``ring_blocks``' per-slot rings do not move."""
    if not moves:
        return cache

    def one(path, leaf):
        if _in_ring(path, ring_blocks):
            return leaf
        if _is_kv(path) or _is_kv_scale(path):
            g = permutation_of(moves, leaf.shape[1])
            return leaf[:, jnp.asarray(g)]
        return leaf
    return jax.tree_util.tree_map_with_path(one, cache)


def reset_slot_state(cache: dict, slot: int) -> dict:
    """Zero a finished slot's dense (non-KV) recurrent state; paged KV
    (and its scales) needs no reset — its pages are simply returned to
    the allocator."""
    def one(path, leaf):
        if _is_kv(path) or _is_kv_scale(path):
            return leaf
        name = path[-1].key if hasattr(path[-1], "key") else ""
        fill = -1e30 if name == "m" else 0.0
        return leaf.at[:, slot].set(fill)
    return jax.tree_util.tree_map_with_path(one, cache)
