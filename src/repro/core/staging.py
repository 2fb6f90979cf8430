"""StagingEngine — the Xilinx QDMA analogue (paper §IV-A).

QDMA moves VF memory between device and host through descriptor queues.
Here the engine moves tenant state pytrees HBM<->host through a pipelined
descriptor engine: leaves are split into fixed-size row-chunk DESCRIPTORS
(so one huge leaf no longer serializes a single queue), and each descriptor
flows through an overlapped 3-stage pipeline:

  save:     on-device pack (``qdma_pack_rows``: blockwise int8, or a plain
            device-side row slice) -> D2H over ``num_queues`` transfer
            streams -> host assemble into the leaf's output buffer
  restore:  host burst -> H2D (batched ``device_put`` per queue) ->
            on-device unpack / concatenate

Every pack/slice for descriptor i+1 is dispatched before descriptor i's
D2H completes (jax dispatch is asynchronous), which is the double-buffering
of the QDMA descriptor ring: the device prepares the next descriptor while
the previous one crosses the link.

Host assembly (stage 3) of a leaf split into several plain descriptors
writes into one buffer, ``np.empty(shape, dtype)``, allocated when the
leaf is dispatched. Each fetched chunk's raw bytes, viewed as unsigned
integers of the leaf's item size, are copied into its row range on the
``qdma`` thread that fetched it, right after its ``device_get``. So the
copies, and the first touch of the fresh buffer's pages that bounds a
single thread's copy, spread over the queues and overlap the other
queues' D2H; each chunk is dropped once placed. A single-descriptor leaf
keeps the fetched buffer itself; packed chunks are concatenated after
the bursts.

Transports (``transport=``):
  borrow   host-device grids (CPU backend): ``device_get`` BORROWS the
           device buffer zero-copy, so non-packed descriptors of one leaf
           are coalesced into a single borrow — forcing row-chunk copies
           there would only add memcpys. Packed descriptors still stream
           chunk-granular (the pack kernel writes fresh buffers anyway).
  stream   real accelerators: every descriptor is an explicit device-side
           row slice D2H'd independently, so all queues stay busy
           regardless of tree shape.
  auto     borrow on the CPU backend, stream elsewhere.

Dirty tracking (``incremental=True``):
  identity  a leaf that is the SAME immutable jax array object as in the
            previous save is not re-transferred (its host copy is reused).
  digest    additionally, mutated-but-EQUAL leaves are skipped via a cheap
            on-device content fingerprint (``qdma_digest``; crc32 for host
            numpy leaves) — this is what makes pre-copy live pause cheap:
            the final stop-and-copy moves only leaves whose bytes actually
            changed since the last pre-copy round.
The memo is scoped PER TENANT (``save(tree, tenant=...)``) and released
via ``clear(tenant)`` — the manager calls it on detach and after pause, so
the memo cannot grow without bound across tenants.

Compression is OFF by default: the paper-faithful pause path is bit-exact.
The int8 path is the beyond-paper optimization measured in EXPERIMENTS.md
§Perf (pause-path hillclimb, HC1-HC5).

``pipeline=False`` preserves the PR-1 engine (whole-leaf round-robin over
queues) as the benchmark baseline — see ``benchmarks/pause_path.py``.
"""
from __future__ import annotations

import concurrent.futures as cf
import dataclasses
import math
import time
import zlib
from typing import Any, Optional

import jax
import numpy as np

from repro.core.fault import crashpoint
from repro.runtime.spans import span

_GLOBAL = "__global__"


@dataclasses.dataclass
class TransferStats:
    bytes_moved: int = 0        # host-repr bytes that crossed the link
    logical_bytes: int = 0      # unpacked logical bytes of the tree
    seconds: float = 0.0
    num_leaves: int = 0
    queues: int = 1
    skipped_bytes: int = 0      # host-repr bytes reused from the memo
    num_descriptors: int = 0
    transport: str = "borrow"
    assembled_bytes: int = 0    # bytes stage 3 copied into host leaf buffers


@dataclasses.dataclass
class QuantizedLeaf:
    """Host-side packed leaf: blockwise int8 + per-block scales."""
    q: np.ndarray                     # int8, original shape
    scale: np.ndarray                 # fp32, shape[:-1] + (blocks,)
    dtype: str
    block: int


class _Opaque:
    """Wrapper so a QuantizedLeaf traverses pytrees as a single leaf."""
    def __init__(self, leaf: QuantizedLeaf):
        self.leaf = leaf


def _nbytes(x) -> int:
    """Host-representation bytes — the symmetric save/restore unit of
    account: a quantized leaf counts its packed q+scale bytes, once."""
    if isinstance(x, _Opaque):
        x = x.leaf
    if isinstance(x, QuantizedLeaf):
        return x.q.nbytes + x.scale.nbytes
    return np.asarray(x).nbytes


_UINT = {1: np.uint8, 2: np.uint16, 4: np.uint32, 8: np.uint64}


def _raw(a: np.ndarray) -> np.ndarray:
    """``a`` viewed as unsigned integers of its item size (bytes where
    there is none), so that a copy moves plain integers whatever the
    leaf's own dtype, e.g. an ``ml_dtypes`` bfloat16."""
    return a.view(_UINT.get(a.dtype.itemsize, np.uint8))


@dataclasses.dataclass
class _Memo:
    ref: Any            # device array object (identity check) or None
    digest: Any         # content fingerprint tuple or None
    host: Any           # host copy (ndarray or QuantizedLeaf)


@dataclasses.dataclass
class _Descriptor:
    leaf: int           # flat leaf index
    chunk: int
    lo: int             # row range in the leaf's 2-D (rows, L) view
    rows: int
    nbytes: int         # estimated D2H bytes (queue balancing)
    packed: bool
    dev: Any = None     # device array / (q, scale) awaiting D2H
    host: Any = None    # fetched host buffer(s)
    dest: Any = None    # the leaf's host buffer, when chunks are placed


class StagingEngine:
    def __init__(self, num_queues: int = 8, compression: str = "none",
                 block: int = 256, min_quant_size: int = 4096,
                 incremental: bool = False, pipeline: bool = True,
                 chunk_bytes: int = 32 << 20, transport: str = "auto",
                 dirty: str = "identity"):
        assert compression in ("none", "int8")
        assert transport in ("auto", "borrow", "stream")
        assert dirty in ("identity", "digest")
        self.num_queues = num_queues
        self.compression = compression
        self.block = block
        self.min_quant_size = min_quant_size
        self.incremental = incremental
        self.pipeline = pipeline
        self.chunk_bytes = max(1, int(chunk_bytes))
        self.transport = transport
        self.dirty = dirty
        self._memos: dict[str, dict[str, _Memo]] = {}
        self._pool: Optional[cf.ThreadPoolExecutor] = None
        self.last_stats: Optional[TransferStats] = None

    # -- memo (per-tenant incremental state) -----------------------------------
    def _memo_for(self, tenant: Optional[str]) -> dict:
        return self._memos.setdefault(tenant or _GLOBAL, {})

    def memo_size(self, tenant: Optional[str] = None) -> int:
        if tenant is None:
            return sum(len(m) for m in self._memos.values())
        return len(self._memos.get(tenant or _GLOBAL, {}))

    def clear(self, tenant: Optional[str] = None) -> None:
        """Drop incremental-snapshot state. ``clear(tid)`` releases one
        tenant's memo (called by the manager on detach and after pause);
        ``clear()`` drops everything."""
        if tenant is None:
            self._memos.clear()
        else:
            self._memos.pop(tenant, None)

    def _digest_dispatch(self, x):
        """Start a digest: for device leaves the kernel is dispatched
        asynchronously (the (2,) uint32 result is materialized later by
        ``_digest_finalize``), so many leaves' digests run concurrently
        and overlap the first D2H bursts."""
        if isinstance(x, jax.Array):
            from repro.kernels import ops as kops
            return ["dev", x.shape, str(x.dtype), kops.qdma_digest(x)]
        a = np.ascontiguousarray(np.asarray(x))
        try:
            crc = zlib.crc32(a)             # buffer protocol: no copy
        except (TypeError, ValueError, BufferError):
            crc = zlib.crc32(a.tobytes())   # exotic dtypes (e.g. bf16)
        return ("crc", a.shape, str(a.dtype), crc)

    @staticmethod
    def _digest_finalize(dg):
        if isinstance(dg, list):          # pending device digest
            return ("dev", dg[1], dg[2],
                    tuple(int(v) for v in np.asarray(dg[3])))
        return dg

    def _digest(self, x):
        return self._digest_finalize(self._digest_dispatch(x))

    def _memo_hit(self, memo: dict, key: str, x, incremental: bool,
                  digest=None):
        """(host copy of x if it provably hasn't changed since the last
        save, else None; digest of x if one was computed — callers hand it
        back to ``_memo_put`` so a missed leaf is digested exactly once).
        ``digest`` lets the pipelined save pass a pre-dispatched digest."""
        if not incremental:
            return None, None
        e = memo.get(key)
        if e is not None and isinstance(x, jax.Array) and e.ref is x:
            return e.host, e.digest   # immutable: identity => equal bytes
        dg = None
        if self.dirty == "digest" and isinstance(x, (jax.Array, np.ndarray)):
            dg = self._digest_finalize(
                digest if digest is not None else self._digest_dispatch(x))
            if e is not None and e.digest is not None and dg == e.digest:
                # refresh the entry: the next save of this same object is
                # a free identity hit, and the superseded device array is
                # released instead of staying pinned by the stale ref
                memo[key] = _Memo(ref=x if isinstance(x, jax.Array)
                                  else None, host=e.host, digest=dg)
                return e.host, dg
        return None, dg

    def _memo_put(self, memo, key, x, host, incremental: bool, digest=None):
        if not incremental:
            return
        if isinstance(x, jax.Array):
            memo[key] = _Memo(ref=x, host=host, digest=digest)
        elif self.dirty == "digest" and isinstance(x, np.ndarray):
            memo[key] = _Memo(ref=None, host=host, digest=digest)

    # -- execution helpers ------------------------------------------------------
    def _transport_mode(self) -> str:
        if self.transport != "auto":
            return self.transport
        return "borrow" if jax.default_backend() == "cpu" else "stream"

    def _executor(self) -> cf.ThreadPoolExecutor:
        if self._pool is None:
            self._pool = cf.ThreadPoolExecutor(
                max_workers=max(1, self.num_queues),
                thread_name_prefix="qdma")
        return self._pool

    def close(self) -> None:
        """Join the transfer-queue threads. Safe to call repeatedly; the
        engine lazily respawns them if used again."""
        if self._pool is not None:
            self._pool.shutdown(wait=False)
            self._pool = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def _row_chunks(self, nbytes: int, R: int) -> list[tuple[int, int]]:
        """Split R rows into [lo, hi) descriptor ranges of ~chunk_bytes
        each (single whole-leaf range when chunking can't help)."""
        n = 1
        if R > 1 and nbytes > self.chunk_bytes:
            n = min(R, math.ceil(nbytes / self.chunk_bytes))
        return [(R * c // n, R * (c + 1) // n) for c in range(n)]

    @staticmethod
    def _row_view_dims(x) -> tuple[int, int]:
        """(rows, L) of the 2-D row view of a leaf (scalars: (1, 1))."""
        L = x.shape[-1] if x.ndim else 1
        return ((x.size // L) if L else 0), L

    def _balance(self, items, nq, weight):
        """Greedy longest-processing-time split of items over nq queues."""
        queues = [[] for _ in range(nq)]
        load = [0] * nq
        for it in sorted(items, key=weight, reverse=True):
            i = load.index(min(load))
            queues[i].append(it)
            load[i] += weight(it)
        return [q for q in queues if q]

    # -- device -> host (pause / checkpoint) -----------------------------------
    def save(self, tree: Any, tenant: Optional[str] = None,
             incremental: Optional[bool] = None) -> Any:
        with span("staging.save"):
            if not self.pipeline:
                return self._save_legacy(tree, tenant, incremental)
            return self._save_pipelined(tree, tenant, incremental)

    def _save_pipelined(self, tree: Any, tenant: Optional[str],
                        incremental: Optional[bool]) -> Any:
        from repro.kernels import ops as kops
        incremental = self.incremental if incremental is None else incremental
        transport = self._transport_mode()
        t0 = time.perf_counter()
        flat_p, treedef = jax.tree_util.tree_flatten_with_path(tree)
        memo = self._memo_for(tenant)
        n = len(flat_p)
        host_flat: list = [None] * n
        logical = skipped = placed = 0
        descs: list[_Descriptor] = []
        digests: dict[int, Any] = {}    # leaf idx -> digest computed at miss
        # transactional publication: memo writes are BUFFERED here and
        # committed only once the whole snapshot is assembled — a crash
        # mid-save (InjectedCrash or real) leaves the memo, and therefore
        # every future incremental save, exactly as before this save
        memo_puts: list = []            # (key, x, host, digest)

        # -- stage -1: pre-dispatch digest kernels for identity misses so
        # they all run concurrently on device (finalized leaf-by-leaf in
        # stage 0, overlapping the first D2H bursts)
        pending: dict[int, Any] = {}
        if incremental and self.dirty == "digest":
            with span("staging.digest"):
                for i, (path, x) in enumerate(flat_p):
                    if isinstance(x, jax.Array):
                        e = memo.get(jax.tree_util.keystr(path))
                        if e is None or e.ref is not x:
                            pending[i] = self._digest_dispatch(x)

        # -- stage 0: dirty filter + stage 1: descriptor dispatch (async) ----
        with span("staging.dispatch"):
            for i, (path, x) in enumerate(flat_p):
                key = jax.tree_util.keystr(path)
                logical += (x.nbytes if isinstance(x, jax.Array)
                            else _nbytes(x))
                hit, digests[i] = self._memo_hit(memo, key, x, incremental,
                                                 digest=pending.get(i))
                if hit is not None:
                    host_flat[i] = hit
                    skipped += _nbytes(hit)
                    continue
                if not isinstance(x, jax.Array):
                    # materialize a real copy: a pause snapshot is the
                    # tenant's ONLY state copy, so it must not alias a
                    # host buffer the tenant may later mutate in place
                    host = np.array(x)
                    host_flat[i] = host
                    memo_puts.append((key, x, host, digests[i]))
                    continue
                ds = self._dispatch_leaf(i, x, transport, kops)
                descs.extend(ds)
                if ds[0].dest is not None:
                    # filled by stage 2's placement; published with the rest
                    host_flat[i] = ds[0].dest
                    placed += x.nbytes
                    memo_puts.append((key, x, host_flat[i], digests[i]))

        # -- stage 2: D2H descriptor queues (burst-batched device_get) --------
        bursts = self._balance(descs, max(1, min(self.num_queues,
                                                 len(descs) or 1)),
                               lambda d: d.nbytes)

        # crash window: descriptors dispatched (and host leaves staged)
        # but the D2H queues have not drained — the half-built snapshot
        # and its buffered memo updates must never become observable
        crashpoint("mid_pipeline_chunk")

        def fetch(burst):
            dev = [d.dev for d in burst]
            with span("staging.d2h", bytes=sum(
                    a.nbytes for a in jax.tree.leaves(dev))):
                got = jax.device_get(dev)
            for d, h in zip(burst, got):
                d.host = h
            del got             # a placed chunk is freed once it is placed
            # stage 3 for the placed chunks, on this queue's thread
            mine = [d for d in burst if d.dest is not None]
            if mine:
                with span("staging.assemble",
                          bytes=sum(d.host.nbytes for d in mine)):
                    for d in mine:
                        self._place(d)
        if len(bursts) <= 1:
            for b in bursts:
                fetch(b)
        else:
            list(self._executor().map(fetch, bursts))

        # -- stage 3 for the other leaves: packed chunks, single chunks ------
        by_leaf: dict[int, list[_Descriptor]] = {}
        for d in descs:
            if d.dest is None:
                by_leaf.setdefault(d.leaf, []).append(d)
        # only packed leaves are left with several chunks, each (q, scale)
        concat = sum(a.nbytes for ds in by_leaf.values() if len(ds) > 1
                     for d in ds for a in d.host)
        if by_leaf:
            with span("staging.assemble", bytes=concat):
                for i, ds in by_leaf.items():
                    path, x = flat_p[i]
                    host = self._assemble(x, sorted(ds,
                                                    key=lambda d: d.chunk))
                    host_flat[i] = host
                    memo_puts.append((jax.tree_util.keystr(path), x, host,
                                      digests[i]))

        # -- publish: the snapshot is complete, commit the memo updates ------
        for key, x, host, dg in memo_puts:
            self._memo_put(memo, key, x, host, incremental, digest=dg)

        dt = time.perf_counter() - t0
        moved = sum(_nbytes(h) for h in host_flat) - skipped
        self.last_stats = TransferStats(
            bytes_moved=moved, logical_bytes=logical, seconds=dt,
            num_leaves=n, queues=self.num_queues, skipped_bytes=skipped,
            num_descriptors=len(descs), transport=transport,
            assembled_bytes=placed + concat)
        return jax.tree_util.tree_unflatten(treedef, [
            _Opaque(h) if isinstance(h, QuantizedLeaf) else h
            for h in host_flat])

    def _pack_eligible(self, x) -> bool:
        return (self.compression == "int8" and x.ndim >= 1
                and x.dtype in (np.dtype("float32"), np.dtype("bfloat16"))
                and x.size >= self.min_quant_size
                and x.shape[-1] % self.block == 0)

    def _dispatch_leaf(self, i, x, transport, kops) -> list[_Descriptor]:
        """Split leaf i into descriptors and dispatch their device-side
        stage (pack kernel / row slice); returns descriptors whose D2H is
        pending. Dispatch is async, so descriptor i+1's pack overlaps
        descriptor i's D2H."""
        packed = self._pack_eligible(x)
        R, L = self._row_view_dims(x)
        chunkable = packed or transport == "stream"
        ranges = self._row_chunks(x.nbytes, R) if chunkable else [(0, R)]
        out = []
        x2 = dest = None
        if len(ranges) > 1 and not packed:
            x2 = x.reshape(R, L)
            dest = np.empty(x.shape, x.dtype)
        per_chunk = max(1, x.nbytes // len(ranges))
        for c, (lo, hi) in enumerate(ranges):
            d = _Descriptor(leaf=i, chunk=c, lo=lo, rows=hi - lo,
                            nbytes=per_chunk, packed=packed, dest=dest)
            if packed:
                d.dev = kops.qdma_pack_rows(x, lo, rows=d.rows,
                                            block=self.block)
                d.nbytes = max(1, per_chunk // x.dtype.itemsize)  # ~int8
            elif x2 is not None:
                d.dev = jax.lax.slice_in_dim(x2, lo, hi, axis=0)
            else:
                d.dev = x          # whole-leaf borrow / single stream chunk
            out.append(d)
        return out

    @staticmethod
    def _place(d: _Descriptor) -> None:
        """Stage 3 of a plain chunk: copy its raw bytes into its rows of
        the leaf's buffer (bit-exact: row-chunking commutes with reshape),
        then drop the fetched chunk."""
        rows = d.dest.reshape(-1, d.dest.shape[-1])
        _raw(rows)[d.lo:d.lo + d.rows] = _raw(np.asarray(d.host))
        d.host = None

    def _assemble(self, x, ds: list[_Descriptor]):
        """Stage 3 of a packed or single-descriptor leaf: combine its
        fetched chunks into the leaf's host representation."""
        if ds[0].packed:
            q2 = np.concatenate([np.asarray(d.host[0]) for d in ds], axis=0) \
                if len(ds) > 1 else np.asarray(ds[0].host[0])
            s2 = np.concatenate([np.asarray(d.host[1]) for d in ds], axis=0) \
                if len(ds) > 1 else np.asarray(ds[0].host[1])
            return QuantizedLeaf(
                q=q2.reshape(x.shape),
                scale=s2.reshape(x.shape[:-1] + (s2.shape[-1],)),
                dtype=str(x.dtype), block=self.block)
        return np.asarray(ds[0].host)

    # -- host -> device (unpause / restore) -------------------------------------
    def restore(self, staged: Any, shardings: Any = None) -> Any:
        with span("staging.restore"):
            if not self.pipeline:
                return self._restore_legacy(staged, shardings)
            return self._restore_pipelined(staged, shardings)

    def _restore_pipelined(self, staged: Any, shardings: Any) -> Any:
        from repro.kernels import ops as kops
        t0 = time.perf_counter()
        flat, treedef = jax.tree_util.tree_flatten(
            staged, is_leaf=lambda x: isinstance(x, _Opaque))
        sflat = self._sharding_leaves(shardings, len(flat))
        n = len(flat)
        dev_flat: list = [None] * n

        plain = [(i, x, sh) for i, (x, sh) in enumerate(zip(flat, sflat))
                 if not isinstance(x, _Opaque)]
        packed = [(i, x, sh) for i, (x, sh) in enumerate(zip(flat, sflat))
                  if isinstance(x, _Opaque)]

        # packed leaves first: their H2D + on-device unpack is dispatched
        # asynchronously, overlapping the plain bursts below (stage overlap
        # on restore mirrors the save pipeline in reverse)
        for i, x, sh in packed:
            with span("staging.h2d", bytes=_nbytes(x)):
                dev_flat[i] = self._restore_packed(x.leaf, sh, kops)

        # plain leaves: burst-batched device_put per queue
        nq = max(1, min(self.num_queues, len(plain) or 1))
        bursts = self._balance(plain, nq, lambda it: _nbytes(it[1]))

        def put(burst):
            nosh = [(i, x) for i, x, sh in burst if sh is None]
            withsh = [(i, x, sh) for i, x, sh in burst if sh is not None]
            with span("staging.h2d",
                      bytes=sum(_nbytes(x) for _, x, _ in burst)):
                if nosh:
                    res = jax.device_put([x for _, x in nosh])
                    for (i, _), r in zip(nosh, res):
                        dev_flat[i] = r
                if withsh:
                    res = jax.device_put([x for _, x, _ in withsh],
                                         [sh for _, _, sh in withsh])
                    for (i, _, _), r in zip(withsh, res):
                        dev_flat[i] = r
        if len(bursts) <= 1:
            for b in bursts:
                put(b)
        else:
            list(self._executor().map(put, bursts))

        with span("staging.ready"):
            jax.block_until_ready([d for d in dev_flat if d is not None])
        dt = time.perf_counter() - t0
        self.last_stats = TransferStats(
            bytes_moved=sum(_nbytes(x) for x in flat),
            logical_bytes=sum(np.asarray(x).nbytes if not hasattr(x, "nbytes")
                              else x.nbytes for x in dev_flat),
            seconds=dt, num_leaves=n, queues=self.num_queues,
            num_descriptors=len(plain) + len(packed),
            transport=self._transport_mode())
        return jax.tree_util.tree_unflatten(treedef, dev_flat)

    def _sharding_leaves(self, shardings, n: int) -> list:
        if shardings is None:
            return [None] * n
        sflat = jax.tree_util.tree_leaves(
            shardings, is_leaf=lambda s: hasattr(s, "device_set"))
        assert len(sflat) == n, (len(sflat), n)
        return sflat

    def _restore_packed(self, ql: QuantizedLeaf, sh, kops):
        """H2D + on-device dequantize, chunk-granular in stream mode so
        upload of chunk i+1 overlaps unpack of chunk i."""
        ssh = None if sh is None else _scale_sharding(sh)
        R, L = self._row_view_dims(ql.q)
        ranges = (self._row_chunks(ql.q.nbytes, R)
                  if self._transport_mode() == "stream" else [(0, R)])
        if len(ranges) == 1:
            q = jax.device_put(ql.q, sh)
            scale = jax.device_put(ql.scale, ssh)
            return kops.qdma_unpack(q, scale, dtype=ql.dtype)
        import jax.numpy as jnp
        q2 = ql.q.reshape(R, L)
        s2 = ql.scale.reshape(R, ql.scale.shape[-1])
        parts = []
        for lo, hi in ranges:
            qd = jax.device_put(q2[lo:hi])
            sd = jax.device_put(s2[lo:hi])
            parts.append(kops.qdma_unpack(qd, sd, dtype=ql.dtype))
        out = jnp.concatenate(parts, axis=0).reshape(ql.q.shape)
        if sh is not None:
            out = jax.device_put(out, sh)
        return out

    # -- PR-1 baseline engine (whole-leaf round-robin) --------------------------
    def _save_legacy(self, tree: Any, tenant: Optional[str],
                     incremental: Optional[bool]) -> Any:
        from repro.kernels import ops as kops
        incremental = self.incremental if incremental is None else incremental
        t0 = time.perf_counter()
        flat_p, treedef = jax.tree_util.tree_flatten_with_path(tree)
        memo = self._memo_for(tenant)
        logical = sum(x.nbytes if isinstance(x, jax.Array) else _nbytes(x)
                      for _, x in flat_p)
        skipped = 0

        def fetch(path_x):
            nonlocal skipped
            path, x = path_x
            key = jax.tree_util.keystr(path)
            hit, dg = self._memo_hit(memo, key, x, incremental)
            if hit is not None:
                skipped += _nbytes(hit)
                return hit, None
            crashpoint("mid_pipeline_chunk")
            if isinstance(x, jax.Array) and self._pack_eligible(x):
                q, scale = kops.qdma_pack(x, block=self.block)
                host = QuantizedLeaf(q=np.asarray(jax.device_get(q)),
                                     scale=np.asarray(jax.device_get(scale)),
                                     dtype=str(x.dtype), block=self.block)
            else:
                host = np.asarray(jax.device_get(x))
            return host, (key, x, host, dg)

        # QDMA-style queues: round-robin leaves over transfer streams
        with cf.ThreadPoolExecutor(max_workers=self.num_queues) as ex:
            fetched = list(ex.map(fetch, flat_p))
        host_flat = [h for h, _ in fetched]
        # transactional publication (see the pipelined save): memo commits
        # only after every leaf crossed the link
        for _, put in fetched:
            if put is not None:
                self._memo_put(memo, *put[:3], incremental, digest=put[3])
        dt = time.perf_counter() - t0
        moved = sum(_nbytes(x) for x in host_flat) - skipped
        self.last_stats = TransferStats(
            bytes_moved=moved, logical_bytes=logical, seconds=dt,
            num_leaves=len(host_flat), queues=self.num_queues,
            skipped_bytes=skipped, num_descriptors=len(host_flat),
            transport="legacy")
        return jax.tree_util.tree_unflatten(treedef, [
            _Opaque(x) if isinstance(x, QuantizedLeaf) else x
            for x in host_flat])

    def _restore_legacy(self, staged: Any, shardings: Any = None) -> Any:
        from repro.kernels import ops as kops
        t0 = time.perf_counter()
        flat, treedef = jax.tree_util.tree_flatten(
            staged, is_leaf=lambda x: isinstance(x, _Opaque))
        sflat = self._sharding_leaves(shardings, len(flat))

        def place(args):
            x, sh = args
            if isinstance(x, _Opaque):
                ql: QuantizedLeaf = x.leaf
                q = jax.device_put(ql.q, sh)
                scale = jax.device_put(
                    ql.scale, None if sh is None else _scale_sharding(sh))
                return kops.qdma_unpack(q, scale, dtype=ql.dtype)
            return jax.device_put(x, sh)

        with cf.ThreadPoolExecutor(max_workers=self.num_queues) as ex:
            dev_flat = list(ex.map(place, zip(flat, sflat)))
        jax.block_until_ready(dev_flat)
        dt = time.perf_counter() - t0
        self.last_stats = TransferStats(
            bytes_moved=sum(_nbytes(x) for x in flat),
            logical_bytes=sum(x.nbytes for x in dev_flat),
            seconds=dt, num_leaves=len(dev_flat), queues=self.num_queues,
            num_descriptors=len(dev_flat), transport="legacy")
        return jax.tree_util.tree_unflatten(treedef, dev_flat)


def _scale_sharding(sh):
    """Scales have one fewer trailing dim granularity; replicate for
    simplicity (they are tiny)."""
    try:
        from jax.sharding import NamedSharding, PartitionSpec
        if isinstance(sh, NamedSharding):
            return NamedSharding(sh.mesh, PartitionSpec())
    except Exception:
        pass
    return None
