"""Device-mesh sharding of the scan step.

Replaces the reference's worker-pool distribution (SURVEY.md §2.7 P1/P4:
errgroup pipelines + client/server sharding) with a 2-D
`jax.sharding.Mesh`:

  axis "dp"  — data parallel over the candidate-pair batch (each pair is
               one (package, advisory-row) predicate evaluation);
  axis "db"  — the advisory table sharded round-robin by row residue
               (the framework's tensor-parallel dimension; SURVEY.md §5
               "TP over the DB dimension" for tables larger than one
               chip's HBM).

Table shard s holds global rows r with r % S == s at local index
r // S, so any bucket interval maps to a contiguous LOCAL range on
every shard — a mega bucket (the real trivy-db's `linux`) spreads its
pair volume over the whole db axis by construction instead of stacking
one shard. The host routes per-QUERY CSR descriptor pieces (≤S per
query), splitting oversized pieces so pair work LPT-balances across
dp, and each device expands its own candidate-pair list on-chip —
multi-chip transfer stays O(queries·S), matching the single-chip
csr_pair_join up to the small db factor. No collectives are needed
inside the step: each device evaluates local pairs against its local
table slice, and the strided perm reassembles the bits.

Everything runs under one jit(shard_map(...)).
"""

from __future__ import annotations

import functools
import threading
from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..db.table import AdvisoryTable
from ..detect import feed as _feed
from ..ops import join as J
from ..ops import next_pow2 as _next_pow2
from ..resilience.hostjoin import CompactBits

def make_mesh(n_devices: int | None = None, db_shards: int = 1,
              devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    n = len(devices)
    if n % db_shards != 0:
        raise ValueError(f"{n} devices not divisible by db={db_shards}")
    dev_array = np.asarray(devices).reshape(n // db_shards, db_shards)
    return Mesh(dev_array, axis_names=("dp", "db"))


def best_db_shards(n_devices: int, db_pref: int) -> int:
    """Largest valid db width for an n-device mesh: the biggest
    divisor of n_devices that is ≤ the preferred shard count. The
    meshguard shrink path uses this to keep ALL survivors in the mesh
    (dp×db must tile them exactly) while staying as close to the
    configured db sharding as the survivor count allows — e.g. losing
    one device of a 4×(db=2) mesh re-meshes the 3 survivors as
    dp3×db1."""
    if n_devices <= 0:
        raise ValueError("best_db_shards: no devices")
    for db in range(min(max(db_pref, 1), n_devices), 0, -1):
        if n_devices % db == 0:
            return db
    return 1


def mesh_from_devices(devices, db_shards: int = 1) -> Mesh:
    """dp×db mesh over exactly these devices, with the largest valid
    factorization for the preferred db width (meshguard shrink/grow
    rebuilds hand this the survivor list)."""
    n = len(devices)
    if n == 0:
        raise ValueError("mesh_from_devices: no devices")
    db = best_db_shards(n, db_shards)
    dev_array = np.asarray(list(devices)).reshape(n // db, db)
    return Mesh(dev_array, axis_names=("dp", "db"))


@dataclass
class ShardedTable:
    """Advisory arrays with a leading shard axis [S, A_pad, ...]."""
    lo_tok: np.ndarray
    hi_tok: np.ndarray
    flags: np.ndarray
    row_offset: np.ndarray  # int64[S]: shard residue ids (0..S-1)
    row_len: np.ndarray     # int64[S]: real (unpadded) rows per shard


def shard_arrays(lo_tok: np.ndarray, hi_tok: np.ndarray,
                 flags: np.ndarray, n_shards: int,
                 pad: int | None = None) -> ShardedTable:
    """shard_table over raw columns: the graftstream mesh path shards
    each hash-range SLICE of the table through here with a caller-
    pinned `pad` (uniform local row count across every slice, so the
    whole stream compiles one sharded-join program family instead of
    one per slice)."""
    a = lo_tok.shape[0]
    lens = [max(0, (a - s + n_shards - 1) // n_shards)
            for s in range(n_shards)]
    if pad is None:
        pad = max(lens) if a else 1

    def _piece(arr, s, fill):
        out = np.full((pad,) + arr.shape[1:], fill, dtype=arr.dtype)
        part = arr[s::n_shards]
        out[:part.shape[0]] = part
        return out

    return ShardedTable(
        lo_tok=np.stack([_piece(lo_tok, s, 1)
                         for s in range(n_shards)]),
        hi_tok=np.stack([_piece(hi_tok, s, 1)
                         for s in range(n_shards)]),
        flags=np.stack([_piece(flags, s, 0)
                        for s in range(n_shards)]),
        # residue ids; kept for shape compatibility and diagnostics
        row_offset=np.arange(n_shards, dtype=np.int64),
        row_len=np.asarray(lens, dtype=np.int64),
    )


def shard_table(table: AdvisoryTable, n_shards: int) -> ShardedTable:
    """Round-robin (strided) row sharding: shard s holds global rows
    r with r % S == s at local index r // S.

    Any contiguous global interval — a query's bucket — then maps to
    a CONTIGUOUS local range on every shard, so per-query work spreads
    ~evenly across the db axis no matter how skewed the bucket sizes
    are. Contiguous range-sharding measured a 30:1 per-device pair
    imbalance at 100k queries against a `linux`-style mega bucket
    (95% of pair volume landing in one shard); strided sharding makes
    that workload balance by construction."""
    return shard_arrays(table.lo_tok, table.hi_tok, table.flags,
                        n_shards)


def sharded_shiftor_scan(mesh: Mesh, kw_words, kw_masks,
                         chunks: np.ndarray, n_words: int) -> np.ndarray:
    """Secret keyword engine sharded over EVERY mesh device: chunk
    rows split across the flattened dp×db axes, the (tiny) multi-word
    keyword bank replicated. The exact shift-or scan is embarrassingly
    parallel over rows, so GSPMD partitions the already-jitted
    ac.shiftor_scan from the input shardings alone — no collectives,
    no shard_map — and the secrets lane rides the same mesh (and
    meshguard fault domains, via the engine's breaker-guarded watch)
    as the advisory join. → int32[rows, n_words] exact keyword
    bitmasks in row order (SURVEY.md §2.7 P2)."""
    from jax.sharding import NamedSharding

    from ..ops import ac
    n = int(mesh.devices.size)
    rows = chunks.shape[0]
    pad_rows = -(-rows // n) * n
    if pad_rows != rows:
        padded = np.zeros((pad_rows, chunks.shape[1]), chunks.dtype)
        padded[:rows] = chunks
        chunks = padded
    row_sharded = NamedSharding(mesh, P(("dp", "db")))
    replicated = NamedSharding(mesh, P())
    if isinstance(kw_words, np.ndarray):  # callers may pre-replicate
        kw_words = jax.device_put(kw_words, replicated)
    if isinstance(kw_masks, np.ndarray):
        kw_masks = jax.device_put(kw_masks, replicated)
    out = ac.shiftor_scan(
        kw_words, kw_masks, jax.device_put(chunks, row_sharded),
        n_words=n_words)
    # lazy slice: stays on device so per-piece calls keep pipelining
    return out[:rows]


class MeshDetector:
    """BatchDetector whose device step runs sharded over a mesh — the
    server-side scale-out path (SURVEY.md §2.7 P4).

    Exposes the scheduler surface (`_prepare`/`dispatch_merged`/
    `fetch_merged`/`_assemble`/`_get_pool`/`detect_many`) so detectd
    (detect/sched.py) routes coalesced dispatches through the mesh
    unchanged, and the server's swap_table generation drain can swap
    a shrunk/grown MeshDetector exactly like a single-chip one.

    meshguard (per-device fault domains): pass `guard` (a
    resilience.MeshGuard over this mesh's device ids) and every
    dispatch probes each active device's `detect.mesh:<id>` site under
    that device's own watchdog/breaker. A faulted domain serves THIS
    dispatch from the bit-identical host join and schedules a shrink
    rebuild; the mesh keeps serving from the survivors once the owner
    swaps it. `mesh=None` is the zero-survivor degraded mode: every
    dispatch is the host join until a readmission grows the mesh back.
    """

    def __init__(self, table: AdvisoryTable, mesh: Mesh | None,
                 db_shards: int | None = None, guard=None,
                 compact: bool = True, hit_floor: int = 128,
                 hit_align: int = 128, stream=None):
        from ..detect.engine import BatchDetector
        self.mesh = mesh
        self.table = table
        self.guard = guard
        # compaction knobs ride the inner engine: its hit-capacity
        # policy sizes the PER-CELL hit buffers here too
        self._inner = BatchDetector(table, compact=compact,
                                    hit_floor=hit_floor,
                                    hit_align=hit_align)
        # graftfeed capability marker (detectd keys on it): merged
        # dispatches accept a dedup plan and partition only the
        # UNIQUE query set over the mesh
        self.dedup = self._inner.dedup
        self._stream_prefetch = bool(stream is not None
                                     and stream.prefetch)
        # graftstream (stream=StreamOptions): when the PER-DEVICE
        # share of the sharded table (whole device footprint ÷ db
        # width) exceeds the budget, the table streams through a
        # double-buffered resident slice pair instead of living on
        # device whole — None / within-budget keeps the resident path
        # byte-for-byte unchanged
        self._stream_bounds = None
        self._slice_cache = None
        if mesh is None:
            # host-only degraded mode (meshguard: survivors below
            # --mesh-min-devices): no shard, no upload, no device ids
            self.dp = 0
            self.st = None
            self._st_dev = None
            self.device_ids = []
            return
        self.dp = mesh.devices.shape[0]
        db = db_shards if db_shards is not None else mesh.devices.shape[1]
        self.db = db
        self.device_ids = [int(d.id) for d in mesh.devices.flat]
        if stream is not None:
            from .stream import SliceCache, plan_slices
            self._stream_bounds = plan_slices(
                table, stream,
                device_bytes=-(-table.device_nbytes() // max(db, 1)))
            if self._stream_bounds is not None:
                # uniform per-slice shard pad: one compiled sharded-
                # join program family for the whole stream
                max_rows = int(np.diff(self._stream_bounds).max())
                self._shard_pad = max(1, -(-max_rows // db))
                self._stream_resident = max(stream.resident, 2)
                self._slice_cache = SliceCache(
                    self._upload_mesh_slice,
                    capacity=self._stream_resident, site="mesh")
                # sharded HOST stacks per slice, built once: steady-
                # state walks re-upload evicted slices constantly, and
                # re-running the shard_arrays restack on every upload
                # would run serially inside the dispatch watch (the
                # StreamingDetector host-copy rationale; costs ≤ ~1×
                # the device column bytes of host RAM)
                self._host_slices: dict[int, ShardedTable] = {}
                self._host_lock = threading.Lock()
                # partition metadata only (row_offset fixes the db
                # width); the real slice arrays live in the cache
                self.st = ShardedTable(
                    lo_tok=None, hi_tok=None, flags=None,
                    row_offset=np.arange(db, dtype=np.int64),
                    row_len=np.zeros(db, dtype=np.int64))
                self._st_dev = None
                from ..obs.perf import LEDGER
                per_slice = (self._shard_pad * db
                             * self._slice_row_bytes())
                LEDGER.note_resident(
                    "advisory_slice_resident",
                    per_slice * min(max(stream.resident, 2),
                                    self._stream_bounds.size - 1))
                return
        # re-shard the advisory table for THIS mesh's db width — the
        # meshguard rebuild path gets table re-sharding for free by
        # constructing a fresh detector over the survivor mesh
        self.st = shard_table(table, db)
        # upload the sharded table once, each db shard straight to the
        # devices of its db column; every detect() reuses the device
        # copies, so a dispatch moves only the query descriptors
        on_db = NamedSharding(mesh, P("db"))
        self._st_dev = ShardedTable(
            lo_tok=jax.device_put(self.st.lo_tok, on_db),
            hi_tok=jax.device_put(self.st.hi_tok, on_db),
            flags=jax.device_put(self.st.flags, on_db),
            row_offset=self.st.row_offset, row_len=self.st.row_len)

    def _slice_row_bytes(self) -> int:
        t = self.table
        return int(t.lo_tok.dtype.itemsize * t.lo_tok.shape[1] * 2
                   + t.flags.dtype.itemsize)

    def _host_mesh_slice(self, k: int) -> ShardedTable:
        """Sharded host stacks for hash-range slice k, built once
        (uniform shard pad across slices — see __init__)."""
        with self._host_lock:
            st = self._host_slices.get(k)
            if st is None:
                t = self.table
                b = self._stream_bounds
                r0, r1 = int(b[k]), int(b[k + 1])
                st = shard_arrays(t.lo_tok[r0:r1], t.hi_tok[r0:r1],
                                  t.flags[r0:r1], self.db,
                                  pad=self._shard_pad)
                self._host_slices[k] = st
            return st

    def _upload_mesh_slice(self, k: int):
        """Ship slice k's (cached) sharded host stacks — the
        graftstream SliceCache upload hook."""
        st = self._host_mesh_slice(k)
        on_db = NamedSharding(self.mesh, P("db"))
        arrays = (jax.device_put(st.lo_tok, on_db),
                  jax.device_put(st.hi_tok, on_db),
                  jax.device_put(st.flags, on_db))
        nbytes = st.lo_tok.nbytes + st.hi_tok.nbytes + st.flags.nbytes
        return arrays, nbytes

    def close(self) -> None:
        """Join the inner engine's worker threads and drop any
        resident stream slices (idempotent)."""
        if self._slice_cache is not None:
            self._slice_cache.drop_all()
        self._inner.close()

    # ---- scheduler surface (detectd routes through these) --------------

    @property
    def _get_pool(self):
        return self._inner._get_pool

    def _prepare(self, queries):
        return self._inner._prepare(queries)

    def _assemble(self, prep, bits):
        return self._inner._assemble(prep, bits)

    def fetch_merged(self, dev, preps, offsets, t_pad):
        # mesh joins are synchronous: `dev` is already host bits and
        # passes straight through the inner fetch
        return self._inner.fetch_merged(dev, preps, offsets, t_pad)

    def warmup(self, max_pairs: int = 1 << 18) -> int:
        """Near-no-op: mesh dispatch shapes depend on the per-cell
        pair partition, which the host-side LPT balancing decides per
        batch — there is no fixed ladder to pre-compile. A streamed
        mesh pre-touches its first resident slice pair so the first
        request's walk starts warm."""
        if self._slice_cache is not None:
            for k in range(min(self._stream_resident,
                               self._stream_bounds.size - 1)):
                self._slice_cache.prefetch(k)
        return 0

    def prefetch_ranges(self, q_start, q_count) -> list[int]:
        """graftfeed admission-aware prefetch, mesh edition: warm the
        stream slices detectd's queued-request peek says the NEXT
        dispatch will touch. No-op on a resident (unstreamed) mesh —
        the whole table is already device-side. → issued slice
        indices."""
        if self._slice_cache is None or not self._stream_prefetch:
            return []
        from .stream import touched_slices
        resident = set(self._slice_cache.resident())
        issued: list[int] = []
        for k in touched_slices(self._stream_bounds, q_start,
                                q_count):
            if k in resident:
                continue
            self._slice_cache.prefetch(k)
            issued.append(k)
            if len(issued) >= self._slice_cache.capacity:
                break
        return issued

    def dispatch_merged(self, preps, plan=_feed.PLAN_AUTO):
        """ONE mesh dispatch covering several prepared batches (the
        detectd coalescing primitive, mesh edition). Concatenated CSR
        descriptors partition and join exactly like one bigger batch,
        so each prep's slice is bit-identical to its solo dispatch.
        With dedup engaged (graftfeed), only the UNIQUE query triples
        partition over the mesh and the host scatter-back restores
        the full merged pair space. Returns (bits, per-prep offsets,
        t_pad) in FULL merged space — bits are host-side already
        (sharded_csr_join fetches synchronously)."""
        from ..obs import note_dispatch, span
        inner = self._inner
        merged, plan, launch = inner._plan_and_launch_args(preps, plan)
        _qs, _qc, _qv, offsets, total, t_pad, u_pad = merged
        ls, lc, lv, l_total, l_tpad = launch

        if plan is not None:
            def host_fallback():
                # same unique set as the device partition (h_cap=0:
                # dense unique-space bits; expand_bits handles either)
                return inner._host_join_csr(ls, lc, lv, l_total,
                                            l_tpad, h_cap=0)
        else:
            def host_fallback():
                return inner._host_bits_merged(preps, offsets, t_pad)

        if self.dedup or plan is not None:
            _feed.note_dedup_ratio(l_total, total)
        with span("detect.dispatch", n_pairs=total, t_pad=t_pad,
                  merged=len(preps), deduped=plan is not None):
            bits = self._launch_mesh(
                ls, lc, lv, l_total, l_tpad, u_pad, host_fallback,
                fallback_counts_slo=plan is not None)
            if plan is not None:
                bits = _feed.expand_bits(plan, bits, t_pad)
        note_dispatch()
        return bits, offsets, t_pad

    # ---- supervised mesh launch ----------------------------------------

    def _launch_mesh(self, q_start, q_count, q_ver, total: int,
                     t_pad: int, u_pad: int, host_fallback,
                     fallback_counts_slo: bool = False):
        """Partition the descriptors over the mesh and run the sharded
        join under graftguard + meshguard supervision. → int8[t_pad]
        host bits (identical whichever path served them).

        Fault-domain order: (1) host-only/zero-survivor mode, the open
        backend breaker, and a mesh that still contains a lost device
        (the pre-swap drain window) all serve from the host join
        without touching a device; (2) per-device domain probes run
        OUTSIDE the backend watch, so a wedged device trips only its
        own breaker; (3) the collective launch runs under the backend
        `detect.dispatch` watch — a whole-launch failure names no
        single chip."""
        from ..log import get as _get_logger
        from ..obs import SLO
        from ..obs import cost as _cost
        from ..resilience import GUARD, DeviceError, failpoint
        inner = self._inner
        raw_fallback = host_fallback

        def host_fallback():
            # one bad device_serving event per mesh DISPATCH served
            # host-side (the inner _host_bits* helpers intentionally
            # do not observe — a merged rebuild would multiply one
            # fault by the coalesce factor; _host_join_csr counts its
            # own, hence fallback_counts_slo)
            if not fallback_counts_slo:
                SLO.observe_join(False)
            return raw_fallback()

        if self.mesh is None or \
                (self.guard is not None
                 and self.guard.any_lost(self.device_ids)):
            return host_fallback()
        # domain probes BEFORE consulting the backend breaker: a
        # MeshDomainError exit charges only the device's own breaker
        # and must never happen between allow_device() admitting the
        # backend's half-open probe and the watch that resolves it —
        # an unresolved admitted probe wedges the breaker half-open
        # forever (the PR 4 dead-backend lesson)
        if self.guard is not None:
            try:
                self.guard.check(self.device_ids)
            except DeviceError:
                _get_logger("mesh").warning(
                    "mesh domain probe failed; host-fallback join",
                    exc_info=True)
                return host_fallback()
        # host-side routing BEFORE allow_device (the half-open-probe
        # rule below): the resident path partitions the whole dispatch
        # over the mesh; the streamed path clips it to the hash-range
        # slices it touches and partitions per slice
        part = plans = parts = None
        if self._stream_bounds is not None:
            from .stream import clip_descriptors
            plans = clip_descriptors(self._stream_bounds, q_start,
                                     q_count, q_ver)
            if not plans:
                out = np.zeros(t_pad, np.int8)
                return out
            parts = [partition_queries(self.st, p.q_start, p.q_count,
                                       p.q_ver, self.dp)
                     for p in plans]
        else:
            part = partition_queries(self.st, q_start, q_count, q_ver,
                                     self.dp)
        # allow_device() LAST, immediately before the watch: when it
        # admits the half-open probe, the watch's exit is guaranteed
        # to record the probe's outcome (success, error, or timeout)
        if not GUARD.allow_device():
            return host_fallback()
        try:
            # version-pool upload inside the watch: a dead backend
            # fails right there, and the probe outcome must be
            # recorded or the breaker wedges half-open. Unlike the
            # single-chip launch, sharded_csr_join fetches its result
            # synchronously, so a clean exit here IS execution success
            # (record_success stays on)
            with GUARD.watch("detect.dispatch"):
                failpoint("detect.dispatch")
                # the inner detector's cached device pool (re-shipped
                # only on growth) doubles as the replicated mesh
                # operand
                ver_dev = inner._ver_device(u_pad)
                # same ledger contract as the single-chip _launch: a
                # blameless caller (redetectd sweep replay) re-tags
                # itself so background refresh never muddies the live
                # mesh-occupancy story
                site = "redetect" if GUARD.blameless_active() \
                    else "mesh"
                if plans is not None:
                    # graftstream: walk the touched slices through the
                    # double-buffered resident set — upload of slice
                    # s+1 rides alongside the sharded join on slice s
                    h_loc = 0
                    bits, hit_notes = self._walk_mesh_slices(
                        plans, parts, ver_dev, total, t_pad, site)
                else:
                    # per-dispatch accounting (occupancy vs the mesh's
                    # total padded cell capacity, batch/compile
                    # counters) — the mesh path launches its own join
                    # and would otherwise go dark on the series the
                    # single-chip dispatch path emits; traffic counts
                    # only after the join actually completed
                    t_total = int(part.t_loc) \
                        * int(part.valid.shape[0]) \
                        * int(part.valid.shape[1])
                    # per-CELL hit buffers, sized by the inner
                    # engine's hit-capacity policy over the cell pair
                    # capacity (the hit rung is part of the compiled
                    # shape)
                    h_loc = inner._hit_capacity(part.t_loc)

                    def _join():
                        if h_loc:
                            return sharded_csr_join_compact(
                                self.mesh, self._st_dev, ver_dev,
                                part, total, h_loc)
                        return sharded_csr_join(
                            self.mesh, self._st_dev, ver_dev, part,
                            total), 0
                    # shared synchronous-site accounting (stream.py):
                    # compile bookkeeping + the ledger dispatch row
                    from .stream import ledgered_sync_join
                    bits, max_cell_hits = ledgered_sync_join(
                        inner, _join, site, total, t_total,
                        int(part.q_start.shape[-1]),
                        int(ver_dev.shape[0]), h_loc, mesh=True)
                    inner._account_traffic(total, t_total)
        except DeviceError:
            _get_logger("mesh").warning(
                "sharded join failed; host-fallback join",
                exc_info=True)
            # a COLLECTIVE failure names no chip — ask the coordinator
            # to run per-device attribution probes off the hot path,
            # so a real (non-injected) dead device still gets expelled
            # and the mesh shrinks instead of riding the backend
            # breaker into full host fallback. (Domain-probe faults
            # attributed themselves in the check() handler above.)
            if self.guard is not None:
                self.guard.request_attribution()
            return host_fallback()
        if plans is not None:
            # streamed: bits is already the merged global result and
            # per-slice transfers were noted in the walk; adapt the
            # hit budget from each slice's worst cell
            for n_h, h_cap_k, t_total_k in hit_notes:
                inner._note_hits(n_h, h_cap_k, site=site,
                                 t_pad=t_total_k)
            return bits
        if h_loc:
            # adapt the shared hit budget on the WORST cell — overflow
            # is per-cell, so the fullest buffer decides the next rung
            inner._note_hits(max_cell_hits, h_loc, site=site,
                             t_pad=t_total)
        if isinstance(bits, CompactBits):
            _cost.ledgered_transfer("compact",
                                    float(bits.pair_idx.nbytes
                                          + bits.bits.nbytes))
            # hits already in global pair order; extend the logical
            # dense length to the padded dispatch size downstream
            # slicing expects
            return CompactBits(bits.pair_idx, bits.bits, t_pad)
        _cost.ledgered_transfer("dense", float(bits.nbytes))
        out = np.zeros(t_pad, np.int8)
        out[:total] = bits
        return out

    def _walk_mesh_slices(self, plans, parts, ver_dev, total: int,
                          t_pad: int, site: str):
        """The graftstream slice walk, mesh edition (runs inside the
        dispatch watch): each touched hash-range slice's db-sharded
        arrays come off the double-buffered resident set (the NEXT
        slice's upload is prefetched before this slice's collective
        launches), the per-slice sharded join runs exactly like a
        resident dispatch, and the slice results concat-merge into one
        global result bit-identical to the unstreamed join.
        → (merged bits, [(max cell hits, h_cap, t_total)] notes)."""
        from ..obs import cost as _cost
        from .stream import ledgered_sync_join, merge_slice_bits
        inner = self._inner
        results: list = []
        hit_notes: list = []
        t_total_sum = 0
        for i, (plan, part) in enumerate(zip(plans, parts)):
            dev = self._slice_cache.get(plan.idx)
            if i + 1 < len(plans):
                self._slice_cache.prefetch(plans[i + 1].idx)
            st = ShardedTable(dev[0], dev[1], dev[2],
                              self.st.row_offset, self.st.row_len)
            t_total = int(part.t_loc) * int(part.valid.shape[0]) \
                * int(part.valid.shape[1])
            t_total_sum += t_total
            h_loc = inner._hit_capacity(part.t_loc)

            def _join():
                if h_loc:
                    return sharded_csr_join_compact(
                        self.mesh, st, ver_dev, part, plan.total,
                        h_loc)
                return sharded_csr_join(self.mesh, st, ver_dev, part,
                                        plan.total), 0
            bits_k, max_hits = ledgered_sync_join(
                inner, _join, site, plan.total, t_total,
                int(part.q_start.shape[-1]), int(ver_dev.shape[0]),
                h_loc, mesh=True)
            if h_loc:
                hit_notes.append((max_hits, h_loc, t_total))
            if isinstance(bits_k, CompactBits):
                _cost.ledgered_transfer("compact",
                                        float(bits_k.pair_idx.nbytes
                                              + bits_k.bits.nbytes))
            else:
                _cost.ledgered_transfer(
                    "dense", float(np.asarray(bits_k).nbytes))
            results.append((plan, bits_k))
        # tail prefetch: the next dispatch over the same hash span
        # starts back at the walk's first slice — ship it into the
        # just-freed buffer before that dispatch needs it
        if len(plans) > 1 or \
                plans[0].idx not in self._slice_cache.resident():
            self._slice_cache.prefetch(plans[0].idx)
        # ONE traffic observation per logical mesh dispatch; the
        # ledger above carries the per-slice collective launches
        inner._account_traffic(total, t_total_sum)
        return merge_slice_bits(results, t_pad), hit_notes

    def _bits(self, prep) -> np.ndarray:
        inner = self._inner
        return self._launch_mesh(
            prep.q_start, prep.q_count, prep.q_ver, prep.n_pairs,
            int(prep.pair_row.shape[0]), prep.u_pad,
            lambda: inner._host_bits(prep))

    # ---- direct detection ----------------------------------------------

    def detect_many(self, batches) -> list:
        """Per-batch prep → sharded join → assemble. The mesh join is
        synchronous (its result gather IS the fetch), so there is no
        async window to pipeline — the server gets its overlap from
        detectd coalescing on top of this surface instead."""
        from ..metrics import METRICS
        inner = self._inner
        out = []
        n_queries = n_pairs = n_hits = 0
        for qs in batches:
            if not qs or len(inner.table) == 0:
                out.append([])
                continue
            n_queries += len(qs)
            prep = inner._prepare(qs)
            if prep is None or prep.n_pairs == 0:
                out.append([])
                continue
            n_pairs += prep.n_pairs
            hits = inner._assemble(prep, self._bits(prep))
            n_hits += len(hits)
            out.append(hits)
        METRICS.inc("trivy_tpu_detect_queries_total", n_queries)
        METRICS.inc("trivy_tpu_detect_pairs_total", n_pairs)
        METRICS.inc("trivy_tpu_detect_hits_total", n_hits)
        return out

    def detect(self, queries) -> list:
        return self.detect_many([queries])[0]


# ---- CSR query partitioning (transfer O(queries), like the
# single-chip csr_pair_join) ------------------------------------------

@dataclass
class QueryPartition:
    """Queries routed to (dp, db) devices as CSR descriptors. Strided
    table sharding gives every db shard a contiguous local slice of
    each query's bucket, so routing emits ≤S descriptors per query and
    the devices expand their own pair lists — multi-chip transfer
    stays O(queries · S), matching
    the single-chip csr_pair_join design."""
    q_start: np.ndarray   # int32[DP, S, Q_loc] shard-LOCAL bucket start
    q_count: np.ndarray   # int32[DP, S, Q_loc]
    q_ver: np.ndarray     # int32[DP, S, Q_loc]
    total: np.ndarray     # int32[DP, S] true pair count per cell
    perm: np.ndarray      # int64[DP, S, T_loc] global pair index
    valid: np.ndarray     # bool [DP, S, T_loc]
    t_loc: int            # static per-cell pair capacity


def partition_queries(st: ShardedTable, q_start: np.ndarray,
                      q_count: np.ndarray, q_ver: np.ndarray,
                      dp: int, floor: int = 128,
                      q_floor: int = 64) -> QueryPartition:
    """Route queries (global bucket starts/counts) to their table shard
    and LPT-balance each shard's work across dp by PAIR count.

    A CSR descriptor is just (start, count, version), so an oversized
    bucket splits into several descriptors with adjusted starts — the
    real trivy-db's skew (one bucket with thousands of rows) spreads
    across the dp axis instead of stacking one device."""
    nz = q_count > 0
    starts = q_start[nz].astype(np.int64)
    counts = q_count[nz].astype(np.int64)
    vers = q_ver[nz]
    # global pair offsets follow _prepare's expansion order
    g_off = np.zeros(starts.size + 1, np.int64)
    np.cumsum(counts, out=g_off[1:])
    s_count = st.row_offset.shape[0]
    # strided sharding (shard_table): shard s holds global rows with
    # r % S == s at local index r // S, so a query's interval [a, b)
    # lands on shard s as the CONTIGUOUS local range starting at
    # r0 // S with ceil((b - r0) / S) rows, r0 = first row ≥ a with
    # the right residue. The piece's pairs map back to global offsets
    # base + (r0 - a) + j*S — perm carries that stride
    pieces: list[list] = []
    ends = starts + counts
    bases = g_off[:-1]
    for s in range(s_count):
        r0 = starts + ((s - starts) % s_count)
        m = r0 < ends
        cnt = (ends[m] - r0[m] + s_count - 1) // s_count
        pieces.append(list(zip(
            (r0[m] // s_count).tolist(), cnt.tolist(),
            vers[m].tolist(), (bases[m] + (r0[m] - starts[m]))
            .tolist())))
    # work items: (shard-local start, count, ver, global pair offset);
    # buckets larger than the per-device fair share split into chunks
    assign: dict[tuple, list] = {}
    for s in range(s_count):
        shard_pairs = sum(p[1] for p in pieces[s])
        cap = max(-(-shard_pairs // dp), 1)
        items = []
        for local_start, cnt, ver, goff in pieces[s]:
            remaining = cnt
            off = 0
            while remaining > 0:
                k = min(remaining, cap)
                items.append((local_start + off, k, ver,
                              goff + off * s_count))
                off += k
                remaining -= k
        # LPT: biggest items first onto the least-loaded dp slot
        items.sort(key=lambda it: -it[1])
        loads = [0] * dp
        cells = [[] for _ in range(dp)]
        for it in items:
            d = loads.index(min(loads))
            cells[d].append(it)
            loads[d] += it[1]
        for d in range(dp):
            assign[(d, s)] = cells[d]
    q_loc = q_floor
    t_loc = floor
    for cell in assign.values():
        q_loc = max(q_loc, _next_pow2(len(cell), q_floor))
        pairs = sum(it[1] for it in cell)
        t_loc = max(t_loc, _next_pow2(pairs, floor))
    qs = np.zeros((dp, s_count, q_loc), np.int32)
    qc = np.zeros((dp, s_count, q_loc), np.int32)
    qv = np.zeros((dp, s_count, q_loc), np.int32)
    total = np.zeros((dp, s_count), np.int32)
    perm = np.zeros((dp, s_count, t_loc), np.int64)
    valid = np.zeros((dp, s_count, t_loc), bool)
    for (d, s), cell in assign.items():
        off = 0
        for i, (lstart, k, ver, goff) in enumerate(cell):
            qs[d, s, i] = lstart
            qc[d, s, i] = k
            qv[d, s, i] = ver
            # strided global pair offsets (see piece construction)
            perm[d, s, off:off + k] = np.arange(
                goff, goff + k * s_count, s_count)
            valid[d, s, off:off + k] = True
            off += k
        total[d, s] = off
    return QueryPartition(qs, qc, qv, total, perm, valid, t_loc)


@functools.partial(jax.jit, static_argnames=("mesh", "t_pad"))
# lint: allow(TPU114) reason=the static Mesh argument is not expressible in the contract grammar; the csr_pair_join contract covers the per-shard local() body this wraps
def _sharded_csr_join(mesh: Mesh, adv_lo, adv_hi, adv_flags, ver_tok,
                      qs, qc, qv, total, t_pad: int):
    def local(adv_lo, adv_hi, adv_flags, ver_tok, qs, qc, qv, total):
        # jax tracks varying-manual-axes (VMA): the replicated version
        # pool must be cast to varying before it meets the per-device
        # descriptors
        ver_tok = jax.lax.pcast(ver_tok, ("dp", "db"), to="varying")
        bits = J._csr_core(adv_lo[0], adv_hi[0], adv_flags[0], ver_tok,
                           qs[0, 0], qc[0, 0], qv[0, 0], total[0, 0],
                           t_pad)
        return bits[None, None]

    f = shard_map(
        local, mesh=mesh,
        in_specs=(P("db"), P("db"), P("db"), P(),
                  P("dp", "db"), P("dp", "db"), P("dp", "db"),
                  P("dp", "db")),
        out_specs=P("dp", "db"),
    )
    return f(adv_lo, adv_hi, adv_flags, ver_tok, qs, qc, qv, total)


def _cell_descriptors(mesh: Mesh, part: QueryPartition) -> list:
    """The partition's [DP, S, ...] query descriptors, each cell's
    slice placed straight on that cell's device."""
    cells = NamedSharding(mesh, P("dp", "db"))
    return [jax.device_put(a, cells) for a in
            (part.q_start, part.q_count, part.q_ver, part.total)]


def sharded_csr_join(mesh: Mesh, st, ver_tok, part: QueryPartition,
                     n_pairs: int) -> np.ndarray:
    """CSR variant of sharded_pair_join: ships [DP, S, Q_loc]
    descriptors, devices expand pairs locally. → int8[n_pairs] bits in
    the caller's original pair order."""
    from ..metrics import METRICS
    bits = jax.device_get(_sharded_csr_join(
        mesh, jnp.asarray(st.lo_tok), jnp.asarray(st.hi_tok),
        jnp.asarray(st.flags), jnp.asarray(ver_tok),
        *_cell_descriptors(mesh, part),
        part.t_loc))
    METRICS.inc("trivy_tpu_detect_transfer_bytes_total",
                float(bits.nbytes), path="dense")
    out = np.zeros(n_pairs, np.int8)
    v = part.valid
    out[part.perm[v]] = bits[v]
    return out


@functools.partial(jax.jit, static_argnames=("mesh", "t_pad", "h_cap"))
# lint: allow(TPU114) reason=the static Mesh argument is not expressible in the contract grammar; the csr_pair_join_compact contract covers the per-shard local() body this wraps
def _sharded_csr_join_compact(mesh: Mesh, adv_lo, adv_hi, adv_flags,
                              ver_tok, qs, qc, qv, total, t_pad: int,
                              h_cap: int):
    def local(adv_lo, adv_hi, adv_flags, ver_tok, qs, qc, qv, total):
        ver_tok = jax.lax.pcast(ver_tok, ("dp", "db"), to="varying")
        bits = J._csr_core(adv_lo[0], adv_hi[0], adv_flags[0], ver_tok,
                           qs[0, 0], qc[0, 0], qv[0, 0], total[0, 0],
                           t_pad)
        # per-cell compaction epilogue: each device emits only ITS
        # hits; the dense cell bits stay on device for the checked
        # overflow fetch
        hit_idx, hit_bits, n_hits = J._compact_core(bits, h_cap)
        return (hit_idx[None, None], hit_bits[None, None],
                n_hits[None, None], bits[None, None])

    f = shard_map(
        local, mesh=mesh,
        in_specs=(P("db"), P("db"), P("db"), P(),
                  P("dp", "db"), P("dp", "db"), P("dp", "db"),
                  P("dp", "db")),
        out_specs=(P("dp", "db"), P("dp", "db"), P("dp", "db"),
                   P("dp", "db")),
    )
    return f(adv_lo, adv_hi, adv_flags, ver_tok, qs, qc, qv, total)


def sharded_csr_join_compact(mesh: Mesh, st, ver_tok,
                             part: QueryPartition, n_pairs: int,
                             h_cap: int):
    """Compact variant of sharded_csr_join: each mesh cell emits only
    its (local hit position, bits) list plus a count — the
    device→host transfer is O(cells × hit capacity), not O(cells ×
    t_loc). The host maps cell-local hit positions through part.perm
    to global pair indices and concatenates the shard hit lists into
    one CompactBits in ascending pair order. Any cell overflowing its
    buffer falls back to the dense fetch for the WHOLE dispatch (the
    cell bits stayed on device), so results are bit-identical by
    construction either way.

    → (CompactBits | dense int8[n_pairs], max per-cell hit count)."""
    from ..metrics import METRICS
    out = _sharded_csr_join_compact(
        mesh, jnp.asarray(st.lo_tok), jnp.asarray(st.hi_tok),
        jnp.asarray(st.flags), jnp.asarray(ver_tok),
        *_cell_descriptors(mesh, part),
        part.t_loc, h_cap)
    hit_idx, hit_bits, n_hits = jax.device_get(out[:3])
    METRICS.inc("trivy_tpu_detect_transfer_bytes_total",
                float(hit_idx.nbytes + hit_bits.nbytes + n_hits.nbytes),
                path="compact")
    max_hits = int(n_hits.max(initial=0))
    if max_hits > h_cap:
        bits = jax.device_get(out[3])
        METRICS.inc("trivy_tpu_detect_transfer_bytes_total",
                    float(bits.nbytes), path="dense")
        dense = np.zeros(n_pairs, np.int8)
        v = part.valid
        dense[part.perm[v]] = bits[v]
        return dense, max_hits
    gidx: list = []
    gbits: list = []
    dp, s_count = n_hits.shape
    for d in range(dp):
        for s in range(s_count):
            k = int(n_hits[d, s])
            if not k:
                continue
            gidx.append(part.perm[d, s][hit_idx[d, s, :k]])
            gbits.append(hit_bits[d, s, :k])
    if not gidx:
        return CompactBits(np.zeros(0, np.int32),
                           np.zeros(0, np.int8), n_pairs), max_hits
    gi = np.concatenate(gidx)
    gb = np.concatenate(gbits)
    # strided perm interleaves the cells' global indices — restore the
    # caller's ascending pair order (host-side; the device epilogue
    # stays sort-free)
    order = np.argsort(gi, kind="stable")
    return CompactBits(gi[order].astype(np.int32), gb[order],
                       n_pairs), max_hits
