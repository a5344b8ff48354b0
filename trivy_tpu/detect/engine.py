"""BatchDetector: host orchestration around ops.join.pair_join.

Pipeline per batch (SURVEY.md §7 step 3):
  host:  queries are encoded against two memo pools — unique
         (ecosystem, version) → token-vector row, unique (source, name) →
         fnv1a64 — so a registry sweep re-encodes nothing; the bucket of
         every query is located with one vectorized np.searchsorted pair
         over the table's sorted uint64 hashes, and queries with empty
         buckets (most packages) are dropped before any device work. The
         remaining buckets expand to a flat candidate-pair list
         (np.repeat — no per-query Python loop anywhere on the hot path);
  device: one pair_join call → 2-bit report per candidate pair;
  host:  numpy group-by over the reported pairs — package-name
         verification (hash-collision guard), positive minus negative
         polarity per advisory group, exact re-check of INEXACT rows.

The reference evaluates the same predicate one package at a time
(pkg/detector/ospkg/alpine/alpine.go:86-117, library/driver.go:111-136).
"""

from __future__ import annotations

import contextvars
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, NamedTuple, Optional

import numpy as np

from .. import compile_cache
from .. import version as V
from ..db.table import AdvisoryTable
from ..log import get as _get_logger
from ..metrics import METRICS
from ..obs import SLO, note_dispatch, span
from ..obs import cost as _cost
from ..obs.perf import LEDGER, stamp_table_resident
from ..ops import bucket_ladder, bucket_size
from ..ops import join as J
from ..ops import next_pow2 as _next_pow2
from ..resilience import GUARD, DeviceError, failpoint
from ..resilience.hostjoin import (
    CompactBits, host_csr_pair_join, host_csr_pair_join_compact,
    host_pair_join,
)
from . import feed as _feed

_log = _get_logger("detect")

# hit-budget bounds for the compaction epilogue: the fraction of a
# dispatch's padded pairs the hit buffer is sized for, adapted by
# powers of two from observed occupancy so the (pair-rung × hit-rung)
# shape set stays bounded (every distinct pair is one XLA compile).
# Budgets ≥ 1/8 are the dense regime (a 5-byte hit slot can't beat the
# 1-byte dense fetch past t_pad/8); MAX sits one doubling above it as
# hysteresis, and the dense-streak recovery in _hit_capacity walks the
# budget back down so a transient hit-dense burst can't disable
# compaction for the rest of the process
_HIT_BUDGET_INIT = 1.0 / 32
_HIT_BUDGET_MIN = 1.0 / 1024
_HIT_BUDGET_MAX = 0.25
# consecutive <25%-full hit buffers before the budget halves — one
# quiet dispatch must not shrink the buffer under bursty hit rates —
# and, symmetrically, consecutive budget-disabled dense dispatches
# before a halving retries compaction
_HIT_LOW_STREAK = 8


class _PendingCompact(NamedTuple):
    """One in-flight compacted dispatch: device refs for the O(hits)
    hit buffers plus the dense bits, which stay ON DEVICE and are
    fetched only when n_hits overflowed the buffer (the checked
    fallback that keeps results bit-identical by construction)."""
    hit_idx: Any
    hit_bits: Any
    n_hits: Any
    dense: Any
    h_cap: int
    t_pad: int
    site: str = "detect"   # graftprof ledger attribution for the fetch


class _StagedMerged(NamedTuple):
    """One stage_merged result: the merged descriptors, the resolved
    dedup plan, the launch-shaped (possibly unique-collapsed) columns,
    and their staged device upload — everything dispatch_merged needs
    to replay the stage without recomputing or re-uploading."""
    merged: tuple
    plan: Any
    launch: tuple
    queries: Any   # feed.StagedQueries


def slice_bits(bits, off: int, n: int):
    """One request's [off, off+n) window of a merged dispatch result:
    dense ndarray bits slice directly; compacted bits recover the
    window with one searchsorted over the sorted hit indices
    (CompactBits.slice) — still bit-identical to serial by
    construction, detectd's merged-dispatch contract."""
    if isinstance(bits, CompactBits):
        return bits.slice(off, n)
    return bits[off:off + n]




@dataclass(slots=True)
class PkgQuery:
    source: str      # advisory bucket, e.g. "alpine 3.9"
    ecosystem: str   # version scheme key
    name: str        # join name (src package name for OS pkgs)
    version: str     # installed version (formatted, e.g. epoch:ver-rel)
    arch: str = ""   # for arch-scoped advisories (Rocky/Alma entries)
    cpe_indices: frozenset = frozenset()  # Red Hat content-set scope
    ref: Any = None  # caller's package object


class Hit(NamedTuple):
    """One detected (package, advisory-group) match. A NamedTuple, not
    a dataclass: dense batches assemble ~100k of these per 512-image
    batch and tuple.__new__ via map() is ~3× cheaper than a dataclass
    __init__ — construction was the assembly hot spot."""
    query: PkgQuery
    vuln_id: str
    fixed_version: str
    status: str
    severity: str
    data_source: Optional[dict]
    vendor_ids: tuple


@dataclass
class _Prepared:
    """Host-side product of _prepare: the candidate-pair list."""
    usable: list          # [(PkgQuery, exact_version: bool)]
    pair_q: np.ndarray    # int64[T] index into usable per pair
    pair_row: np.ndarray  # int32[T_pad] advisory row per pair
    pair_ver: np.ndarray  # int32[T_pad] version-pool row per pair
    n_pairs: int          # T (pairs beyond are padding)
    u_pad: int            # version-pool rows to ship (power of two)
    # CSR descriptors for device-side pair expansion (_dispatch ships
    # these [Q]-sized arrays instead of the [T_pad] expansion above —
    # the expansion stays host-side only for _assemble)
    q_start: np.ndarray = None   # int32[Q_pad] bucket start per query
    q_count: np.ndarray = None   # int32[Q_pad] bucket length per query
    q_ver: np.ndarray = None     # int32[Q_pad] version row per query
    n_queries: int = 0    # real (nonzero-bucket) queries in q_* arrays;
    # rows beyond are zero-count padding — a coalesced dispatch
    # (dispatch_merged) concatenates only the real prefixes, because an
    # interior zero count would shift every later CSR segment
    # per-prep verification columns, built ONCE here: _assemble used to
    # rebuild these object arrays from `usable` on every call —
    # including merged-dispatch re-assembles of the same prep
    q_name: np.ndarray = None    # object[len(usable)] join names
    q_source: np.ndarray = None  # object[len(usable)] advisory sources
    q_exact: np.ndarray = None   # bool[len(usable)] exact-version keys
    q_obj: np.ndarray = None     # object[len(usable)] the PkgQuery objs


class BatchDetector:
    def __init__(self, table: AdvisoryTable, pair_floor: int = 256,
                 pair_growth: float = 2.0,
                 max_pairs_in_flight: int = 1 << 22,
                 assemble_workers: int = 2, compact: bool = True,
                 hit_floor: int = 128, hit_align: int = 128,
                 dedup: bool = True):
        import threading
        self.table = table
        self.pair_floor = pair_floor
        # graftfeed: collapse duplicate query triples in merged
        # dispatches (detect/feed.py); also the capability marker
        # detectd keys on — a detector without the attribute gets the
        # legacy dispatch_merged(preps) call
        self.dedup = dedup
        # geometric bucket ladder for padded dispatch shapes; 2.0 with
        # a pow2 floor reproduces the legacy next_pow2 policy exactly
        self.pair_growth = pair_growth
        # device-side hit compaction: dispatches big enough for the
        # hit buffer to beat the dense fetch ship only (pair_idx,
        # bits) hit pairs + a count back to the host (O(hits), not
        # O(padded pairs)); the buffer capacity is a bucket-ladder
        # rung of t_pad × _hit_budget
        self.compact = compact
        self.hit_floor = hit_floor
        self.hit_align = hit_align      # TPU lane width; tests shrink it
        self._hit_budget = _HIT_BUDGET_INIT
        self._hit_low_streak = 0
        self._hit_dense_streak = 0
        # pipeline backpressure: detect_many stops issuing dispatches
        # once this many padded pairs are in flight (bounds device
        # memory and keeps one giant scan from starving coalescing)
        self.max_pairs_in_flight = max_pairs_in_flight
        kw = table.lo_tok.shape[1] if len(table) else V.KEY_WIDTH
        # version pool: unique (eco, version) → row in _ver_mat
        self._ver_idx: dict[tuple[str, str], int] = {}
        self._ver_mat = np.zeros((256, kw), np.int32)
        self._ver_exact: list[bool] = []
        self._ver_count = 0
        self._ver_dev = None       # device snapshot of the pool
        self._ver_dev_rows = 0     # pool rows covered by the snapshot
        # hash pool: unique (source, name) → uint64
        self._hash_cache: dict[tuple[str, str], int] = {}
        # the detector is shared across server handler threads
        # (server/listen.py ThreadingHTTPServer): slot allocation and
        # pool growth are check-then-act and need the lock
        self._lock = threading.Lock()
        self._g_arrays = None
        self._g_arrays_len = -1
        self._g_cols = None
        self._g_cols_len = -1
        # dispatch shapes already seen by this process: a new key means
        # an XLA compile (the recompile counter the bucket ladder and
        # warmup exist to bound)
        self._seen_shapes: set = set()
        self._closed = False
        # single background thread for result fetches (detect_many and
        # the scheduler share it — one thread keeps transfers ordered);
        # created eagerly — lazy init would race across server threads
        from concurrent.futures import ThreadPoolExecutor
        self._get_pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="detect-get")
        # small worker pool for hit assembly, so batch N assembles
        # while batch N+1's result streams over the link
        self._asm_pool = ThreadPoolExecutor(
            max_workers=assemble_workers,
            thread_name_prefix="detect-asm")
        # graftprof memory telemetry: the table's columnar footprint —
        # whole-table AND per-column (AdvisoryTable.nbytes_by_column)
        # — re-stamped on every detector build (so a DB hot swap's
        # growth toward the HBM cliff is visible in /healthz, column
        # by column)
        stamp_table_resident(table)

    def close(self) -> None:
        """Join the engine's worker threads. Idempotent; the engine is
        unusable afterwards. Every owner that replaces a detector
        (ServerState.swap_table, server shutdown) must call this — the
        executors' threads are non-daemon and otherwise live until
        interpreter exit."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
        self._get_pool.shutdown(wait=True)
        self._asm_pool.shutdown(wait=True)

    # ---- memo pools ---------------------------------------------------

    def _ver_index(self, eco: str, ver: str) -> Optional[int]:
        ck = (eco, ver)
        idx = self._ver_idx.get(ck, -1)
        if idx != -1:
            return idx if idx is not None else None
        try:
            k = V.encode_version(eco, ver)
        except (ValueError, KeyError):
            # Reference skips packages whose installed version doesn't
            # parse (alpine.go:96-100 logs debug and continues).
            with self._lock:
                self._ver_idx[ck] = None
            return None
        from ..db.constraints import _NPM_ECOS, _has_prerelease
        if eco in _NPM_ECOS and _has_prerelease(ver):
            # node-semver prerelease rule: range satisfaction depends
            # on the constraint's comparators, which interval tokens
            # can't express — force the exact host recheck
            k.exact = False
        with self._lock:
            idx = self._ver_idx.get(ck, -1)
            if idx != -1:  # another thread won the slot
                return idx if idx is not None else None
            i = self._ver_count
            if i == self._ver_mat.shape[0]:
                self._ver_mat = np.concatenate(
                    [self._ver_mat, np.zeros_like(self._ver_mat)])
            self._ver_mat[i] = k.tokens
            self._ver_exact.append(k.exact)
            self._ver_count = i + 1
            self._ver_idx[ck] = i
        return i

    def _hashes(self, keys: list[tuple[str, str]]) -> np.ndarray:
        """→ uint64[len(keys)], batch-hashing cold keys natively."""
        cache = self._hash_cache
        cold = list({ck for ck in keys if ck not in cache})
        if cold:
            from ..native import fnv1a64_batch
            hv = fnv1a64_batch(
                [s.encode() + b"\x00" + n.encode() for s, n in cold])
            with self._lock:
                for ck, h in zip(cold, hv):
                    cache[ck] = int(h)
        return np.fromiter((cache[ck] for ck in keys),
                           dtype=np.uint64, count=len(keys))

    def ver_snapshot(self, u_pad: int | None = None) -> np.ndarray:
        """Padded host snapshot of the version pool (rows beyond the pool
        are zero and never referenced by pair_ver). Thread-safe: callers
        outside the lock (MeshDetector) get a consistent count/matrix."""
        with self._lock:
            return self._ver_snapshot_locked(u_pad)

    def _ver_snapshot_locked(self, u_pad: int | None = None) -> np.ndarray:
        rows = max(u_pad or 0, _next_pow2(self._ver_count))
        snap = np.zeros((rows, self._ver_mat.shape[1]), np.int32)
        snap[:self._ver_count] = self._ver_mat[:self._ver_count]
        return snap

    def _ver_device(self, u_pad: int):
        """Device snapshot of the version pool, re-shipped only when the
        pool outgrew the last upload."""
        import jax
        with self._lock:
            if self._ver_dev is None \
                    or self._ver_dev_rows < self._ver_count \
                    or self._ver_dev.shape[0] < u_pad:
                snap = self._ver_snapshot_locked(u_pad)
                # lint: allow(TPU111) reason=re-upload happens only when the pool outgrew the last transfer; the cached array and its row count must stay coherent under the lock
                self._ver_dev = jax.device_put(snap)
                self._ver_dev_rows = self._ver_count
                LEDGER.note_resident("version_pool", snap.nbytes)
            return self._ver_dev

    # ---- batch pipeline -----------------------------------------------

    def _prepare(self, queries: list[PkgQuery]) -> Optional[_Prepared]:
        """Instrumented shell around _prepare_impl: one graftscope span
        per batch. (The batch-occupancy histogram moved to the dispatch
        path — occupancy is a per-DISPATCH property, and a coalesced
        dispatch merges several prepared batches.)"""
        with span("detect.prepare", queries=len(queries)) as sp:
            prep = self._prepare_impl(queries)
            if prep is not None and prep.n_pairs:
                t_pad = int(prep.pair_row.shape[0])
                sp.attrs.update(n_pairs=prep.n_pairs, t_pad=t_pad,
                                pad_waste=t_pad - prep.n_pairs)
            return prep

    def _prepare_impl(self, queries: list[PkgQuery]) -> Optional[_Prepared]:
        t = self.table
        usable: list[tuple[PkgQuery, bool]] = []
        ver_rows: list[int] = []
        # warm-pool fast path: one dict probe per query, no method
        # call — registry sweeps hit the memo >99% of the time and the
        # per-query call overhead was a third of prepare
        ver_idx = self._ver_idx
        ver_exact = self._ver_exact
        for q in queries:
            vi = ver_idx.get((q.ecosystem, q.version), -1)
            if vi == -1:
                vi = self._ver_index(q.ecosystem, q.version)
            if vi is not None:
                usable.append((q, ver_exact[vi]))
                ver_rows.append(vi)
        if not usable:
            return None
        hashes = self._hashes([(q.source, q.name) for q, _ in usable])
        start = np.searchsorted(t.hash_u64, hashes, side="left")
        end = np.searchsorted(t.hash_u64, hashes, side="right")
        counts = end - start
        nz = np.nonzero(counts)[0]
        if nz.size == 0:
            return _Prepared(usable, np.zeros(0, np.int64),
                             np.zeros(0, np.int32), np.zeros(0, np.int32),
                             0, 0)
        counts_nz = counts[nz]
        offsets = np.zeros(nz.size + 1, np.int64)
        np.cumsum(counts_nz, out=offsets[1:])
        n_pairs = int(offsets[-1])
        pair_q = np.repeat(nz, counts_nz)
        pair_row = (np.arange(n_pairs, dtype=np.int64)
                    - np.repeat(offsets[:-1], counts_nz)
                    + np.repeat(start[nz], counts_nz)).astype(np.int32)
        ver_arr = np.asarray(ver_rows, np.int32)
        t_pad = bucket_size(n_pairs, self.pair_floor, self.pair_growth)
        row_p = np.zeros(t_pad, np.int32)
        row_p[:n_pairs] = pair_row
        ver_p = np.zeros(t_pad, np.int32)
        ver_p[:n_pairs] = ver_arr[pair_q]
        # CSR descriptors (padded with empty buckets; the device clamps
        # the tail segment so padding never contributes valid pairs)
        q_pad = bucket_size(nz.size, 64, self.pair_growth, align=64)
        q_start = np.zeros(q_pad, np.int32)
        q_start[:nz.size] = start[nz]
        q_count = np.zeros(q_pad, np.int32)
        q_count[:nz.size] = counts_nz
        # the device CSR expansion (ops/join._csr_core) scatters one
        # segment mark per nonzero bucket: an interior zero count
        # would silently shift every later segment
        assert counts_nz.min() > 0
        q_ver = np.zeros(q_pad, np.int32)
        q_ver[:nz.size] = ver_arr[nz]
        # verification columns, built once per prep (not per assemble:
        # a coalesced dispatch re-assembles the same prep under load)
        q_name = np.array([q.name for q, _ in usable], dtype=object)
        q_source = np.array([q.source for q, _ in usable], dtype=object)
        q_exact = np.fromiter((e for _, e in usable), bool,
                              count=len(usable))
        q_obj = np.empty(len(usable), dtype=object)
        q_obj[:] = [q for q, _ in usable]
        return _Prepared(usable, pair_q, row_p, ver_p, n_pairs,
                         _next_pow2(self._ver_count),
                         q_start=q_start, q_count=q_count, q_ver=q_ver,
                         n_queries=int(nz.size),
                         q_name=q_name, q_source=q_source,
                         q_exact=q_exact, q_obj=q_obj)

    def _dispatch(self, prep: _Prepared):
        """Instrumented shell around _dispatch_impl: spans the (async)
        launch and stamps the backend view /healthz serves."""
        with span("detect.dispatch", n_pairs=prep.n_pairs,
                  t_pad=int(prep.pair_row.shape[0])):
            out = self._dispatch_impl(prep)
        note_dispatch()
        return out

    def _note_shape(self, t_pad: int, q_pad: int, u_rows: int,
                    h_cap: int = 0) -> bool:
        """Compile accounting: a (t_pad, q_pad, ver-pool rows, table
        size, hit capacity) key this process has not dispatched before
        is a new XLA program — the hit-buffer rung is a static shape
        too, so a compact dispatch whose capacity rung moved counts as
        a fresh compile (h_cap=0 is the dense program). → whether the
        shape is new (the detect.compile failpoint keys off it). Runs
        BEFORE the launch — the compile happens whether or not the
        dispatch then fails."""
        key = (t_pad, q_pad, u_rows, len(self.table), h_cap)
        with self._lock:
            new_shape = key not in self._seen_shapes
            if new_shape:
                self._seen_shapes.add(key)
        if new_shape:
            METRICS.inc("trivy_tpu_detect_compiles_total")
        return new_shape

    def _hit_capacity(self, t_pad: int,
                      budget: float | None = None) -> int:
        """Hit-buffer rung for a t_pad-pair dispatch: the bucket-ladder
        rung covering t_pad × hit-budget (lane-aligned, floored).
        Returns 0 — dispatch dense — when compaction is off or the
        buffer could not beat the dense fetch anyway (a hit slot costs
        5 bytes vs 1 for a dense pair, so past t_pad/8 the compact
        transfer stops winning; small dispatches stay dense).

        Dense-regime recovery: _note_hits only fires on COMPACT
        fetches, so a budget pushed into the dense regime by an
        overflow burst would otherwise stay there forever (no compact
        dispatch ever observes the sparse occupancy that halves it).
        When the budget — not the dispatch geometry — is what keeps a
        dispatch dense, a streak counter walks the budget back down
        after _HIT_LOW_STREAK dense dispatches, so compaction is
        retried once the burst passes (at worst one overflow per
        streak window while the workload is genuinely hit-dense)."""
        if not self.compact:
            return 0
        adapt = budget is None
        if adapt:
            with self._lock:
                budget = self._hit_budget
        cap = bucket_size(max(int(t_pad * budget), self.hit_floor),
                          self.hit_floor, self.pair_growth,
                          align=self.hit_align)
        if cap * 8 < t_pad:
            if adapt:
                with self._lock:
                    self._hit_dense_streak = 0
            return cap
        # dense at this budget; count toward recovery only when a
        # smaller budget COULD engage at this t_pad (the floor rung
        # wins), i.e. the budget is the reason, not the geometry
        floor_cap = bucket_size(self.hit_floor, self.hit_floor,
                                self.pair_growth, align=self.hit_align)
        if adapt and budget > _HIT_BUDGET_MIN and floor_cap * 8 < t_pad:
            adapted = False
            with self._lock:
                self._hit_dense_streak += 1
                if self._hit_dense_streak >= _HIT_LOW_STREAK:
                    self._hit_budget = max(self._hit_budget / 2,
                                           _HIT_BUDGET_MIN)
                    self._hit_dense_streak = 0
                    adapted = True
            if adapted:
                LEDGER.note_budget_adapt("down")
        return 0

    def _note_hits(self, n_hits: int, h_cap: int,
                   site: str = "detect", t_pad: int = 0) -> None:
        """Adapt the hit budget from observed buffer occupancy, in
        powers of two so the compiled shape set stays bounded: an
        overflow (the dispatch fell back to the dense fetch) doubles
        it immediately; a sustained streak of <25%-full buffers halves
        it. Every compacted dispatch lands one occupancy observation —
        the overflow-fallback rate is the histogram's >1.0 mass.
        `site`/`t_pad` attribute the fill fraction and any adaptation
        to the graftprof ledger's shape row."""
        METRICS.observe("trivy_tpu_detect_hit_occupancy",
                        n_hits / h_cap)
        LEDGER.note_hits(site, t_pad, h_cap, n_hits)
        adapted = None
        with self._lock:
            if n_hits > h_cap:
                self._hit_budget = min(self._hit_budget * 2,
                                       _HIT_BUDGET_MAX)
                self._hit_low_streak = 0
                adapted = "up"
            elif n_hits * 4 <= h_cap:
                self._hit_low_streak += 1
                if self._hit_low_streak >= _HIT_LOW_STREAK:
                    self._hit_budget = max(self._hit_budget / 2,
                                           _HIT_BUDGET_MIN)
                    self._hit_low_streak = 0
                    adapted = "down"
            else:
                self._hit_low_streak = 0
        if adapted:
            LEDGER.note_budget_adapt(adapted)

    def _account_traffic(self, n_pairs: int, t_pad: int,
                         warm: bool = False) -> None:
        """Per-DISPATCH traffic metrics: one occupancy observation and
        one batch count per device launch (a coalesced dispatch
        covering N requests is still ONE dispatch). Called AFTER the
        launch is accepted, so failed dispatches that fell back to the
        host never inflate the device series (they count in
        trivy_tpu_fallback_joins_total instead, per the metric help).
        Warmup dispatches are compiles, not traffic — excluded."""
        if warm:
            return
        METRICS.inc("trivy_tpu_detect_batches_total")
        SLO.observe_join(True)
        if t_pad:
            METRICS.observe("trivy_tpu_batch_occupancy_ratio",
                            n_pairs / t_pad)

    def _host_join_csr(self, q_start: np.ndarray, q_count: np.ndarray,
                       q_ver: np.ndarray, total: int,
                       t_pad: int, h_cap: int = 0):
        """Host fallback for a CSR launch: the NumPy reference join
        over the same descriptors (graftguard degraded mode). With
        compaction off (h_cap=0) returns the int8[t_pad] bit vector a
        dense fetch would have; with it on, the NumPy compaction
        mirror emits the same CompactBits a compacted fetch would —
        either way callers downstream (_fetch_bits pass-through,
        _assemble, the scheduler's slice recovery) cannot tell the
        difference, and the bits are identical by the hostjoin
        contract. The overflow rule mirrors the device path exactly:
        n_hits past capacity serves the dense vector."""
        METRICS.inc("trivy_tpu_fallback_joins_total")
        SLO.observe_join(False)
        # the fallback join is a first-class trace phase (graftwatch):
        # a degraded-mode scan's time must be attributable, and the
        # incident drill asserts the fallback is VISIBLE in the
        # assembled trace, not inferred from a counter
        t0 = time.perf_counter()
        try:
            with span("detect.host_join", n_pairs=total, t_pad=t_pad):
                ver = self.ver_snapshot()
                t = self.table
                if h_cap:
                    hit_idx, hit_bits, n_hits, bits = \
                        host_csr_pair_join_compact(
                            t.lo_tok, t.hi_tok, t.flags, ver, q_start,
                            q_count, q_ver, total, t_pad, h_cap)
                    if n_hits <= h_cap:
                        return CompactBits(hit_idx[:n_hits],
                                           hit_bits[:n_hits], t_pad)
                    return bits
                return host_csr_pair_join(t.lo_tok, t.hi_tok, t.flags,
                                          ver, q_start, q_count, q_ver,
                                          total, t_pad)
        finally:
            # graftcost: degraded-mode joins bill host CPU ms (not
            # device ms), apportioned like the dispatch they replaced
            _cost.charge_host_ms((time.perf_counter() - t0) * 1e3)

    def _host_bits(self, prep: _Prepared) -> np.ndarray:
        """Host fallback from an already-expanded prep (used when the
        device accepted the dispatch but the FETCH failed: the pair
        expansion is still on the host, so recompute locally).

        SLO accounting lives with the CALLERS, not here: a merged
        rebuild invokes this once per prep, but the device_serving
        objective counts one bad event per DISPATCH resolution — the
        per-prep counting would overstate a single fetch failure by
        the coalesce factor and fire false burn-rate pages."""
        METRICS.inc("trivy_tpu_fallback_joins_total")
        t0 = time.perf_counter()
        with span("detect.host_join", n_pairs=prep.n_pairs):
            ver = self.ver_snapshot()
            t = self.table
            t_pad = int(prep.pair_row.shape[0])
            bits = np.zeros(t_pad, np.int8)
            n = prep.n_pairs
            bits[:n] = host_pair_join(
                t.lo_tok, t.hi_tok, t.flags, ver,
                prep.pair_row[:n], prep.pair_ver[:n], np.ones(n, bool))
        _cost.charge_host_ms((time.perf_counter() - t0) * 1e3)
        return bits

    def _launch(self, q_start: np.ndarray, q_count: np.ndarray,
                q_ver: np.ndarray, total: int, t_pad: int, u_pad: int,
                warm: bool = False, h_cap: int | None = None,
                site: str = "detect",
                staged: _feed.StagedQueries | None = None):
        """Ship CSR descriptors and launch the join (async).

        graftfeed: `staged` carries a pre-issued query-column upload
        (detectd stages dispatch i+1's columns while dispatch i
        computes); the launch then only waits for residency — the
        steady-state query_upload stall ≈ 0. Without one, the columns
        upload inline (the cold path, ledgered as such). A staging
        failure was already supervised and breaker-charged at stage
        time, so it degrades straight to the host join here.

        graftprof: `site` attributes the dispatch in the ledger
        ("detect" per-request, "detectd" via dispatch_merged); a
        launch issued under GUARD.blameless() — a redetectd sweep
        replay — re-tags itself "redetect" so background refresh
        traffic never muddies the live-occupancy story.

        Compaction: when the hit-capacity policy engages (h_cap > 0),
        the compact kernel runs instead and the return value is a
        _PendingCompact — device refs for the O(hits) hit buffers plus
        the dense bits the overflow path fetches. Callers resolve
        either shape through _fetch_bits.

        graftguard supervision: with the breaker open the device is
        never touched — the NumPy host join runs instead and its bits
        flow through the unchanged downstream (jax.device_get is a
        no-op on host arrays). Otherwise the dispatch runs under a
        watchdog deadline; a backend error or deadline expiry counts
        against the breaker and THIS launch falls back to the host, so
        the request completes either way with identical bits."""
        if h_cap is None:
            h_cap = self._hit_capacity(t_pad)
        if GUARD.blameless_active():
            site = "redetect"
        if staged is not None and staged.error is not None:
            _log.warning("staged query upload had failed; "
                         "host-fallback join")
            return self._host_join_csr(q_start, q_count, q_ver, total,
                                       t_pad, h_cap)
        if not GUARD.allow_device():
            return self._host_join_csr(q_start, q_count, q_ver, total,
                                       t_pad, h_cap)
        import jax
        try:
            t_watch = time.perf_counter()
            # the table/version-pool uploads live INSIDE the watch: on
            # a dead backend device_put is exactly where the failure
            # surfaces, and an unrecorded probe failure would wedge
            # the breaker in half-open forever (no probe ever resolves).
            # record_success=False: the launch is ASYNC — execution
            # success is only proven at the paired fetch
            # (_fetch_bits), which carries the success-recording watch
            with GUARD.watch("detect.dispatch", record_success=False):
                adv_lo, adv_hi, adv_flags = self.table.device_arrays()
                ver_dev = self._ver_device(u_pad)
                new_shape = self._note_shape(t_pad,
                                             int(q_start.shape[0]),
                                             int(ver_dev.shape[0]),
                                             h_cap)
                if new_shape:
                    failpoint("detect.compile")
                failpoint("detect.dispatch")
                if staged is not None and staged.refs is not None:
                    qs_dev, qc_dev, qv_dev = staged.take()
                else:
                    # cold: the upload runs inside the launch window
                    # (and, per-request, inside the dispatch watch, so
                    # a wedged one trips the same watchdog). device_put
                    # is async on real accelerators — the measured
                    # stall is issue time; the kernel pays residency
                    t_up = time.perf_counter()
                    qs_dev, qc_dev, qv_dev = _feed.upload_queries(
                        q_start, q_count, q_ver, prefetched=False)
                    LEDGER.note_shard_wait(
                        "query_upload",
                        (time.perf_counter() - t_up) * 1e3, cold=True)
                args = (adv_lo, adv_hi, adv_flags, ver_dev,
                        qs_dev, qc_dev, qv_dev, np.int32(total))

                def _kernel():
                    if h_cap:
                        hit_idx, hit_bits, n_hits, dense = \
                            J.csr_pair_join_compact(*args, t_pad, h_cap)
                        return _PendingCompact(hit_idx, hit_bits,
                                               n_hits, dense, h_cap,
                                               t_pad, site)
                    return J.csr_pair_join(*args, t_pad)

                if new_shape:
                    # a first-of-shape launch pays trace+lower+compile
                    # synchronously inside this call (dispatch itself
                    # is async and cheap): time it, span it so a
                    # mid-measurement compile shows up in Perfetto,
                    # and ledger it under the warmup/traffic phase
                    with span("detect.compile", t_pad=t_pad,
                              h_cap=h_cap, warm=warm), \
                            compile_cache.watch() as got:
                        t0 = time.perf_counter()
                        out = _kernel()
                        compile_ms = (time.perf_counter() - t0) * 1e3
                    LEDGER.note_compile(site, t_pad, h_cap,
                                        compile_ms, warm=warm,
                                        loaded=got.compiled == 0)
                else:
                    out = _kernel()
                self._account_traffic(total, t_pad, warm=warm)
                LEDGER.note_dispatch(site, total, t_pad, h_cap,
                                     warm=warm)
            # graftcost: the supervised launch region (uploads +
            # trace/compile + dispatch enqueue) is device-path wall
            # ms, apportioned by the context's share vector. Warm and
            # first-of-shape launches skip the EWMA feed — a compile's
            # ms-per-row is not an exchange rate
            _cost.charge_device_ms(
                site, (time.perf_counter() - t_watch) * 1e3,
                real_rows=0 if (warm or new_shape) else total)
            return out
        except DeviceError:
            # logged with the chained traceback: the first
            # fail_threshold-1 failures would otherwise be invisible,
            # and 'breaker opened after 3 failures' alone cannot tell a
            # code bug inside the watch from a real device outage
            _log.warning("device launch failed; host-fallback join",
                         exc_info=True)
            return self._host_join_csr(q_start, q_count, q_ver, total,
                                       t_pad, h_cap)

    # ---- supervised result fetch (graftguard) -------------------------

    def _fetch_bits(self, dev):
        """Device→host fetch under watchdog supervision. Host-fallback
        results (ndarrays / CompactBits from _host_join_csr) pass
        through without touching the device or the failpoints. A
        _PendingCompact fetches only the O(hits) hit buffers; the
        checked overflow path (n_hits > capacity) additionally fetches
        the dense bits retained on device, so results stay
        bit-identical by construction. Raises DeviceError/
        DeviceTimeout on a failed or wedged fetch."""
        if isinstance(dev, (np.ndarray, CompactBits)):
            return dev
        import jax
        if isinstance(dev, _PendingCompact):
            t0 = time.perf_counter()
            with GUARD.watch("detect.device_get"):
                failpoint("detect.device_get")
                hit_idx, hit_bits, n_hits = jax.device_get(
                    (dev.hit_idx, dev.hit_bits, dev.n_hits))
            # the fetch is the launch's sync point: its wall time is
            # compute + transfer, billed to the same site/shares
            _cost.charge_device_ms(
                dev.site, (time.perf_counter() - t0) * 1e3)
            n = int(n_hits)
            self._note_hits(n, dev.h_cap, site=dev.site,
                            t_pad=dev.t_pad)
            compact_bytes = float(hit_idx.nbytes + hit_bits.nbytes
                                  + n_hits.nbytes)
            METRICS.inc("trivy_tpu_detect_transfer_bytes_total",
                        compact_bytes, path="compact")
            _cost.ledgered_transfer("compact", compact_bytes)
            if n > dev.h_cap:
                # overflow: the buffer holds only a prefix of the
                # hits — this dispatch pays the dense fetch instead
                # (the budget already doubled for the next one)
                t0 = time.perf_counter()
                with GUARD.watch("detect.device_get"):
                    bits = jax.device_get(dev.dense)
                _cost.charge_device_ms(
                    dev.site, (time.perf_counter() - t0) * 1e3)
                METRICS.inc("trivy_tpu_detect_transfer_bytes_total",
                            float(bits.nbytes), path="dense")
                # ledger path "overflow": same bytes as a dense fetch,
                # but distinguishable — this transfer was paid ON TOP
                # of the wasted compact one
                _cost.ledgered_transfer("overflow", float(bits.nbytes))
                return bits
            return CompactBits(hit_idx[:n], hit_bits[:n], dev.t_pad)
        t0 = time.perf_counter()
        with GUARD.watch("detect.device_get"):
            failpoint("detect.device_get")
            out = jax.device_get(dev)
        _cost.charge_device_ms("detect",
                               (time.perf_counter() - t0) * 1e3)
        METRICS.inc("trivy_tpu_detect_transfer_bytes_total",
                    float(out.nbytes), path="dense")
        _cost.ledgered_transfer("dense", float(out.nbytes))
        return out

    def _fetch_or_fallback(self, prep: _Prepared, dev) -> np.ndarray:
        """Fetch one prep's bits; on a supervised failure recompute
        them on the host from the prep's own pair expansion — the
        request completes with identical bits either way. Runs on the
        get thread, under a detect.fetch span."""
        with span("detect.fetch", n_pairs=prep.n_pairs):
            try:
                return self._fetch_bits(dev)
            except DeviceError:
                _log.warning("device fetch failed; host-fallback join",
                             exc_info=True)
                # one bad device_serving event per dispatch RESOLUTION
                # (the launch already recorded its optimistic good)
                SLO.observe_join(False)
                return self._host_bits(prep)

    def _host_bits_merged(self, preps: list, offsets: list,
                          t_pad: int) -> np.ndarray:
        """Rebuild a merged dispatch's bit vector from each prep's
        host join (shared by the single-chip fetch fallback and the
        mesh launch fallback — the offset math must match
        _merge_descriptors in exactly one place)."""
        bits = np.zeros(t_pad, np.int8)
        for p, off in zip(preps, offsets):
            bits[off:off + p.n_pairs] = self._host_bits(p)[:p.n_pairs]
        return bits

    def fetch_merged(self, dev, preps: list, offsets: list,
                     t_pad: int) -> np.ndarray:
        """Fetch a merged (coalesced) dispatch's bits; on a supervised
        failure rebuild the merged bit vector from each prep's host
        join so every coalesced request still completes.

        graftfeed: a deduped dispatch (PendingExpand) fetches the
        unique-space result and scatters it back through the plan's
        index map; its fetch-failure rebuild runs the host join over
        the SAME unique descriptor set (then scatters identically) —
        the hostjoin contract survives dedup by construction. Runs on
        the get thread, under a detect.fetch span."""
        with span("detect.fetch", merged=len(preps)):
            if isinstance(dev, _feed.PendingExpand):
                try:
                    bits_u = self._fetch_bits(dev.dev)
                except DeviceError:
                    # _host_join_csr counts the one bad device_serving
                    # event itself (unlike the per-prep rebuild below)
                    _log.warning(
                        "merged device fetch failed; rebuilding the "
                        "unique-query join on the host", exc_info=True)
                    ls, lc, lv, l_total, l_tpad = dev.launch
                    bits_u = self._host_join_csr(ls, lc, lv, l_total,
                                                 l_tpad, h_cap=0)
                return _feed.expand_bits(dev.plan, bits_u, t_pad)
            try:
                return self._fetch_bits(dev)
            except DeviceError:
                _log.warning("merged device fetch failed; rebuilding %d "
                             "request slices on the host", len(preps),
                             exc_info=True)
                # ONE bad device_serving event for the whole merged
                # dispatch — the per-prep host rebuild below must not
                # multiply a single fetch failure by the coalesce factor
                SLO.observe_join(False)
                return self._host_bits_merged(preps, offsets, t_pad)

    def _dispatch_impl(self, prep: _Prepared):
        """Launch the pair join; returns the device array (async).

        Ships only the [Q]-sized CSR descriptors; the device expands
        them to the [T_pad] pair list (ops/join.py csr_pair_join).
        Shipping the host expansion instead costs ~9 bytes x T_pad per
        batch, which dominates scan time over a slow host<->device
        link."""
        return self._launch(prep.q_start, prep.q_count, prep.q_ver,
                            prep.n_pairs, int(prep.pair_row.shape[0]),
                            prep.u_pad)

    def _plan_and_launch_args(self, preps: list[_Prepared], plan):
        """Resolve the dedup plan and the launch-shaped descriptor set
        for one merged dispatch (shared by stage_merged and
        dispatch_merged so the two can never disagree on what ships).
        → (merged tuple, plan | None, (q_start, q_count, q_ver,
        total, t_pad) actually launched)."""
        merged = self._merge_descriptors(preps)
        q_start, q_count, q_ver, _offsets, total, t_pad, _u_pad = \
            merged
        if plan is _feed.PLAN_AUTO:
            plan = _feed.plan_merged(
                q_start, q_count, q_ver,
                [p.n_queries for p in preps]) if self.dedup else None
        if plan is not None:
            launch = _feed.padded_unique(plan, self.pair_floor,
                                         self.pair_growth)
        else:
            launch = (q_start, q_count, q_ver, total, t_pad)
        return merged, plan, launch

    def stage_merged(self, preps: list[_Prepared], plan=_feed.PLAN_AUTO):
        """graftfeed: merge + dedup-plan + pre-upload the query
        columns for a FUTURE dispatch_merged. detectd calls this
        before parking on backpressure, so dispatch i+1's H2D
        transfer rides dispatch i's device time; the result hands
        back into dispatch_merged(staged=...)."""
        merged, plan, launch = self._plan_and_launch_args(preps, plan)
        queries = _feed.stage_queries(launch[0], launch[1], launch[2])
        return _StagedMerged(merged, plan, launch, queries)

    def dispatch_merged(self, preps: list[_Prepared],
                        plan=_feed.PLAN_AUTO, staged=None):
        """ONE device dispatch covering several prepared batches — the
        coalescing primitive detectd (detect/sched.py) is built on.

        The CSR expansion treats concatenated descriptors exactly like
        one bigger batch: only the real (nonzero-count) prefix of each
        prep's q_* arrays is copied, because an interior zero-count
        query would shift every later segment (ops/join._csr_core).
        Each prep's pairs land contiguously in the merged bit vector,
        so the per-batch result slice is [off, off + n_pairs) and the
        ordinary _assemble over it is bit-identical to an uncoalesced
        dispatch by construction — the predicate is elementwise.

        graftfeed: with a dedup `plan` (PLAN_AUTO computes one when
        self.dedup), the join dispatches over the collapsed
        unique-query descriptors only and the fetch scatters the bits
        back through the plan's host-side index map — same contract,
        fewer real pairs. `staged` replays a stage_merged result (the
        double-buffered query upload); its merge/plan are reused
        verbatim.

        Returns (device bits, per-prep bit offsets, t_pad) — t_pad and
        the offsets stay in FULL merged pair space either way (the
        scheduler's in-flight accounting and slicing are dedup-blind)."""
        if staged is not None:
            merged, plan, launch = \
                staged.merged, staged.plan, staged.launch
            queries = staged.queries
        else:
            merged, plan, launch = self._plan_and_launch_args(preps,
                                                              plan)
            queries = None
        _qs, _qc, _qv, offsets, total, t_pad, u_pad = merged
        ls, lc, lv, l_total, l_tpad = launch
        if self.dedup or plan is not None:
            _feed.note_dedup_ratio(l_total if plan is not None
                                   else total, total)
        with span("detect.dispatch", n_pairs=total, t_pad=t_pad,
                  merged=len(preps), deduped=plan is not None):
            # site="detectd": a merged dispatch is ONE ledger row, so
            # the per-site sums reconcile with the batch counter
            # without double-counting the coalesced requests
            out = self._launch(ls, lc, lv, l_total, l_tpad,
                               u_pad, site="detectd", staged=queries)
        note_dispatch()
        if plan is not None:
            out = _feed.PendingExpand(out, plan,
                                      (ls, lc, lv, l_total, l_tpad))
        return out, offsets, t_pad

    def _merge_descriptors(self, preps: list[_Prepared]):
        """Concatenate several preps' real CSR prefixes into one
        descriptor set (shared by dispatch_merged and the mesh
        detector's merged dispatch). → (q_start, q_count, q_ver,
        offsets, total, t_pad, u_pad)."""
        total = sum(p.n_pairs for p in preps)
        q_n = sum(p.n_queries for p in preps)
        t_pad = bucket_size(total, self.pair_floor, self.pair_growth)
        q_pad = bucket_size(q_n, 64, self.pair_growth, align=64)
        q_start = np.zeros(q_pad, np.int32)
        q_count = np.zeros(q_pad, np.int32)
        q_ver = np.zeros(q_pad, np.int32)
        offsets = []
        pos = off = 0
        for p in preps:
            k = p.n_queries
            q_start[pos:pos + k] = p.q_start[:k]
            q_count[pos:pos + k] = p.q_count[:k]
            q_ver[pos:pos + k] = p.q_ver[:k]
            offsets.append(off)
            pos += k
            off += p.n_pairs
        # the shared version pool only grows; the max of the preps'
        # snapshots and the current count covers every pair_ver row
        u_pad = max(_next_pow2(self._ver_count),
                    max(p.u_pad for p in preps))
        return q_start, q_count, q_ver, offsets, total, t_pad, u_pad

    def warmup(self, max_pairs: int = 1 << 18) -> int:
        """Pre-compile the join across the pair-bucket ladder (server
        --detect-warmup): one empty dispatch per rung, so steady-state
        traffic reuses cached XLA programs instead of paying a compile
        on the first batch of each new size. With compaction on, each
        pair rung also pre-compiles its (pair-rung × hit-rung) compact
        programs: the policy capacity at the current budget, plus the
        rungs one budget-doubling up AND one halving down — the first
        shapes an occupancy adaptation in either direction (overflow,
        or the sparse-streak halving real-image traffic hits) would
        otherwise pay a first-request compile for. Bounds — not eliminates — recompiles: the version
        pool's growth and query-count buckets can still introduce new
        shapes. Returns the rung count."""
        if len(self.table) == 0:
            return 0
        import jax
        rungs = bucket_ladder(max_pairs, self.pair_floor,
                              self.pair_growth)
        u_pad = _next_pow2(max(self._ver_count, 1))
        with self._lock:
            budget = self._hit_budget
        done = []
        for t_pad in rungs:
            # representative query bucket: real workloads average a few
            # pairs per nonzero query, so warm the q_pad rung that a
            # t_pad-sized dispatch most often arrives with
            q_pad = bucket_size(max(t_pad // 8, 1), 64,
                                self.pair_growth, align=64)
            z = np.zeros(q_pad, np.int32)
            # policy h_cap (or dense when compaction can't win here)
            done.append(self._launch(z, z, z, 0, t_pad, u_pad,
                                     warm=True))
            here = self._hit_capacity(t_pad, budget=budget)
            warmed = {here}
            for adapted in (budget * 2, budget / 2):
                nxt = self._hit_capacity(t_pad, budget=adapted)
                if nxt and nxt not in warmed:
                    warmed.add(nxt)
                    done.append(self._launch(z, z, z, 0, t_pad, u_pad,
                                             warm=True, h_cap=nxt))
        jax.block_until_ready(done)
        return len(rungs)

    def detect(self, queries: list[PkgQuery]) -> list[Hit]:
        return self.detect_many([queries])[0]

    def detect_many(self, batches: list[list[PkgQuery]]) -> list[list[Hit]]:
        """Run every batch through the staged pipeline
        prep → dispatch → fetch → assemble.

        Each batch's dispatch is issued the moment its prep lands (the
        device no longer idles through the whole host-prep phase), the
        fetch streams on the shared get thread, and assembly runs on
        the small worker pool overlapped with later batches' transfers.
        In-flight dispatches are bounded by max_pairs_in_flight.
        Recording a trace runs this same pipeline: each stage's span
        times it where it runs (the fetch on the get thread, the
        handler's wait for it as detect.device_wait)."""
        if len(self.table) == 0:
            return [[] for _ in batches]
        out: list = [[] for _ in batches]
        window: deque = deque()   # (idx, prep, get_future) in order
        asm_futs: list = []       # (idx, assemble future)
        state = {"pairs": 0}
        n_queries = n_pairs_total = 0

        def drain_one():
            idx, prep, gf = window.popleft()
            t_get = time.perf_counter()
            try:
                with span("detect.device_wait", n_pairs=prep.n_pairs):
                    bits = gf.result()
            finally:
                # decrement even when the fetch raises — the entry is
                # already popped, so the outer cleanup can't see it
                METRICS.gauge_add("trivy_tpu_dispatch_depth", -1.0)
                state["pairs"] -= int(prep.pair_row.shape[0])
            METRICS.observe("trivy_tpu_device_get_stall_seconds",
                            time.perf_counter() - t_get)
            # copy_context: the assemble worker inherits this thread's
            # trace id / span parentage (graftscope is contextvar-based)
            ctx = contextvars.copy_context()
            asm_futs.append((idx, self._asm_pool.submit(
                ctx.run, self._assemble, prep, bits)))

        try:
            for idx, qs in enumerate(batches):
                if not qs:
                    continue
                n_queries += len(qs)
                prep = self._prepare(qs)
                if prep is None or prep.n_pairs == 0:
                    continue
                n_pairs_total += prep.n_pairs
                t_pad = int(prep.pair_row.shape[0])
                # backpressure: block on the oldest fetch until the
                # pair budget admits this dispatch
                while window and \
                        state["pairs"] + t_pad > self.max_pairs_in_flight:
                    drain_one()
                dev = self._dispatch(prep)
                METRICS.gauge_add("trivy_tpu_dispatch_depth", 1.0)
                state["pairs"] += t_pad
                # device_get, not np.asarray: asarray falls into the
                # generic __array__ element path on accelerator arrays
                # (~500x slower for the 512KB bit vectors); device_get
                # is one memcpy, on the get thread so batch N+1's
                # result streams while batch N assembles. The fetch is
                # graftguard-supervised: a wedged/failed get falls back
                # to the host join instead of sinking the batch.
                # copy_context: the get thread inherits this request's
                # trace id, so a fetch-failure fallback logs and spans
                # under the trace it serves, not as an orphan
                getctx = contextvars.copy_context()
                window.append((idx, prep,
                               self._get_pool.submit(
                                   getctx.run, self._fetch_or_fallback,
                                   prep, dev)))
                # opportunistic: hand finished fetches to assembly
                # without blocking the prep of the next batch
                while window and window[0][2].done():
                    drain_one()
            while window:
                drain_one()
        finally:
            # a batch that raises mid-loop must not leave the in-flight
            # gauge ratcheted up forever
            for _ in range(len(window)):
                METRICS.gauge_add("trivy_tpu_dispatch_depth", -1.0)
        for idx, f in asm_futs:
            out[idx] = f.result()
        METRICS.inc("trivy_tpu_detect_queries_total", n_queries)
        METRICS.inc("trivy_tpu_detect_pairs_total", n_pairs_total)
        METRICS.inc("trivy_tpu_detect_hits_total",
                    sum(len(h) for h in out))
        return out

    def _assemble(self, prep: _Prepared, bits: np.ndarray) -> list[Hit]:
        """Instrumented shell around _assemble_impl."""
        with span("detect.assemble", n_pairs=prep.n_pairs) as sp:
            hits = self._assemble_impl(prep, bits)
            sp.attrs["hits"] = len(hits)
            return hits

    def _assemble_impl(self, prep: _Prepared,
                       bits) -> list[Hit]:
        t = self.table
        if isinstance(bits, CompactBits):
            # compacted result: the hit indices ARE the keep set —
            # assembly is direct index lookups into the prep's pair
            # expansion, with no dense materialization and no host
            # nonzero (the r04 assemble hot spot)
            keep = bits.pair_idx
            b = bits.bits
        else:
            bits = bits[:prep.n_pairs]
            keep = np.nonzero(bits)[0]
            b = bits[keep]
        if keep.size == 0:
            return []
        rows = prep.pair_row[keep]
        qidx = prep.pair_q[keep]
        gids = t.group[rows]
        flags = t.flags[rows]
        sat = (b & J.SATISFIED) != 0
        neg = (flags & J.NEGATIVE) != 0
        inexact = (b & J.NEEDS_RECHECK) != 0

        # group-by (pkg query, advisory group) in numpy. Pairs come out
        # of the CSR expansion already sorted by (query, group): pair_q
        # is non-decreasing, rows within a bucket walk it in order, and
        # the table's stable hash lexsort keeps a bucket's rows in
        # group-append order — so segment boundaries fall out of one
        # diff, no argsort. (Guarded: a future table layout that broke
        # the invariant would silently corrupt polarity folding.)
        key = qidx.astype(np.int64) * (len(t.groups) + 1) + gids
        if key.size > 1 and not np.all(key[1:] >= key[:-1]):
            order = np.argsort(key, kind="stable")
            key, sat, neg, inexact = \
                key[order], sat[order], neg[order], inexact[order]
        seg_start = np.flatnonzero(
            np.concatenate(([True], key[1:] != key[:-1])))
        uniq = key[seg_start]
        pos_any = np.maximum.reduceat(sat & ~neg, seg_start)
        neg_any = np.maximum.reduceat(sat & neg, seg_start)
        inex_any = np.maximum.reduceat(inexact, seg_start)

        pkg_of = (uniq // (len(t.groups) + 1)).astype(np.int64)
        gid_of = (uniq % (len(t.groups) + 1)).astype(np.int64)

        # vectorized verification: the collision guard (name+source
        # equality) runs as two numpy object-array compares instead of
        # a Python loop over every (query, group) pair; only scoped
        # (arch/CPE) or inexact pairs take the slow per-item path.
        # On dense workloads (~45k reported groups per 256-image batch)
        # this is the difference between the assembly dominating the
        # device time and not. The per-prep columns were built once in
        # _prepare — a merged dispatch re-assembles the same prep.
        g_name, g_source, g_scoped = self._group_arrays()
        q_name = prep.q_name
        q_source = prep.q_source
        q_exact = prep.q_exact

        ok = (g_name[gid_of] == q_name[pkg_of]) & \
            (g_source[gid_of] == q_source[pkg_of])
        slow = ok & (g_scoped[gid_of] | inex_any | ~q_exact[pkg_of])
        fast = ok & ~slow & pos_any & ~neg_any

        usable = prep.usable
        groups = t.groups
        # fast path: all columns are fancy-indexed object arrays;
        # construction goes through the C slot tuple.__new__ directly
        # (namedtuple's Python-level __new__ costs ~1 µs/frame and was
        # the single largest assembly item at ~100k hits/batch)
        from itertools import repeat
        g_vuln, g_fix, g_status, g_sev, g_ds, g_vids = \
            self._group_cols()
        q_obj = prep.q_obj
        fsel = np.nonzero(fast)[0]
        gsel = gid_of[fsel]
        psel = pkg_of[fsel]
        hits: list[Hit] = list(map(tuple.__new__, repeat(Hit), zip(
            q_obj[psel].tolist(), g_vuln[gsel].tolist(),
            g_fix[gsel].tolist(), g_status[gsel].tolist(),
            g_sev[gsel].tolist(), g_ds[gsel].tolist(),
            g_vids[gsel].tolist())))
        for u in np.nonzero(slow)[0]:
            i = int(pkg_of[u])
            g = groups[int(gid_of[u])]
            q, ver_exact = usable[i]
            if g.arches and q.arch and q.arch not in g.arches:
                continue  # advisory scoped to other architectures
            if g.cpe_indices and not \
                    q.cpe_indices.intersection(g.cpe_indices):
                continue  # Red Hat: entry's CPEs outside content sets
            if inex_any[u] or not ver_exact:
                pos, negv = self._exact_eval(g, q)
            else:
                pos, negv = bool(pos_any[u]), bool(neg_any[u])
            if pos and not negv:
                hits.append(Hit(
                    query=q, vuln_id=g.vuln_id,
                    fixed_version=g.fixed_version, status=g.status,
                    severity=g.severity, data_source=g.data_source,
                    vendor_ids=g.vendor_ids))
        return hits

    def _group_arrays(self):
        """Cached per-table verification arrays (names, sources, and a
        scoped flag for arch/CPE-gated groups). Built under the lock —
        the detector is shared across server handler threads."""
        if self._g_arrays is None or \
                self._g_arrays_len != len(self.table.groups):
            with self._lock:
                if self._g_arrays is None or \
                        self._g_arrays_len != len(self.table.groups):
                    gs = self.table.groups
                    arrays = (
                        np.array([g.pkg_name for g in gs], dtype=object),
                        np.array([g.source for g in gs], dtype=object),
                        np.fromiter((bool(g.arches or g.cpe_indices)
                                     for g in gs), bool, count=len(gs)),
                    )
                    self._g_arrays = arrays
                    self._g_arrays_len = len(gs)
        return self._g_arrays

    def _group_cols(self):
        """Cached columnar group attributes for fast-path Hit
        construction (vuln_id, fixed_version, status, severity,
        data_source, vendor_ids as object arrays)."""
        if self._g_cols is None or \
                self._g_cols_len != len(self.table.groups):
            with self._lock:
                if self._g_cols is None or \
                        self._g_cols_len != len(self.table.groups):
                    gs = self.table.groups
                    n = len(gs)

                    def col(attr):
                        a = np.empty(n, dtype=object)
                        a[:] = [getattr(g, attr) for g in gs]
                        return a
                    self._g_cols = tuple(
                        col(a) for a in ("vuln_id", "fixed_version",
                                         "status", "severity",
                                         "data_source", "vendor_ids"))
                    self._g_cols_len = n
        return self._g_cols

    def _exact_eval(self, g, q: PkgQuery) -> tuple[bool, bool]:
        """Host fallback: evaluate the group's intervals with the exact
        comparator (used for inexact-keyed rows/packages). Groups whose
        constraint grammar wasn't interval-representable carry the raw
        spec strings instead and get the reference's full IsVulnerable
        semantics (compare.go:21-55)."""
        if g.raw_specs is not None:
            return self._raw_eval(g, q)
        pos = neg = False
        for positive, iv in g.rows:
            ok = True
            try:
                if iv.lo is not None:
                    c = V.compare(q.ecosystem, iv.lo, q.version)
                    ok &= c < 0 or (iv.lo_incl and c == 0)
                if ok and iv.hi is not None:
                    c = V.compare(q.ecosystem, q.version, iv.hi)
                    ok &= c < 0 or (iv.hi_incl and c == 0)
            except (ValueError, KeyError):
                ok = False
            if positive:
                pos = pos or ok
            else:
                neg = neg or ok
        return pos, neg

    def _raw_eval(self, g, q: PkgQuery) -> tuple[bool, bool]:
        """Reference IsVulnerable (compare.go:21-55) over raw constraint
        strings: empty member in vulnerable/patched lists ⇒ always
        detect; constraint errors ⇒ warn-equivalent no-match."""
        from ..db.constraints import eval_constraint
        vuln, patched, unaffected = g.raw_specs
        for spec in (vuln, patched):
            if spec and any(not b.strip() for b in spec.split("||")):
                return True, False
        if vuln:
            try:
                if not eval_constraint(q.ecosystem, vuln, q.version):
                    return False, False
            except (ValueError, KeyError):
                return False, False  # compare.go:33-38 warn → no match
        secure = " || ".join(s for s in (patched, unaffected) if s)
        if not secure:
            return bool(vuln), False
        try:
            return True, eval_constraint(q.ecosystem, secure, q.version)
        except (ValueError, KeyError):
            return False, False
