"""graftstream — double-buffered advisory-shard streaming for tables
larger than one device's memory budget.

`shard_table` (mesh.py) splits one table ACROSS devices; nothing so far
let a table exceed what a single device (or a single db shard) can hold
resident — the cap ROADMAP item 4 calls out, and the one that blocks
whole vulnerability-DB history and the ATVHunter/LibAM-scale
fingerprint corpora (arxiv 2102.08172, 2305.04026), which are the same
hash-sorted columnar join at 10–100× the rows.

The streaming move: split the logical `AdvisoryTable` into S contiguous
HASH-RANGE slices (the table is hash-sorted, so a row range IS a hash
range), keep a double-buffered resident set of `StreamOptions.resident`
(default 2) uploaded slices, and round-robin the table through it
between dispatches:

  * while the join kernel runs against slice s, the host→device upload
    of slice s+1 is already in flight on the second buffer, so the
    steady-state dispatch time is max(compute, transfer), not the sum;
  * because queries are located by the same hash order
    (`BatchDetector._prepare`'s searchsorted), each prepared CSR
    descriptor routes only to the slices its bucket interval overlaps —
    most dispatches touch 1–2 slices, not S (`clip_descriptors`);
  * per-slice results carry a slice-local→global pair map (`gmap`), so
    the merged bits — dense or `CompactBits` — are bit-identical to the
    single-shot unstreamed join by construction (the predicate is
    elementwise and every pair meets the same advisory row either way),
    parity-gated against the host oracle in tests/test_stream.py.

Slice planning (`plan_slices`) sizes S from a per-device byte budget:
an explicit `--table-stream-slices`, else `--table-device-budget-mb`,
else `budget_fraction` of the graftprof `hbm_bytes` limit view (LEDGER
memory telemetry). A table that fits the budget never engages — the
resident path stays byte-for-byte what it was.

Supervision is the mesh pattern: the whole slice walk runs under one
graftguard `detect.dispatch` watch; an open breaker, a launch error, or
a watchdog trip serves the dispatch from the NumPy host join over the
FULL table (host RAM is not the constraint — device memory is), so a
degraded streamed server answers bit-identically, only slower.

Everything here is host orchestration; the device code is the
unchanged `ops.join` kernels fed slice-shaped operands.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from dataclasses import dataclass

import numpy as np

from .. import compile_cache
from ..detect import feed as _feed
from ..log import get as _get_logger
from ..metrics import METRICS
from ..obs import SLO, note_dispatch, span
from ..obs import cost as _cost
from ..obs.perf import LEDGER
from ..resilience import GUARD, DeviceError, failpoint
from ..resilience.hostjoin import CompactBits

_log = _get_logger("stream")

_MiB = 1 << 20


@dataclass
class StreamOptions:
    """graftstream knobs (server flags --table-device-budget-mb,
    --table-stream-slices; flagcfg mesh.*). Streaming engages only
    when the table's device footprint exceeds the per-device budget
    (or `slices` forces a count); otherwise the resident path runs
    unchanged."""
    device_budget_mb: float = 0.0  # per-device byte budget for resident
    # advisory slices; 0 = auto from the graftprof hbm_bytes view
    slices: int = 0                # explicit slice-count override
    budget_fraction: float = 0.35  # auto budget = fraction × hbm limit
    # (leaves headroom for the version pool, dispatch operands, and the
    # transient third slice while an eviction's buffers drain)
    resident: int = 2              # double buffer: slices kept uploaded
    prefetch: bool = True          # graftfeed: honor admission-aware
    # prefetch_ranges() peeks from detectd (warm the slices the NEXT
    # dispatch's bucket ranges will touch); the in-walk double-buffer
    # prefetch is unconditional — it is the streaming design itself


def hbm_budget_bytes(fraction: float) -> int:
    """Auto per-device budget off graftprof's backend memory view:
    `fraction` of the smallest device's bytes_limit. The view is
    normally sampled (throttled) on the dispatch path; at detector
    BUILD time — before any dispatch — it can be empty, so an empty
    view forces one sample first (jax is about to ship the table
    anyway; sample_memory never raises). 0 when the backend exposes
    no memory stats (CPU) — streaming then engages only via an
    explicit budget or slice count."""
    def limits():
        backends = LEDGER.memory_status().get("backends") or {}
        return [b.get("bytes_limit", 0) for b in backends.values()
                if b.get("bytes_limit")]
    got = limits()
    if not got:
        LEDGER.sample_memory(force=True)
        got = limits()
    if not got:
        return 0
    return int(min(got) * fraction)


def plan_slices(table, opts: StreamOptions | None,
                device_bytes: int | None = None) -> np.ndarray | None:
    """→ int64[S+1] contiguous row bounds (equal hash-range slices of
    the sorted table), or None when streaming should not engage (the
    table fits the budget, or no budget source is configured).

    `device_bytes` overrides the footprint the budget is compared
    against — the mesh path passes its per-device share (the full
    device footprint ÷ db shards)."""
    if opts is None or len(table) == 0:
        return None
    dev_bytes = device_bytes if device_bytes is not None \
        else table.device_nbytes()
    if opts.slices > 0:
        n = opts.slices
    else:
        budget = int(opts.device_budget_mb * _MiB)
        if not budget:
            budget = hbm_budget_bytes(opts.budget_fraction)
        if not budget:
            return None
        per_slice = max(budget // max(opts.resident, 1), 1)
        n = -(-dev_bytes // per_slice)
    n = max(1, min(int(n), len(table)))
    if n <= 1:
        return None
    return slice_bounds(len(table), n)


def slice_bounds(n_rows: int, n_slices: int) -> np.ndarray:
    """Equal-row contiguous slice bounds over the hash-sorted table:
    int64[S+1] with bounds[k] ≤ bounds[k+1], covering [0, n_rows)."""
    return (np.arange(n_slices + 1, dtype=np.int64)
            * n_rows // n_slices)


# ---------------------------------------------------------------------------
# CSR descriptor routing: clip each query's bucket interval to the
# slices it overlaps

@dataclass
class SlicePlan:
    """One slice's share of a dispatch: slice-LOCAL CSR descriptors
    plus the map from slice-local pair offsets back to the dispatch's
    global pair index space (contiguous per clipped piece — both sides
    are hash-sorted, so a bucket's rows inside one slice are one
    contiguous run)."""
    idx: int
    q_start: np.ndarray   # int32[n] slice-local bucket starts
    q_count: np.ndarray   # int32[n]
    q_ver: np.ndarray     # int32[n]
    total: int            # true pair count in this slice
    gmap: np.ndarray      # int64[total] slice-local pair → global pair


def clip_descriptors(bounds: np.ndarray, q_start: np.ndarray,
                     q_count: np.ndarray,
                     q_ver: np.ndarray) -> list[SlicePlan]:
    """Route prepared CSR descriptors (global advisory-row intervals,
    zero-count padding allowed) to the hash-range slices they overlap.
    → SlicePlans for exactly the touched slices, in ascending slice
    order. The union of all plans' gmaps is a permutation of
    [0, total) — every global pair lands in exactly one slice."""
    nz = q_count > 0
    starts = q_start[nz].astype(np.int64)
    counts = q_count[nz].astype(np.int64)
    vers = q_ver[nz]
    g_off = np.zeros(starts.size + 1, np.int64)
    np.cumsum(counts, out=g_off[1:])
    ends = starts + counts
    plans: list[SlicePlan] = []
    if starts.size == 0:
        return plans
    # only slices the dispatch's hash span can touch: the descriptors
    # are not sorted by row (query order rules), so use min/max
    k_lo = int(np.searchsorted(bounds, starts.min(), "right")) - 1
    k_hi = int(np.searchsorted(bounds, ends.max() - 1, "right")) - 1
    for k in range(max(k_lo, 0), min(k_hi, bounds.size - 2) + 1):
        r0, r1 = int(bounds[k]), int(bounds[k + 1])
        lo = np.maximum(starts, r0)
        hi = np.minimum(ends, r1)
        m = lo < hi
        if not m.any():
            continue
        cnt = hi[m] - lo[m]
        goff = g_off[:-1][m] + (lo[m] - starts[m])
        total = int(cnt.sum())
        loff = np.zeros(cnt.size, np.int64)
        np.cumsum(cnt[:-1], out=loff[1:])
        gmap = np.repeat(goff - loff, cnt) \
            + np.arange(total, dtype=np.int64)
        plans.append(SlicePlan(
            idx=k, q_start=(lo[m] - r0).astype(np.int32),
            q_count=cnt.astype(np.int32), q_ver=vers[m],
            total=total, gmap=gmap))
    return plans


def touched_slices(bounds: np.ndarray, q_start: np.ndarray,
                   q_count: np.ndarray) -> list[int]:
    """Which hash-range slices would a dispatch over these CSR
    descriptors touch? The interval math is clip_descriptors' —
    per-query searchsorted of the bucket interval into the slice
    bounds — without materializing any SlicePlan, so detectd's
    admission peek (graftfeed prefetch) can ask cheaply for requests
    it has NOT merged yet. → ascending slice indices."""
    nz = q_count > 0
    if not nz.any():
        return []
    starts = q_start[nz].astype(np.int64)
    ends = starts + q_count[nz].astype(np.int64)
    n = int(bounds.size - 1)
    lo = np.clip(np.searchsorted(bounds, starts, "right") - 1,
                 0, n - 1)
    hi = np.clip(np.searchsorted(bounds, ends - 1, "right") - 1,
                 0, n - 1)
    mark = np.zeros(n, bool)
    for a, b in np.unique(np.stack([lo, hi], axis=1), axis=0):
        mark[int(a):int(b) + 1] = True
    return [int(k) for k in np.nonzero(mark)[0]]


def merge_slice_bits(results: list, t_pad: int):
    """Concat-merge per-slice results into ONE dispatch result in the
    caller's global pair order. `results` is [(SlicePlan, bits)] where
    bits is a dense int8 vector (slice-local, padded) or a slice-local
    CompactBits. All-compact inputs merge into one global CompactBits
    (one stable argsort — per-slice hit lists interleave across
    queries); any dense input materializes the global dense vector.
    Either shape is downstream-identical (slice_bits/_assemble)."""
    if any(not isinstance(b, CompactBits) for _p, b in results):
        out = np.zeros(t_pad, np.int8)
        for plan, bits in results:
            if isinstance(bits, CompactBits):
                out[plan.gmap[bits.pair_idx]] = bits.bits
            else:
                out[plan.gmap] = bits[:plan.total]
        return out
    gidx: list = []
    gbits: list = []
    for plan, cb in results:
        if cb.pair_idx.size:
            gidx.append(plan.gmap[cb.pair_idx])
            gbits.append(cb.bits)
    if not gidx:
        return CompactBits(np.zeros(0, np.int32),
                           np.zeros(0, np.int8), t_pad)
    gi = np.concatenate(gidx)
    gb = np.concatenate(gbits)
    order = np.argsort(gi, kind="stable")
    return CompactBits(gi[order].astype(np.int32), gb[order], t_pad)


def ledgered_sync_join(inner, run, site: str, real: int, t_total: int,
                       q_pad: int, u_rows: int, h_cap: int,
                       **span_attrs):
    """Shared per-launch accounting for the SYNCHRONOUS join sites —
    the streamed slice walks (single-chip and mesh) and the resident
    mesh join: compile bookkeeping (`_note_shape` → the
    `detect.compile` failpoint, a timed `detect.compile` span, and the
    ledger's compile row — a synchronous site's first-of-shape wall
    time is compile + one execution, the honest upper bound on what a
    mid-traffic compile costs a request) followed by the ledger
    dispatch row. One implementation so the ledger contract cannot
    drift between the three launch shapes (the PR 13 blameless re-tag
    fix had to patch two hand-synced copies). `run()` performs the
    launch + fetch and its return value passes through.

    graftcost rides the same seam: a synchronous site's `run()` wall
    time IS its device ms (launch + compute + fetch in one call), so
    one clock read feeds the shape ledger and the per-tenant
    apportionment — the conservation contract, in the one place all
    three launch shapes share."""
    new_shape = inner._note_shape(t_total, q_pad, u_rows, h_cap)
    t_run = time.perf_counter()
    if new_shape:
        failpoint("detect.compile")
        with span("detect.compile", t_pad=t_total, h_cap=h_cap,
                  **span_attrs), compile_cache.watch() as got:
            t0 = time.perf_counter()
            out = run()
            compile_ms = (time.perf_counter() - t0) * 1e3
        LEDGER.note_compile(site, t_total, h_cap, compile_ms,
                            loaded=got.compiled == 0)
    else:
        out = run()
    _cost.charge_device_ms(site, (time.perf_counter() - t_run) * 1e3,
                           real_rows=0 if new_shape else real)
    LEDGER.note_dispatch(site, real, t_total, h_cap)
    return out


# ---------------------------------------------------------------------------
# the double-buffered resident set

class _Entry:
    __slots__ = ("ready", "arrays", "error", "nbytes")

    def __init__(self):
        self.ready = threading.Event()
        self.arrays = None
        self.error: BaseException | None = None
        self.nbytes = 0


class SliceCache:
    """Double-buffered resident set of uploaded slices.

    `upload(k)` ships slice k's device operands (jax.device_put —
    async on real accelerators) and returns (pytree, nbytes shipped).
    `prefetch(k)` issues the upload without waiting; `get(k)` returns
    the resident operands, blocking until the upload lands — the block
    time is the dispatch's UPLOAD STALL, recorded per wait in the
    graftprof ledger (`shard_upload` rows) so the double-buffer
    overlap is an asserted property, not a hope: after the first slice
    of a walk, every wait hits a prefetched entry and stalls ≈ 0.

    Eviction is LRU over READY entries once the set exceeds
    `capacity`; an entry another thread is still uploading is never
    evicted. Lock discipline (TPU106): all shared-state mutation under
    `_lock`; uploads and blocking waits run outside it."""

    def __init__(self, upload, capacity: int = 2,
                 site: str = "stream"):
        self._upload = upload
        self.capacity = max(int(capacity), 2)
        self.site = site
        self._lock = threading.Lock()
        self._entries: OrderedDict[int, _Entry] = OrderedDict()

    def _admit(self, k: int):
        """→ (entry, owner): under the lock, find-or-create slice k's
        entry; `owner` means the caller must perform the upload."""
        with self._lock:
            e = self._entries.get(k)
            if e is not None:
                self._entries.move_to_end(k)
                return e, False
            e = _Entry()
            self._entries[k] = e
            # evict the least-recently-used READY entry; dropping the
            # last reference frees its device buffers (the walk keeps
            # its own reference to the slice it is computing on, so an
            # in-use slice survives its eviction until the launch ends)
            while len(self._entries) > self.capacity:
                victim = next((key for key, v in self._entries.items()
                               if key != k and v.ready.is_set()), None)
                if victim is None:
                    break
                del self._entries[victim]
            return e, True

    def _do_upload(self, k: int, e: _Entry, prefetched: bool) -> None:
        try:
            arrays, nbytes = self._upload(k)
            e.arrays = arrays
            e.nbytes = int(nbytes)
        except BaseException as exc:  # noqa: BLE001 — relayed to every
            # waiter; a failed upload must never wedge get() forever
            e.error = exc
            with self._lock:
                self._entries.pop(k, None)
            raise
        finally:
            e.ready.set()
        LEDGER.note_shard_upload(self.site, e.nbytes,
                                 prefetched=prefetched)

    def prefetch(self, k: int) -> None:
        """Issue slice k's upload without waiting (the double-buffer
        overlap: called while the PREVIOUS slice's join computes). A
        failed prefetch only logs — the paying get() retries it."""
        try:
            # fired BEFORE _admit, so a tripped prefetch leaves no
            # entry behind: the paying get() later re-admits and
            # uploads cold — the fault costs one un-overlapped upload
            # (latency), never a wedged or wrong entry (correctness)
            failpoint("stream.prefetch")
        except BaseException:  # noqa: BLE001 — latency-only by design
            _log.warning("slice %d prefetch failpoint tripped; the "
                         "dispatch uploads it cold", k)
            return
        e, owner = self._admit(k)
        if not owner:
            return
        try:
            self._do_upload(k, e, prefetched=True)
        except BaseException:  # noqa: BLE001
            _log.warning("slice %d prefetch failed; the dispatch "
                         "retries it cold", k, exc_info=True)

    def get(self, k: int):
        """Resident operands for slice k, uploading cold if needed.
        Blocks until the slice is device-ready; the blocked time is
        recorded as this wait's upload stall (cold = the upload itself
        ran inside the wait — the un-overlapped worst case)."""
        import jax
        t0 = time.perf_counter()
        e, owner = self._admit(k)
        if owner:
            self._do_upload(k, e, prefetched=False)
        else:
            e.ready.wait()
            if e.error is not None:
                raise DeviceError(
                    f"slice {k} upload failed: {e.error}") from e.error
        jax.block_until_ready(e.arrays)
        stall_ms = (time.perf_counter() - t0) * 1e3
        LEDGER.note_shard_wait(self.site, stall_ms, cold=owner)
        return e.arrays

    def drop_all(self) -> None:
        with self._lock:
            self._entries.clear()

    def resident(self) -> list[int]:
        with self._lock:
            return list(self._entries)


# ---------------------------------------------------------------------------
# the single-chip streaming detector

class StreamingDetector:
    """BatchDetector whose advisory table streams through a
    double-buffered resident slice pair instead of living on device
    whole — the larger-than-HBM path (ROADMAP item 4).

    Exposes the scheduler surface (`_prepare`/`dispatch_merged`/
    `fetch_merged`/`_assemble`/`_get_pool`/`detect_many`) so detectd
    routes coalesced dispatches through it unchanged — a coalesced
    chunk walks the touched slices ONCE, not once per request — and
    the server's swap_table generation drain swaps it like any other
    detector.

    Like the mesh path, dispatches resolve synchronously (the slice
    walk's final merge IS the fetch); pipelining comes from detectd
    coalescing on top, and from the upload/compute overlap inside each
    walk. graftguard: an open breaker or any supervised failure serves
    the dispatch from the NumPy host join over the FULL table — host
    RAM holds the whole table regardless; only device memory is
    budgeted."""

    def __init__(self, table, opts: StreamOptions | None = None,
                 bounds: np.ndarray | None = None,
                 compact: bool = True, hit_floor: int = 128,
                 hit_align: int = 128):
        from ..detect.engine import BatchDetector
        self.table = table
        self.opts = opts or StreamOptions()
        self._inner = BatchDetector(table, compact=compact,
                                    hit_floor=hit_floor,
                                    hit_align=hit_align)
        # graftfeed capability marker (detectd keys on it): merged
        # dispatches accept a dedup plan and walk the slices over the
        # UNIQUE query set only
        self.dedup = self._inner.dedup
        self.bounds = bounds if bounds is not None \
            else plan_slices(table, self.opts)
        if self.bounds is None:
            raise ValueError(
                "StreamingDetector: streaming did not engage (table "
                "fits the budget, or no budget configured) — use "
                "BatchDetector, or pass explicit bounds")
        self.n_slices = int(self.bounds.size - 1)
        # uniform padded slice-row count: ONE device array shape for
        # every slice, so the whole stream compiles one XLA program
        # family per (t_pad, q_pad, h_cap) rung instead of S
        self.rows_pad = max(1, int(np.diff(self.bounds).max()))
        self._cache = SliceCache(self._upload_slice,
                                 capacity=self.opts.resident)
        # padded HOST copies of each slice's columns, built once and
        # kept for the detector's lifetime: steady-state walks
        # re-upload evicted slices constantly, and re-padding (a
        # budget/2-sized memcpy) on every upload would run serially
        # inside the dispatch watch before the async device_put. Costs
        # ≤ ~1× the device column bytes of host RAM — host RAM holds
        # the whole table anyway; device memory is what's budgeted.
        self._host_slices: dict[int, tuple] = {}
        self._host_lock = threading.Lock()
        self.slice_nbytes = self.rows_pad * self._row_bytes()
        LEDGER.note_resident("advisory_slice_resident",
                             self.slice_nbytes
                             * min(self.opts.resident, self.n_slices))

    def _row_bytes(self) -> int:
        t = self.table
        return int(t.lo_tok.dtype.itemsize * t.lo_tok.shape[1] * 2
                   + t.flags.dtype.itemsize)

    def _host_slice(self, k: int) -> tuple:
        """Padded host columns for slice k, built once. Padding rows
        carry flags=0 (no bounds ⇒ the predicate is vacuously true)
        but no valid pair can ever reference them — clipped
        descriptors only cover real rows."""
        with self._host_lock:
            arrays = self._host_slices.get(k)
            if arrays is not None:
                return arrays
            t = self.table
            r0, r1 = int(self.bounds[k]), int(self.bounds[k + 1])
            n = r1 - r0
            kw = t.lo_tok.shape[1]
            lo = np.ones((self.rows_pad, kw), t.lo_tok.dtype)
            hi = np.ones((self.rows_pad, kw), t.hi_tok.dtype)
            fl = np.zeros(self.rows_pad, t.flags.dtype)
            lo[:n] = t.lo_tok[r0:r1]
            hi[:n] = t.hi_tok[r0:r1]
            fl[:n] = t.flags[r0:r1]
            arrays = (lo, hi, fl)
            self._host_slices[k] = arrays
            return arrays

    def _upload_slice(self, k: int):
        """Ship slice k's (cached) padded host columns — the
        SliceCache upload hook."""
        import jax
        lo, hi, fl = self._host_slice(k)
        arrays = tuple(jax.device_put(a) for a in (lo, hi, fl))
        return arrays, lo.nbytes + hi.nbytes + fl.nbytes

    def close(self) -> None:
        """Join the inner engine's workers and drop the resident
        slices (idempotent)."""
        self._cache.drop_all()
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
        # streamed joins resolve synchronously: `dev` is already host
        # bits and passes straight through the inner fetch
        return self._inner.fetch_merged(dev, preps, offsets, t_pad)

    def warmup(self, max_pairs: int = 1 << 18) -> int:
        """Pre-touch the stream: upload the first resident pair so the
        first request's walk starts warm. The join shapes themselves
        depend on per-slice clip geometry — no fixed ladder to
        pre-compile (the mesh warmup rationale)."""
        for k in range(min(self.opts.resident, self.n_slices)):
            self._cache.prefetch(k)
        return 0

    def prefetch_ranges(self, q_start: np.ndarray,
                        q_count: np.ndarray) -> list[int]:
        """graftfeed admission-aware prefetch: detectd peeks the
        requests still queued behind the round it just dispatched and
        hands their (unmerged) bucket ranges here; warm the slices
        that NEXT dispatch will touch while the device is busy.
        Advisory — failures cost a cold upload, never correctness.
        → the slice indices actually issued."""
        if not self.opts.prefetch:
            return []
        resident = set(self._cache.resident())
        issued: list[int] = []
        for k in touched_slices(self.bounds, q_start, q_count):
            if k in resident:
                continue
            self._cache.prefetch(k)
            issued.append(k)
            # never churn more than one resident set's worth — a peek
            # spanning the whole table must not evict what the CURRENT
            # walk still needs
            if len(issued) >= self._cache.capacity:
                break
        return issued

    def dispatch_merged(self, preps, plan=_feed.PLAN_AUTO):
        """ONE logical dispatch covering several prepared batches: the
        merged CSR descriptors walk the touched slices once, so N
        coalesced requests pay ONE pass over the resident set instead
        of N (the detectd coalescing contract, stream edition).
        With dedup engaged (graftfeed), the walk covers only the
        UNIQUE query triples and the host scatter-back expands the
        result to the full merged pair space — bit-identical by the
        plan's construction. Returns (bits, per-prep offsets, t_pad)
        in FULL merged space; bits are host-side already (the slice
        walk fetches synchronously)."""
        inner = self._inner
        merged, plan, launch = inner._plan_and_launch_args(preps, plan)
        _qs, _qc, _qv, offsets, total, t_pad, u_pad = merged
        ls, lc, lv, l_total, l_tpad = launch

        if plan is not None:
            def host_fallback():
                # same unique set as the device walk (h_cap=0: dense
                # bits — expand_bits handles either, this is simplest)
                return inner._host_join_csr(ls, lc, lv, l_total,
                                            l_tpad, h_cap=0)
        else:
            def host_fallback():
                return inner._host_bits_merged(preps, offsets, t_pad)

        if self.dedup or plan is not None:
            _feed.note_dedup_ratio(l_total, total)
        with span("detect.dispatch", n_pairs=total, t_pad=t_pad,
                  merged=len(preps), deduped=plan is not None):
            bits = self._launch_stream(
                ls, lc, lv, l_total, l_tpad, u_pad, host_fallback,
                fallback_counts_slo=plan is not None)
            if plan is not None:
                bits = _feed.expand_bits(plan, bits, t_pad)
        note_dispatch()
        return bits, offsets, t_pad

    # ---- the supervised slice walk -------------------------------------

    def _launch_stream(self, q_start, q_count, q_ver, total: int,
                       t_pad: int, u_pad: int, host_fallback,
                       fallback_counts_slo: bool = False):
        """Walk the touched slices under graftguard supervision.
        → int8[t_pad] or CompactBits host bits, identical whichever
        path served them. The whole walk runs under ONE
        `detect.dispatch` watch: an open breaker never touches a
        device, and any launch/fetch failure or watchdog trip falls
        back to the host join over the FULL table.
        `fallback_counts_slo`: the fallback observes its own (single)
        device_serving event — _host_join_csr does — so don't count a
        second one here."""
        from ..ops import bucket_size
        from ..ops import join as J
        inner = self._inner
        raw_fallback = host_fallback

        def host_fallback():
            # one bad device_serving event per DISPATCH served
            # host-side (never per prep — the coalesce-factor lesson)
            if not fallback_counts_slo:
                SLO.observe_join(False)
            return raw_fallback()

        if total == 0:
            return np.zeros(t_pad, np.int8)
        plans = clip_descriptors(self.bounds, q_start, q_count, q_ver)
        if not plans:
            return np.zeros(t_pad, np.int8)
        if not GUARD.allow_device():
            return host_fallback()
        site = "redetect" if GUARD.blameless_active() else "stream"
        results: list = []
        hit_notes: list = []
        try:
            with GUARD.watch("detect.dispatch"):
                failpoint("detect.dispatch")
                ver_dev = inner._ver_device(u_pad)
                for i, plan in enumerate(plans):
                    adv = self._cache.get(plan.idx)
                    # double buffer: the NEXT touched slice's upload
                    # rides alongside this slice's compute + fetch
                    if i + 1 < len(plans):
                        self._cache.prefetch(plans[i + 1].idx)
                    results.append(
                        (plan, self._join_slice(J, bucket_size, adv,
                                                ver_dev, plan, site,
                                                hit_notes)))
                # tail prefetch: steady-state scans walk the same hash
                # span again, so ship the walk's FIRST slice back into
                # the freed buffer before the next dispatch needs it
                if len(plans) > 1 or plans[0].idx \
                        not in self._cache.resident():
                    self._cache.prefetch(plans[0].idx)
                # one traffic observation per LOGICAL dispatch (the
                # batch counter stays per-request-visible dispatch;
                # the graftprof ledger carries the per-slice launches)
                inner._account_traffic(
                    total, sum(self._slice_tpad(bucket_size, p)
                               for p in plans))
        except DeviceError:
            _log.warning("streamed join failed; host-fallback join "
                         "over the full table", exc_info=True)
            return host_fallback()
        # hit-budget adaptation outside the watch (mesh pattern): the
        # fullest slice buffer decides the next rung
        for n_hits, h_cap, t_pad_k in hit_notes:
            inner._note_hits(n_hits, h_cap, site=site, t_pad=t_pad_k)
        return merge_slice_bits(results, t_pad)

    def _slice_tpad(self, bucket_size, plan: SlicePlan) -> int:
        return bucket_size(plan.total, self._inner.pair_floor,
                           self._inner.pair_growth)

    def _join_slice(self, J, bucket_size, adv, ver_dev,
                    plan: SlicePlan, site: str, hit_notes: list):
        """One slice's launch + synchronous fetch (runs inside the
        dispatch watch). → dense int8[t_pad_k] or slice-local
        CompactBits."""
        import jax
        inner = self._inner
        adv_lo, adv_hi, adv_flags = adv
        t_pad_k = self._slice_tpad(bucket_size, plan)
        q_pad_k = bucket_size(plan.q_start.size, 64,
                              inner.pair_growth, align=64)
        qs = np.zeros(q_pad_k, np.int32)
        qs[:plan.q_start.size] = plan.q_start
        qc = np.zeros(q_pad_k, np.int32)
        qc[:plan.q_count.size] = plan.q_count
        qv = np.zeros(q_pad_k, np.int32)
        qv[:plan.q_ver.size] = plan.q_ver
        h_cap = inner._hit_capacity(t_pad_k)
        args = (adv_lo, adv_hi, adv_flags, ver_dev,
                jax.device_put(qs), jax.device_put(qc),
                jax.device_put(qv), np.int32(plan.total))

        def _run():
            if h_cap:
                out = J.csr_pair_join_compact(*args, t_pad_k, h_cap)
                hit_idx, hit_bits, n_hits = jax.device_get(out[:3])
                n = int(n_hits)
                hit_notes.append((n, h_cap, t_pad_k))
                nbytes = float(hit_idx.nbytes + hit_bits.nbytes
                               + n_hits.nbytes)
                METRICS.inc("trivy_tpu_detect_transfer_bytes_total",
                            nbytes, path="compact")
                _cost.ledgered_transfer("compact", nbytes)
                if n > h_cap:
                    # checked overflow: the dense bits stayed on
                    # device — this slice pays the dense fetch and the
                    # merged result stays bit-identical by construction
                    bits = jax.device_get(out[3])
                    METRICS.inc(
                        "trivy_tpu_detect_transfer_bytes_total",
                        float(bits.nbytes), path="dense")
                    _cost.ledgered_transfer("overflow",
                                            float(bits.nbytes))
                    return bits
                return CompactBits(hit_idx[:n], hit_bits[:n], t_pad_k)
            bits = jax.device_get(J.csr_pair_join(*args, t_pad_k))
            METRICS.inc("trivy_tpu_detect_transfer_bytes_total",
                        float(bits.nbytes), path="dense")
            _cost.ledgered_transfer("dense", float(bits.nbytes))
            return bits

        return ledgered_sync_join(inner, _run, site, plan.total,
                                  t_pad_k, q_pad_k,
                                  int(ver_dev.shape[0]), h_cap)

    def _bits(self, prep):
        inner = self._inner
        return self._launch_stream(
            prep.q_start, prep.q_count, prep.q_ver, prep.n_pairs,
            int(prep.pair_row.shape[0]), prep.u_pad,
            lambda: inner._host_bits(prep))

    # ---- direct detection ----------------------------------------------

    def detect_many(self, batches) -> list:
        """Per-batch prep → slice walk → assemble (the MeshDetector
        shape: the walk's merge is synchronous, so pipelining comes
        from detectd coalescing above this surface)."""
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

    def status(self) -> dict:
        """→ the /healthz `stream` block (slice plan + resident set;
        server/listen.py surfaces it when this detector serves)."""
        return {
            "slices": self.n_slices,
            "rows_pad": self.rows_pad,
            "slice_nbytes": self.slice_nbytes,
            "resident": self._cache.resident(),
        }
