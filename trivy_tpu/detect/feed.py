"""graftfeed — the input-path twin of hit compaction.

Hit compaction made the join's *output* O(hits) device→host; this
module closes the input-path gaps:

  * **Cross-request unique-query dedup** (`plan_merged`/`expand_bits`):
    when detectd merges coalesced descriptors, each real query is fully
    described by its canonical key triple — (bucket start, bucket
    count, version-pool row). The advisory table and the version pool
    are detector-global, and the join predicate is elementwise, so two
    queries with the same triple produce the SAME pair-segment bits by
    definition. The plan collapses duplicate triples into one
    unique-query CSR descriptor, the join dispatches over uniques only,
    and a host-side index map scatters the bits (dense or CompactBits)
    back into every duplicate's global pair range — bit-identical to
    serial by construction. graftmemo dedups *blob-level* repeats
    across scans; this catches the intra-dispatch duplication memo
    cannot see (cold blobs, mixed units, live remainders sharing a
    base layer).

  * **Double-buffered query upload** (`stage_queries`/`upload_queries`):
    the padded CSR query columns used to device_put synchronously
    inside the launch window. detectd now stages the upload for
    dispatch i+1 while dispatch i computes, supervised by its own
    `detect.query_upload` GUARD.watch so a wedged upload trips the
    breaker exactly like a wedged launch. Stalls are ledgered as
    `query_upload` rows in the graftprof upload ledger: steady-state
    stall ≈ 0 is an asserted property, not a hope.

The advisory table itself is resident: whole on one chip, or
db-sharded over the mesh (parallel/mesh.py) — so a dispatch moves
only its query columns and its results.
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

from ..log import get as _get_logger
from ..metrics import METRICS
from ..obs.perf import LEDGER
from ..resilience import GUARD, DeviceError, failpoint
from ..resilience.breaker import CLOSED as _CLOSED
from ..resilience.hostjoin import CompactBits

_log = _get_logger("feed")

# sentinel for dispatch_merged(plan=...): "compute the plan yourself if
# dedup is on". Distinct from None, which means "dedup found nothing
# (or is off) — dispatch the full descriptor set as-is"; detectd passes
# the plan it computed (possibly None) so the detector never re-hashes.
PLAN_AUTO = object()


@dataclass(slots=True)
class DedupPlan:
    """Unique-query collapse of one merged descriptor set, plus the
    host-side scatter-back map. All index spaces are PAIRS unless
    named otherwise; "global" = the merged dispatch's real pair order
    (the order _merge_descriptors concatenates preps in)."""
    # the unique-query CSR descriptor set (unpadded; launch sites pad)
    u_start: np.ndarray       # int32[U] bucket start per unique query
    u_count: np.ndarray       # int32[U]
    u_ver: np.ndarray         # int32[U] version-pool row
    n_unique: int             # U
    unique_total: int         # pairs the deduped dispatch runs
    # scatter-back map, one row per ORIGINAL real query j:
    ustart: np.ndarray        # int64[Nq] unique-space pair offset of
    # j's segment (all duplicates of one triple share it)
    goff: np.ndarray          # int64[Nq] global pair offset of j
    counts: np.ndarray        # int64[Nq] pair count of j
    total: int                # real global pairs (== sum(counts))
    # per-prep cost attribution (chunk order == preps order): the
    # first occurrence of a triple OWNS its unique pairs; every later
    # duplicate's pairs are collapsed (work avoided)
    unique_by_prep: np.ndarray    # int64[P]
    collapsed_by_prep: np.ndarray  # int64[P]


def plan_merged(q_start: np.ndarray, q_count: np.ndarray,
                q_ver: np.ndarray,
                prep_nq: list[int]) -> DedupPlan | None:
    """Build the dedup plan for one merged descriptor set whose first
    sum(prep_nq) rows are the real queries (merge order: prep by
    prep). → None when every triple is unique — the zero-cost exit
    that keeps duplicate-free traffic byte-for-byte on the old path."""
    nq = int(sum(prep_nq))
    if nq <= 1:
        return None
    key = np.stack([q_start[:nq].astype(np.int64),
                    q_count[:nq].astype(np.int64),
                    q_ver[:nq].astype(np.int64)], axis=1)
    uniq, first_idx, inv = np.unique(
        key, axis=0, return_index=True, return_inverse=True)
    u = int(uniq.shape[0])
    if u == nq:
        return None
    inv = inv.reshape(-1)
    counts = key[:, 1]
    u_counts = uniq[:, 1]
    u_off = np.zeros(u + 1, np.int64)
    np.cumsum(u_counts, out=u_off[1:])
    goff = np.zeros(nq + 1, np.int64)
    np.cumsum(counts, out=goff[1:])
    # prep attribution: first occurrence owns; later duplicates collapse
    n_preps = len(prep_nq)
    prep_of = np.repeat(np.arange(n_preps),
                        np.asarray(prep_nq, np.int64))
    owner = np.zeros(nq, bool)
    owner[first_idx] = True
    unique_by_prep = np.bincount(
        prep_of[owner], weights=counts[owner],
        minlength=n_preps).astype(np.int64)
    collapsed_by_prep = np.bincount(
        prep_of[~owner], weights=counts[~owner],
        minlength=n_preps).astype(np.int64)
    return DedupPlan(
        u_start=uniq[:, 0].astype(np.int32),
        u_count=uniq[:, 1].astype(np.int32),
        u_ver=uniq[:, 2].astype(np.int32),
        n_unique=u, unique_total=int(u_off[-1]),
        ustart=u_off[:-1][inv], goff=goff[:-1],
        counts=counts, total=int(goff[-1]),
        unique_by_prep=unique_by_prep,
        collapsed_by_prep=collapsed_by_prep)


def plan_from_preps(preps) -> DedupPlan | None:
    """plan_merged over a prep list without a prior _merge_descriptors
    (detectd computes the plan for detectors that merge internally —
    the mesh/stream paths)."""
    nq = [p.n_queries for p in preps]
    if sum(nq) <= 1:
        return None
    qs = np.concatenate([p.q_start[:p.n_queries] for p in preps])
    qc = np.concatenate([p.q_count[:p.n_queries] for p in preps])
    qv = np.concatenate([p.q_ver[:p.n_queries] for p in preps])
    return plan_merged(qs, qc, qv, nq)


def padded_unique(plan: DedupPlan, pair_floor: int,
                  pair_growth: float):
    """Pad the plan's unique CSR descriptors to the detector's bucket
    ladder — the launch-shaped twin of _merge_descriptors' padding.
    → (q_start, q_count, q_ver, unique_total, t_pad)."""
    from ..ops import bucket_size
    q_pad = bucket_size(plan.n_unique, 64, pair_growth, align=64)
    qs = np.zeros(q_pad, np.int32)
    qc = np.zeros(q_pad, np.int32)
    qv = np.zeros(q_pad, np.int32)
    qs[:plan.n_unique] = plan.u_start
    qc[:plan.n_unique] = plan.u_count
    qv[:plan.n_unique] = plan.u_ver
    t_pad = bucket_size(plan.unique_total, pair_floor, pair_growth)
    return qs, qc, qv, plan.unique_total, t_pad


def note_dedup_ratio(unique_pairs: int, real_pairs: int) -> None:
    """One merged dispatch's dedup win: unique pairs ÷ real pairs
    (1.0 = nothing collapsed). Observed per merged dispatch whenever
    dedup is enabled, so the histogram's mass says how duplicated the
    admitted traffic actually is."""
    if real_pairs > 0:
        METRICS.observe("trivy_tpu_detect_dedup_ratio",
                        unique_pairs / real_pairs)


def expand_bits(plan: DedupPlan, bits_u, t_pad: int):
    """Scatter unique-space join results back to the merged dispatch's
    global pair space (the host-side index map of the dedup contract).
    `bits_u` is the unique dispatch's dense int8 vector or CompactBits;
    the return value has the same shape kind, sized/declared for the
    FULL merged dispatch (t_pad). Bit-identical by construction: every
    duplicate's segment is a copy of its unique segment."""
    if isinstance(bits_u, CompactBits):
        hidx = bits_u.pair_idx.astype(np.int64)
        lo = np.searchsorted(hidx, plan.ustart)
        hi = np.searchsorted(hidx, plan.ustart + plan.counts)
        lens = hi - lo
        tot = int(lens.sum())
        if tot == 0:
            return CompactBits(np.zeros(0, np.int32),
                               np.zeros(0, np.int8), t_pad)
        starts = np.zeros(lens.size, np.int64)
        np.cumsum(lens[:-1], out=starts[1:])
        take = np.repeat(lo - starts, lens) \
            + np.arange(tot, dtype=np.int64)
        # per-element: global = hit - ustart_j + goff_j; queries are
        # in ascending global order with disjoint ranges and hits are
        # ascending within each query, so the result is sorted — the
        # CompactBits.slice searchsorted contract holds with no sort
        out_idx = hidx[take] \
            + np.repeat(plan.goff - plan.ustart, lens)
        return CompactBits(out_idx.astype(np.int32),
                           bits_u.bits[take], t_pad)
    out = np.zeros(t_pad, np.int8)
    if plan.total:
        take = np.repeat(plan.ustart - plan.goff, plan.counts) \
            + np.arange(plan.total, dtype=np.int64)
        out[:plan.total] = bits_u[take]
    return out


class PendingExpand:
    """One in-flight DEDUPED merged dispatch: the unique-space device
    result (async — whatever _launch returned) plus the plan that
    scatters it back to global pair space at fetch time, and the
    padded unique launch arguments so a failed fetch's host rebuild
    consumes the SAME unique set (the hostjoin contract, dedup
    edition)."""

    __slots__ = ("dev", "plan", "launch")

    def __init__(self, dev, plan: DedupPlan, launch):
        self.dev = dev
        self.plan = plan
        self.launch = launch   # (q_start, q_count, q_ver, total, t_pad)


# ---------------------------------------------------------------------------
# double-buffered query upload


class StagedQueries:
    """One pre-issued H2D upload of a dispatch's CSR query columns.
    `refs` are the device arrays (None when the breaker was open at
    stage time — the paired launch will host-join anyway); `error` is
    the supervised staging failure, recorded so the paired launch
    degrades to the host join instead of re-driving a wedged link."""

    __slots__ = ("refs", "error")

    def __init__(self):
        self.refs = None
        self.error: BaseException | None = None

    def take(self):
        """Block until the staged columns are device-resident; the
        blocked time is the dispatch's query-upload stall (≈ 0 in
        steady state — the transfer rode the previous dispatch's
        compute)."""
        import jax
        t0 = time.perf_counter()
        jax.block_until_ready(self.refs)
        LEDGER.note_shard_wait("query_upload",
                               (time.perf_counter() - t0) * 1e3,
                               cold=False)
        return self.refs


def upload_queries(q_start: np.ndarray, q_count: np.ndarray,
                   q_ver: np.ndarray, prefetched: bool):
    """device_put the CSR query columns (async on real accelerators)
    under the `detect.query_upload` failpoint, ledgering the H2D bytes
    as a `query_upload` transfer in the graftprof upload ledger.
    `prefetched` = staged ahead of need (detectd's double buffer);
    False = the upload ran inside the launch window (the cold path)."""
    import jax
    failpoint("detect.query_upload")
    refs = (jax.device_put(q_start), jax.device_put(q_count),
            jax.device_put(q_ver))
    LEDGER.note_shard_upload(
        "query_upload",
        q_start.nbytes + q_count.nbytes + q_ver.nbytes,
        prefetched=prefetched, path="query_upload")
    return refs


def stage_queries(q_start: np.ndarray, q_count: np.ndarray,
                  q_ver: np.ndarray) -> StagedQueries:
    """Issue the query-column upload for a FUTURE launch under its own
    `detect.query_upload` watch — a wedged upload trips the breaker
    exactly like a wedged launch (record_success=False: staging proves
    nothing about execution; the paired fetch carries the success
    watch). Never raises: a failure is recorded on the result so the
    paired launch degrades to the host join bit-identically."""
    staged = StagedQueries()
    # non-consuming health check: a half-open breaker admits exactly
    # ONE probe per window, and it must be the REAL dispatch (whose
    # fetch resolves it) — an advisory stage calling allow_device()
    # here would consume the probe under a record_success=False watch
    # and wedge the breaker half-open forever
    if GUARD.breaker.state != _CLOSED:
        return staged
    try:
        with GUARD.watch("detect.query_upload",
                         record_success=False):
            staged.refs = upload_queries(q_start, q_count, q_ver,
                                         prefetched=True)
    except DeviceError as exc:
        _log.warning("staged query upload failed; the paired "
                     "dispatch degrades to the host join",
                     exc_info=True)
        staged.refs = None
        staged.error = exc
    return staged
