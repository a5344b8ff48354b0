"""detectd — the shared continuous-batching dispatch scheduler.

Every per-request path pays the same tax: a 16-client registry sweep
runs 16 concurrent `detect_many` calls, each dispatching its own
pow2-padded join (before detectd, a server measured 57.3 images/s vs
82.7 local on the same backend — the gap is almost entirely dispatch
overhead and padding waste multiplied by request count). detectd
closes it the way inference servers do, with continuous batching:

  handler threads   prep their request's query batches (host work
                    parallelizes across RPC threads) and enqueue the
                    prepared CSR descriptors;
  dispatcher thread wakes on the first pending request, sweeps
                    everything already queued, holds the window open
                    for up to `coalesce_wait_ms` only while the device
                    is busy, concatenates the prepared descriptors,
                    and issues ONE device join per gathered chunk
                    (BatchDetector.dispatch_merged) under a
                    `max_pairs_in_flight` in-flight bound;
  get thread        streams each merged result back (the detector's
                    fetch thread — one thread keeps transfers ordered);
  handler threads   wake with their contiguous bits slice and run the
                    ordinary per-batch assembly themselves — assembly
                    parallelism stays per-request (a shared assemble
                    pool here measured SLOWER than the per-request
                    path at c=16: it funneled the most host-expensive
                    stage through two workers).

Correctness falls out of the merge point: coalescing happens at the
*prepared-CSR* level, so each batch keeps its own `_Prepared` (pair
expansion, usable-query list) and its bits slice is exactly what an
uncoalesced dispatch would have produced — the join predicate is
elementwise, so results are bit-identical, ordering included
(tests/test_sched.py hammers this).

Latency policy: with an idle device a request dispatches immediately
(no added latency at c=1 beyond one queue hop); while a dispatch is in
flight, arrivals gather for at most `coalesce_wait_ms` — so the merge
window rides on top of device time the request would have waited out
anyway, and `coalesce_wait_ms` stays the hard bound on single-request
regression.
"""

from __future__ import annotations

import contextvars
import queue as queue_mod
import threading
import time
from collections import deque
from concurrent.futures import Future, InvalidStateError
from dataclasses import dataclass

from ..metrics import METRICS
from ..obs import cost as _cost
from ..obs import current_trace_id, span
from . import feed as _feed
from .engine import BatchDetector, Hit, PkgQuery, slice_bits


@dataclass
class SchedOptions:
    """detectd knobs (server flags --detect-coalesce-wait-ms,
    --detect-max-inflight-pairs, --detect-warmup, --detect-dedup,
    --detect-tenant-max-share)."""
    coalesce_wait_ms: float = 2.0     # max wait gathering co-dispatchers
    max_pairs_in_flight: int = 1 << 22  # padded-pair in-flight bound
    warmup: bool = False              # pre-compile the bucket ladder
    warmup_max_pairs: int = 1 << 18   # top rung the warmup compiles
    enabled: bool = True              # False → per-request dispatch
    dedup: bool = True                # graftfeed: collapse duplicate
    #                                   query triples across the merge
    tenant_max_share: float = 1.0     # graftfair: max fraction of a
    #                                   merged round's pair budget one
    #                                   tenant may fill while other
    #                                   tenants are pending (1.0 = off)


class _Request:
    """One submitted detect_many call. The future resolves to the
    per-slot list once every slot has its bits slice; empty slots
    resolve to [] and dispatched slots to (prep, bits) — the CALLER
    assembles its own slices (see the module docstring's latency
    note)."""

    __slots__ = ("future", "results", "slots", "n_pairs", "_lock",
                 "_remaining", "ctx", "trace_id", "cost", "t_submit",
                 "queue_charged", "tenant")

    def __init__(self, n_slots: int):
        self.future: Future = Future()
        self.results: list = [None] * n_slots
        self.slots: list = []       # (slot_idx, _Prepared), n_pairs > 0
        self.n_pairs = 0
        self._lock = threading.Lock()
        self._remaining = 0
        # graftwatch: the submitting request's context (trace id, span
        # parentage). The dispatcher thread runs the merged dispatch
        # under ONE request's context — so its spans join a real trace
        # instead of orphaning — and every merged trace id rides the
        # dispatch span's attrs for cross-request attribution
        self.ctx = contextvars.copy_context()
        self.trace_id = current_trace_id()
        # graftcost: the submitting request's ledger (None outside a
        # request → the merged dispatch bills that share to SYSTEM);
        # submit→first-dispatch wall time is the coalesce-window
        # queue-ms charge, taken once per request even when its slots
        # split across chunks
        self.cost = _cost.active()
        self.t_submit = time.perf_counter()
        self.queue_charged = False
        # graftfair: the fair-queue key — the aggregator-CLAMPED
        # tenant label (bounded top-K + "other"), "system" when no
        # request ledger is installed (warmup, blameless redetect)
        self.tenant = (_cost.TENANTS.resolve(self.cost.tenant)
                       if self.cost is not None else "system")

    def arm(self) -> None:
        with self._lock:
            self._remaining = len(self.slots)

    def complete(self, slot: int, part) -> None:
        with self._lock:
            self.results[slot] = part
            self._remaining -= 1
            done = self._remaining == 0
        if done:
            try:
                self.future.set_result(self.results)
            except InvalidStateError:
                pass  # lost the race with fail()

    def fail(self, exc: BaseException) -> None:
        try:
            self.future.set_exception(exc)
        except InvalidStateError:
            pass  # first error wins


class DispatchScheduler:
    """detectd: merges concurrent requests' prepared batches into
    shared device dispatches. One instance per LocalScanner (the server
    shares that scanner across handler threads).

    `detector` is a BatchDetector OR anything exposing its dispatch
    surface (`table`/`_prepare`/`dispatch_merged`/`fetch_merged`/
    `_get_pool`/`_assemble`) — the mesh path plugs a
    parallel.MeshDetector in here, so coalesced dispatches route over
    the (possibly shrunk) device mesh unchanged and a meshguard swap
    only ever replaces the detector behind the scheduler's back via
    the generation drain, never the scheduler protocol."""

    def __init__(self, detector: BatchDetector,
                 opts: SchedOptions | None = None):
        self.detector = detector
        self.opts = opts or SchedOptions()
        self._queue: queue_mod.SimpleQueue = queue_mod.SimpleQueue()
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._inflight_pairs = 0
        self._closed = False
        # graftfair: per-tenant pending queues drained by deficit
        # round-robin on real pair count. submit() registers a request
        # here (under self._lock) BEFORE putting its wake token on
        # self._queue; the dispatcher pops rounds via _fair_take.
        # Tenant labels are aggregator-clamped, so the dicts stay
        # bounded at top-K + reserved.
        self._fair: dict[str, deque] = {}
        self._rr: deque[str] = deque()       # tenant rotation order
        self._deficit: dict[str, float] = {}  # DRR deficit counters
        self._fair_pairs = 0                  # total pending pairs
        # daemon: an unclosed scheduler must not block interpreter
        # exit; close() still joins it for a clean shutdown
        self._thread = threading.Thread(
            target=self._run, name="detectd-dispatch", daemon=True)
        self._thread.start()

    # ---- submission ---------------------------------------------------

    def submit(self, batches: list[list[PkgQuery]]) -> Future:
        """Prep every batch on the CALLING thread (host prep scales
        with handler threads; the dispatcher only merges + launches)
        and enqueue; resolves to detect_many-shaped results."""
        det = self.detector
        req = _Request(len(batches))
        n_queries = 0
        if len(det.table):
            for i, qs in enumerate(batches):
                if not qs:
                    req.results[i] = []
                    continue
                n_queries += len(qs)
                prep = det._prepare(qs)
                if prep is None or prep.n_pairs == 0:
                    req.results[i] = []
                    continue
                req.slots.append((i, prep))
                req.n_pairs += prep.n_pairs
        else:
            for i in range(len(batches)):
                req.results[i] = []
        req.arm()
        METRICS.inc("trivy_tpu_detect_queries_total", n_queries)
        METRICS.inc("trivy_tpu_detect_pairs_total", req.n_pairs)
        if not req.slots:
            req.future.set_result(req.results)
            return req.future
        with self._lock:
            if self._closed:
                raise RuntimeError("DispatchScheduler is closed")
            # enqueue under the lock: close() flips _closed before its
            # sentinel, so every accepted request precedes the sentinel.
            # The fair queues are the registry of record; the queue
            # item is only a wake token (the dispatcher pops rounds
            # from the fair structure, not from the token stream)
            self._fair_put_locked(req)
            self._queue.put(req)
        return req.future

    def detect_many(self, batches: list[list[PkgQuery]]
                    ) -> list[list[Hit]]:
        fut = self.submit(batches)
        # the handler's wait: queue, merged dispatch, device, fetch
        with span("detectd.wait"):
            parts = fut.result()
        # assemble HERE, on the requesting thread: the most
        # host-expensive stage keeps the same per-request parallelism
        # as the uncoalesced path (concurrent RPC handlers assemble
        # concurrently) while the dispatches stay merged
        out = []
        for part in parts:
            if isinstance(part, tuple):
                prep, bits = part
                out.append(self.detector._assemble(prep, bits))
            else:
                out.append(part)
        METRICS.inc("trivy_tpu_detect_hits_total",
                    sum(len(h) for h in out))
        return out

    def detect(self, queries: list[PkgQuery]) -> list[Hit]:
        return self.detect_many([queries])[0]

    # ---- lifecycle ----------------------------------------------------

    def close(self) -> None:
        """Flush pending requests, stop the dispatcher, and wait for
        in-flight work to settle. Idempotent; the scheduler rejects
        submissions afterwards. (The detector's fetch/assemble pools
        are owned by the detector — BatchDetector.close() joins them.)"""
        with self._lock:
            if self._closed:
                return
            self._closed = True
        self._queue.put(None)
        self._thread.join()
        # dispatched work completes on the detector's pools; wait so
        # close() guarantees no scheduler-driven work is still running
        with self._cv:
            self._cv.wait_for(lambda: self._inflight_pairs == 0,
                              timeout=60.0)

    # ---- dispatcher ---------------------------------------------------

    # ---- graftfair fair queues (all _locked helpers require
    # self._lock; NEVER call them while also needing self._cv — the
    # condition shares the same lock) -----------------------------------

    def _fair_put_locked(self, req: _Request) -> None:
        dq = self._fair.get(req.tenant)
        if dq is None:
            # lint: allow(TPU106) reason=caller holds self._lock — the _locked-helper contract is an interprocedural hold the intraprocedural rule cannot see
            dq = self._fair[req.tenant] = deque()
            # lint: allow(TPU106) reason=caller holds self._lock — the _locked-helper contract is an interprocedural hold the intraprocedural rule cannot see
            self._deficit[req.tenant] = 0.0
            self._rr.append(req.tenant)
        dq.append(req)
        self._fair_pairs += req.n_pairs

    def _fair_take_locked(self, budget: int) -> list[_Request]:
        """One deficit-round-robin sweep over the per-tenant queues →
        the round's requests in drain order. Each tenant's turn banks
        one quantum of pair credit and drains whole requests against
        it; with more than one tenant pending, no tenant may fill more
        than tenant_max_share of the round's pair budget — the rest of
        its queue waits for the next round (bounded share, not
        starvation: a solo tenant always gets the whole window)."""
        active = [t for t in self._rr if self._fair.get(t)]
        if not active:
            return []
        share = self.opts.tenant_max_share
        cap = (budget if len(active) <= 1 or share >= 1.0
               else max(1, int(budget * share)))
        quantum = max(1, budget // max(1, len(active)))
        taken: list[_Request] = []
        taken_by: dict[str, int] = {}
        total = 0
        progress = True
        while progress and total < budget:
            progress = False
            for label in list(self._rr):
                dq = self._fair.get(label)
                if not dq:
                    # idle queues bank no credit (classic DRR reset)
                    # lint: allow(TPU106) reason=caller holds self._lock — the _locked-helper contract is an interprocedural hold the intraprocedural rule cannot see
                    self._deficit[label] = 0.0
                    continue
                # lint: allow(TPU106) reason=caller holds self._lock — the _locked-helper contract is an interprocedural hold the intraprocedural rule cannot see
                self._deficit[label] += quantum
                while dq and total < budget:
                    head = dq[0]
                    w = max(1, head.n_pairs)
                    if taken_by.get(label, 0) + w > cap and taken:
                        break   # share spent — next tenant
                    if w > self._deficit[label] and taken:
                        break   # credit spent — next tenant
                    dq.popleft()
                    self._fair_pairs -= head.n_pairs
                    # lint: allow(TPU106) reason=caller holds self._lock — the _locked-helper contract is an interprocedural hold the intraprocedural rule cannot see
                    self._deficit[label] = max(
                        0.0, self._deficit[label] - w)
                    taken.append(head)
                    taken_by[label] = taken_by.get(label, 0) + w
                    total += w
                    progress = True
        if not taken:
            # forced progress: an oversize head larger than any credit
            # this sweep could bank still dispatches (alone)
            for label in list(self._rr):
                dq = self._fair.get(label)
                if dq:
                    head = dq.popleft()
                    self._fair_pairs -= head.n_pairs
                    # lint: allow(TPU106) reason=caller holds self._lock — the _locked-helper contract is an interprocedural hold the intraprocedural rule cannot see
                    self._deficit[label] = 0.0
                    taken.append(head)
                    break
        # rotate so the next round's sweep starts one tenant later —
        # ties don't always break toward the same queue
        if self._rr:
            self._rr.rotate(-1)
        return taken

    def _drain_tokens(self) -> bool:
        """Consume every wake token already queued (their requests are
        in the fair structure). → True when the close() sentinel was
        seen."""
        saw_stop = False
        while True:
            try:
                tok = self._queue.get_nowait()
            except queue_mod.Empty:
                return saw_stop
            if tok is None:
                saw_stop = True

    def _run(self) -> None:
        import jax  # noqa: F401 — fail fast off the request path
        opts = self.opts
        stopping = False
        while True:
            with self._lock:
                idle = self._fair_pairs == 0 and not any(
                    self._fair.values())
            if idle:
                if stopping:
                    break
                try:
                    tok = self._queue.get(timeout=0.5)
                except queue_mod.Empty:
                    continue
                if tok is None:
                    # drain-then-exit: every accepted request precedes
                    # the sentinel (submit registers under the lock),
                    # so loop once more to flush any residue
                    stopping = True
                    continue
            with span("detectd.gather"):
                stopping, pending = self._gather(stopping)
            if not pending:
                continue
            METRICS.observe("trivy_tpu_detect_queue_depth",
                            float(len(pending)))
            self._observe_dispatch_share(pending)
            try:
                self._dispatch_round(pending)
            except BaseException as e:  # noqa: BLE001 — detectd must
                # survive any one round; the affected requests fail
                for req in pending:
                    req.fail(e)

    def _gather(self, stopping: bool) -> tuple[bool, list[_Request]]:
        """Sweep everything already queued (free coalescing), then
        hold the window open — but ONLY while a dispatch is in flight:
        with an idle device, waiting would trade latency for nothing,
        while a busy device makes the wait free (the request would be
        queued behind it anyway). → (stopping, the round's requests)."""
        opts = self.opts
        deadline = time.monotonic() + opts.coalesce_wait_ms / 1e3
        while not stopping:
            stopping |= self._drain_tokens()
            with self._lock:
                pairs = self._fair_pairs
            if stopping or pairs >= opts.max_pairs_in_flight:
                break
            with self._cv:
                busy = self._inflight_pairs > 0
            timeout = deadline - time.monotonic()
            if not busy or timeout <= 0:
                break
            try:
                tok = self._queue.get(
                    timeout=min(timeout, opts.coalesce_wait_ms / 4e3))
            except queue_mod.Empty:
                continue
            if tok is None:
                stopping = True
        # graftfair: pop the round in deficit-round-robin order — a
        # flooding tenant's surplus stays queued instead of
        # monopolizing the window
        with self._lock:
            pending = self._fair_take_locked(opts.max_pairs_in_flight)
        return stopping, pending

    def _observe_dispatch_share(self, pending: list[_Request]) -> None:
        """Per merged round: each participating tenant's fraction of
        the round's real pairs (the fair sweep bounds the max at
        tenant_max_share when tenants compete)."""
        total = sum(r.n_pairs for r in pending)
        if total <= 0:
            return
        by_tenant: dict[str, int] = {}
        for r in pending:
            by_tenant[r.tenant] = by_tenant.get(r.tenant, 0) + r.n_pairs
        for label, pairs in by_tenant.items():
            METRICS.observe("trivy_tpu_tenant_qos_dispatch_share",
                            pairs / total, tenant=label)

    def _dispatch_round(self, pending: list[_Request]) -> None:
        """Chunk the gathered slots under the pair budget and issue one
        merged dispatch per chunk."""
        budget = self.opts.max_pairs_in_flight
        chunk: list = []   # (req, slot_idx, prep)
        chunk_pairs = 0

        def flush():
            if not chunk:
                return
            preps = [p for _, _, p in chunk]
            det = self.detector
            # graftfeed: merge + dedup-plan + stage the query upload
            # BEFORE parking on backpressure — while a prior dispatch
            # still owns the device, its compute time hides this
            # chunk's H2D transfer. Detectors without
            # the graftfeed surface (test fakes, older shims) take the
            # bare dispatch_merged path unchanged
            dedup_on = self.opts.dedup and getattr(det, "dedup", False)
            stage = getattr(det, "stage_merged", None)
            staged = plan = None
            with span("detectd.stage", merged=len(preps)):
                if stage is not None:
                    staged = (stage(preps) if dedup_on
                              else stage(preps, plan=None))
                    plan = staged.plan
                elif hasattr(det, "dedup"):
                    plan = (_feed.plan_from_preps(preps) if dedup_on
                            else None)
            # backpressure: admit this dispatch only when the in-flight
            # padded pairs leave room (a chunk bigger than the whole
            # budget still goes — alone — once the device drains)
            with self._cv:
                self._cv.wait_for(
                    lambda: self._inflight_pairs == 0
                    or self._inflight_pairs + chunk_pairs <= budget,
                    timeout=30.0)
            n_req = len({id(r) for r, _, _ in chunk})
            # run the merged dispatch under the FIRST request's
            # captured context: its spans join that request's trace
            # (the dispatcher thread has none of its own) and the
            # detectd.round span lists every merged trace id, so any
            # coalesced request's trace can find the shared dispatch.
            # Fresh copies per use — a Context can't be entered twice
            # concurrently, and the fetch below runs on another thread
            req0 = chunk[0][0]
            tids = sorted({r.trace_id for r, _, _ in chunk
                           if r.trace_id})
            dispatch_ctx = req0.ctx.run(contextvars.copy_context)
            fetch_ctx = req0.ctx.run(contextvars.copy_context)
            # graftcost: time parked between submit and first dispatch
            # is queue ms (charged once per request), and the merged
            # launch's device ms / transfer bytes apportion pro-rata
            # by each request's real pair share — install the share
            # vector into BOTH contexts the round runs under
            # (Context.run mutations persist in the Context object)
            now = time.perf_counter()
            # graftcost x graftfeed: when a dedup plan collapsed
            # duplicate triples, each request's share weight is its
            # UNIQUE pair count (the pairs the device actually ran for
            # it) and its collapsed duplicates are billed as
            # work_avoided — priced by the device-ms-per-row EWMA, so
            # a tenant riding another tenant's base layer shows the
            # ride in avoided_ms instead of inflating device_ms
            per_req: dict[int, int] = {}
            avoided: dict[int, int] = {}
            for k, (r, _, p) in enumerate(chunk):
                w = (int(plan.unique_by_prep[k]) if plan is not None
                     else p.n_pairs)
                per_req[id(r)] = per_req.get(id(r), 0) + w
                if plan is not None:
                    avoided[id(r)] = (avoided.get(id(r), 0)
                                      + int(plan.collapsed_by_prep[k]))
                if not r.queue_charged:
                    r.queue_charged = True
                    METRICS.observe("trivy_tpu_detect_queue_wait_seconds",
                                    now - r.t_submit)
                    _cost.charge_queue_ms((now - r.t_submit) * 1e3,
                                          ledger=r.cost)
            seen: set[int] = set()
            shares = []
            for r, _, _p in chunk:
                if id(r) not in seen:
                    seen.add(id(r))
                    shares.append((r.cost, per_req[id(r)]))
                    av = avoided.get(id(r), 0)
                    if av > 0:
                        _cost.note_work_avoided(av, ledger=r.cost)
            dispatch_ctx.run(_cost.install_shares, shares)
            fetch_ctx.run(_cost.install_shares, shares)

            def _dispatch():
                with span("detectd.round", merged=n_req,
                          trace_ids=",".join(tids[:16])):
                    if staged is not None:
                        return det.dispatch_merged(preps,
                                                   staged=staged)
                    if hasattr(det, "dedup"):
                        return det.dispatch_merged(preps, plan=plan)
                    return det.dispatch_merged(preps)

            dev, offsets, t_pad = dispatch_ctx.run(_dispatch)
            METRICS.observe("trivy_tpu_detect_coalesce_size",
                            float(n_req))
            METRICS.gauge_add("trivy_tpu_dispatch_depth", 1.0)
            with self._cv:
                self._inflight_pairs += t_pad
            # graftguard-supervised fetch: a wedged/failed transfer
            # rebuilds the merged bits from each prep's host join, so
            # every coalesced request behind one bad dispatch still
            # completes (bit-identically)
            gf = self.detector._get_pool.submit(
                fetch_ctx.run, self.detector.fetch_merged, dev, preps,
                offsets, t_pad)
            items = list(chunk)
            gf.add_done_callback(
                lambda fut: self._on_fetched(fut, items, offsets,
                                             t_pad))

        for req, (slot, prep) in ((r, s) for r in pending
                                  for s in r.slots):
            if chunk and chunk_pairs + prep.n_pairs > budget:
                flush()
                chunk, chunk_pairs = [], 0
            chunk.append((req, slot, prep))
            chunk_pairs += prep.n_pairs
            if chunk_pairs >= budget:
                flush()
                chunk, chunk_pairs = [], 0
        flush()

    # ---- fetch callback (runs on the get thread) ----------------------

    def _on_fetched(self, fut, items: list, offsets: list,
                    t_pad: int) -> None:
        with self._cv:
            self._inflight_pairs -= t_pad
            self._cv.notify_all()
        METRICS.gauge_add("trivy_tpu_dispatch_depth", -1.0)
        try:
            bits = fut.result()
        except BaseException as e:  # noqa: BLE001 — device/transfer
            for req, _, _ in items:
                req.fail(e)
            return
        # hand each request its contiguous slice (dense) or recover it
        # from the compacted hit indices with one searchsorted
        # (slice_bits); the waiting handler thread assembles it
        # (DispatchScheduler.detect_many)
        for (req, slot, prep), off in zip(items, offsets):
            req.complete(slot,
                         (prep, slice_bits(bits, off, prep.n_pairs)))
