"""detectd (trivy_tpu/detect/sched.py) tier-1 gate: the coalescing
scheduler must be hit-for-hit identical (order included) to serial
per-request detect_many under concurrent load, the pipelined
detect_many must match the staged path, close() must be idempotent and
leave no worker threads, and the bucket ladder / per-dispatch metrics
must behave."""

import glob
import os
import random
import threading
import time

import pytest

from trivy_tpu.db import build_table
from trivy_tpu.db.fixtures import load_fixture_files
from trivy_tpu.detect import (
    BatchDetector, DispatchScheduler, PkgQuery, SchedOptions,
)
from trivy_tpu.metrics import METRICS
from trivy_tpu.ops import bucket_ladder, bucket_size, next_pow2

FIXTURES = sorted(glob.glob(
    os.path.join(os.path.dirname(__file__), "fixtures", "db", "*.yaml")))


@pytest.fixture(scope="module")
def table():
    advisories, details, _ = load_fixture_files(FIXTURES)
    t = build_table(advisories, details)
    assert len(t) > 0
    return t


# query pool: known-vulnerable, known-clean, unknown-package
# (empty-bucket), and unparseable-version shapes — the mix detectd
# must scatter back correctly
_POOL = [
    ("alpine 3.17", "alpine", "openssl", "3.0.7-r0"),
    ("alpine 3.17", "alpine", "openssl", "3.0.8-r0"),
    ("alpine 3.17", "alpine", "musl", "1.2.3-r4"),
    ("alpine 3.17", "alpine", "zlib", "1.2.12-r2"),
    ("alpine 3.18", "alpine", "openssl", "3.0.8-r0"),
    ("debian 11", "debian", "openssl", "1.1.1n-0+deb11u3"),
    ("debian 11", "debian", "bash", "5.1-2+deb11u1"),
    ("pip::GitHub Security Advisory Pip", "pip", "flask", "2.2.2"),
    ("pip::GitHub Security Advisory Pip", "pip", "flask", "2.3.1"),
    ("pip::GitHub Security Advisory Pip", "pip", "requests", "2.30.0"),
    ("npm::GitHub Security Advisory Npm", "npm", "lodash", "4.17.20"),
    ("debian 11", "debian", "openssl", "not!!a@version"),
]


def _rand_query(rng: random.Random, i: int) -> PkgQuery:
    # ~60% empty-bucket queries: most packages in a real image have no
    # advisories, and the CSR merge must stay correct when whole
    # batches prep down to nothing
    if rng.random() < 0.6:
        return PkgQuery(source="alpine 3.17", ecosystem="alpine",
                        name=f"no-such-package-{i}", version="1.0.0")
    s, e, n, v = _POOL[rng.randrange(len(_POOL))]
    return PkgQuery(source=s, ecosystem=e, name=n, version=v, ref=i)


def _rand_requests(seed: int, n_requests: int):
    rng = random.Random(seed)
    reqs = []
    for _ in range(n_requests):
        reqs.append([
            [_rand_query(rng, rng.randrange(1000))
             for _ in range(rng.randrange(0, 14))]
            for _ in range(rng.randrange(1, 4))
        ])
    return reqs


class TestEquivalence:
    def test_hammer_coalesced_equals_serial(self, table):
        """N threads hammer the coalescing scheduler; every request's
        results must be hit-for-hit identical (order included) to a
        serial per-request detect_many on a fresh detector."""
        requests = _rand_requests(11, 24)
        serial = BatchDetector(table)
        expected = [serial.detect_many(b) for b in requests]
        serial.close()

        det = BatchDetector(table)
        sched = DispatchScheduler(det, SchedOptions(coalesce_wait_ms=5.0))
        results: list = [None] * len(requests)
        errors: list = []

        def worker(ids):
            try:
                for i in ids:
                    results[i] = sched.detect_many(requests[i])
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(
            target=worker, args=(range(k, len(requests), 6),))
            for k in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        sched.close()
        det.close()
        assert not errors
        assert results == expected

    def test_hammer_empty_bucket_heavy(self, table):
        """All-empty and tiny requests: the degenerate workload where
        most requests never reach the device at all."""
        rng = random.Random(3)
        requests = []
        for r in range(16):
            requests.append([[
                PkgQuery(source="alpine 3.17", ecosystem="alpine",
                         name=f"ghost-{rng.randrange(50)}",
                         version="1.0")
                for _ in range(rng.randrange(0, 6))]])
        serial = BatchDetector(table)
        expected = [serial.detect_many(b) for b in requests]
        serial.close()
        det = BatchDetector(table)
        sched = DispatchScheduler(det, SchedOptions(coalesce_wait_ms=2.0))
        results = [None] * len(requests)

        def worker(ids):
            for i in ids:
                results[i] = sched.detect_many(requests[i])

        threads = [threading.Thread(
            target=worker, args=(range(k, len(requests), 4),))
            for k in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        sched.close()
        det.close()
        assert results == expected

    def test_pipelined_detect_many_equals_per_batch(self, table):
        """The staged-pipeline detect_many must match one-batch-at-a-
        time calls on a fresh detector (the pre-pipelining shape)."""
        requests = _rand_requests(7, 10)
        flat = [b for req in requests for b in req]
        serial = BatchDetector(table)
        expected = [serial.detect_many([b])[0] for b in flat]
        serial.close()
        det = BatchDetector(table)
        got = det.detect_many(flat)
        det.close()
        assert got == expected

    def test_merged_dispatch_bits_identical(self, table):
        """The coalescing primitive itself: each prep's slice of a
        merged dispatch equals its solo dispatch, bit for bit."""
        import jax
        det = BatchDetector(table)
        requests = _rand_requests(5, 6)
        preps = [det._prepare(req[0]) for req in requests]
        preps = [p for p in preps if p is not None and p.n_pairs]
        assert len(preps) >= 2
        dev, offsets, t_pad = det.dispatch_merged(preps)
        assert t_pad >= sum(p.n_pairs for p in preps)
        # fetch through the contract path: a deduped merged dispatch
        # resolves its unique-space result + scatter-back here
        bits = det.fetch_merged(dev, preps, offsets, t_pad)
        for p, off in zip(preps, offsets):
            solo = jax.device_get(det._dispatch(p))[:p.n_pairs]
            assert (bits[off:off + p.n_pairs] == solo).all()
        det.close()

    def test_small_pair_budget_still_correct(self, table):
        """A max_pairs_in_flight smaller than one request forces
        chunked merged dispatches and pipeline backpressure — results
        must not change."""
        requests = _rand_requests(13, 8)
        serial = BatchDetector(table)
        expected = [serial.detect_many(b) for b in requests]
        serial.close()
        det = BatchDetector(table, max_pairs_in_flight=128)
        sched = DispatchScheduler(det, SchedOptions(
            coalesce_wait_ms=5.0, max_pairs_in_flight=128))
        got = [sched.detect_many(b) for b in requests]
        sched.close()
        det.close()
        assert got == expected


class TestLifecycle:
    def _thread_names(self):
        return [t.name for t in threading.enumerate()]

    def test_close_idempotent_and_no_threads_survive(self, table):
        # snapshot first: other fixtures (module-scoped detectors,
        # background servers) may legitimately hold their own workers
        before = set(threading.enumerate())
        det = BatchDetector(table)
        sched = DispatchScheduler(det, SchedOptions(coalesce_wait_ms=1.0))
        qs = [PkgQuery(source="alpine 3.17", ecosystem="alpine",
                       name="openssl", version="3.0.7-r0")]
        assert sched.detect(qs)
        sched.close()
        sched.close()   # idempotent
        det.close()
        det.close()     # idempotent
        leftover = [t for t in threading.enumerate()
                    if t not in before and t.is_alive()]
        assert leftover == [], [t.name for t in leftover]

    def test_submit_after_close_raises(self, table):
        det = BatchDetector(table)
        sched = DispatchScheduler(det)
        sched.close()
        with pytest.raises(RuntimeError):
            sched.submit([[PkgQuery(source="alpine 3.17",
                                    ecosystem="alpine", name="openssl",
                                    version="3.0.7-r0")]])
        det.close()

    def test_swap_waits_for_straddling_request(self, table, tmp_path):
        """A request started before swap_table may hold the OLD scanner
        for its whole lifetime: the old engine must stay usable until
        that request finishes, then close."""
        import time as _time

        from trivy_tpu.server.listen import ServerState
        state = ServerState(table, str(tmp_path))
        old = state.scanner
        gen = state.request_started()     # straddling request
        state.swap_table(table)
        assert state.scanner is not old
        # the straddler can still detect on the old scanner
        hits = old.detector.detect([PkgQuery(
            source="alpine 3.17", ecosystem="alpine",
            name="openssl", version="3.0.7-r0")])
        assert hits
        state.request_finished(gen)
        # the drain waiter retires the old engine shortly after
        for _ in range(200):
            if old.detector._closed:
                break
            _time.sleep(0.05)
        assert old.detector._closed
        state.close()

    def test_server_state_swap_and_close_join_workers(self, table,
                                                      tmp_path):
        """swap_table must retire the OLD scanner's executors (the
        pre-detectd leak: one stranded get-thread per swap) and
        close() the new one's."""
        from trivy_tpu.server.listen import ServerState
        before = {t for t in threading.enumerate()}
        state = ServerState(table, str(tmp_path))
        state.swap_table(table)
        state.swap_table(table)
        state.close()
        after = [t for t in threading.enumerate()
                 if t not in before and t.is_alive()
                 and t.name.startswith(("detectd", "detect-get",
                                        "detect-asm"))]
        assert after == []


class TestBucketLadder:
    def test_growth_two_matches_next_pow2(self):
        for n in (0, 1, 7, 255, 256, 257, 1000, 4096, 70000):
            assert bucket_size(n, 256, 2.0) == next_pow2(n, 256)
            assert bucket_size(n, 64, 2.0, align=64) == next_pow2(n, 64)

    def test_sub_two_growth_is_monotonic_aligned_and_denser(self):
        prev = 0
        for n in range(1, 50000, 777):
            b = bucket_size(n, 256, 1.5)
            assert b >= n and b >= prev
            assert b % 128 == 0
            prev = b
        # a 1.5x ladder wastes less padding than pow2 on this shape
        assert bucket_size(70000, 256, 1.5) < next_pow2(70000, 256)

    def test_ladder_covers_max_and_matches_bucket_size(self):
        rungs = bucket_ladder(100_000, 256, 2.0)
        assert rungs[0] == 256 and rungs[-1] >= 100_000
        assert rungs == sorted(set(rungs))
        for r in rungs:
            assert bucket_size(r, 256, 2.0) == r

    def test_bad_growth_rejected(self):
        with pytest.raises(ValueError):
            bucket_size(10, 256, 1.0)


class TestMetricsPerDispatch:
    def test_warmup_counts_compiles_and_skips_traffic_series(self, table):
        det = BatchDetector(table)
        c0 = METRICS.get("trivy_tpu_detect_compiles_total")
        b0 = METRICS.get("trivy_tpu_detect_batches_total")
        rungs = det.warmup(max_pairs=1 << 11)
        assert rungs >= 1
        assert METRICS.get("trivy_tpu_detect_compiles_total") \
            >= c0 + rungs
        # warmup dispatches are compiles, not traffic
        assert METRICS.get("trivy_tpu_detect_batches_total") == b0
        det.close()

    def test_coalesced_dispatch_counts_once(self, table):
        """Satellite guard: N coalesced requests must account ONE
        dispatch (occupancy observation + batch count), not N."""
        det = BatchDetector(table)
        preps = []
        for req in _rand_requests(17, 8):
            p = det._prepare(req[0])
            if p is not None and p.n_pairs:
                preps.append(p)
        assert len(preps) >= 2
        _row, s0, n0 = METRICS.hist_get("trivy_tpu_batch_occupancy_ratio")
        b0 = METRICS.get("trivy_tpu_detect_batches_total")
        det.dispatch_merged(preps)
        _row, s1, n1 = METRICS.hist_get("trivy_tpu_batch_occupancy_ratio")
        assert n1 == n0 + 1
        assert METRICS.get("trivy_tpu_detect_batches_total") == b0 + 1
        det.close()

    def test_scheduler_emits_coalesce_and_queue_series(self, table):
        det = BatchDetector(table)
        sched = DispatchScheduler(det, SchedOptions(coalesce_wait_ms=1.0))
        _r, _s, c0 = METRICS.hist_get("trivy_tpu_detect_coalesce_size")
        sched.detect([PkgQuery(source="alpine 3.17", ecosystem="alpine",
                               name="openssl", version="3.0.7-r0")])
        _r, _s, c1 = METRICS.hist_get("trivy_tpu_detect_coalesce_size")
        assert c1 == c0 + 1
        _r, _s, q1 = METRICS.hist_get("trivy_tpu_detect_queue_depth")
        assert q1 >= 1
        sched.close()
        det.close()
        assert METRICS.get("trivy_tpu_dispatch_depth") == 0

    def test_queue_wait_is_observed_once_per_request(self, table):
        det = BatchDetector(table)
        sched = DispatchScheduler(det, SchedOptions(coalesce_wait_ms=2.0))
        name = "trivy_tpu_detect_queue_wait_seconds"
        _r, s0, c0 = METRICS.hist_get(name)
        requests = _rand_requests(11, 12)
        t0 = time.perf_counter()
        threads = [threading.Thread(target=sched.detect_many, args=(r,))
                   for r in requests]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t0
        _r, s1, c1 = METRICS.hist_get(name)
        # one observation per request that reached a dispatch (a
        # request whose batches prep to no pairs resolves unqueued),
        # however many chunks its slots split over
        queued = sum(1 for r in requests
                     if any(p is not None and p.n_pairs
                            for p in map(det._prepare, filter(None, r))))
        sched.close()
        det.close()
        assert c1 - c0 == queued > 0
        assert 0 <= s1 - s0 <= queued * elapsed


class _FakeReq:
    """Bare stand-in for _Request — the fair-queue helpers only read
    .tenant and .n_pairs."""
    __slots__ = ("tenant", "n_pairs")

    def __init__(self, tenant, n_pairs):
        self.tenant = tenant
        self.n_pairs = n_pairs


def _bare_sched(share=1.0):
    """A DispatchScheduler shell with ONLY the graftfair state — no
    dispatcher thread, no detector. The _locked helpers are pure
    data-structure code, so the unit tests drive them directly."""
    from collections import deque
    s = DispatchScheduler.__new__(DispatchScheduler)
    s.opts = SchedOptions(tenant_max_share=share)
    s._fair = {}
    s._rr = deque()
    s._deficit = {}
    s._fair_pairs = 0
    return s


class TestFairQueue:
    """graftfair DRR sweep unit gate: share cap, deficit carry, and
    forced progress."""

    def test_share_cap_bounds_flooding_tenant(self):
        s = _bare_sched(share=0.5)
        for _ in range(20):
            s._fair_put_locked(_FakeReq("flood", 1))
        for _ in range(2):
            s._fair_put_locked(_FakeReq("victim", 1))
        taken = s._fair_take_locked(10)
        by = {}
        for r in taken:
            by[r.tenant] = by.get(r.tenant, 0) + r.n_pairs
        # the flooder never exceeds share * budget while the victim is
        # pending, and the victim's whole (small) queue drains now
        assert by["flood"] <= 5
        assert by["victim"] == 2
        assert s._fair_pairs == 22 - sum(by.values())

    def test_solo_tenant_gets_full_budget_despite_share(self):
        s = _bare_sched(share=0.25)
        for _ in range(8):
            s._fair_put_locked(_FakeReq("solo", 1))
        taken = s._fair_take_locked(8)
        assert len(taken) == 8       # no cap with one active tenant
        assert s._fair_pairs == 0

    def test_deficit_carries_across_rounds(self):
        """A big head that outweighs one round's quantum waits, banking
        credit, then dispatches once the deficit covers it — classic
        DRR, no starvation and no oversized early grab."""
        s = _bare_sched()
        s._fair_put_locked(_FakeReq("small", 1))   # first in rotation
        s._fair_put_locked(_FakeReq("small", 1))
        s._fair_put_locked(_FakeReq("big", 6))
        r1 = s._fair_take_locked(4)    # quantum = 2 per tenant
        assert [r.tenant for r in r1] == ["small", "small"]
        assert s._deficit["big"] >= 2.0  # banked, not spent
        r2 = s._fair_take_locked(4)
        assert [r.tenant for r in r2] == ["big"]
        assert s._fair_pairs == 0

    def test_forced_progress_oversize_head(self):
        """A head larger than the entire budget still dispatches —
        alone — instead of wedging the queue forever."""
        s = _bare_sched()
        s._fair_put_locked(_FakeReq("whale", 1000))
        s._fair_put_locked(_FakeReq("whale", 1))
        taken = s._fair_take_locked(8)
        assert len(taken) >= 1
        assert taken[0].n_pairs == 1000
        assert s._fair_pairs <= 1

    def test_rotation_rotates_between_rounds(self):
        s = _bare_sched()
        s._fair_put_locked(_FakeReq("a", 1))
        s._fair_put_locked(_FakeReq("b", 1))
        order0 = list(s._rr)
        s._fair_take_locked(1)
        assert list(s._rr) == order0[1:] + order0[:1]
