"""graftfeed tier-1 gate (trivy_tpu/detect/feed.py): the dedup plan /
scatter-back index map must be bit-identical to the undeduped path by
construction — property-tested over random duplicate densities (all
unique through all duplicate) for dense int8 and CompactBits results,
then end-to-end through the real merged, streamed-slice and mesh
dispatch paths; a c=8 duplicate-heavy hammer through detectd must stay
hit-for-hit identical to serial; the double-buffered query upload must
show steady-state stall ≈ 0 in the ledger, a hung upload must trip the
breaker and degrade to the host join bit-identically, and a faulted
slice prefetch must cost latency only."""

import glob
import os
import random
import threading

import numpy as np
import pytest

from trivy_tpu.db import build_table
from trivy_tpu.db.fixtures import load_fixture_files
from trivy_tpu.detect import feed as _feed
from trivy_tpu.detect.engine import BatchDetector, PkgQuery
from trivy_tpu.detect.sched import DispatchScheduler, SchedOptions
from trivy_tpu.metrics import METRICS
from trivy_tpu.obs.perf import LEDGER
from trivy_tpu.parallel.mesh import MeshDetector, make_mesh
from trivy_tpu.parallel.stream import StreamingDetector, StreamOptions
from trivy_tpu.resilience import FAILPOINTS, GUARD
from trivy_tpu.resilience.hostjoin import CompactBits
from trivy_tpu.resilience.storm import storm_table

from helpers import parse_exposition

FIXTURES = sorted(glob.glob(
    os.path.join(os.path.dirname(__file__), "fixtures", "db", "*.yaml")))


@pytest.fixture(scope="module")
def table():
    advisories, details, _ = load_fixture_files(FIXTURES)
    t = build_table(advisories, details)
    assert len(t) > 0
    return t


@pytest.fixture(scope="module")
def big_table():
    return storm_table(n_pkgs=96)


@pytest.fixture(autouse=True)
def _clean_guard():
    FAILPOINTS.configure("")
    GUARD.reset_for_tests()
    yield
    FAILPOINTS.configure("")
    GUARD.reset_for_tests()
    # the watchdog drills shorten the deadline; later test files in the
    # same worker must not inherit it
    GUARD.configure(dispatch_timeout_s=120.0, fail_threshold=3,
                    reset_timeout_s=5.0)


def _keys(hits):
    return [(h.query.name, h.query.version, h.vuln_id) for h in hits]


def _dense(bits) -> np.ndarray:
    return bits.dense() if isinstance(bits, CompactBits) \
        else np.asarray(bits)


# duplicate-heavy traffic: a handful of storm triples repeated across
# every request — the intra-dispatch duplication graftmemo cannot see
def _dup_queries(seed: int, n: int, pool: int = 8):
    rng = random.Random(seed)
    out = []
    for _ in range(n):
        k = rng.randrange(pool + 2)     # a couple of empty buckets too
        ver = f"{1 + k % 3}.{k % 10}.0-r0"
        out.append(PkgQuery(source="alpine 3.17", ecosystem="alpine",
                            name=f"storm-pkg-{k}", version=ver))
    return out


# ---------------------------------------------------------------------------
# plan_merged / expand_bits scatter-back properties (synthetic)


def _segment(start: int, count: int, ver: int) -> np.ndarray:
    """Deterministic per-triple pair bits: equal triples MUST map to
    equal segments (exactly the invariant the dedup contract rides)."""
    base = np.arange(count, dtype=np.int64)
    return (((start * 31 + ver * 7 + base) % 3) != 0).astype(np.int8)


def _synthetic(rng: np.random.Generator, nq: int, n_pool: int):
    """nq query triples drawn (with duplicates when n_pool < nq) from
    n_pool distinct triples, split into random prep chunks."""
    pool_start = rng.permutation(4096)[:n_pool].astype(np.int64)
    pool_count = rng.integers(1, 7, n_pool)
    pool_ver = rng.integers(0, 64, n_pool)
    pick = rng.integers(0, n_pool, nq)
    qs = pool_start[pick].astype(np.int32)
    qc = pool_count[pick].astype(np.int32)
    qv = pool_ver[pick].astype(np.int32)
    # random prep split covering all nq queries
    cuts = np.sort(rng.choice(np.arange(1, nq), size=min(3, nq - 1),
                              replace=False)) if nq > 1 else []
    prep_nq = np.diff(np.concatenate([[0], cuts, [nq]])).tolist()
    return qs, qc, qv, prep_nq


class TestPlanScatterBack:
    @pytest.mark.parametrize("nq,n_pool", [
        (24, 1),      # all 24 queries are ONE triple
        (24, 6),      # heavy duplication
        (24, 12),     # moderate
        (7, 3),       # small, uneven preps
    ])
    def test_dense_scatter_is_bit_identical(self, nq, n_pool):
        rng = np.random.default_rng(nq * 100 + n_pool)
        qs, qc, qv, prep_nq = _synthetic(rng, nq, n_pool)
        plan = _feed.plan_merged(qs, qc, qv, prep_nq)
        assert plan is not None
        assert plan.n_unique <= n_pool
        assert plan.total == int(qc.sum())
        assert plan.unique_total == int(plan.u_count.sum())
        assert plan.unique_total < plan.total
        # cost attribution: first occurrence owns, duplicates collapse,
        # and together they account for every real pair
        assert int(plan.unique_by_prep.sum()) == plan.unique_total
        assert int(plan.unique_by_prep.sum()
                   + plan.collapsed_by_prep.sum()) == plan.total
        # unique-space join result + expected global result, both from
        # the same per-triple segment function
        bits_u = np.concatenate(
            [_segment(int(s), int(c), int(v)) for s, c, v in
             zip(plan.u_start, plan.u_count, plan.u_ver)])
        expect = np.concatenate(
            [_segment(int(s), int(c), int(v)) for s, c, v in
             zip(qs, qc, qv)])
        t_pad = plan.total + 13
        out = _feed.expand_bits(plan, bits_u, t_pad)
        assert out.shape == (t_pad,)
        np.testing.assert_array_equal(out[:plan.total], expect)
        assert not out[plan.total:].any()

    @pytest.mark.parametrize("nq,n_pool", [(24, 1), (24, 6), (9, 4)])
    def test_compact_scatter_is_bit_identical(self, nq, n_pool):
        """The CompactBits scatter must agree with the dense one AND
        keep the pair_idx strictly ascending (the searchsorted slice
        contract every downstream consumer indexes by)."""
        rng = np.random.default_rng(7000 + nq * 10 + n_pool)
        qs, qc, qv, prep_nq = _synthetic(rng, nq, n_pool)
        plan = _feed.plan_merged(qs, qc, qv, prep_nq)
        assert plan is not None
        bits_u = np.concatenate(
            [_segment(int(s), int(c), int(v)) for s, c, v in
             zip(plan.u_start, plan.u_count, plan.u_ver)])
        t_pad = plan.total + 5
        dense = _feed.expand_bits(plan, bits_u, t_pad)
        nz = np.nonzero(bits_u)[0]
        cb_u = CompactBits(nz.astype(np.int32), bits_u[nz],
                           len(bits_u))
        cb = _feed.expand_bits(plan, cb_u, t_pad)
        assert isinstance(cb, CompactBits)
        assert cb.n_pairs == t_pad
        if cb.pair_idx.size > 1:
            assert (np.diff(cb.pair_idx) > 0).all()
        np.testing.assert_array_equal(cb.dense(), dense)

    def test_compact_scatter_empty_hits(self):
        rng = np.random.default_rng(3)
        qs, qc, qv, prep_nq = _synthetic(rng, 16, 4)
        plan = _feed.plan_merged(qs, qc, qv, prep_nq)
        cb = _feed.expand_bits(
            plan, CompactBits(np.zeros(0, np.int32),
                              np.zeros(0, np.int8),
                              plan.unique_total), plan.total + 7)
        assert cb.pair_idx.size == 0 and cb.n_pairs == plan.total + 7

    def test_all_unique_returns_none(self):
        """Duplicate-free traffic must stay byte-for-byte on the old
        path — the zero-cost exit."""
        rng = np.random.default_rng(11)
        qs, qc, qv, prep_nq = _synthetic(rng, 16, 16)
        # force distinct triples (distinct starts are enough)
        qs = np.arange(16, dtype=np.int32)
        assert _feed.plan_merged(qs, qc, qv, prep_nq) is None

    def test_degenerate_sizes_return_none(self):
        one = np.asarray([5], np.int32)
        assert _feed.plan_merged(one, one, one, [1]) is None
        z = np.zeros(0, np.int32)
        assert _feed.plan_merged(z, z, z, []) is None


# ---------------------------------------------------------------------------
# the real merged-dispatch paths: single chip, streamed slices, mesh


class TestDetectorDedupPaths:
    def _preps(self, det, seed: int, n_batches: int = 5):
        batches = [_dup_queries(seed + b, 20) for b in range(n_batches)]
        return [p for p in (det._prepare(b) for b in batches)
                if p is not None and p.n_pairs > 0]

    @pytest.mark.parametrize("compact", [False, True])
    def test_merged_dispatch_dedup_bits_identical(self, big_table,
                                                  compact):
        """dispatch_merged with the dedup plan (dense and compact hit
        shapes) must produce the very bits the dedup-off dispatch
        does, over the full merged pair space."""
        kw = dict(hit_floor=8, hit_align=8) if compact \
            else dict(compact=False)
        d_on = BatchDetector(big_table, dedup=True, **kw)
        d_off = BatchDetector(big_table, dedup=False, **kw)
        try:
            p_on = self._preps(d_on, 500)
            p_off = self._preps(d_off, 500)
            total = sum(p.n_pairs for p in p_on)
            dev, off_on, tp_on = d_on.dispatch_merged(p_on)
            # duplicates exist by construction, so the plan engaged
            assert isinstance(dev, _feed.PendingExpand)
            assert dev.plan.unique_total < total
            bits_on = _dense(
                d_on.fetch_merged(dev, p_on, off_on, tp_on))
            dev2, off2, tp2 = d_off.dispatch_merged(p_off)
            assert not isinstance(dev2, _feed.PendingExpand)
            bits_off = _dense(
                d_off.fetch_merged(dev2, p_off, off2, tp2))
            assert (off_on, tp_on) == (off2, tp2)
            np.testing.assert_array_equal(bits_on[:total],
                                          bits_off[:total])
        finally:
            d_on.close()
            d_off.close()

    def test_deduped_fetch_failure_host_rebuild_identical(self,
                                                          big_table):
        """A deduped dispatch whose FETCH fails rebuilds the host join
        over the SAME unique descriptor set and scatters identically —
        the hostjoin contract survives dedup."""
        det = BatchDetector(big_table, dedup=True)
        try:
            preps = self._preps(det, 640)
            dev, offsets, t_pad = det.dispatch_merged(preps)
            assert isinstance(dev, _feed.PendingExpand)
            want = _dense(det.fetch_merged(dev, preps, offsets, t_pad))
            dev2, off2, tp2 = det.dispatch_merged(preps)
            GUARD.configure(fail_threshold=100, reset_timeout_s=60.0)
            FAILPOINTS.set("detect.device_get", "error")
            got = _dense(det.fetch_merged(dev2, preps, off2, tp2))
            np.testing.assert_array_equal(got, want)
        finally:
            det.close()

    def test_streamed_dedup_parity(self, big_table):
        """Duplicate-heavy traffic through the slice walk: the plan
        clips per slice exactly like the full descriptor set would."""
        dev = big_table.device_nbytes()
        sd = StreamingDetector(
            big_table,
            StreamOptions(device_budget_mb=dev / (4 * (1 << 20))))
        bd = BatchDetector(big_table, dedup=False)
        batches = [_dup_queries(70 + b, 24) for b in range(5)]
        try:
            assert sd.n_slices >= 2
            expect = bd.detect_many(batches)
            got = sd.detect_many(batches)
            assert [_keys(h) for h in got] == \
                [_keys(h) for h in expect]
            assert sum(len(h) for h in expect) > 0
        finally:
            sd.close()
            bd.close()

    @pytest.mark.parametrize("db_shards", [1, 2])
    def test_mesh_dedup_parity(self, big_table, db_shards):
        mesh = make_mesh(8, db_shards=db_shards)
        md = MeshDetector(big_table, mesh, db_shards=db_shards)
        bd = BatchDetector(big_table, dedup=False)
        batches = [_dup_queries(90 + b, 24) for b in range(4)]
        try:
            expect = bd.detect_many(batches)
            got = md.detect_many(batches)
            assert [_keys(h) for h in got] == \
                [_keys(h) for h in expect]
        finally:
            md.close()
            bd.close()


# ---------------------------------------------------------------------------
# detectd end to end: dedup hammer, upload ledger, failure drills


def _hammer(sched, requests, n_threads=8):
    results: list = [None] * len(requests)
    errors: list = []

    def worker(ids):
        try:
            for i in ids:
                results[i] = sched.detect_many(requests[i])
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    ts = [threading.Thread(
        target=worker, args=(range(k, len(requests), n_threads),))
        for k in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    return results, errors


class TestDetectdDedup:
    def _requests(self, n=24):
        # every request draws from the SAME few triples: the coalesced
        # rounds are duplicate-saturated across requests
        return [[_dup_queries(200 + r * 2 + b, 16) for b in range(2)]
                for r in range(n)]

    def test_c8_duplicate_hammer_equals_serial(self, big_table):
        """c=8 duplicate-heavy hammer through detectd(dedup=True):
        hit-for-hit identical (order included) to serial, with the
        dedup-ratio histogram actually observing collapsed rounds."""
        requests = self._requests()
        serial = BatchDetector(big_table, dedup=False)
        expected = [serial.detect_many(b) for b in requests]
        serial.close()
        det = BatchDetector(big_table, dedup=True)
        sched = DispatchScheduler(
            det, SchedOptions(coalesce_wait_ms=5.0, dedup=True))
        try:
            results, errors = _hammer(sched, requests)
        finally:
            sched.close()
            det.close()
        assert not errors
        assert results == expected
        fam = parse_exposition(METRICS.render())[
            "trivy_tpu_detect_dedup_ratio"]
        counts = [v for n, _l, v in fam["samples"]
                  if n.endswith("_count")]
        assert counts and counts[0] > 0

    def test_dedup_off_is_identical_too(self, big_table):
        requests = self._requests(n=8)
        serial = BatchDetector(big_table, dedup=False)
        expected = [serial.detect_many(b) for b in requests]
        serial.close()
        det = BatchDetector(big_table)
        sched = DispatchScheduler(
            det, SchedOptions(coalesce_wait_ms=5.0, dedup=False))
        try:
            results, errors = _hammer(sched, requests, n_threads=4)
        finally:
            sched.close()
            det.close()
        assert not errors
        assert results == expected

    def test_query_upload_ledger_steady_state(self, big_table):
        """Every detectd dispatch consumes a PRE-STAGED query upload:
        the query_upload ledger rows must show prefetched == uploads
        and zero cold waits — the asserted steady-state stall ≈ 0
        property, plus exposition of the new transfer path."""
        LEDGER.reset_for_tests()
        det = BatchDetector(big_table)
        sched = DispatchScheduler(det, SchedOptions())
        try:
            for r in range(6):
                sched.detect_many([_dup_queries(300 + r, 16)])
        finally:
            sched.close()
            det.close()
        stats = LEDGER.shard_upload_stats()["query_upload"]
        assert stats["uploads"] >= 6
        assert stats["prefetched"] == stats["uploads"]
        assert stats["cold_waits"] == 0
        assert stats["bytes"] > 0
        assert stats["stall_ms"] >= stats["cold_stall_ms"] == 0
        agg = LEDGER.aggregate()
        assert agg["transfer_bytes"]["query_upload"] == stats["bytes"]
        families = parse_exposition(METRICS.render())
        transfer = families["trivy_tpu_device_transfer_bytes_total"]
        upload = [v for _n, labels, v in transfer["samples"]
                  if labels.get("path") == "query_upload"]
        assert upload and upload[0] > 0

    def test_c8_hung_query_upload_degrades_bit_identical(self,
                                                         big_table):
        """The ISSUE drill: detect.query_upload=hang at c=8 — the
        staging watch trips the watchdog, the breaker opens, and every
        request still completes via the host join hit-for-hit
        identical to serial."""
        requests = self._requests(n=16)
        serial = BatchDetector(big_table, dedup=False)
        expected = [serial.detect_many(b) for b in requests]
        serial.close()
        GUARD.configure(dispatch_timeout_s=0.02, fail_threshold=3,
                        reset_timeout_s=60.0)
        trips0 = METRICS.get("trivy_tpu_device_watchdog_trips_total")
        FAILPOINTS.set("detect.query_upload", "hang", 80.0)
        det = BatchDetector(big_table)
        sched = DispatchScheduler(
            det, SchedOptions(coalesce_wait_ms=3.0))
        try:
            results, errors = _hammer(sched, requests)
        finally:
            sched.close()
            det.close()
        assert not errors
        assert results == expected
        assert METRICS.get("trivy_tpu_device_watchdog_trips_total") \
            > trips0
        assert GUARD.breaker.status()["opens_total"] >= 1

    def test_query_upload_error_and_flaky_stay_identical(self,
                                                         big_table):
        """error / seeded-flaky staging faults degrade the paired
        dispatch to the host join without ever surfacing to callers."""
        requests = self._requests(n=8)
        serial = BatchDetector(big_table, dedup=False)
        expected = [serial.detect_many(b) for b in requests]
        serial.close()
        for mode, arg in (("error", 0.0), ("flaky", 0.5)):
            GUARD.configure(fail_threshold=3, reset_timeout_s=0.05)
            FAILPOINTS.set("detect.query_upload", mode, arg, seed=13)
            det = BatchDetector(big_table)
            sched = DispatchScheduler(
                det, SchedOptions(coalesce_wait_ms=3.0))
            try:
                results, errors = _hammer(sched, requests,
                                          n_threads=4)
            finally:
                sched.close()
                det.close()
            assert not errors
            assert results == expected
            FAILPOINTS.configure("")
            GUARD.reset_for_tests()

    def test_stream_prefetch_fault_is_latency_only(self, big_table):
        """A faulted admission prefetch (stream.prefetch=error) must
        cost only the lost overlap: results identical, no error
        escapes, and the breaker never even counts it."""
        dev = big_table.device_nbytes()
        batches = [_dup_queries(400 + b, 24) for b in range(6)]
        serial = BatchDetector(big_table, dedup=False)
        expected = serial.detect_many(batches)
        serial.close()
        FAILPOINTS.set("stream.prefetch", "error")
        sd = StreamingDetector(
            big_table,
            StreamOptions(device_budget_mb=dev / (4 * (1 << 20))))
        sched = DispatchScheduler(
            sd, SchedOptions(coalesce_wait_ms=3.0, prefetch=True))
        out: dict = {}
        try:
            ts = [threading.Thread(
                target=lambda k=k: out.__setitem__(
                    k, sched.detect_many(batches[3 * k:3 * k + 3])))
                for k in range(2)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            got = out[0] + out[1]
        finally:
            sched.close()
            sd.close()
        assert [_keys(h) for h in got] == \
            [_keys(h) for h in expected]
        assert GUARD.breaker.state_name() == "closed"

    def test_prefetch_ranges_warms_touched_slices(self, big_table):
        """The admission peek's entry point: prefetch_ranges on the
        pending descriptors uploads exactly the touched, non-resident
        slices (prefetched rows, no cold waits charged)."""
        dev = big_table.device_nbytes()
        sd = StreamingDetector(
            big_table,
            StreamOptions(device_budget_mb=dev / (4 * (1 << 20))))
        try:
            LEDGER.reset_for_tests()
            prep = sd._prepare(_dup_queries(77, 24))
            assert prep is not None and prep.n_pairs > 0
            sd.prefetch_ranges(prep.q_start[:prep.n_queries],
                               prep.q_count[:prep.n_queries])
            stats = LEDGER.shard_upload_stats()["stream"]
            assert stats["uploads"] >= 1
            assert stats["prefetched"] == stats["uploads"]
            assert stats["cold_waits"] == 0
        finally:
            sd.close()
