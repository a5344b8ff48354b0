"""Parity of the two advisory-table placements against the host oracle.

A table is either resident whole on one chip (`BatchDetector`) or
db-sharded over a dp×db mesh (`MeshDetector`). Every placement —
one chip, mesh db 1, db 2, db 4 over the 8 virtual CPU devices of
conftest.py — must answer hit for hit what the NumPy host join answers
(resilience/hostjoin.py, served by the detector's own host path with
the breaker held open): on the device path, under an injected
dispatch fault, when tiny hit buffers overflow into the dense fetch,
and through detectd's merged rounds."""

import random
import threading

import pytest

from trivy_tpu.detect.engine import BatchDetector, PkgQuery
from trivy_tpu.detect.sched import DispatchScheduler, SchedOptions
from trivy_tpu.metrics import METRICS
from trivy_tpu.obs.perf import LEDGER
from trivy_tpu.parallel.mesh import MeshDetector, make_mesh
from trivy_tpu.resilience import FAILPOINTS, GUARD
from trivy_tpu.resilience.storm import storm_table

N_PKGS = 96
PLACEMENTS = ("chip", "mesh-db1", "mesh-db2", "mesh-db4")


@pytest.fixture(scope="module")
def table():
    return storm_table(n_pkgs=N_PKGS)


@pytest.fixture(autouse=True)
def _clean_guard():
    FAILPOINTS.configure("")
    GUARD.reset_for_tests()
    yield
    FAILPOINTS.configure("")
    GUARD.reset_for_tests()


def _build(table, placement: str, **kw):
    if placement == "chip":
        return BatchDetector(table, **kw)
    db = int(placement.removeprefix("mesh-db"))
    return MeshDetector(table, make_mesh(8, db_shards=db),
                        db_shards=db, **kw)


def _query(k: int, ver: str) -> PkgQuery:
    return PkgQuery(source="alpine 3.17", ecosystem="alpine",
                    name=f"storm-pkg-{k}", version=ver)


def _uniform(seed: int, n: int) -> list:
    rng = random.Random(seed)
    return [_query(rng.randrange(N_PKGS + 8),
                   f"{rng.randrange(1, 4)}.{rng.randrange(10)}.0-r0")
            for _ in range(n)]


def _duplicate_heavy(seed: int, n: int) -> list:
    # six distinct triples, each repeated ~n/6 times
    pool = _uniform(seed, 6)
    rng = random.Random(seed + 1)
    return [rng.choice(pool) for _ in range(n)]


def _empty_heavy(seed: int, n: int) -> list:
    # ~80% of the names have no bucket in the table
    rng = random.Random(seed)
    return [_query(rng.randrange(N_PKGS) if rng.random() < 0.2
                   else N_PKGS + rng.randrange(1000),
                   f"{rng.randrange(1, 4)}.{rng.randrange(10)}.0-r0")
            for _ in range(n)]


MIXES = {"uniform": _uniform, "duplicate_heavy": _duplicate_heavy,
         "empty_heavy": _empty_heavy}


def _keys(hits):
    return [(h.query.name, h.query.version, h.vuln_id) for h in hits]


def _host_oracle(table, batches) -> list:
    """Hits from the host join, as BatchDetector serves them when the
    breaker is open and the device is never touched."""
    reset_s = GUARD.breaker.reset_timeout_s
    GUARD.configure(reset_timeout_s=3600.0)
    GUARD.breaker.trip()
    bd = BatchDetector(table)
    fb0 = METRICS.get("trivy_tpu_fallback_joins_total")
    try:
        out = [_keys(h) for h in bd.detect_many(batches)]
        assert METRICS.get("trivy_tpu_fallback_joins_total") > fb0
        return out
    finally:
        bd.close()
        GUARD.configure(reset_timeout_s=reset_s)
        GUARD.reset_for_tests()


def _device_dispatches(placement: str) -> float:
    if placement == "chip":
        return METRICS.get("trivy_tpu_device_dispatches_total",
                           site="detect")
    return METRICS.get("trivy_tpu_mesh_dispatches_total", path="device")


@pytest.mark.parametrize("mix", sorted(MIXES))
@pytest.mark.parametrize("placement", PLACEMENTS)
def test_placement_matches_host_join(table, placement, mix):
    batches = [MIXES[mix](10 + s, 48) for s in range(6)]
    expect = _host_oracle(table, batches)
    assert sum(len(h) for h in expect) > 0
    det = _build(table, placement)
    fb0 = METRICS.get("trivy_tpu_fallback_joins_total")
    dev0 = _device_dispatches(placement)
    try:
        got = [_keys(h) for h in det.detect_many(batches)]
    finally:
        det.close()
    assert got == expect
    # the device path served every batch: no host fallback
    assert METRICS.get("trivy_tpu_fallback_joins_total") == fb0
    assert _device_dispatches(placement) > dev0
    assert GUARD.breaker.state_name() == "closed"


@pytest.mark.parametrize("placement", PLACEMENTS)
def test_placement_host_fallback_identical(table, placement):
    """An armed detect.dispatch=error — the site each placement's
    launch is watched under — still answers bit-identically."""
    batches = [_uniform(40 + s, 32) for s in range(4)]
    expect = _host_oracle(table, batches)
    det = _build(table, placement)
    GUARD.configure(fail_threshold=1, reset_timeout_s=60.0)
    FAILPOINTS.set("detect.dispatch", "error")
    fb0 = METRICS.get("trivy_tpu_fallback_joins_total")
    mesh_fb0 = METRICS.get("trivy_tpu_mesh_dispatches_total",
                           path="host")
    try:
        got = [_keys(h) for h in det.detect_many(batches)]
    finally:
        det.close()
    assert got == expect
    if placement == "chip":
        assert METRICS.get("trivy_tpu_fallback_joins_total") > fb0
    else:
        assert METRICS.get("trivy_tpu_mesh_dispatches_total",
                           path="host") >= mesh_fb0 + len(batches)
    # the first dispatch errored and opened the breaker (threshold 1)
    assert GUARD.breaker.state_name() == "open"


@pytest.mark.parametrize("placement", PLACEMENTS)
def test_placement_compact_overflow_identical(table, placement):
    """Hit buffers floored at 8 overflow on a hit-dense batch; the
    checked dense re-fetch keeps the bits those of the host join."""
    # low installed versions: almost every pair is affected
    dense = [[_query(k, "1.0.0-r0") for k in range(N_PKGS)]]
    sparse = [_uniform(9, 64)]
    LEDGER.reset_for_tests()
    det = _build(table, placement, hit_floor=8, hit_align=8)
    try:
        for batches in (dense, sparse):
            got = [_keys(h) for h in det.detect_many(batches)]
            assert got == _host_oracle(table, batches)
    finally:
        det.close()
    assert LEDGER.aggregate()["overflows"] >= 1


@pytest.mark.parametrize("placement", PLACEMENTS)
def test_detectd_coalesced_equals_serial(table, placement):
    """Six threads through DispatchScheduler: every request carries
    two batches, so each round merges at least two prepared batches
    into one dispatch; the results still equal the host join."""
    requests = [[_uniform(100 + r * 3 + b, 24) for b in range(2)]
                for r in range(12)]
    expected = [_host_oracle(table, b) for b in requests]
    det = _build(table, placement)
    sched = DispatchScheduler(det, SchedOptions(coalesce_wait_ms=5.0))
    results: list = [None] * len(requests)
    errors: list = []

    def worker(ids):
        try:
            for i in ids:
                results[i] = [_keys(h)
                              for h in sched.detect_many(requests[i])]
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(
        target=worker, args=(range(k, len(requests), 6),))
        for k in range(6)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        sched.close()
        det.close()
    assert not errors
    assert results == expected


@pytest.mark.parametrize("placement", PLACEMENTS)
def test_placement_empty_batches(table, placement):
    """Empty batches and batches whose names have no bucket answer []
    in their slots; the slots around them keep their own hits."""
    missing = [_query(N_PKGS + k, "1.0.0-r0") for k in range(16)]
    batches = [[], missing, _uniform(7, 32), []]
    expect = _host_oracle(table, batches)
    assert expect[0] == expect[1] == expect[3] == []
    assert expect[2]
    det = _build(table, placement)
    try:
        got = [_keys(h) for h in det.detect_many(batches)]
    finally:
        det.close()
    assert got == expect


@pytest.mark.parametrize("db", [1, 2, 4])
def test_shard_table_is_strided(table, db):
    """Shard s holds global rows r with r % db == s at local index
    r // db; the pad rows carry the fill values (tokens 1, flags 0)."""
    from trivy_tpu.parallel.mesh import shard_table
    st = shard_table(table, db)
    n = table.lo_tok.shape[0]
    pad = -(-n // db)
    assert st.lo_tok.shape == (db, pad) + table.lo_tok.shape[1:]
    assert st.row_offset.tolist() == list(range(db))
    assert st.row_len.tolist() == [len(range(s, n, db))
                                   for s in range(db)]
    for r in (0, 1, n // 2, n - 1):
        s, i = r % db, r // db
        assert (st.lo_tok[s, i] == table.lo_tok[r]).all()
        assert (st.hi_tok[s, i] == table.hi_tok[r]).all()
        assert st.flags[s, i] == table.flags[r]
    for s in range(db):
        k = int(st.row_len[s])
        assert (st.lo_tok[s, k:] == 1).all()
        assert (st.hi_tok[s, k:] == 1).all()
        assert (st.flags[s, k:] == 0).all()
