"""graftcost tier-1 gate: the apportionment contract (pro-rata shares,
request ledger, SYSTEM fallthrough), the X-Trivy-Cost header codec and
cross-hop merge, the top-K-plus-"other" tenant clamp, the attribution
on/off bench baseline, obs.check validation of costs documents, the
live server surfaces (cost header, /debug/costs, /healthz tenants,
warmup absorbed by the SYSTEM tenant), the obs.collect fleet merge,
and the ISSUE acceptance drill: cost conservation on a fleet topology
with a mid-load replica kill, c=8 coalesced load, and a 3-tenant mix.
"""

import contextvars
import glob
import json
import os
import socket

import pytest

from helpers import ALPINE_OS_RELEASE, APK_INSTALLED, make_image
from trivy_tpu.metrics import METRICS
from trivy_tpu.obs import cost
from trivy_tpu.obs.check import (check_costs, check_file,
                                 check_storm_replay)
from trivy_tpu.obs.collect import _merge_tenant_tables, collect_costs

FIXDIR = os.path.join(os.path.dirname(__file__), "fixtures", "db")
FIXGLOB = os.path.join(FIXDIR, "*.yaml")


def _in_ctx(fn):
    """Run `fn` in a fresh contextvars Context so ledger/share
    installs never leak into other tests."""
    return contextvars.copy_context().run(fn)


# ---------------------------------------------------------------------------
# the ledger + the one apportionment helper


class TestLedgerAndApportionment:
    def test_header_doc_queue_service_split(self):
        led = cost.CostLedger("acme")
        led.charge("queue_ms", 5.0)
        led.charge("device_ms", 2.5)
        led.charge("secret_bytes.device", 100)
        led.charge("secret_bytes.host", 50)
        doc = led.header_doc()
        assert doc["tenant"] == "acme"
        assert doc["queue_ms"] == 5.0
        assert doc["device_ms"] == 2.5
        assert doc["secret_bytes"] == 150
        assert doc["hops"] == 1
        # service is wall-since-install MINUS queue, floored at 0
        assert doc["service_ms"] >= 0.0
        assert "ingest_bytes" not in doc   # optional when untouched
        # the compact JSON round-trips through the header parser
        assert cost.parse_cost_header(led.header_json()) == doc

    def test_request_ledger_routes_charges(self):
        def body():
            with cost.request_ledger("acme") as led:
                assert cost.active() is led
                cost.charge_host_ms(3.0)
                cost.charge_queue_ms(2.0)
                cost.charge_ingest(1024, 1.5)
                cost.charge_secret_bytes("device", 64)
            assert cost.active() is None
            return led

        led = _in_ctx(body)
        snap = led.snapshot()
        assert snap["host_ms"] == pytest.approx(3.0)
        assert snap["queue_ms"] == pytest.approx(2.0)
        assert snap["ingest_bytes"] == pytest.approx(1024)
        assert snap["ingest_ms"] == pytest.approx(1.5)
        assert snap["secret_bytes.device"] == pytest.approx(64)

    def test_unattributed_charge_lands_in_system(self):
        sys0 = cost.SYSTEM.value("host_ms")
        _in_ctx(lambda: cost.charge_host_ms(4.0))
        assert cost.SYSTEM.value("host_ms") == pytest.approx(sys0 + 4.0)

    def test_queue_ms_outside_request_is_dropped(self):
        """Queue time nobody requested is nobody's cost — not even
        SYSTEM's (it would poison the queue-vs-service split)."""
        sys0 = cost.SYSTEM.value("queue_ms")
        _in_ctx(lambda: cost.charge_queue_ms(5.0))
        assert cost.SYSTEM.value("queue_ms") == sys0

    def test_shares_split_pro_rata_by_real_share(self):
        """A merged dispatch splits by real pair share: 0-weight
        requests pay 0, a None ledger's share bills SYSTEM."""
        a, b, z = (cost.CostLedger("a"), cost.CostLedger("b"),
                   cost.CostLedger("z"))
        sys0 = cost.SYSTEM.value("host_ms")

        def body():
            cost.install_shares([(a, 512), (b, 512), (z, 0),
                                 (None, 1024)])
            cost.charge_host_ms(8.0)

        _in_ctx(body)
        assert a.value("host_ms") == pytest.approx(2.0)
        assert b.value("host_ms") == pytest.approx(2.0)
        assert z.value("host_ms") == 0.0
        assert cost.SYSTEM.value("host_ms") == pytest.approx(sys0 + 4.0)

    def test_charge_device_ms_writes_both_sides(self):
        """The conservation contract: ONE measurement feeds the
        graftprof LEDGER and the cost apportionment."""
        from trivy_tpu.obs.perf import LEDGER
        ms0 = float(LEDGER.aggregate().get("device_ms_total", 0.0))

        def body():
            with cost.request_ledger("acme") as led:
                cost.charge_device_ms("test.cost", 6.0)
            return led

        led = _in_ctx(body)
        assert led.value("device_ms") == pytest.approx(6.0)
        ms1 = float(LEDGER.aggregate().get("device_ms_total", 0.0))
        assert ms1 - ms0 == pytest.approx(6.0, abs=1e-6)

    def test_ledgered_transfer_conserved_paths_only(self):
        """query_upload bytes stay out of the cost side — they are
        host→device, ledgered separately."""
        def body():
            with cost.request_ledger("acme") as led:
                cost.ledgered_transfer("compact", 1000)
                cost.ledgered_transfer("query_upload", 500)
            return led

        led = _in_ctx(body)
        assert led.value("transfer_bytes") == pytest.approx(1000)


# ---------------------------------------------------------------------------
# header codec + cross-hop merge (the router failover contract)


class TestHeaderCodec:
    @pytest.mark.parametrize("raw", ["", "not json", "[1,2]", "42"])
    def test_parse_junk_is_none(self, raw):
        assert cost.parse_cost_header(raw) is None

    def test_merge_sums_hops_exactly_once(self):
        a = {"tenant": "acme", "queue_ms": 2.0, "service_ms": 10.0,
             "device_ms": 4.0, "hops": 1}
        b = {"queue_ms": 1.0, "service_ms": 5.0, "device_ms": 3.0,
             "transfer_bytes": 100, "hops": 1}
        out = cost.merge_cost_docs([a, b])
        assert out["tenant"] == "acme"     # last hop that stated one
        assert out["hops"] == 2
        assert out["queue_ms"] == pytest.approx(3.0)
        assert out["service_ms"] == pytest.approx(15.0)
        assert out["device_ms"] == pytest.approx(7.0)
        assert out["transfer_bytes"] == 100
        assert isinstance(out["transfer_bytes"], int)
        # headline fields always present, even if no hop carried them
        assert out["host_ms"] == 0 and out["avoided_ms"] == 0

    def test_merge_skips_junk_entries(self):
        out = cost.merge_cost_docs([None, "junk",
                                    {"tenant": "t", "hops": 1}])
        assert out["tenant"] == "t" and out["hops"] == 1


# ---------------------------------------------------------------------------
# tenant aggregation: the top-K + "other" cardinality clamp


class TestTenantAggregator:
    def test_top_k_clamp_folds_tail_into_other(self):
        agg = cost.TenantAggregator(top_k=2)
        assert agg.resolve("t1") == "t1"
        assert agg.resolve("t2") == "t2"
        assert agg.resolve("t3") == "other"    # budget exhausted
        assert agg.resolve("t1") == "t1"       # minted rows keep theirs
        # reserved rows never consume the K budget
        assert agg.resolve("system") == "system"
        assert agg.resolve("") == "default"
        assert set(agg.labels()) == {"default", "system", "t1", "t2",
                                     "other"}

    def test_settle_folds_row_and_exports_series(self):
        agg = cost.TenantAggregator(top_k=4)
        led = cost.CostLedger("clamp-x")
        led.charge("device_ms", 2.0)
        m0 = METRICS.get("trivy_tpu_tenant_device_ms_total",
                         tenant="clamp-x")
        s0 = METRICS.get("trivy_tpu_tenant_scans_total",
                         tenant="clamp-x", outcome="ok")
        assert agg.settle(led, outcome="ok") == "clamp-x"
        row = agg.table(include_system_live=False)["clamp-x"]
        assert row["device_ms"] == pytest.approx(2.0)
        assert row["scans"] == {"ok": 1}
        assert METRICS.get("trivy_tpu_tenant_device_ms_total",
                           tenant="clamp-x") == pytest.approx(m0 + 2.0)
        assert METRICS.get("trivy_tpu_tenant_scans_total",
                           tenant="clamp-x", outcome="ok") == s0 + 1

    def test_fold_doc_without_export(self):
        """The router folds relayed headers into its fleet table
        without re-exporting tenant series (the replica already did
        from the same measurement)."""
        agg = cost.TenantAggregator(top_k=4)
        m0 = METRICS.get("trivy_tpu_tenant_device_ms_total",
                         tenant="fold-y")
        agg.fold_doc({"tenant": "fold-y", "device_ms": 5.0},
                     outcome="ok", export=False)
        assert METRICS.get("trivy_tpu_tenant_device_ms_total",
                           tenant="fold-y") == m0
        row = agg.table(include_system_live=False)["fold-y"]
        assert row["device_ms"] == pytest.approx(5.0)
        assert row["scans"] == {"ok": 1}

    def test_healthz_block_shape(self):
        agg = cost.TenantAggregator(top_k=2)
        led = cost.CostLedger("hz")
        led.charge("queue_ms", 1.0)
        agg.settle(led, outcome="ok")
        block = agg.healthz_block(include_system_live=False)
        assert set(block) == {"default", "system", "hz"}
        row = block["hz"]
        assert row["scans"] == 1
        assert set(row) == {"scans", "device_ms", "transfer_bytes",
                            "queue_ms", "avoided_ms"}


class TestHostileTenantClamp:
    """graftfair cardinality containment: a hostile client minting
    tenant ids cannot mint unbounded label/state cardinality — the
    syntactic clamp (normalize_tenant) plus the aggregator's top-K
    fold keep the label space bounded no matter what the header
    says."""

    def test_normalize_clamps_length_and_control_chars(self):
        assert cost.normalize_tenant(None) == "default"
        assert cost.normalize_tenant("") == "default"
        assert cost.normalize_tenant("  ") == "default"
        assert cost.normalize_tenant("team-a") == "team-a"
        # exposition-format injection: newlines can never reach a
        # metric label or a log line as line breaks
        assert "\n" not in cost.normalize_tenant("evil\ntenant 1")
        assert "\r" not in cost.normalize_tenant("evil\r\nx")
        assert len(cost.normalize_tenant("x" * 100_000)) <= 64

    def test_ten_thousand_hostile_names_stay_bounded(self):
        agg = cost.TenantAggregator(top_k=8)
        labels = {
            agg.resolve(cost.normalize_tenant(f"hostile-{i:05d}\n"))
            for i in range(10_000)}
        # 8 minted rows + "other"; reserved rows aren't consumed here
        assert len(labels) <= 9
        assert "other" in labels
        assert len(agg.labels()) <= 8 + 1 + len(cost.TenantAggregator.RESERVED)


# ---------------------------------------------------------------------------
# attribution on/off: the bench A/B baseline switch


class TestAttributionToggle:
    def test_disabled_keeps_perf_ledger_but_skips_attribution(self):
        from trivy_tpu.obs.perf import LEDGER
        ms0 = float(LEDGER.aggregate().get("device_ms_total", 0.0))
        sys0 = cost.SYSTEM.value("device_ms")
        cost.set_attribution_enabled(False)
        try:
            assert not cost.attribution_enabled()

            def body():
                with cost.request_ledger("acme") as led:
                    # nothing installed: charges have no victim
                    assert cost.active() is None
                    cost.charge_device_ms("test.off", 3.0)
                return led

            led = _in_ctx(body)
            # perf telemetry unchanged under the A/B...
            ms1 = float(LEDGER.aggregate().get("device_ms_total", 0.0))
            assert ms1 - ms0 == pytest.approx(3.0, abs=1e-6)
            # ...but no cost side moved: not the ledger, not SYSTEM
            assert led.value("device_ms") == 0.0
            assert cost.SYSTEM.value("device_ms") == sys0
            # settle is a no-op while off
            assert cost.TENANTS.settle(led, outcome="ok") == "default"
        finally:
            cost.set_attribution_enabled(True)
        assert cost.attribution_enabled()


# ---------------------------------------------------------------------------
# work avoided: EWMA-priced memo hits


class TestWorkAvoided:
    def test_ewma_prices_memo_hits_in_ms(self):
        cost.reset_for_tests()
        # feed the exchange rate: 10 ms for 100 real rows
        _in_ctx(lambda: cost.charge_device_ms("test.rate", 10.0,
                                              real_rows=100))

        def body():
            with cost.request_ledger("acme") as led:
                cost.note_work_avoided(50)
            return led

        led = _in_ctx(body)
        assert led.value("avoided_ms") == pytest.approx(5.0)
        assert led.header_doc()["avoided_ms"] == pytest.approx(5.0)

    def test_zero_units_and_cold_rate_charge_nothing(self):
        cost.reset_for_tests()

        def body():
            with cost.request_ledger("acme") as led:
                cost.note_work_avoided(0)
                cost.note_work_avoided(10)   # rate still 0.0
            return led

        led = _in_ctx(body)
        assert led.value("avoided_ms") == 0.0


# ---------------------------------------------------------------------------
# graftfeed x graftcost: dedup share apportionment


class TestDedupApportionment:
    def test_two_tenants_shared_base_layer_one_dispatch(self):
        """The graftfeed billing regression: two tenants submit the
        SAME base-layer queries into one coalesced round. The first
        occurrence owns every unique pair — tenant A pays the whole
        dispatch — while tenant B's fully-collapsed duplicates bill
        as avoided_ms (EWMA-priced), never as device/host ms. The
        conserved fields stay conserved: the dispatch's real ms lands
        on exactly one tenant."""
        from trivy_tpu.db import build_table
        from trivy_tpu.db.fixtures import load_fixture_files
        from trivy_tpu.detect.engine import BatchDetector, PkgQuery
        from trivy_tpu.detect.sched import (DispatchScheduler,
                                            SchedOptions)
        from trivy_tpu.resilience import FAILPOINTS

        cost.reset_for_tests()
        # seed the exchange rate so collapsed pairs price to > 0 ms
        _in_ctx(lambda: cost.charge_device_ms("test.rate", 10.0,
                                              real_rows=1000))
        advisories, details, _ = load_fixture_files(
            sorted(glob.glob(FIXGLOB)))
        table = build_table(advisories, details)
        qs = [PkgQuery(source="alpine 3.17", ecosystem="alpine",
                       name=n, version=v)
              for n, v in (("openssl", "3.0.7-r0"),
                           ("openssl", "3.0.8-r0"),
                           ("musl", "1.2.3-r4"),
                           ("zlib", "1.2.12-r2"))]
        det = BatchDetector(table)
        sched = DispatchScheduler(
            det, SchedOptions(coalesce_wait_ms=400.0))

        def submit(tenant):
            def body():
                with cost.request_ledger(tenant) as led:
                    return led, sched.submit([qs])
            return _in_ctx(body)

        try:
            # park the dispatcher in a slowed warm round so A and B
            # both enqueue behind it and coalesce into ONE round; A
            # enqueues first, so FIFO merge order makes A the first
            # occurrence of every triple and B the duplicate rider.
            # The window is timing-dependent on a loaded box, so widen
            # and retry until the round actually merged (B's whole
            # descriptor set collapsing is the merge witness)
            warm = [PkgQuery(source="debian 11", ecosystem="debian",
                             name="bash", version="5.1-2+deb11u1")]
            for attempt in range(4):
                FAILPOINTS.set("detect.dispatch", "slow",
                               150.0 * (attempt + 1))
                fut_w = sched.submit([warm])
                led_a, fut_a = submit("acme")
                led_b, fut_b = submit("borg")
                fut_w.result(60)
                hits_a, hits_b = fut_a.result(60), fut_b.result(60)
                if led_b.value("avoided_ms") > 0.0:
                    break
        finally:
            FAILPOINTS.configure("")
            sched.close()
            det.close()
        assert len(hits_a) == len(hits_b) == 1
        # identical queries, identical results either way
        assert led_a.value("avoided_ms") == 0.0
        assert led_b.value("avoided_ms") > 0.0
        # B's unique share is ZERO: its whole descriptor set collapsed
        # into A's, so the conserved ms of the round are A's alone
        assert led_a.value("device_ms") + led_a.value("host_ms") > 0.0
        assert led_b.value("device_ms") == 0.0
        assert led_b.value("host_ms") == 0.0


# ---------------------------------------------------------------------------
# conservation + document validation


class TestConservationReport:
    def test_report_shape(self):
        rep = cost.conservation_report()
        for axis in ("device_ms", "transfer_bytes"):
            rec = rep[axis]
            assert isinstance(rec["ledger"], (int, float))
            assert isinstance(rec["attributed"], (int, float))
            assert isinstance(rec["ok"], bool)


def _good_costs_doc():
    row = {"scans": {"ok": 2}, "queue_ms": 1.0, "service_ms": 2.0,
           "device_ms": 3.0, "transfer_bytes": 4, "host_ms": 0.0,
           "ingest_bytes": 0.0, "ingest_ms": 0.0, "secret_bytes": 0.0,
           "avoided_ms": 0.0}
    return {
        "schema": "trivy-tpu-costs/1",
        "tenants": {"default": dict(row, scans=dict(row["scans"]))},
        "conservation": {
            "device_ms": {"ledger": 3.0, "attributed": 3.0,
                          "ok": True},
            "transfer_bytes": {"ledger": 4, "attributed": 4,
                               "ok": True},
        },
    }


class TestCheckCosts:
    def test_good_doc_clean(self, tmp_path):
        doc = _good_costs_doc()
        assert check_costs(doc) == []
        # check_file dispatches on the schema prefix
        p = tmp_path / "costs.json"
        p.write_text(json.dumps(doc))
        assert check_file(str(p)) == []

    def test_bad_docs_flagged(self):
        assert check_costs({"schema": "nope"})
        doc = _good_costs_doc()
        doc["tenants"]["default"]["device_ms"] = -1
        assert any("device_ms" in p for p in check_costs(doc))
        doc = _good_costs_doc()
        del doc["conservation"]["device_ms"]["ok"]
        assert any("ok verdict" in p for p in check_costs(doc))
        doc = _good_costs_doc()
        doc["tenants"]["default"]["scans"] = {"ok": 1.5}
        assert any("scans" in p for p in check_costs(doc))

    def test_merged_sources_validate_recursively(self):
        doc = _good_costs_doc()
        doc["scope"] = "fleet-merged"
        doc["sources"] = [
            {"url": "http://dead:1", "error": "unreachable"},  # stub ok
            {"schema": "nope"},                                # bad frag
        ]
        probs = check_costs(doc)
        assert any(p.startswith("sources[1]") for p in probs)
        assert not any(p.startswith("sources[0]") for p in probs)

    def test_merge_tenant_tables_sums(self):
        t1 = {"a": {"device_ms": 1.0, "scans": {"ok": 1}}}
        t2 = {"a": {"device_ms": 2.0, "scans": {"ok": 1, "shed": 1}},
              "b": {"device_ms": 4.0, "scans": {}}}
        out = _merge_tenant_tables([t1, t2])
        assert out["a"]["device_ms"] == pytest.approx(3.0)
        assert out["a"]["scans"] == {"ok": 2, "shed": 1}
        assert out["b"]["device_ms"] == pytest.approx(4.0)

    def test_storm_replay_accepts_tenant_mix(self):
        doc = {"schedule": {"seed": 1, "topology": "single",
                            "horizon_ms": 100.0, "events": []},
               "load": {"requests": 1, "concurrency": 1,
                        "load_seed": 1, "tenants": 3},
               "violations": {}}
        assert check_storm_replay(doc) == []
        doc["load"]["tenants"] = 0
        assert any("tenants" in p for p in check_storm_replay(doc))


# ---------------------------------------------------------------------------
# live server: header, /debug/costs, /healthz tenants, SYSTEM warmup


class TestLiveServer:
    @pytest.fixture(scope="class")
    def server(self, tmp_path_factory):
        from trivy_tpu.db import build_table
        from trivy_tpu.db.fixtures import load_fixture_files
        from trivy_tpu.detect.sched import SchedOptions
        from trivy_tpu.server.listen import serve_background
        cost.TENANTS.reset_for_tests()
        advisories, details, _ = load_fixture_files(
            sorted(glob.glob(FIXGLOB)))
        table = build_table(advisories, details)
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        # warmup ON: the boot-time ladder compile runs outside any
        # request — the SYSTEM tenant must absorb it (conservation)
        httpd, state = serve_background(
            "127.0.0.1", port, table,
            cache_dir=str(tmp_path_factory.mktemp("costcache")),
            detect_opts=SchedOptions(warmup=True,
                                     warmup_max_pairs=1 << 12))
        yield f"http://127.0.0.1:{port}"
        httpd.shutdown()
        state.close()

    @pytest.fixture(scope="class")
    def scanned(self, server, tmp_path_factory):
        from trivy_tpu.fanal.artifact import ImageArchiveArtifact
        from trivy_tpu.server.client import RemoteCache, RemoteScanner
        img = str(tmp_path_factory.mktemp("costimg") / "img.tar")
        make_image(img, [{
            "etc/os-release": ALPINE_OS_RELEASE,
            "lib/apk/db/installed": APK_INSTALLED,
        }])
        cache = RemoteCache(server)
        ref = ImageArchiveArtifact(img, cache).inspect()
        scanner = RemoteScanner(server, tenant="acme")
        results, _ = scanner.scan(ref.name, ref.id, ref.blob_ids)
        assert results
        return scanner

    def test_scan_returns_cost_header(self, scanned):
        doc = scanned.last_cost
        assert doc is not None
        assert doc["tenant"] == "acme"
        assert doc["hops"] == 1
        assert doc["service_ms"] > 0
        assert doc["device_ms"] >= 0

    def test_debug_costs_and_healthz_tenants(self, server, scanned):
        import urllib.request
        doc = json.loads(urllib.request.urlopen(
            server + "/debug/costs").read())
        assert check_costs(doc) == []
        assert doc["schema"] == "trivy-tpu-costs/1"
        assert doc["tenants"]["acme"]["scans"].get("ok", 0) >= 1
        # boot warmup ran outside any request → SYSTEM absorbed it
        assert doc["tenants"]["system"]["device_ms"] > 0
        hz = json.loads(urllib.request.urlopen(
            server + "/healthz").read())
        assert hz["tenants"]["acme"]["scans"] >= 1

    def test_collect_costs_merges_fleet_doc(self, server, scanned):
        doc = collect_costs("", urls=[server])
        assert doc["schema"] == "trivy-tpu-costs/1"
        assert doc["scope"] == "fleet-merged"
        assert check_costs(doc) == []
        assert doc["tenants"]["acme"]["scans"].get("ok", 0) >= 1
        assert "conservation" in doc
        # unreachable processes are recorded, not fatal
        doc2 = collect_costs("", urls=[server,
                                       "http://127.0.0.1:9/"],
                             timeout=0.5)
        assert any(f.get("error") for f in doc2["sources"])

    def test_exposition_stays_strict(self, server, scanned):
        import urllib.request

        from helpers import parse_exposition
        body = urllib.request.urlopen(server + "/metrics").read()
        parse_exposition(body.decode())


# ---------------------------------------------------------------------------
# ISSUE acceptance: cost conservation under a fleet storm


class TestStormConservationDrill:
    def test_fleet_kill_c8_three_tenants_conserves(self, tmp_path):
        """The headline drill: a routed fleet at c=8 with coalescing
        ON, a 3-tenant round-robin mix, and a replica killed mid-load.
        The cost_conservation invariant must hold (apportioned totals
        reconcile with the graftprof ledger deltas), every tenant's
        scans settle under its own bounded label, the replay artifact
        records the tenant mix, and the exposition stays strict."""
        from trivy_tpu.resilience import FAILPOINTS, GUARD
        from trivy_tpu.resilience.storm import (
            Schedule, StormEvent, StormOptions, check_exposition,
            load_replay, run_storm, storm_table, write_replay)
        FAILPOINTS.configure("")
        GUARD.reset_for_tests()
        cost.TENANTS.reset_for_tests()   # deterministic label budget
        table = storm_table()
        sched = Schedule(seed=117, topology="fleet",
                         horizon_ms=1200.0, events=[
                             StormEvent(at_ms=60.0,
                                        kind="kill_replica",
                                        replica=0, dur_ms=400.0),
                         ])
        opts = StormOptions(requests=21, concurrency=8, replicas=2,
                            tenants=3)
        tenants = [f"storm-t{i}" for i in range(3)]
        # the per-tenant settle observation is wall-clock coupled
        # (a shed run settles under "shed"); allow one re-run for the
        # side-asserts — the conservation verdict must hold every time
        for attempt in range(2):
            s0 = {t: METRICS.get("trivy_tpu_tenant_scans_total",
                                 tenant=t, outcome="ok")
                  for t in tenants}
            report = run_storm(sched, opts, table=table)
            assert report.ok, report.violations
            settled = [t for t in tenants
                       if METRICS.get("trivy_tpu_tenant_scans_total",
                                      tenant=t, outcome="ok") > s0[t]]
            if len(settled) == 3:
                break
        else:
            raise AssertionError(
                "3-tenant mix did not settle in 2 drills")
        # every tenant landed under its own bounded label (no clamp
        # spill into "other" at this cardinality) and the attribution
        # moved real numbers
        tbl = cost.TENANTS.table()
        for t in tenants:
            assert t in tbl
            assert tbl[t]["service_ms"] > 0
        assert check_exposition(METRICS.render()) == []
        # the replay artifact records the mix, validates, and loads
        # back into the same round-robin
        path = str(tmp_path / "replay.json")
        write_replay(path, sched, opts, report, minimized=False)
        with open(path) as f:
            doc = json.load(f)
        assert doc["load"]["tenants"] == 3
        assert check_storm_replay(doc) == []
        _, opts2 = load_replay(path)
        assert opts2.tenants == 3
