"""graftwatch tests: flight-recorder retention properties (pinned
traces survive ring churn, memory bounded by construction), SLO
burn-rate math on synthetic traffic (injectable clock) with strict
exposition gating, the offline incident/trace validator, per-process
/debug endpoints, cross-process trace assembly with the golden
ROUTED-scan topology fixture (failover hop visible), and the ISSUE
acceptance drill: a routed scan at c=8 with an injected
detect.dispatch hang trips the watchdog, completes via host fallback,
and yields one assembled trace + an auto-captured incident + SLO
gauges that reflect it."""

import glob as _glob
import json
import os
import socket
import tempfile
import threading
import time
import urllib.request

import pytest

from helpers import (ALPINE_OS_RELEASE, APK_INSTALLED, FakeRedis,
                     make_image, parse_exposition)
from trivy_tpu.db import build_table
from trivy_tpu.db.fixtures import load_fixture_files
from trivy_tpu.metrics import METRICS
from trivy_tpu.obs import RECORDER, check as obs_check, collect, new_trace, span
from trivy_tpu.obs.recorder import FlightRecorder
from trivy_tpu.obs.slo import SLOEngine
from trivy_tpu.obs.trace import Span
from trivy_tpu.resilience import FAILPOINTS, GUARD

FIXDIR = os.path.join(os.path.dirname(__file__), "fixtures", "db")
FIXGLOB = os.path.join(FIXDIR, "*.yaml")
GOLDEN_ROUTED = os.path.join(os.path.dirname(__file__), "fixtures",
                             "obs", "golden_routed_trace_edges.json")


def _fixture_table():
    advisories, details, _ = load_fixture_files(
        sorted(_glob.glob(FIXGLOB)))
    return build_table(advisories, details)


@pytest.fixture(autouse=True)
def _clean_guard():
    """GUARD and FAILPOINTS are process-global (like METRICS): every
    test starts and ends with defaults, so the drill's 50ms watchdog
    can never leak into another test's real dispatches."""
    FAILPOINTS.configure("")
    GUARD.reset_for_tests()
    GUARD.configure(dispatch_timeout_s=120.0, fail_threshold=3,
                    reset_timeout_s=5.0)
    yield
    FAILPOINTS.configure("")
    GUARD.reset_for_tests()
    GUARD.configure(dispatch_timeout_s=120.0, fail_threshold=3,
                    reset_timeout_s=5.0)


def _mk_span(name="x", trace_id="t" * 32, dur=0.001, parent_id="",
             **attrs):
    s = Span(name, trace_id, parent_id, dict(attrs))
    s.wall_start = time.time()
    s.dur = dur
    s.thread_id = 1
    return s


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# flight recorder: retention properties

class TestFlightRecorder:
    def test_ring_memory_is_bounded(self):
        r = FlightRecorder(span_slots=64, log_slots=16)
        for i in range(1000):
            r.record_span(_mk_span(trace_id=f"{i:032d}"))
            r.record_log({"ts_unix": float(i), "msg": "m"})
        assert len(r.spans()) <= 64
        assert len(r.logs()) <= 16
        # and the slot arrays themselves never grew
        assert len(r._span_ring) == 64
        assert len(r._log_ring) == 16

    def test_pinned_trace_survives_churn(self):
        r = FlightRecorder(span_slots=64)
        tid = "a" * 32
        for i in range(3):
            r.record_span(_mk_span(f"keep{i}", trace_id=tid))
        r.pin(tid, "test")
        for i in range(5000):   # churn far past the ring size
            r.record_span(_mk_span("churn", trace_id=f"{i:032d}"))
        kept = r.spans(tid)
        assert {s["name"] for s in kept} == {"keep0", "keep1", "keep2"}
        # spans of a pinned trace recorded AFTER the pin land too
        r.record_span(_mk_span("late", trace_id=tid))
        assert "late" in {s["name"] for s in r.spans(tid)}

    def test_pin_store_is_bounded(self):
        r = FlightRecorder(span_slots=64)
        r.max_pinned = 8
        for i in range(40):
            r.pin(f"{i:032d}", "test")
        assert len(r.pinned()) <= 8
        per = r.max_spans_per_pin
        tid = "39".zfill(32)
        for _ in range(per + 100):
            r.record_span(_mk_span("s", trace_id=tid))
        assert len(r.pinned()[tid]["spans"]) <= per

    def test_slow_root_span_pins_its_trace(self):
        r = FlightRecorder(span_slots=64)
        r.slow_trace_s = 1.0
        r.record_span(_mk_span("server.rpc", trace_id="b" * 32,
                               dur=0.9))
        assert "b" * 32 not in r.pinned()   # fast root: ages out
        r.record_span(_mk_span("inner", trace_id="d" * 32, dur=9.0))
        assert "d" * 32 not in r.pinned()   # slow but not a root span
        r.record_span(_mk_span("scan", trace_id="f" * 32, dur=1.5))
        assert r.pinned()["f" * 32]["reason"] == "slow_trace"

    def test_error_span_pins_its_trace(self):
        r = FlightRecorder(span_slots=64)
        r.record_span(_mk_span("router.forward", trace_id="9" * 32,
                               error="conn refused"))
        assert r.pinned()["9" * 32]["reason"] == "error"

    def test_note_event_pins_and_is_bounded(self):
        r = FlightRecorder(span_slots=64)
        r.max_events = 10
        for i in range(50):
            r.note_event("watchdog_trip", trace_id=f"{i:032d}",
                         site="detect.dispatch")
        assert len(r.events()) == 10
        assert len(r.pinned()) <= r.max_pinned

    def test_incident_write_cooldown_and_force(self, tmp_path):
        r = FlightRecorder(span_slots=64)
        r.configure(incident_dir=str(tmp_path), incident_cooldown_s=60)
        r.record_span(_mk_span("server.rpc"))
        p1 = r.incident("breaker_open", detail={"breaker": "detect"})
        assert p1 and os.path.exists(p1)
        assert r.incident("breaker_open") is None   # inside cooldown
        p2 = r.incident("manual", force=True)       # operator bypass
        assert p2 and p2 != p1
        listing = r.incidents()
        assert {e["path"] for e in listing} == {p1, p2}
        # the files validate offline
        assert obs_check.check_file(p1) == []
        doc = json.load(open(p1))
        assert doc["schema"] == FlightRecorder.SCHEMA
        assert doc["reason"] == "breaker_open"
        assert doc["detail"] == {"breaker": "detect"}
        assert any(s["name"] == "server.rpc" for s in doc["spans"])


# ---------------------------------------------------------------------------
# SLO engine: burn-rate math on synthetic traffic

class TestSLO:
    def _engine(self):
        clock = {"t": 1000.0}
        eng = SLOEngine(windows=(60.0, 600.0),
                        latency_threshold_s=1.0,
                        clock=lambda: clock["t"])
        return eng, clock

    def test_burn_rate_math(self):
        eng, clock = self._engine()
        # 100 scans, 2 over the latency threshold → bad_ratio 0.02;
        # target 0.99 → budget 0.01 → burn 2.0
        for i in range(98):
            eng.observe_scan(0.1, "ok")
        eng.observe_scan(5.0, "ok")
        eng.observe_scan(2.0, "ok")
        rates = eng.burn_rates()
        w = rates["scan_latency_p99"]["windows"]["60s"]
        assert w["total"] == 100 and w["bad"] == 2
        assert w["burn_rate"] == pytest.approx(2.0)

    def test_sheds_are_load_not_errors(self):
        eng, clock = self._engine()
        for _ in range(7):
            eng.observe_scan(0.1, "ok")
        for _ in range(2):
            eng.observe_scan(0.0, "shed")
        eng.observe_scan(0.0, "error")
        rates = eng.burn_rates()
        err = rates["scan_errors"]["windows"]["60s"]
        # sheds count in the denominator as good: 10 total, 1 bad
        assert err["total"] == 10 and err["bad"] == 1
        # and sheds never enter the latency objective at all
        lat = rates["scan_latency_p99"]["windows"]["60s"]
        assert lat["total"] == 8   # 7 ok + 1 error, no sheds

    def test_sliding_window_forgets(self):
        eng, clock = self._engine()
        eng.observe_scan(5.0, "ok")    # bad, at t=1000
        clock["t"] += 120.0            # past the 60s window
        eng.observe_scan(0.1, "ok")
        rates = eng.burn_rates()
        short = rates["scan_latency_p99"]["windows"]["60s"]
        long_ = rates["scan_latency_p99"]["windows"]["600s"]
        assert short["total"] == 1 and short["bad"] == 0
        assert long_["total"] == 2 and long_["bad"] == 1
        assert long_["burn_rate"] > short["burn_rate"]

    def test_empty_windows_burn_zero(self):
        eng, _ = self._engine()
        rates = eng.burn_rates()
        for obj in rates.values():
            for w in obj["windows"].values():
                assert w["burn_rate"] == 0.0

    def test_device_serving_and_gauge_export(self):
        eng, _ = self._engine()
        for _ in range(3):
            eng.observe_join(True)
        eng.observe_join(False)
        eng.export()
        assert METRICS.get("trivy_tpu_device_serving_ratio") \
            == pytest.approx(0.75)
        burn = METRICS.get("trivy_tpu_slo_burn_rate",
                           objective="device_serving", window="60s")
        # bad_ratio 0.25 / budget 0.05 = 5.0
        assert burn == pytest.approx(5.0)
        # strict exposition gate over the real registry
        fams = parse_exposition(METRICS.render())
        assert fams["trivy_tpu_slo_burn_rate"]["type"] == "gauge"
        assert fams["trivy_tpu_device_serving_ratio"]["type"] == "gauge"

    def test_configure_targets_and_unknown_objective(self):
        eng, _ = self._engine()
        eng.configure(targets={"device_serving": 0.5})
        eng.observe_join(False)
        rates = eng.burn_rates()
        w = rates["device_serving"]["windows"]["60s"]
        assert w["burn_rate"] == pytest.approx(2.0)  # 1.0 / 0.5
        with pytest.raises(ValueError):
            eng.configure(targets={"nope": 0.9})


# ---------------------------------------------------------------------------
# offline validator

class TestCheck:
    def _spans(self):
        return [
            {"name": "a", "trace_id": "t" * 32, "span_id": "s1",
             "parent_id": "", "ts_unix": 1.0, "dur_ms": 2.0},
            {"name": "b", "trace_id": "t" * 32, "span_id": "s2",
             "parent_id": "s1", "ts_unix": 1.1, "dur_ms": 1.0},
        ]

    def _incident(self, spans=None):
        return {"schema": "trivy-tpu-incident/1", "reason": "test",
                "detail": {}, "captured_unix": 1.0, "pid": 1,
                "spans": spans if spans is not None else self._spans(),
                "logs": [], "events": [], "pinned": {}}

    def test_clean_incident_and_trace(self, tmp_path):
        inc = tmp_path / "incident-x.json"
        inc.write_text(json.dumps(self._incident()))
        assert obs_check.check_file(str(inc)) == []
        doc = collect.assemble([{"url": "p", "spans": self._spans()}])
        tr = tmp_path / "trace.json"
        tr.write_text(json.dumps(doc))
        assert obs_check.check_file(str(tr)) == []
        assert obs_check.main([str(inc), str(tr), "--quiet"]) == 0

    def test_cycle_detected(self, tmp_path):
        spans = self._spans()
        spans[0]["parent_id"] = "s2"   # s1 → s2 → s1
        inc = tmp_path / "incident-cycle.json"
        inc.write_text(json.dumps(self._incident(spans)))
        problems = obs_check.check_file(str(inc))
        assert any("cycle" in p for p in problems)
        assert obs_check.main([str(inc), "--quiet"]) == 1

    def test_duplicate_span_ids_detected(self, tmp_path):
        spans = self._spans()
        spans[1]["span_id"] = "s1"
        inc = tmp_path / "i.json"
        inc.write_text(json.dumps(self._incident(spans)))
        assert any("duplicate" in p
                   for p in obs_check.check_file(str(inc)))

    def test_schema_violations_detected(self, tmp_path):
        bad = self._incident()
        del bad["reason"]
        bad["schema"] = "nope/9"
        bad["spans"][0].pop("name")
        bad["spans"][1]["dur_ms"] = -1
        p = tmp_path / "i.json"
        p.write_text(json.dumps(bad))
        problems = obs_check.check_file(str(p))
        assert len(problems) >= 4

    def test_unreadable_is_exit_2(self, tmp_path):
        p = tmp_path / "garbage.json"
        p.write_text("{not json")
        assert obs_check.main([str(p), "--quiet"]) == 2

    def test_pinned_trace_membership_checked(self, tmp_path):
        inc = self._incident()
        inc["pinned"] = {"x" * 32: {"reason": "r", "pinned_unix": 1.0,
                                    "spans": [{
                                        "name": "n", "span_id": "p1",
                                        "parent_id": "",
                                        "trace_id": "y" * 32,
                                        "ts_unix": 1.0, "dur_ms": 1.0,
                                    }]}}
        p = tmp_path / "i.json"
        p.write_text(json.dumps(inc))
        assert any("belongs to trace" in m
                   for m in obs_check.check_file(str(p)))


# ---------------------------------------------------------------------------
# collect: assembly rules

class TestCollect:
    def test_dedupes_and_labels_processes(self):
        spans = [{"name": "a", "trace_id": "t" * 32, "span_id": "s1",
                  "parent_id": "", "ts_unix": 5.0, "dur_ms": 1.0}]
        doc = collect.assemble([
            {"url": "http://router", "spans": spans},
            {"url": "http://replica", "spans": spans},  # duplicate
        ])
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert len(xs) == 1
        names = [e for e in doc["traceEvents"]
                 if e.get("name") == "process_name"]
        assert [n["args"]["name"] for n in names] == ["http://router"]

    def test_wall_clock_offsets(self):
        frags = [
            {"url": "a", "spans": [
                {"name": "x", "trace_id": "", "span_id": "s1",
                 "parent_id": "", "ts_unix": 100.0, "dur_ms": 1.0}]},
            {"url": "b", "spans": [
                {"name": "y", "trace_id": "", "span_id": "s2",
                 "parent_id": "", "ts_unix": 100.5, "dur_ms": 1.0}]},
        ]
        doc = collect.assemble(frags)
        ts = {e["args"]["span_id"]: e["ts"]
              for e in doc["traceEvents"] if e["ph"] == "X"}
        assert ts["s1"] == 0.0
        assert ts["s2"] == pytest.approx(0.5e6)

    def test_unreachable_fragment_is_skipped(self):
        port = _free_port()   # nothing listening
        frags = collect.fetch_fragments(
            [f"http://127.0.0.1:{port}"], timeout=0.3)
        assert frags[0]["spans"] == [] and "error" in frags[0]


# ---------------------------------------------------------------------------
# fleet fixture: router + 2 replicas on a shared cache backend

@pytest.fixture(scope="class")
def fleet(tmp_path_factory):
    from trivy_tpu.fleet.router import serve_router_background
    from trivy_tpu.server.listen import serve_background
    table = _fixture_table()
    redis = FakeRedis()
    backend = f"redis://127.0.0.1:{redis.port}"
    incident_dir = str(tmp_path_factory.mktemp("incidents"))
    RECORDER.configure(incident_dir=incident_dir,
                       incident_cooldown_s=0.0)
    replicas = []
    for _ in range(2):
        port = _free_port()
        httpd, state = serve_background(
            "127.0.0.1", port, table,
            cache_dir=str(tmp_path_factory.mktemp("cache")),
            cache_backend=backend)
        replicas.append([f"http://127.0.0.1:{port}", httpd, state])
    rport = _free_port()
    rhttpd, rstate = serve_router_background(
        "127.0.0.1", rport, [u for u, _, _ in replicas])
    fleet = {
        "router": f"http://127.0.0.1:{rport}",
        "rstate": rstate,
        "replicas": replicas,
        "incident_dir": incident_dir,
    }
    yield fleet
    FAILPOINTS.configure("")
    GUARD.reset_for_tests()
    RECORDER.configure(incident_cooldown_s=30.0)
    rhttpd.shutdown()
    rstate.close()
    for _, httpd, state in replicas:
        try:
            httpd.shutdown()
        except Exception:
            pass
        state.close()
    redis.close()


def _push_image(base, tmp_path):
    from trivy_tpu.fanal.artifact import ImageArchiveArtifact
    from trivy_tpu.server.client import RemoteCache
    img = str(tmp_path / "img.tar")
    make_image(img, [{
        "etc/os-release": ALPINE_OS_RELEASE,
        "lib/apk/db/installed": APK_INSTALLED,
    }])
    return ImageArchiveArtifact(img, RemoteCache(base)).inspect()


# ---------------------------------------------------------------------------
# the ISSUE acceptance drill + routed golden topology

class TestIncidentDrill:
    def test_routed_hang_drill_end_to_end(self, fleet, tmp_path):
        """c=8 routed scans with detect.dispatch=hang → watchdog trip,
        host fallback, then a known-trace scan past a killed owner:
        (a) ONE assembled trace router → replica → fallback join with
        the failover hop visible (golden topology fixture), (b) an
        auto-captured incident file containing that trace, (c) SLO
        burn-rate + device-serving gauges reflecting the incident —
        asserted through the strict exposition parser."""
        from trivy_tpu.server.client import RemoteScanner
        router = fleet["router"]
        ref = _push_image(router, tmp_path)
        baseline, _ = RemoteScanner(router).scan(
            ref.name, ref.id, ref.blob_ids)
        base_vulns = sum(len(r.vulnerabilities) for r in baseline)
        assert base_vulns > 0

        # ---- phase 1: injected hang mid-fleet at c=8 ----------------
        GUARD.configure(dispatch_timeout_s=0.05, fail_threshold=3,
                        reset_timeout_s=60.0)   # stay open all drill
        trips0 = METRICS.get("trivy_tpu_device_watchdog_trips_total")
        fb0 = METRICS.get("trivy_tpu_fallback_joins_total")
        FAILPOINTS.set("detect.dispatch", "hang", 100.0)
        results: list = [None] * 8
        errors: list = []

        def worker(i):
            try:
                res, _ = RemoteScanner(router).scan(
                    ref.name, ref.id, ref.blob_ids)
                results[i] = sum(len(r.vulnerabilities) for r in res)
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        # every scan completed via host fallback, results intact
        assert results == [base_vulns] * 8
        assert METRICS.get("trivy_tpu_device_watchdog_trips_total") \
            > trips0
        assert GUARD.breaker.state_name() == "open"
        assert METRICS.get("trivy_tpu_fallback_joins_total") > fb0

        # ---- phase 2: kill the ring owner, scan with a known id -----
        owner = fleet["rstate"].ring.successors(ref.id)[0]
        for entry in fleet["replicas"]:
            if entry[0] == owner:
                entry[1].shutdown()
                entry[1].server_close()
        tid = "feedc0de" * 4
        with new_trace(tid):
            res, os_info = RemoteScanner(router).scan(
                ref.name, ref.id, ref.blob_ids)
        assert os_info.family == "alpine"
        assert sum(len(r.vulnerabilities) for r in res) == base_vulns

        # ---- (a) one assembled trace, failover hop visible ----------
        doc = collect.collect_trace(router, tid)
        events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert all(e["args"]["trace_id"] == tid for e in events)
        by_id = {e["args"]["span_id"]: e["name"] for e in events}
        edges = sorted({(by_id.get(e["args"]["parent_id"], ""),
                         e["name"]) for e in events})
        with open(GOLDEN_ROUTED) as f:
            golden = [tuple(e) for e in json.load(f)]
        assert edges == golden, (
            "routed span topology drifted; update "
            "tests/fixtures/obs/golden_routed_trace_edges.json: "
            + json.dumps(edges))
        forwards = [e for e in events if e["name"] == "router.forward"]
        assert len(forwards) == 2   # the failover hop is VISIBLE
        assert {e["args"]["hop"] for e in forwards} == {1, 2}
        dead_hop = next(e for e in forwards if e["args"]["hop"] == 1)
        live_hop = next(e for e in forwards if e["args"]["hop"] == 2)
        assert "error" in dead_hop["args"]
        assert live_hop["args"]["failover"] is True
        # graftcost: the hop that served carries the billed cost doc's
        # headline numbers as span attrs; the dead hop returned no
        # response, so it has nothing to bill
        assert live_hop["args"]["cost_tenant"] == "default"
        assert live_hop["args"]["cost_device_ms"] >= 0
        assert "cost_queue_ms" in live_hop["args"]
        assert "cost_tenant" not in dead_hop["args"]
        assert any(e["name"] == "detect.host_join" for e in events)
        # the dump validates offline, and the failover pinned the trace
        dump = tmp_path / "routed.trace.json"
        collect.write_trace(str(dump), doc)
        assert obs_check.check_file(str(dump)) == []
        assert tid in RECORDER.pinned()

        # ---- (b) auto-captured incident containing that trace -------
        FAILPOINTS.configure("")
        FAILPOINTS.set("rpc.scan", "error")
        with pytest.raises(Exception):
            RemoteScanner(router).scan(ref.name, ref.id, ref.blob_ids)
        FAILPOINTS.configure("")
        incidents = RECORDER.incidents()
        assert incidents
        containing = None
        for entry in incidents:
            inc = json.load(open(entry["path"]))
            tids = {s["trace_id"] for s in inc["spans"]} \
                | set(inc["pinned"])
            if tid in tids:
                containing = entry["path"]
                break
        assert containing, "no incident file contains the drill trace"
        assert obs_check.check_file(containing) == []
        # the debug surface lists them too (any live process)
        live = next(u for u, _, _ in fleet["replicas"] if u != owner)
        listing = json.loads(urllib.request.urlopen(
            live + "/debug/incidents").read())
        assert listing["incidents"]

        # ---- (c) SLO gauges reflect the incident --------------------
        body = urllib.request.urlopen(live + "/metrics").read().decode()
        fams = parse_exposition(body)
        burn = {(l["objective"], l["window"]): v
                for n, l, v in
                fams["trivy_tpu_slo_burn_rate"]["samples"]}
        assert burn[("device_serving", "300s")] > 0
        ratio = fams["trivy_tpu_device_serving_ratio"]["samples"][0][2]
        assert 0.0 <= ratio < 1.0
        assert fams["trivy_tpu_incidents_total"]["type"] == "counter"
        # /healthz mirrors the same burn-rate document
        health = json.loads(urllib.request.urlopen(
            live + "/healthz").read())
        slo = health["slo"]
        assert slo["device_serving"]["windows"]["300s"]["bad"] > 0
        GUARD.reset_for_tests()


# ---------------------------------------------------------------------------
# per-process debug endpoints + headers (single server, no fleet)

@pytest.fixture(scope="class")
def watch_server(tmp_path_factory):
    from trivy_tpu.server.listen import serve_background
    port = _free_port()
    httpd, state = serve_background(
        "127.0.0.1", port, _fixture_table(),
        cache_dir=str(tmp_path_factory.mktemp("wcache")))
    yield f"http://127.0.0.1:{port}"
    httpd.shutdown()
    state.close()


def _rpc_trace(base: str, tid: str, wait_s: float = 2.0) -> dict:
    """→ /debug/traces for `tid` once its `server.rpc` root is there:
    the handler records the root span after the response is on the
    wire, so a client that asks at once can beat it."""
    deadline = time.monotonic() + wait_s
    while True:
        doc = json.loads(urllib.request.urlopen(
            base + f"/debug/traces?trace_id={tid}").read())
        if "server.rpc" in {s["name"] for s in doc["spans"]} \
                or time.monotonic() > deadline:
            return doc
        time.sleep(0.01)


class TestDebugEndpoints:
    def test_debug_traces_serves_the_rpc_trace(self, watch_server):
        req = urllib.request.Request(
            watch_server + "/twirp/trivy.cache.v1.Cache/MissingBlobs",
            data=json.dumps({"artifact_id": "x",
                             "blob_ids": []}).encode(),
            headers={"Content-Type": "application/json"},
            method="POST")
        with urllib.request.urlopen(req) as r:
            tid = r.headers.get("X-Trivy-Trace-Id")
        doc = _rpc_trace(watch_server, tid)
        assert doc["trace_id"] == tid
        assert "server.rpc" in {s["name"] for s in doc["spans"]}
        # no trace_id → the buffer listing
        listing = json.loads(urllib.request.urlopen(
            watch_server + "/debug/traces").read())
        assert tid in listing["traces"]

    def test_remote_parent_header_adopted(self, watch_server):
        req = urllib.request.Request(
            watch_server + "/twirp/trivy.cache.v1.Cache/MissingBlobs",
            data=json.dumps({"artifact_id": "x",
                             "blob_ids": []}).encode(),
            headers={"Content-Type": "application/json",
                     "X-Trivy-Trace-Id": "ab" * 16,
                     "X-Trivy-Parent-Span": "c0ffee0012345678"},
            method="POST")
        urllib.request.urlopen(req).read()
        doc = _rpc_trace(watch_server, "ab" * 16)
        root = next(s for s in doc["spans"]
                    if s["name"] == "server.rpc")
        assert root["parent_id"] == "c0ffee0012345678"

    def test_debug_surface_is_token_gated(self, tmp_path_factory):
        """A server started with --token must gate /debug/traces and
        /debug/incidents like the POST surface — the buffers carry
        scan detail (file paths, other requests' trace ids) the token
        was configured to protect. /healthz stays open for probes."""
        import urllib.error

        from trivy_tpu.server.listen import serve_background
        port = _free_port()
        httpd, state = serve_background(
            "127.0.0.1", port, _fixture_table(),
            cache_dir=str(tmp_path_factory.mktemp("tcache")),
            token="s3cret")
        base = f"http://127.0.0.1:{port}"
        try:
            for path in ("/debug/traces", "/debug/incidents"):
                with pytest.raises(urllib.error.HTTPError) as e:
                    urllib.request.urlopen(base + path)
                assert e.value.code == 401
                req = urllib.request.Request(
                    base + path, headers={"Trivy-Token": "s3cret"})
                with urllib.request.urlopen(req) as r:
                    assert r.status == 200
            # liveness surface stays open
            req = urllib.request.Request(
                base + "/healthz", headers={"Accept": "text/plain"})
            assert urllib.request.urlopen(req).read() == b"ok"
        finally:
            httpd.shutdown()
            state.close()

    def test_healthz_has_slo_block(self, watch_server):
        doc = json.loads(urllib.request.urlopen(
            watch_server + "/healthz").read())
        assert set(doc["slo"]) == {"scan_latency_p99", "scan_errors",
                                   "device_serving"}
        for obj in doc["slo"].values():
            assert set(obj["windows"]) == {"300s", "3600s"}


# ---------------------------------------------------------------------------
# fanal attribution spans (graftwatch piece 4)

class TestFanalAttribution:
    def test_layer_analyze_cache_spans(self, tmp_path):
        from trivy_tpu.fanal.artifact import ImageArchiveArtifact
        from trivy_tpu.fanal.cache import MemoryCache
        img = str(tmp_path / "img.tar")
        make_image(img, [{
            "etc/os-release": ALPINE_OS_RELEASE,
            "lib/apk/db/installed": APK_INSTALLED,
        }])
        cache = MemoryCache()
        tid = "ba" * 16
        with new_trace(tid):
            with span("test.root"):
                ImageArchiveArtifact(img, cache).inspect()
        names = [s["name"] for s in RECORDER.spans(tid)]
        assert "fanal.cache_check" in names
        assert "fanal.layer_walk" in names
        assert "fanal.analyze" in names
        analyzers = {s["attrs"]["analyzer"]
                     for s in RECORDER.spans(tid)
                     if s["name"] == "fanal.analyze"}
        assert {"apk", "os-release"} <= analyzers
        # second inspect: cache hits short-circuit the walk entirely
        tid2 = "cb" * 16
        with new_trace(tid2):
            with span("test.root"):
                ImageArchiveArtifact(img, cache).inspect()
        spans2 = RECORDER.spans(tid2)
        checks = [s for s in spans2 if s["name"] == "fanal.cache_check"]
        assert checks and checks[0]["attrs"]["misses"] == 0
        assert not any(s["name"] == "fanal.layer_walk" for s in spans2)
