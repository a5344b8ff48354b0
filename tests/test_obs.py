"""graftscope tests: metrics v2 exposition round-trips, span nesting,
Chrome-trace export from a tiny scan (golden span topology), trace-id
propagation client→server→logs, /healthz device status, and the
strict-parser CI gate for the live /metrics endpoint."""

import glob as _glob
import io
import json
import os
import socket
import urllib.error
import urllib.request

import pytest

from helpers import (ALPINE_OS_RELEASE, APK_INSTALLED, make_image,
                     parse_exposition)
from trivy_tpu import log as tlog
from trivy_tpu.db import build_table
from trivy_tpu.db.fixtures import load_fixture_files
from trivy_tpu.metrics import METRICS, Registry
from trivy_tpu.obs import trace as obs_trace
from trivy_tpu.obs.trace import (COLLECTOR, chrome_trace,
                                 current_trace_id, ensure_trace,
                                 new_trace, span)

FIXDIR = os.path.join(os.path.dirname(__file__), "fixtures", "db")
FIXGLOB = os.path.join(FIXDIR, "*.yaml")
GOLDEN_EDGES = os.path.join(os.path.dirname(__file__), "fixtures",
                            "obs", "golden_trace_edges.json")


def _fixture_table():
    advisories, details, _ = load_fixture_files(
        sorted(_glob.glob(FIXGLOB)))
    return build_table(advisories, details)


# ---------------------------------------------------------------------------
# metrics v2: exposition round-trips through the strict parser

class TestMetricsV2:
    def test_histogram_roundtrip(self):
        r = Registry()
        r.declare("t_lat_seconds", "histogram", "Test latency.",
                  buckets=(0.1, 1.0, 5.0))
        for v in (0.05, 0.1, 0.10001, 2.0, 99.0):
            r.observe("t_lat_seconds", v)
        fams = parse_exposition(r.render())
        fam = fams["t_lat_seconds"]
        assert fam["type"] == "histogram"
        assert fam["help"] == "Test latency."
        by_name = {}
        for sname, labels, value in fam["samples"]:
            by_name.setdefault(sname, []).append((labels, value))
        # le is inclusive: 0.05 and 0.1 land in le="0.1"
        buckets = {l["le"]: v for l, v in by_name["t_lat_seconds_bucket"]}
        assert buckets == {"0.1": 2, "1": 3, "5": 4, "+Inf": 5}
        assert by_name["t_lat_seconds_count"][0][1] == 5
        assert by_name["t_lat_seconds_sum"][0][1] == pytest.approx(
            0.05 + 0.1 + 0.10001 + 2.0 + 99.0)

    def test_histogram_with_labels_and_escaping(self):
        r = Registry()
        r.declare("t_h", "histogram", "h", buckets=(1.0,))
        r.observe("t_h", 0.5, route='a"b\\c\nd')
        fams = parse_exposition(r.render())
        samples = fams["t_h"]["samples"]
        label_vals = {l["route"] for _, l, _ in samples}
        assert label_vals == {'a"b\\c\nd'}

    def test_gauge_roundtrip(self):
        r = Registry()
        r.declare("t_depth", "gauge", "Depth.")
        r.gauge_add("t_depth", 3)
        r.gauge_add("t_depth", -1)
        assert r.get("t_depth") == 2
        r.set_gauge("t_depth", 7.5)
        fams = parse_exposition(r.render())
        assert fams["t_depth"]["type"] == "gauge"
        assert fams["t_depth"]["samples"] == [("t_depth", {}, 7.5)]

    def test_counters_keep_legacy_shape(self):
        r = Registry()
        r.inc("t_total", 2, source="alpine 3.19")
        text = r.render()
        assert "# TYPE t_total counter" in text
        assert 't_total{source="alpine 3.19"} 2' in text
        parse_exposition(text)

    def test_parser_rejects_malformed(self):
        with pytest.raises(ValueError):
            parse_exposition("t_x 1\n")  # sample without TYPE
        with pytest.raises(ValueError):
            parse_exposition("# TYPE t_x counter\nt_x{a=\"b} 1\n")
        with pytest.raises(ValueError):  # non-cumulative buckets
            parse_exposition(
                "# TYPE t_h histogram\n"
                't_h_bucket{le="1"} 5\n'
                't_h_bucket{le="+Inf"} 3\n'
                "t_h_sum 1\nt_h_count 3\n")
        with pytest.raises(ValueError):  # missing +Inf
            parse_exposition(
                "# TYPE t_h histogram\n"
                't_h_bucket{le="1"} 1\n'
                "t_h_sum 1\nt_h_count 1\n")
        with pytest.raises(ValueError):  # _count != +Inf bucket
            parse_exposition(
                "# TYPE t_h histogram\n"
                't_h_bucket{le="+Inf"} 2\n'
                "t_h_sum 1\nt_h_count 3\n")

    def test_redeclare_with_new_buckets_resets_series(self):
        r = Registry()
        r.observe("t_h2", 0.5)  # picks up DEFAULT_BUCKETS
        r.declare("t_h2", "histogram", "h", buckets=(1.0, 2.0))
        r.observe("t_h2", 1.5)
        fams = parse_exposition(r.render())
        buckets = [(l["le"], v) for n, l, v in fams["t_h2"]["samples"]
                   if n == "t_h2_bucket"]
        assert buckets == [("1", 0), ("2", 1), ("+Inf", 1)]

    def test_parser_accepts_summary_quantiles(self):
        fams = parse_exposition(
            "# TYPE t_s summary\n"
            't_s{quantile="0.5"} 0.1\n'
            "t_s_sum 1\nt_s_count 3\n")
        assert fams["t_s"]["type"] == "summary"
        assert len(fams["t_s"]["samples"]) == 3

    def test_global_registry_render_stays_strict(self):
        """The CI gate on the process-wide registry: whatever the suite
        has pumped into METRICS so far must render parseable."""
        parse_exposition(METRICS.render())


# ---------------------------------------------------------------------------
# tracer core

class TestTracer:
    def test_span_nesting_and_trace_ids(self):
        COLLECTOR.enable()
        try:
            with new_trace("f" * 32) as tid:
                assert current_trace_id() == tid
                with span("outer", a=1) as so:
                    with span("inner") as si:
                        si.attrs["b"] = 2
            assert current_trace_id() == ""
        finally:
            COLLECTOR.disable()
        spans = {s.name: s for s in COLLECTOR.drain()}
        assert spans["inner"].parent_id == spans["outer"].span_id
        assert spans["outer"].parent_id == ""
        assert {s.trace_id for s in spans.values()} == {"f" * 32}
        assert spans["outer"].dur >= spans["inner"].dur >= 0
        assert spans["inner"].attrs == {"b": 2}

    def test_ensure_trace_reuses_active(self):
        with new_trace("a" * 32):
            with ensure_trace() as tid:
                assert tid == "a" * 32
        with ensure_trace() as tid:
            assert len(tid) == 32 and tid != "a" * 32

    def test_disabled_collector_records_nothing(self):
        COLLECTOR.disable()
        before = len(COLLECTOR.snapshot())
        with span("ignored") as sp:
            sp.attrs["x"] = 1  # attr writes on the no-op span are fine
        assert len(COLLECTOR.snapshot()) == before

    def test_span_limit_truncation_is_marked(self):
        COLLECTOR.enable(limit=2)
        try:
            for i in range(4):
                with span(f"s{i}"):
                    pass
        finally:
            COLLECTOR.disable()
        assert COLLECTOR.dropped == 2
        doc = chrome_trace(COLLECTOR.drain())
        marker = [e for e in doc["traceEvents"]
                  if e["name"] == "graftscope.dropped_spans"]
        assert marker and marker[0]["args"]["dropped"] == 2
        COLLECTOR.enable(limit=200_000)  # restore default for later tests
        COLLECTOR.disable()

    def test_chrome_trace_schema(self):
        COLLECTOR.enable()
        try:
            with span("a"):
                with span("b"):
                    pass
        finally:
            COLLECTOR.disable()
        doc = chrome_trace(COLLECTOR.drain())
        assert doc["displayTimeUnit"] == "ms"
        for ev in doc["traceEvents"]:
            assert ev["ph"] == "X"
            assert set(ev) >= {"name", "ts", "dur", "pid", "tid", "args"}
        json.dumps(doc)  # must be JSON-serializable


def _burn(seconds: float) -> None:
    """Spin the calling thread's CPU for `seconds` of its own time."""
    import time
    t0 = time.thread_time()
    while time.thread_time() - t0 < seconds:
        pass


class TestSpanTime:
    def test_cpu_is_the_threads_own(self):
        import threading
        import time
        stop = threading.Event()
        other = threading.Thread(
            target=lambda: [_burn(0.005) for _ in iter(stop.is_set, True)])
        other.start()
        try:
            with span("t.sleeper") as sp:
                time.sleep(0.1)
        finally:
            stop.set()
            other.join()
        # another thread burned CPU all along; the sleeping span's own
        # thread used next to none
        assert sp.dur >= 0.1
        assert sp.cpu < 0.03

    def test_self_time_subtracts_same_thread_children_only(self):
        import contextvars
        import threading
        with span("t.parent") as parent:
            _burn(0.02)
            with span("t.child") as child:
                _burn(0.03)
            # a child on another thread, under this context, runs
            # beside the parent: it subtracts nothing
            ctx = contextvars.copy_context()
            got = []
            t = threading.Thread(target=ctx.run, args=(
                lambda: got.append(_in_span("t.remote", 0.03)),))
            t.start()
            t.join()
        remote = got[0]
        assert remote.parent_id == parent.span_id
        assert parent.child_dur == pytest.approx(child.dur)
        assert parent.child_cpu == pytest.approx(child.cpu)
        assert parent.self_dur == pytest.approx(parent.dur - child.dur)
        assert parent.self_cpu == pytest.approx(parent.cpu - child.cpu)
        assert parent.self_cpu >= 0.02 and child.self_cpu >= 0.03
        assert remote.self_cpu >= 0.03
        # the remote child's time stays in the parent's self wall time
        assert parent.self_dur >= remote.dur

    def test_span_counters_aggregate_self_time(self):
        name = "t.counted"
        key = (("span", name),)

        def read():
            return (METRICS.family("trivy_tpu_spans_total").get(key, 0.0),
                    METRICS.family(
                        "trivy_tpu_span_self_seconds_total").get(key, 0.0),
                    METRICS.family(
                        "trivy_tpu_span_self_cpu_seconds_total").get(
                            key, 0.0))
        n0, wall0, cpu0 = read()
        spans = []
        for _ in range(3):
            with span(name) as sp:
                _burn(0.002)
                with span("t.counted_child"):
                    _burn(0.002)
            spans.append(sp)
        n1, wall1, cpu1 = read()
        assert n1 - n0 == 3
        assert wall1 - wall0 == pytest.approx(
            sum(s.self_dur for s in spans))
        assert cpu1 - cpu0 == pytest.approx(sum(s.self_cpu for s in spans))
        assert obs_trace.span_totals()[name][0] == n1
        # /metrics exports the aggregate under the span label
        fams = parse_exposition(METRICS.render())
        samples = {(n, l.get("span")): v for n, l, v in
                   fams["trivy_tpu_spans_total"]["samples"]}
        assert samples[("trivy_tpu_spans_total", name)] == n1
        assert "trivy_tpu_span_self_cpu_seconds_total" in fams

    def test_phase_totals_count_since_recording_began(self):
        with span("t.phase"):
            pass
        COLLECTOR.enable()
        try:
            for _ in range(2):
                with span("t.phase"):
                    _burn(0.001)
            phases = COLLECTOR.phase_totals()
        finally:
            COLLECTOR.disable()
        assert phases["t.phase"]["count"] == 2
        assert phases["t.phase"]["self_cpu_ms"] >= 2.0
        assert phases["t.phase"]["self_ms"] >= \
            phases["t.phase"]["self_cpu_ms"] * 0.5

    def test_span_ids_are_16_hex_and_unique_across_processes(self):
        import re
        import subprocess
        import sys
        ids = []
        for _ in range(2000):
            with span("t.id") as sp:
                pass
            ids.append(sp.span_id)
        assert len(set(ids)) == len(ids)
        assert all(re.fullmatch(r"[0-9a-f]{16}", i) for i in ids)
        code = ("from trivy_tpu.obs.trace import span\n"
                "with span('x') as sp: pass\nprint(sp.span_id)")
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        others = {subprocess.run(
            [sys.executable, "-c", code], cwd=root, check=True,
            capture_output=True, text=True).stdout.strip()
            for _ in range(3)}
        # each process draws its own prefix
        assert len({i[:8] for i in others} | {ids[0][:8]}) == 4

    def test_span_lands_inside_an_annotation_in_the_xplane(self, tmp_path):
        import jax
        from jax.profiler import ProfileData
        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation("t.enclosing"):
                with span("t.profiled"):
                    _burn(0.005)
        finally:
            jax.profiler.stop_trace()
        (path,) = _glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                             recursive=True)
        events = {}
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith("t."):
                            events[ev.name] = (ev.start_ns, ev.start_ns
                                               + ev.duration_ns)
        outer, inner = events["t.enclosing"], events["t.profiled"]
        assert outer[0] <= inner[0] < inner[1] <= outer[1]
        assert inner[1] - inner[0] >= 5e6


def _in_span(name: str, seconds: float):
    with span(name) as sp:
        _burn(seconds)
    return sp


# ---------------------------------------------------------------------------
# golden chrome trace from a tiny scan

class TestTinyScanTrace:
    def _scan_events(self, tmp_path):
        from trivy_tpu import types as T
        from trivy_tpu.fanal.artifact import ImageArchiveArtifact
        from trivy_tpu.fanal.cache import MemoryCache
        from trivy_tpu.scanner import LocalScanner
        img = str(tmp_path / "img.tar")
        make_image(img, [{
            "etc/os-release": ALPINE_OS_RELEASE,
            "lib/apk/db/installed": APK_INSTALLED,
        }])
        cache = MemoryCache()
        ref = ImageArchiveArtifact(img, cache,
                                   scanners=("vuln",)).inspect()
        scanner = LocalScanner(cache, _fixture_table())
        COLLECTOR.enable()
        try:
            results, _ = scanner.scan(
                ref.name, ref.id, ref.blob_ids,
                T.ScanOptions(scanners=("vuln",)))
        finally:
            COLLECTOR.disable()
        assert any(r.vulnerabilities for r in results)
        return chrome_trace(COLLECTOR.drain())["traceEvents"]

    def test_trace_has_nested_detect_phases_under_one_trace_id(
            self, tmp_path):
        events = self._scan_events(tmp_path)
        tids = {e["args"]["trace_id"] for e in events}
        assert len(tids) == 1 and "" not in tids  # one per-scan trace
        names = {e["name"] for e in events}
        assert {"scan", "scan.apply_layers", "fanal.apply_layers",
                "scan.build_queries", "scan.detect", "detect.prepare",
                "detect.dispatch", "detect.fetch",
                "detect.device_wait", "detect.assemble",
                "scan.assemble_results"} <= names
        # detect phases nest inside scan.detect by both parentage and
        # time containment — the fetch and assembly on their pools'
        # threads too
        by_id = {e["args"]["span_id"]: e for e in events}
        detect = next(e for e in events if e["name"] == "scan.detect")
        for phase in ("detect.prepare", "detect.dispatch", "detect.fetch",
                      "detect.device_wait", "detect.assemble"):
            ev = next(e for e in events if e["name"] == phase)
            assert by_id[ev["args"]["parent_id"]] is detect
            assert ev["ts"] >= detect["ts"] - 1e-3
            assert ev["ts"] + ev["dur"] <= \
                detect["ts"] + detect["dur"] + 1e-3
        # prepare carries the padding-waste attribution
        prep = next(e for e in events if e["name"] == "detect.prepare")
        assert prep["args"]["n_pairs"] >= 1
        assert prep["args"]["t_pad"] >= prep["args"]["n_pairs"]

    def test_recording_keeps_the_detectd_path(self, tmp_path):
        """A recorded scan through a scanner with detectd runs what
        the server runs: the handler waits on detectd, whose merged
        round dispatches."""
        from trivy_tpu import types as T
        from trivy_tpu.detect import SchedOptions
        from trivy_tpu.fanal.artifact import ImageArchiveArtifact
        from trivy_tpu.fanal.cache import MemoryCache
        from trivy_tpu.scanner import LocalScanner
        img = str(tmp_path / "img.tar")
        make_image(img, [{
            "etc/os-release": ALPINE_OS_RELEASE,
            "lib/apk/db/installed": APK_INSTALLED,
        }])
        cache = MemoryCache()
        ref = ImageArchiveArtifact(img, cache,
                                   scanners=("vuln",)).inspect()
        scanner = LocalScanner(cache, _fixture_table(),
                               sched=SchedOptions())
        COLLECTOR.enable()
        try:
            results, _ = scanner.scan(
                ref.name, ref.id, ref.blob_ids,
                T.ScanOptions(scanners=("vuln",)))
        finally:
            COLLECTOR.disable()
            scanner.close()
        assert any(r.vulnerabilities for r in results)
        names = {s.name for s in COLLECTOR.drain()}
        assert {"detectd.wait", "detectd.round", "detect.fetch",
                "detect.assemble"} <= names
        assert "detect.device_wait" not in names

    def test_trace_topology_matches_golden(self, tmp_path):
        """The span topology (parent→child name edges) of a tiny vuln
        scan is a checked-in golden: pipeline restructurings must
        update it consciously."""
        events = self._scan_events(tmp_path)
        by_id = {e["args"]["span_id"]: e["name"] for e in events}
        edges = sorted({
            (by_id.get(e["args"]["parent_id"], ""), e["name"])
            for e in events})
        with open(GOLDEN_EDGES) as f:
            golden = [tuple(e) for e in json.load(f)]
        assert edges == golden, (
            "span topology drifted; update "
            "tests/fixtures/obs/golden_trace_edges.json: "
            + json.dumps(edges))


# ---------------------------------------------------------------------------
# client → server propagation, logs, healthz, live /metrics

@pytest.fixture(scope="module")
def obs_server(tmp_path_factory):
    from trivy_tpu.server.listen import serve_background
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    httpd, state = serve_background(
        "127.0.0.1", port, _fixture_table(),
        cache_dir=str(tmp_path_factory.mktemp("obscache")))
    yield f"http://127.0.0.1:{port}"
    httpd.shutdown()


def _push_and_scan(base, tmp_path):
    from trivy_tpu.fanal.artifact import ImageArchiveArtifact
    from trivy_tpu.server.client import RemoteCache, RemoteScanner
    img = str(tmp_path / "img.tar")
    make_image(img, [{
        "etc/os-release": ALPINE_OS_RELEASE,
        "lib/apk/db/installed": APK_INSTALLED,
    }])
    cache = RemoteCache(base)
    ref = ImageArchiveArtifact(img, cache).inspect()
    return RemoteScanner(base).scan(ref.name, ref.id, ref.blob_ids)


class TestPropagation:
    def test_trace_id_client_to_server_to_logs(self, obs_server,
                                               tmp_path):
        buf = io.StringIO()
        tlog.configure(stream=buf, fmt="json")
        tlog.set_debug(True)
        tid = "deadbeef" * 4
        try:
            with obs_trace.new_trace(tid):
                results, os_info = _push_and_scan(obs_server, tmp_path)
        finally:
            tlog.set_debug(False)
            tlog.configure()
        assert os_info.family == "alpine"
        lines = [json.loads(l) for l in
                 buf.getvalue().splitlines() if l.strip()]
        server_scan_logs = [l for l in lines
                            if l["logger"] == "trivy_tpu.server"
                            and l["msg"].startswith("scan ")]
        # the server handler thread logged under the CLIENT's trace id
        assert server_scan_logs
        assert all(l["trace_id"] == tid for l in server_scan_logs)

    def test_response_echoes_forwarded_trace_header(self, obs_server):
        req = urllib.request.Request(
            obs_server + "/twirp/trivy.scanner.v1.Scanner/Scan",
            data=json.dumps({"target": "t", "artifact_id": "missing",
                             "blob_ids": ["nope"]}).encode(),
            headers={"Content-Type": "application/json",
                     "X-Trivy-Trace-Id": "cafe" * 8},
            method="POST")
        try:
            with urllib.request.urlopen(req) as r:
                hdr = r.headers.get("X-Trivy-Trace-Id")
        except urllib.error.HTTPError as e:
            hdr = e.headers.get("X-Trivy-Trace-Id")
        assert hdr == "cafe" * 8

    def test_keepalive_get_does_not_echo_previous_trace(
            self, obs_server):
        import http.client
        host = obs_server[len("http://"):]
        conn = http.client.HTTPConnection(host)
        try:
            body = json.dumps({"artifact_id": "x", "blob_ids": []})
            conn.request("POST",
                         "/twirp/trivy.cache.v1.Cache/MissingBlobs",
                         body=body,
                         headers={"Content-Type": "application/json",
                                  "X-Trivy-Trace-Id": "beef" * 8})
            r = conn.getresponse()
            r.read()
            assert r.headers.get("X-Trivy-Trace-Id") == "beef" * 8
            # same keep-alive connection, same handler instance: the
            # health probe must not inherit the scan's trace id
            conn.request("GET", "/healthz")
            r = conn.getresponse()
            r.read()
            assert r.headers.get("X-Trivy-Trace-Id") is None
        finally:
            conn.close()

    def test_server_mints_trace_id_when_absent(self, obs_server):
        req = urllib.request.Request(
            obs_server + "/twirp/trivy.cache.v1.Cache/MissingBlobs",
            data=json.dumps({"artifact_id": "x",
                             "blob_ids": []}).encode(),
            headers={"Content-Type": "application/json"},
            method="POST")
        with urllib.request.urlopen(req) as r:
            hdr = r.headers.get("X-Trivy-Trace-Id")
        assert hdr and len(hdr) == 32

    def test_healthz_json_and_plain(self, obs_server, tmp_path):
        # default: JSON device-backend status
        doc = json.loads(urllib.request.urlopen(
            obs_server + "/healthz").read())
        assert doc["status"] == "ok"
        assert set(doc["device"]) == {"platform", "device_count",
                                      "last_dispatch_age_s",
                                      "memory"}
        # after a scan the dispatch stamp is fresh and the backend
        # identity is resolved
        _push_and_scan(obs_server, tmp_path)
        doc = json.loads(urllib.request.urlopen(
            obs_server + "/healthz").read())
        assert doc["device"]["platform"] not in ("", "uninitialized")
        assert doc["device"]["device_count"] >= 1
        assert doc["device"]["last_dispatch_age_s"] is not None
        assert doc["device"]["last_dispatch_age_s"] < 60
        # probes asking for text/plain keep the byte-exact fast path
        req = urllib.request.Request(
            obs_server + "/healthz",
            headers={"Accept": "text/plain"})
        assert urllib.request.urlopen(req).read() == b"ok"

    def test_live_metrics_strictly_parseable_with_histograms(
            self, obs_server, tmp_path):
        """CI gate: the real /metrics payload after real traffic must
        survive the strict parser and expose a consistent scan-latency
        histogram."""
        _push_and_scan(obs_server, tmp_path)
        body = urllib.request.urlopen(
            obs_server + "/metrics").read().decode()
        fams = parse_exposition(body)
        lat = fams["trivy_tpu_scan_latency_seconds"]
        assert lat["type"] == "histogram"
        count = [v for n, l, v in lat["samples"]
                 if n.endswith("_count")][0]
        assert count >= 1
        occ = fams["trivy_tpu_batch_occupancy_ratio"]
        assert occ["type"] == "histogram"
        assert fams["trivy_tpu_dispatch_depth"]["type"] == "gauge"
        assert fams["trivy_tpu_dispatch_depth"]["samples"][0][2] == 0
        stall = fams["trivy_tpu_device_get_stall_seconds"]
        inf_bucket = [v for n, l, v in stall["samples"]
                      if l.get("le") == "+Inf"]
        assert inf_bucket and inf_bucket[0] >= 1


# ---------------------------------------------------------------------------
# log formatter satellites

class TestLogging:
    def test_text_format_carries_logger_name_and_trace(self):
        buf = io.StringIO()
        tlog.configure(stream=buf, fmt="text")
        try:
            with obs_trace.new_trace("ab" * 16):
                tlog.get("fanal").warning("boom %d", 7)
        finally:
            tlog.configure()
        line = buf.getvalue().strip()
        assert "\ttrivy_tpu.fanal\t" in line
        assert f"trace={'ab' * 16}" in line
        assert line.endswith("boom 7")

    def test_text_format_without_trace(self):
        buf = io.StringIO()
        tlog.configure(stream=buf, fmt="text")
        try:
            tlog.logger.warning("plain")
        finally:
            tlog.configure()
        assert "trace=-\t" in buf.getvalue()

    def test_json_format_env_optin(self, monkeypatch):
        monkeypatch.setenv("TRIVY_TPU_LOG_FORMAT", "json")
        buf = io.StringIO()
        tlog.configure(stream=buf)  # fmt=None → env
        try:
            tlog.get("db").warning("hello")
        finally:
            monkeypatch.delenv("TRIVY_TPU_LOG_FORMAT")
            tlog.configure()
        doc = json.loads(buf.getvalue())
        assert doc["logger"] == "trivy_tpu.db"
        assert doc["level"] == "WARNING"
        assert doc["msg"] == "hello"
        assert doc["trace_id"] == "-"


# ---------------------------------------------------------------------------
# --trace FILE end to end through the CLI

class TestCliTrace:
    def test_image_scan_writes_chrome_trace(self, tmp_path, capsys):
        from trivy_tpu import cli
        img = str(tmp_path / "img.tar")
        make_image(img, [{
            "etc/os-release": ALPINE_OS_RELEASE,
            "lib/apk/db/installed": APK_INSTALLED,
        }])
        out_trace = str(tmp_path / "scan.trace.json")
        code = cli.main([
            "image", "--input", img, "--db", FIXGLOB,
            "--cache-dir", str(tmp_path / "c"),
            "--trace", out_trace])
        capsys.readouterr()
        assert code == 0
        with open(out_trace) as f:
            doc = json.load(f)
        names = {e["name"] for e in doc["traceEvents"]}
        assert {"scan", "detect.prepare", "detect.dispatch",
                "detect.device_wait", "detect.assemble"} <= names
        # recording starts before artifact inspection, so the walker
        # phase is in the trace too (the README's promise) — per-layer
        # fanald walk spans since the pipeline rebuild
        assert "fanal.layer_walk" in names
        tids = {e["args"]["trace_id"] for e in doc["traceEvents"]
                if e["name"].startswith(("scan", "detect"))}
        assert len(tids) == 1 and "" not in tids
