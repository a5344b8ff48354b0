"""graftprof tests: dispatch-ledger accounting (per-shape rows, waste
ratios, transfer paths, budget adaptations), compile_ms phase labels +
the detect.compile span, strict exposition gating for every new
trivy_tpu_device_* series, the live profiler (one-at-a-time, cooldown,
obs.check-valid manifests, SLO burn auto-trigger), the /debug/perf +
/debug/profile server/router surfaces, the perfcheck regression gate
(clean pass, genuine regression, noise within spread, allow-listed
regression with reason, malformed schema → exit 2, checked-in golden
tail pair), and the ISSUE 13 acceptance drill: a c=8 routed load whose
/debug/perf shape table reconciles with the trivy_tpu_detect_* counters
and the graftscope phase breakdown (no merged-dispatch double-count),
a live /debug/profile capture mid-load, and perfcheck flagging a
planted 20% scan_throughput regression while passing an identical-tail
diff."""

import glob as _glob
import json
import os
import socket
import threading
import time
import urllib.error
import urllib.request

import pytest

from helpers import (ALPINE_OS_RELEASE, APK_INSTALLED, FakeRedis,
                     make_image, parse_exposition)
from trivy_tpu.db import build_table
from trivy_tpu.db.fixtures import load_fixture_files
from trivy_tpu.metrics import METRICS
from trivy_tpu.obs import COLLECTOR, RECORDER, check as obs_check
from trivy_tpu.obs import perfcheck
from trivy_tpu.obs.perf import (LEDGER, PROF, DispatchLedger, Profiler,
                                ProfilerBusy, ProfilerCooldown,
                                debug_perf_payload,
                                debug_profile_payload)
from trivy_tpu.resilience import FAILPOINTS, GUARD

FIXDIR = os.path.join(os.path.dirname(__file__), "fixtures", "db")
GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")
GOLDEN_BASE = os.path.join(GOLDEN_DIR, "bench_tail_base.json")
GOLDEN_NEXT = os.path.join(GOLDEN_DIR, "bench_tail_next.json")


def _fixture_table():
    advisories, details, _ = load_fixture_files(
        sorted(_glob.glob(os.path.join(FIXDIR, "*.yaml"))))
    return build_table(advisories, details)


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.fixture(autouse=True)
def _clean_singletons():
    """GUARD/FAILPOINTS/PROF are process-global: every test starts
    and ends with defaults (the ledger is NOT reset here — tests
    assert on deltas or reset it themselves when they need absolute
    counts)."""
    FAILPOINTS.configure("")
    GUARD.reset_for_tests()
    PROF.reset_for_tests()
    yield
    FAILPOINTS.configure("")
    GUARD.reset_for_tests()
    PROF.reset_for_tests()


# ---------------------------------------------------------------------------
# dispatch ledger unit properties

class TestDispatchLedger:
    def test_note_dispatch_aggregates_per_shape(self):
        led = DispatchLedger()
        led.note_dispatch("detect", 100, 256)
        led.note_dispatch("detect", 200, 256)
        led.note_dispatch("detectd", 900, 1024, h_cap=128)
        rows = {(r["site"], r["t_pad"]): r for r in led.shape_table()}
        assert rows[("detect", 256)]["dispatches"] == 2
        assert rows[("detect", 256)]["mean_occupancy"] == \
            pytest.approx(300 / 512, abs=1e-4)
        assert rows[("detect", 256)]["waste_bytes"] == 156 + 56
        assert rows[("detectd", 1024)]["h_cap"] == 128
        agg = led.aggregate()
        assert agg["dispatches"] == 3
        assert agg["distinct_shapes"] == 2
        assert agg["padding_waste_ratio"] == \
            pytest.approx(1 - 1200 / 1536, abs=1e-4)
        assert led.site_dispatches() == {"detect": 2, "detectd": 1}

    def test_warm_dispatches_are_not_traffic(self):
        led = DispatchLedger()
        led.note_dispatch("detect", 0, 256, warm=True)
        row = led.shape_table()[0]
        assert row["dispatches"] == 0
        assert row["warm_dispatches"] == 1
        agg = led.aggregate()
        assert agg["dispatches"] == 0
        assert agg["warm_dispatches"] == 1
        # warm rows contribute no occupancy (0/0 stays None, not 0.0)
        assert row["mean_occupancy"] is None

    def test_row_bytes_scales_waste(self):
        led = DispatchLedger()
        led.note_dispatch("secret", 60, 64, row_bytes=16384)
        assert led.shape_table()[0]["waste_bytes"] == 4 * 16384

    def test_hits_overflow_and_budget_adaptations(self):
        led = DispatchLedger()
        led.note_hits("detect", 1024, 128, 64)
        led.note_hits("detect", 1024, 128, 200)   # overflow
        row = led.shape_table()[0]
        assert row["overflows"] == 1
        assert row["mean_hit_fill"] == \
            pytest.approx((64 / 128 + 200 / 128) / 2, abs=1e-4)
        led.note_budget_adapt("up")
        led.note_budget_adapt("down")
        led.note_budget_adapt("down")
        assert led.aggregate()["budget_adaptations"] == \
            {"up": 1, "down": 2}

    def test_transfer_paths_accumulate(self):
        led = DispatchLedger()
        led.note_transfer("compact", 100)
        led.note_transfer("compact", 50)
        led.note_transfer("dense", 1000)
        led.note_transfer("overflow", 1000)
        assert led.aggregate()["transfer_bytes"] == \
            {"compact": 150, "dense": 1000, "overflow": 1000}

    def test_compile_accounting(self):
        led = DispatchLedger()
        led.note_compile("detect", 256, 0, 500.0, warm=True)
        led.note_compile("detect", 256, 0, 100.0)
        row = led.shape_table()[0]
        assert row["compiles"] == 2
        assert row["compile_ms"] == pytest.approx(600.0)

    def test_resident_and_memory_status(self):
        led = DispatchLedger()
        led.note_resident("advisory_table", 4096)
        led.note_resident("secret_bank", 128)
        led.note_resident("advisory_table", 8192)   # re-stamp, not add
        mem = led.memory_status()
        assert mem["resident_bytes"] == {"advisory_table": 8192,
                                         "secret_bank": 128}
        # CPU backends expose no memory_stats: the sample is a no-op
        # and the cached view stays empty (never raises)
        led.sample_memory(force=True)
        assert isinstance(led.memory_status()["backends"], dict)

    def test_ledger_is_thread_safe(self):
        led = DispatchLedger()

        def hammer():
            for _ in range(500):
                led.note_dispatch("detect", 10, 64)
                led.note_transfer("dense", 64)
        threads = [threading.Thread(target=hammer) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        agg = led.aggregate()
        assert agg["dispatches"] == 4000
        assert agg["transfer_bytes"]["dense"] == 4000 * 64


# ---------------------------------------------------------------------------
# engine integration: ledger rows + compile phases from real dispatches

class TestEngineIntegration:
    def test_detect_populates_ledger_and_compile_phase(self):
        from trivy_tpu.detect.engine import BatchDetector, PkgQuery
        table = _fixture_table()
        d0 = LEDGER.site_dispatches().get("detect", 0)
        _h_row, _h_sum, h_n0 = METRICS.hist_get(
            "trivy_tpu_device_compile_ms", phase="traffic")
        COLLECTOR.enable()
        try:
            det = BatchDetector(table)
            hits = det.detect(
                [PkgQuery("alpine 3.17", "apk", "openssl", "3.0.7-r0")])
            phases = COLLECTOR.phase_totals()
        finally:
            COLLECTOR.disable()
            det.close()
        assert hits
        assert LEDGER.site_dispatches()["detect"] == d0 + 1
        # a fresh detector's first shape is a compile: histogram moved
        # under phase="traffic" and the detect.compile span exists so
        # Perfetto shows the mid-measurement compile
        _h_row, _h_sum, h_n1 = METRICS.hist_get(
            "trivy_tpu_device_compile_ms", phase="traffic")
        assert h_n1 == h_n0 + 1
        assert "detect.compile" in phases
        # the ledger's resident gauge covers the table
        assert LEDGER.memory_status()["resident_bytes"][
            "advisory_table"] > 0

    def test_warmup_compiles_land_in_warmup_phase(self):
        from trivy_tpu.detect.engine import BatchDetector
        table = _fixture_table()
        _row, _sum, n0 = METRICS.hist_get(
            "trivy_tpu_device_compile_ms", phase="warmup")
        det = BatchDetector(table)
        try:
            rungs = det.warmup(max_pairs=1 << 10)
        finally:
            det.close()
        assert rungs > 0
        _row, _sum, n1 = METRICS.hist_get(
            "trivy_tpu_device_compile_ms", phase="warmup")
        assert n1 > n0
        # warm launches never count as ledger traffic dispatches
        agg = LEDGER.aggregate()
        assert agg["warm_dispatches"] > 0

    def test_exposition_strict_for_device_series(self):
        """Every trivy_tpu_device_* series the ledger emits renders
        under the strict exposition parser with its declared type."""
        LEDGER.note_dispatch("detect", 10, 64)
        LEDGER.note_compile("detect", 64, 0, 12.0)
        LEDGER.note_transfer("compact", 123)
        LEDGER.note_budget_adapt("up")
        LEDGER.note_resident("advisory_table", 1024)
        fams = parse_exposition(METRICS.render())
        want = {
            "trivy_tpu_device_dispatches_total": "counter",
            "trivy_tpu_device_padding_waste_ratio": "histogram",
            "trivy_tpu_device_compile_ms": "histogram",
            "trivy_tpu_device_transfer_bytes_total": "counter",
            "trivy_tpu_device_hit_budget_adaptations_total": "counter",
            "trivy_tpu_device_resident_bytes": "gauge",
        }
        for name, kind in want.items():
            assert name in fams, name
            assert fams[name]["type"] == kind
        # label discipline: the dispatch counter is per-site
        sites = {l["site"] for _n, l, _v in
                 fams["trivy_tpu_device_dispatches_total"]["samples"]}
        assert "detect" in sites


# ---------------------------------------------------------------------------
# live profiler

class TestProfiler:
    def _prof(self, tmp_path, cooldown=0.0):
        RECORDER.configure(incident_dir=str(tmp_path))
        p = Profiler()
        p.configure(cooldown_s=cooldown)
        return p

    def test_capture_writes_checkvalid_manifest(self, tmp_path):
        p = self._prof(tmp_path)
        c0 = METRICS.get("trivy_tpu_profile_captures_total",
                         reason="manual")
        doc = p.capture(40, reason="manual")
        assert doc["schema"] == "trivy-tpu-profile/1"
        assert os.path.isdir(doc["artifact_dir"])
        assert doc["files"], "capture produced no artifact files"
        assert obs_check.check_file(doc["manifest"]) == []
        assert METRICS.get("trivy_tpu_profile_captures_total",
                           reason="manual") == c0 + 1

    def test_one_at_a_time(self, tmp_path):
        p = self._prof(tmp_path)
        started = threading.Event()
        done: list = []

        def long_capture():
            started.set()
            done.append(p.capture(600, reason="manual"))

        t = threading.Thread(target=long_capture)
        t.start()
        started.wait()
        time.sleep(0.1)   # let start_trace land
        with pytest.raises(ProfilerBusy):
            p.capture(10)
        t.join()
        assert done and done[0]["files"]

    def test_cooldown_limits_and_force_bypasses(self, tmp_path):
        p = self._prof(tmp_path, cooldown=60.0)
        p.capture(10)
        with pytest.raises(ProfilerCooldown) as e:
            p.capture(10)
        assert e.value.retry_after_s > 0
        # operator force is never rate-limited
        assert p.capture(10, force=True)["files"]

    def test_capture_dir_context_is_exclusive(self, tmp_path):
        p = self._prof(tmp_path, cooldown=60.0)
        out = str(tmp_path / "cli-profile")
        with p.capture_dir(out):
            with pytest.raises(ProfilerBusy):
                p.capture(10)
        assert any(files for _r, _d, files in os.walk(out))

    def test_burn_auto_trigger_captures_once(self, tmp_path):
        p = self._prof(tmp_path, cooldown=120.0)
        p.configure(auto_burn_threshold=2.0, auto_capture_ms=20)
        rates = {"scan_errors": {"target": 0.999, "windows": {
            "300s": {"total": 10, "bad": 5, "bad_ratio": 0.5,
                     "burn_rate": 500.0},
            "3600s": {"total": 10, "bad": 5, "bad_ratio": 0.5,
                      "burn_rate": 500.0}}}}
        p.observe_burn(rates)
        # generous: a 20 ms capture's stop_trace alone can take
        # >10 s on a contended box (observed in tier-1) — the
        # assertion is THAT it lands, not how fast
        deadline = time.monotonic() + 60.0
        manifests = []
        while time.monotonic() < deadline and not manifests:
            manifests = _glob.glob(
                str(tmp_path / "profile-*slo_burn*.json"))
            time.sleep(0.05)
        assert manifests, "burn threshold did not auto-capture"
        assert obs_check.check_file(manifests[0]) == []
        # the cooldown makes a sustained burn capture ONCE per window
        p.observe_burn(rates)
        time.sleep(0.3)
        assert len(_glob.glob(
            str(tmp_path / "profile-*slo_burn*.json"))) == 1

    def test_below_threshold_never_triggers(self, tmp_path):
        p = self._prof(tmp_path)
        p.configure(auto_burn_threshold=10.0)
        p.observe_burn({"scan_errors": {"windows": {
            "300s": {"burn_rate": 0.5}}}})
        time.sleep(0.2)
        assert not _glob.glob(str(tmp_path / "profile-*.json"))

    def test_slo_export_feeds_the_auto_trigger(self, tmp_path):
        """The wiring contract: SLO.export() hands its burn document
        to PROF — bad traffic past the threshold yields a capture
        without any scrape-side glue."""
        from trivy_tpu.obs.slo import SLOEngine
        RECORDER.configure(incident_dir=str(tmp_path))
        PROF.configure(cooldown_s=0.0, auto_burn_threshold=2.0,
                       auto_capture_ms=20)
        eng = SLOEngine()
        for _ in range(10):
            eng.observe_scan(0.0, "error")
        eng.export()
        # generous: a 20 ms capture's stop_trace alone can take
        # >10 s on a contended box (observed in tier-1) — the
        # assertion is THAT it lands, not how fast
        deadline = time.monotonic() + 60.0
        manifests = []
        while time.monotonic() < deadline and not manifests:
            manifests = _glob.glob(
                str(tmp_path / "profile-*slo_burn*.json"))
            time.sleep(0.05)
        assert manifests
        doc = json.load(open(manifests[0]))
        assert doc["reason"].startswith("slo_burn:")

    def test_profile_manifest_schema_violations_detected(self,
                                                         tmp_path):
        bad = {"schema": "trivy-tpu-profile/1", "reason": "",
               "requested_ms": -1, "duration_ms": "x",
               "started_unix": 1.0, "artifact_dir": "",
               "files": []}
        path = tmp_path / "profile-bad.json"
        path.write_text(json.dumps(bad))
        problems = obs_check.check_file(str(path))
        assert any("reason" in p for p in problems)
        assert any("requested_ms" in p for p in problems)
        assert any("duration_ms" in p for p in problems)
        assert any("artifact_dir" in p for p in problems)
        assert any("no profile artifacts" in p for p in problems)


# ---------------------------------------------------------------------------
# perfcheck: the regression gate

class TestPerfcheck:
    def _write(self, tmp_path, name, doc):
        p = tmp_path / name
        p.write_text(json.dumps(doc))
        return str(p)

    def test_direction_classification(self):
        assert perfcheck.direction("images_per_sec_server") == "higher"
        assert perfcheck.direction("secrets.secret_mbps_device") == \
            "higher"
        assert perfcheck.direction("scan_throughput") == "higher"
        assert perfcheck.direction("secrets_host_find_mb_s") == \
            "higher"
        assert perfcheck.direction("assemble_ms") == "lower"
        assert perfcheck.direction("graftprof.compile_ms") == "lower"
        assert perfcheck.direction("p99_ms") == "lower"
        assert perfcheck.direction(
            "graftprof.transfer_bytes.dense") == "lower"
        assert perfcheck.direction("padding_waste_ratio") == "lower"
        assert perfcheck.direction("n_pairs") is None
        assert perfcheck.direction("replicas") is None

    def test_identical_tails_pass(self, capsys):
        assert perfcheck.main([GOLDEN_BASE, GOLDEN_BASE]) == 0

    def test_golden_pair_passes(self, capsys):
        """The checked-in golden pair is the tier-1 wiring: a healthy
        round-over-round diff exits 0."""
        assert perfcheck.main([GOLDEN_BASE, GOLDEN_NEXT]) == 0

    def test_planted_20pct_regression_flagged(self, tmp_path, capsys):
        old = self._write(tmp_path, "old.json",
                          {"scan_throughput": 100.0, "p99_ms": 40.0})
        new = self._write(tmp_path, "new.json",
                          {"scan_throughput": 80.0, "p99_ms": 40.0})
        assert perfcheck.main([old, new]) == 1
        out = capsys.readouterr().out
        assert "REGRESS" in out and "scan_throughput" in out

    def test_latency_regression_flagged(self, tmp_path):
        old = self._write(tmp_path, "old.json", {"p99_ms": 40.0})
        new = self._write(tmp_path, "new.json", {"p99_ms": 60.0})
        assert perfcheck.main([old, new]) == 1

    def test_noise_within_spread_passes(self, tmp_path):
        """A 23% median drop whose repeat spread (MAD) covers it is
        noise, not a regression — the repeat lists already in the
        tail widen the bound."""
        old = self._write(tmp_path, "old.json",
                          {"scan_throughput_repeats":
                           [100.0, 130.0, 160.0]})
        new = self._write(tmp_path, "new.json",
                          {"scan_throughput_repeats":
                           [80.0, 100.0, 125.0]})
        assert perfcheck.main([old, new]) == 0
        # the same drop WITHOUT a spread regresses
        old2 = self._write(tmp_path, "old2.json",
                           {"scan_throughput": 130.0})
        new2 = self._write(tmp_path, "new2.json",
                           {"scan_throughput": 100.0})
        assert perfcheck.main([old2, new2]) == 1

    def test_allowlisted_regression_with_reason(self, tmp_path,
                                                capsys):
        old = self._write(tmp_path, "old.json",
                          {"scan_throughput": 100.0})
        new = self._write(tmp_path, "new.json",
                          {"scan_throughput": 70.0})
        assert perfcheck.main([old, new]) == 1
        assert perfcheck.main(
            [old, new, "--allow",
             "scan_throughput=r06 trades throughput for exactness"]
        ) == 0
        out = capsys.readouterr().out
        assert "ALLOWED" in out and "r06 trades" in out
        # a reason-less waiver is a schema error, not a silent pass
        assert perfcheck.main(
            [old, new, "--allow", "scan_throughput"]) == 2
        assert perfcheck.main(
            [old, new, "--allow", "scan_throughput="]) == 2

    def test_allow_file_requires_reasons(self, tmp_path):
        old = self._write(tmp_path, "old.json",
                          {"scan_throughput": 100.0})
        new = self._write(tmp_path, "new.json",
                          {"scan_throughput": 70.0})
        good = self._write(tmp_path, "allow.json", {"allow": [
            {"metric": "scan_throughput",
             "reason": "accepted in ISSUE 13"}]})
        assert perfcheck.main([old, new, "--allow-file", good]) == 0
        bad = self._write(tmp_path, "allow_bad.json", {"allow": [
            {"metric": "scan_throughput"}]})
        assert perfcheck.main([old, new, "--allow-file", bad]) == 2

    def test_malformed_tail_schema_exits_2(self, tmp_path, capsys):
        arr = self._write(tmp_path, "arr.json", [1, 2, 3])
        ok = self._write(tmp_path, "ok.json", {"scan_throughput": 1.0})
        assert perfcheck.main([arr, ok]) == 2
        empty = self._write(tmp_path, "empty.json",
                            {"device": "unavailable"})
        assert perfcheck.main([empty, ok]) == 2
        nan = tmp_path / "nan.json"
        nan.write_text('{"scan_throughput": NaN}')
        assert perfcheck.main([str(nan), ok]) == 2
        unreadable = tmp_path / "nope.json"
        assert perfcheck.main([str(unreadable), ok]) == 2

    def test_bench_wrapper_is_unwrapped(self, tmp_path):
        """BENCH_rXX.json driver artifacts ({"parsed": {...}}) diff
        directly against bare tails."""
        wrapped = self._write(
            tmp_path, "wrapped.json",
            {"n": 5, "rc": 0, "parsed": {"scan_throughput": 100.0}})
        bare = self._write(tmp_path, "bare.json",
                           {"scan_throughput": 99.0})
        assert perfcheck.main([wrapped, bare]) == 0

    def test_missing_metric_is_reported_not_fatal(self, tmp_path,
                                                  capsys):
        old = self._write(tmp_path, "old.json",
                          {"scan_throughput": 100.0,
                           "secret_mbps_device": 200.0})
        new = self._write(tmp_path, "new.json",
                          {"scan_throughput": 100.0})
        assert perfcheck.main([old, new]) == 0
        assert "missing" in capsys.readouterr().out

    def test_recorded_bench_tail_round_trips(self, tmp_path):
        """The repo's actual recorded rounds satisfy the tail schema —
        the gate can baseline what the driver already records."""
        r05 = os.path.join(os.path.dirname(__file__), "..",
                           "BENCH_r05.json")
        flat = perfcheck.load_tail(r05)
        assert any("images_per_sec" in k for k in flat)


# ---------------------------------------------------------------------------
# server + router debug surfaces

@pytest.fixture(scope="class")
def perf_server(tmp_path_factory):
    from trivy_tpu.server.listen import serve_background
    port = _free_port()
    httpd, state = serve_background(
        "127.0.0.1", port, _fixture_table(),
        cache_dir=str(tmp_path_factory.mktemp("pcache")))
    yield f"http://127.0.0.1:{port}"
    httpd.shutdown()
    state.close()


def _push_image(base, tmp_path):
    from trivy_tpu.fanal.artifact import ImageArchiveArtifact
    from trivy_tpu.server.client import RemoteCache
    img = str(tmp_path / "img.tar")
    make_image(img, [{
        "etc/os-release": ALPINE_OS_RELEASE,
        "lib/apk/db/installed": APK_INSTALLED,
    }])
    return ImageArchiveArtifact(img, RemoteCache(base)).inspect()


class TestDebugSurfaces:
    def test_debug_perf_serves_the_ledger(self, perf_server,
                                          tmp_path):
        from trivy_tpu.server.client import RemoteScanner
        ref = _push_image(perf_server, tmp_path)
        res, _ = RemoteScanner(perf_server).scan(
            ref.name, ref.id, ref.blob_ids)
        assert sum(len(r.vulnerabilities) for r in res) > 0
        doc = json.loads(urllib.request.urlopen(
            perf_server + "/debug/perf").read())
        assert doc["shapes"], "a served scan left no ledger rows"
        row = doc["shapes"][0]
        assert {"site", "t_pad", "h_cap", "dispatches", "compile_ms",
                "mean_occupancy", "waste_bytes"} <= set(row)
        assert doc["totals"]["dispatches"] >= 1
        assert doc["memory"]["resident_bytes"]["advisory_table"] > 0

    def test_healthz_device_block_has_memory(self, perf_server):
        doc = json.loads(urllib.request.urlopen(
            perf_server + "/healthz").read())
        mem = doc["device"]["memory"]
        assert set(mem) == {"backends", "watermark_bytes",
                            "resident_bytes"}
        assert mem["resident_bytes"].get("advisory_table", 0) > 0

    def test_debug_profile_captures_live(self, perf_server, tmp_path):
        RECORDER.configure(incident_dir=str(tmp_path))
        PROF.configure(cooldown_s=0.0)
        doc = json.loads(urllib.request.urlopen(
            perf_server + "/debug/profile?ms=40").read())
        assert doc["schema"] == "trivy-tpu-profile/1"
        assert obs_check.check_file(doc["manifest"]) == []

    def test_debug_profile_cooldown_is_429(self, perf_server,
                                           tmp_path):
        RECORDER.configure(incident_dir=str(tmp_path))
        PROF.configure(cooldown_s=60.0)
        json.loads(urllib.request.urlopen(
            perf_server + "/debug/profile?ms=20").read())
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(perf_server
                                   + "/debug/profile?ms=20")
        assert e.value.code == 429
        body = json.loads(e.value.read())
        assert body["retry_after_s"] > 0

    def test_debug_profile_bad_ms_is_400(self, perf_server):
        # nan fails BOTH range comparisons — it must 400, not start a
        # capture that 500s in time.sleep and burns the cooldown
        for q in ("ms=abc", "ms=0", "ms=999999999", "ms=nan",
                  "ms=inf"):
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(
                    perf_server + "/debug/profile?" + q)
            assert e.value.code == 400

    def test_perf_surface_is_token_gated(self, tmp_path_factory,
                                         tmp_path):
        from trivy_tpu.server.listen import serve_background
        RECORDER.configure(incident_dir=str(tmp_path))
        PROF.configure(cooldown_s=0.0)
        port = _free_port()
        httpd, state = serve_background(
            "127.0.0.1", port, _fixture_table(),
            cache_dir=str(tmp_path_factory.mktemp("tkcache")),
            token="s3cret")
        base = f"http://127.0.0.1:{port}"
        try:
            for path in ("/debug/perf", "/debug/profile?ms=10"):
                with pytest.raises(urllib.error.HTTPError) as e:
                    urllib.request.urlopen(base + path)
                assert e.value.code == 401
                req = urllib.request.Request(
                    base + path, headers={"Trivy-Token": "s3cret"})
                with urllib.request.urlopen(req) as r:
                    assert r.status == 200
        finally:
            httpd.shutdown()
            state.close()


# ---------------------------------------------------------------------------
# ISSUE 13 acceptance drill

@pytest.fixture(scope="class")
def drill_fleet(tmp_path_factory):
    from trivy_tpu.fleet.router import serve_router_background
    from trivy_tpu.server.listen import serve_background
    table = _fixture_table()
    redis = FakeRedis()
    backend = f"redis://127.0.0.1:{redis.port}"
    incident_dir = str(tmp_path_factory.mktemp("drill-incidents"))
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
    yield {"router": f"http://127.0.0.1:{rport}",
           "replicas": replicas, "incident_dir": incident_dir}
    RECORDER.configure(incident_cooldown_s=30.0)
    rhttpd.shutdown()
    rstate.close()
    for _, httpd, state in replicas:
        httpd.shutdown()
        state.close()
    redis.close()


class TestAcceptanceDrill:
    def test_routed_load_ledger_reconciles_and_live_profile(
            self, drill_fleet, tmp_path):
        """ISSUE 13 drill: a c=8 routed load produces a /debug/perf
        shape table whose ledger sums reconcile with the
        trivy_tpu_detect_* dispatch counters AND the graftscope
        detect.dispatch span count (no double-count from merged
        dispatches); a live /debug/profile capture during the load
        yields an obs.check-valid artifact; perfcheck on two recorded
        tails flags a planted 20% scan_throughput regression while
        passing an identical-tail diff."""
        from trivy_tpu.server.client import RemoteScanner
        router = drill_fleet["router"]
        ref = _push_image(router, tmp_path)
        baseline, _ = RemoteScanner(router).scan(
            ref.name, ref.id, ref.blob_ids)
        base_vulns = sum(len(r.vulnerabilities) for r in baseline)
        assert base_vulns > 0

        # clean slate for absolute reconciliation: the ledger resets,
        # the monotonic counters diff against snapshots
        LEDGER.reset_for_tests()
        b0 = METRICS.get("trivy_tpu_detect_batches_total")
        fb0 = METRICS.get("trivy_tpu_fallback_joins_total")
        PROF.configure(cooldown_s=0.0)

        results: list = [None] * 8
        errors: list = []
        profile_doc: list = []

        def worker(i):
            try:
                for _ in range(3):
                    res, _ = RemoteScanner(router).scan(
                        ref.name, ref.id, ref.blob_ids)
                    results[i] = sum(len(r.vulnerabilities)
                                     for r in res)
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        def live_profile():
            # capture WHILE the c=8 load runs — live traffic, not an
            # idle process
            try:
                profile_doc.append(json.loads(urllib.request.urlopen(
                    drill_fleet["replicas"][0][0]
                    + "/debug/profile?ms=300").read()))
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        COLLECTOR.enable()
        try:
            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(8)]
            prof_thread = threading.Thread(target=live_profile)
            for t in threads:
                t.start()
            prof_thread.start()
            for t in threads:
                t.join()
            prof_thread.join()
            phases = COLLECTOR.phase_totals()
        finally:
            COLLECTOR.disable()
        assert not errors
        assert results == [base_vulns] * 8

        # ---- ledger ↔ counter ↔ span reconciliation -----------------
        batches = METRICS.get("trivy_tpu_detect_batches_total") - b0
        assert batches > 0
        # no host fallbacks muddied the count
        assert METRICS.get("trivy_tpu_fallback_joins_total") == fb0
        payload = json.loads(urllib.request.urlopen(
            drill_fleet["replicas"][0][0] + "/debug/perf").read())
        ledger_total = sum(r["dispatches"] for r in payload["shapes"])
        # every device batch is exactly ONE ledger row increment —
        # a merged dispatch covering N requests counts once (site
        # "detectd"), so the sums reconcile with no double-count
        assert ledger_total == int(batches)
        sites = {r["site"] for r in payload["shapes"]
                 if r["dispatches"]}
        assert sites <= {"detect", "detectd"}
        # graftscope agrees: one detect.dispatch span per device batch
        span_count = phases.get("detect.dispatch", {}).get("count", 0)
        assert span_count == int(batches)
        # occupancy/waste present for every traffic row
        for row in payload["shapes"]:
            if row["dispatches"]:
                assert row["mean_occupancy"] is not None
                assert 0.0 < row["mean_occupancy"] <= 1.0

        # ---- live profile artifact ----------------------------------
        assert profile_doc, "live /debug/profile returned nothing"
        doc = profile_doc[0]
        assert doc["schema"] == "trivy-tpu-profile/1"
        assert obs_check.check_file(doc["manifest"]) == []
        assert doc["files"]

        # ---- perfcheck on two recorded tails ------------------------
        ips = 24 / max(sum(
            p.get("total_ms", 0.0)
            for n, p in phases.items() if n == "server.rpc") / 1e3,
            1e-6)
        tail = {"scan_throughput": round(ips, 2),
                "graftprof": LEDGER.aggregate()}
        old = tmp_path / "tail_old.json"
        new_same = tmp_path / "tail_same.json"
        new_reg = tmp_path / "tail_reg.json"
        old.write_text(json.dumps(tail))
        new_same.write_text(json.dumps(tail))
        regressed = dict(tail)
        regressed["scan_throughput"] = round(ips * 0.8, 2)
        new_reg.write_text(json.dumps(regressed))
        assert perfcheck.main([str(old), str(new_same)]) == 0
        assert perfcheck.main([str(old), str(new_reg)]) == 1


class TestCompileCache:
    """trivy_tpu.compile_cache: JAX_COMPILATION_CACHE_DIR wins and
    nothing is set in code; without it, the fixed in-checkout dir."""

    @pytest.fixture
    def cache_config(self):
        import jax
        prev = jax.config.jax_compilation_cache_dir
        yield jax.config
        jax.config.update("jax_compilation_cache_dir", prev)

    def test_env_var_is_honoured(self, cache_config, monkeypatch,
                                 tmp_path):
        from trivy_tpu import compile_cache
        before = cache_config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable() == str(tmp_path)
        assert cache_config.jax_compilation_cache_dir == before

    def test_fixed_checkout_dir_when_unset(self, cache_config,
                                           monkeypatch):
        from trivy_tpu import compile_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        root = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
        want = os.path.join(root, ".jax_cache")
        assert compile_cache.enable() == want
        assert cache_config.jax_compilation_cache_dir == want
