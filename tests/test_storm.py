"""graftstorm tier-1 gate: seeded schedule generation (byte-for-byte
replayable), the invariant engine over real in-process topologies
(single / mesh / fleet), failing-schedule minimization + replay
artifacts (validated by obs.check), the SIGTERM/SIGINT graceful drain,
and the advisory-DB version-identity satellites."""

import json
import signal
import threading
import time
import urllib.error
import urllib.request

import pytest

from trivy_tpu.metrics import METRICS
from trivy_tpu.obs.check import check_file, check_storm_replay
from trivy_tpu.resilience import FAILPOINTS, GUARD
from trivy_tpu.resilience.storm import (
    Schedule, StormEvent, StormOptions, check_exposition,
    generate_schedule, load_replay, minimize_schedule, request_doc,
    run_storm, storm_table, write_replay,
)

pytestmark = []


@pytest.fixture(scope="module")
def table():
    return storm_table()


@pytest.fixture(autouse=True)
def _clean_guard():
    FAILPOINTS.configure("")
    GUARD.reset_for_tests()
    GUARD.configure(dispatch_timeout_s=120.0, fail_threshold=3,
                    reset_timeout_s=5.0)
    yield
    FAILPOINTS.configure("")
    GUARD.reset_for_tests()
    GUARD.configure(dispatch_timeout_s=120.0, fail_threshold=3,
                    reset_timeout_s=5.0)


# ---------------------------------------------------------------------------
# schedule generation: seeded, byte-for-byte replayable


class TestScheduleGeneration:
    def test_same_seed_same_schedule_json(self):
        for topo in ("single", "mesh", "fleet"):
            a = generate_schedule(41, topo)
            b = generate_schedule(41, topo)
            assert a.to_json() == b.to_json()
            assert json.dumps(a.to_json(), sort_keys=True) == \
                json.dumps(b.to_json(), sort_keys=True)

    def test_different_seeds_differ(self):
        schedules = {json.dumps(generate_schedule(s, "single").to_json(),
                                sort_keys=True) for s in range(8)}
        assert len(schedules) > 1

    def test_json_round_trip(self):
        sched = generate_schedule(7, "fleet", n_events=6)
        again = Schedule.from_json(sched.to_json())
        assert again == sched

    def test_events_are_sane(self):
        from trivy_tpu.resilience.failpoints import known_site
        for seed in range(6):
            sched = generate_schedule(seed, "fleet", n_events=6,
                                      watchdog_ms=50.0)
            assert sched.events == sorted(
                sched.events, key=lambda e: (e.at_ms, e.kind, e.site,
                                             e.replica))
            sites = [e.site for e in sched.events
                     if e.kind == "failpoint"]
            assert len(sites) == len(set(sites))   # one spec per site
            for ev in sched.events:
                assert ev.at_ms >= 0
                if ev.kind == "failpoint":
                    assert known_site(ev.site)
                    if ev.mode == "hang":
                        # a "hang" below the watchdog deadline is not
                        # a hang — it would never trip the breaker
                        assert ev.arg > 50.0 * 2

    def test_mesh_sites_only_for_mesh(self):
        for seed in range(6):
            for ev in generate_schedule(seed, "single").events:
                assert not ev.site.startswith("detect.mesh:")
                assert ev.kind != "kill_replica"

    def test_unknown_topology_rejected(self):
        with pytest.raises(ValueError):
            generate_schedule(1, "galaxy")

    def test_mesh_host_loss_events_generate_and_validate(self):
        """The mesh menu samples host_loss events (hang args that
        genuinely outlive the watchdog, hosts within range), and
        obs.check accepts the kind in replay artifacts."""
        found = False
        for seed in range(40):
            sched = generate_schedule(seed, "mesh", n_events=6,
                                      watchdog_ms=50.0, mesh_hosts=2)
            for ev in sched.events:
                if ev.kind != "host_loss":
                    continue
                found = True
                assert ev.mode == "hang" and ev.arg > 50.0 * 2
                assert 0 <= ev.host < 2 and ev.dur_ms > 0
            doc = {"schedule": sched.to_json(),
                   "load": {"requests": 1, "concurrency": 1,
                            "load_seed": 0},
                   "violations": {}}
            assert check_storm_replay(doc) == []
        assert found
        # non-mesh topologies never sample host loss
        for seed in range(10):
            for topo in ("single", "fleet", "ingest"):
                assert not any(
                    e.kind == "host_loss"
                    for e in generate_schedule(seed, topo).events)


# ---------------------------------------------------------------------------
# acceptance: compound schedules per topology pass every invariant


class TestAcceptance:
    def test_single_compound_hang_flaky_swap_c8(self, table):
        """ISSUE acceptance (single): detect.dispatch=hang overlapping
        detect.device_get=flaky and a DB hot swap at c=8 — zero lost
        requests, bit-identical to the oracle, breakers re-closed, no
        leaked threads, strict /metrics (admission bounded so shed
        well-formedness is exercised too)."""
        sched = Schedule(seed=101, topology="single",
                         horizon_ms=1000.0, events=[
                             StormEvent(at_ms=60.0,
                                        site="detect.dispatch",
                                        mode="hang", arg=150.0,
                                        dur_ms=400.0),
                             StormEvent(at_ms=120.0,
                                        site="detect.device_get",
                                        mode="flaky", arg=0.3, seed=7,
                                        dur_ms=500.0),
                             StormEvent(at_ms=200.0,
                                        kind="swap_table"),
                         ])
        report = run_storm(sched, StormOptions(
            requests=24, concurrency=8, admit_max_active=6,
            admit_max_queue=8), table=table)
        assert report.ok, report.violations
        assert len(report.outcomes) == 24
        assert all(o is not None for o in report.outcomes)

    def test_mesh_domain_fault_c8(self, table):
        """ISSUE acceptance (mesh): a detect.mesh:<id> hang overlapping
        a dispatch slowdown at c=8 — the victim's domain trips (device
        lost counted), the mesh shrinks and grows back, and every
        invariant probe passes."""
        sched = Schedule(seed=102, topology="mesh",
                         horizon_ms=1000.0, events=[
                             StormEvent(at_ms=60.0,
                                        site="detect.mesh:1",
                                        mode="hang", arg=150.0,
                                        dur_ms=400.0),
                             StormEvent(at_ms=120.0,
                                        site="detect.dispatch",
                                        mode="slow", arg=10.0,
                                        dur_ms=400.0),
                         ])
        # the device-lost observation is wall-clock coupled (like the
        # fleet drill's failover count below): under heavy suite load
        # the paced dispatches can slip past the domain-fault window —
        # the drill's dispatches then fail on the BACKEND watchdog and
        # the attribution probes rightly find every CPU device healthy.
        # Allow one re-run for THAT side-assert; the invariant verdict
        # must hold on every attempt.
        for attempt in range(2):
            lost0 = METRICS.get("trivy_tpu_mesh_device_lost_total")
            report = run_storm(sched, StormOptions(
                requests=16, concurrency=8), table=table)
            assert report.ok, report.violations
            if METRICS.get("trivy_tpu_mesh_device_lost_total") > lost0:
                break
        else:
            raise AssertionError("no mesh device lost in 2 drills")

    def test_mesh_host_loss_c8(self, table):
        """Host fault domains: a host_loss event kills
        every detect.mesh domain sharing synthetic host 1 at c=8 —
        meshguard answers with EXACTLY ONE shrink rebuild
        re-factorizing dp×db over the surviving host, zero failed
        requests, results bit-identical to the unfaulted oracle,
        breakers re-closed, and the lost host readmitted by the probe
        path (grow rebuilds restore the full mesh before settle)."""
        # dur_ms=0: the fault stays armed until the load drains (the
        # driver's flush reverts it before settle) — under heavy suite
        # load the paced dispatches can lag the schedule clock, and a
        # finite window could revert before the first dispatch ever
        # probes a domain (observed: the sibling probe then finds a
        # healthy device and the host never fully trips)
        sched = Schedule(seed=104, topology="mesh",
                         horizon_ms=1000.0, events=[
                             StormEvent(at_ms=60.0, kind="host_loss",
                                        mode="hang", arg=150.0,
                                        dur_ms=0.0, host=1),
                         ])
        # still wall-clock coupled like the other mesh drill (the hold
        # window can expire mid-sibling-probe under extreme load and
        # split the host loss into two shrinks); one re-run for the
        # strict side-asserts — the invariant verdict must hold on
        # every attempt.
        for attempt in range(2):
            host0 = METRICS.get("trivy_tpu_mesh_host_lost_total")
            shrink0 = METRICS.get("trivy_tpu_mesh_rebuilds_total",
                                  reason="shrink")
            grow0 = METRICS.get("trivy_tpu_mesh_rebuilds_total",
                                reason="grow")
            lost0 = METRICS.get("trivy_tpu_mesh_device_lost_total")
            report = run_storm(sched, StormOptions(
                requests=16, concurrency=8, mesh_devices=4,
                mesh_hosts=2), table=table)
            assert report.ok, report.violations
            host_lost = METRICS.get(
                "trivy_tpu_mesh_host_lost_total") - host0
            shrinks = METRICS.get("trivy_tpu_mesh_rebuilds_total",
                                  reason="shrink") - shrink0
            if host_lost == 1 and shrinks == 1:
                # both of host 1's devices were expelled, in ONE
                # debounced rebuild, and the probe path grew back
                assert METRICS.get(
                    "trivy_tpu_mesh_device_lost_total") - lost0 == 2
                assert METRICS.get("trivy_tpu_mesh_rebuilds_total",
                                   reason="grow") > grow0
                break
        else:
            raise AssertionError(
                "host loss did not coalesce into one shrink in 2 "
                "drills")

    def test_fleet_replica_kill_c8(self, table):
        """ISSUE acceptance (fleet): a replica kill overlapping seeded
        rpc.route flakes and a dispatch hang at c=8 — failovers absorb
        everything, the restarted replica is readmitted, and every
        invariant probe passes."""
        sched = Schedule(seed=103, topology="fleet",
                         horizon_ms=1200.0, events=[
                             StormEvent(at_ms=50.0,
                                        kind="kill_replica",
                                        replica=0, dur_ms=400.0),
                             StormEvent(at_ms=120.0, site="rpc.route",
                                        mode="flaky", arg=0.2, seed=9,
                                        dur_ms=400.0),
                             StormEvent(at_ms=160.0,
                                        site="detect.dispatch",
                                        mode="hang", arg=150.0,
                                        dur_ms=300.0),
                         ])
        # the failover-count observation is wall-clock coupled: under
        # heavy suite load the paced requests can slip entirely past
        # the kill window, so allow one re-run for THAT side-assert —
        # the invariant verdict must hold on every attempt
        for attempt in range(2):
            fail0 = METRICS.get("trivy_tpu_fleet_failovers_total")
            report = run_storm(sched, StormOptions(
                requests=20, concurrency=8, replicas=2), table=table)
            assert report.ok, report.violations
            if METRICS.get("trivy_tpu_fleet_failovers_total") > fail0:
                break
        else:
            raise AssertionError("no failover observed in 2 drills")

    def test_fleet_db_swap_memo_faults_c8(self, table):
        """ISSUE acceptance (graftmemo): a rolling DB upgrade under
        load on a shared-memo fleet, with the memo backend faulted
        through the swap window — memo.get/memo.put failures must
        degrade to plain re-detects (never a 5xx, never a
        stale-version result), every response must match the oracle
        its own X-Trivy-DB-Version names, and the skew counter must
        go quiet once the roll converges (the db_swap_converged
        invariant)."""
        sched = Schedule(seed=104, topology="fleet",
                         horizon_ms=1200.0, events=[
                             StormEvent(at_ms=40.0, site="memo.get",
                                        mode="error", dur_ms=600.0),
                             StormEvent(at_ms=60.0, site="memo.put",
                                        mode="flaky", arg=0.4,
                                        seed=11, dur_ms=600.0),
                             StormEvent(at_ms=200.0, kind="db_swap"),
                         ])
        report = run_storm(sched, StormOptions(
            requests=20, concurrency=8, replicas=2), table=table)
        assert report.ok, report.violations

    def test_generated_schedule_smoke(self, table):
        """A generator-sampled schedule (fixed seed) passes end to end
        — the seeded path the CLI runs in tier-1."""
        sched = generate_schedule(3, "single")
        report = run_storm(sched, StormOptions(
            requests=12, concurrency=4), table=table)
        assert report.ok, report.violations


# ---------------------------------------------------------------------------
# graftfair: adversarial-tenant isolation


class TestTenantIsolation:
    def test_adversarial_tenant_drill_c8(self, table):
        """ISSUE acceptance (graftfair): at c=8, one tenant floods a
        20-request burst while two tenants trickle the paced load.
        With per-tenant quotas armed, the victims see ZERO sheds and
        results bit-identical to the unfaulted oracle (tenant_isolation
        + bit_identity + cost_conservation all pass), while the
        flood's overflow comes back as well-formed 429s with finite
        Retry-After — the flooder pays for its own burst."""
        sched = Schedule(seed=777, topology="single",
                         horizon_ms=900.0, events=[
                             StormEvent(at_ms=80.0,
                                        kind="adversarial_tenant",
                                        arg=20.0),
                         ])
        report = run_storm(sched, StormOptions(
            requests=16, concurrency=8, tenants=2,
            admit_tenant_max_active=4, admit_tenant_max_queue=2),
            table=table)
        assert report.ok, report.violations
        # every victim request completed — zero sheds, zero losses
        assert all(o is not None and o.status == "ok"
                   for o in report.outcomes)
        # the flood ran in full and its overflow shed well-formed:
        # a 20-burst against a 4-active/2-queued cap cannot fit
        assert len(report.flood_outcomes) == 20
        sheds = [o for o in report.flood_outcomes
                 if o.status == "shed"]
        assert sheds, "20-burst against cap 4+2 never overflowed"
        assert all(o.code == 429 and o.well_formed for o in sheds)
        assert all(o.status in ("ok", "shed")
                   for o in report.flood_outcomes)
        assert report.summary()["flood"]["sheds"] == len(sheds)

    def test_generated_adversarial_schedule_passes(self, table):
        """The generator samples adversarial_tenant events (every
        topology), replay artifacts validate, and a sampled schedule
        passes end to end with NO explicit quota opts — run_storm
        derives victim-safe defaults, so the seeded CLI path keeps
        its green-by-construction contract."""
        found = None
        for seed in range(40):
            sched = generate_schedule(seed, "single")
            adv = [e for e in sched.events
                   if e.kind == "adversarial_tenant"]
            if not adv:
                continue
            assert len(adv) == 1      # at most one flood per schedule
            assert adv[0].arg >= 1
            doc = {"schedule": sched.to_json(),
                   "load": {"requests": 1, "concurrency": 1,
                            "load_seed": 0},
                   "violations": {}}
            assert check_storm_replay(doc) == []
            found = found or sched
        assert found is not None
        report = run_storm(found, StormOptions(
            requests=10, concurrency=4), table=table)
        assert report.ok, report.violations
        assert report.flood_outcomes

    def test_quota_failpoint_sheds_well_formed(self, table):
        """admission.quota storm probe: an injected quota-bookkeeping
        fault fails CLOSED — every affected request sheds as a
        well-formed 429 (never a 500/lost), and the run's invariants
        all hold (tenant_isolation is vacuous without a flood; the
        shed-accounting leg of metrics_wellformed sees the counter
        move)."""
        sched = Schedule(seed=555, topology="single",
                         horizon_ms=800.0, events=[
                             StormEvent(at_ms=0.0,
                                        site="admission.quota",
                                        mode="flaky", arg=0.5, seed=3,
                                        dur_ms=800.0),
                         ])
        report = run_storm(sched, StormOptions(
            requests=12, concurrency=4, tenants=2,
            admit_tenant_max_active=8), table=table)
        assert report.ok, report.violations
        sheds = [o for o in report.outcomes if o.status == "shed"]
        assert sheds, "flaky(0.5) over the whole load never fired"
        assert all(o.code == 429 and o.well_formed for o in sheds)

    def test_replay_round_trips_tenant_quota_knobs(self, table,
                                                   tmp_path):
        """write_replay persists the graftfair quota knobs and
        load_replay re-arms them — a failing adversarial schedule
        replays under the exact quotas that produced it."""
        sched = Schedule(seed=9, topology="single", horizon_ms=500.0,
                         events=[StormEvent(
                             at_ms=50.0, kind="adversarial_tenant",
                             arg=6.0)])
        opts = StormOptions(requests=4, concurrency=2, tenants=2,
                            admit_tenant_max_active=3,
                            admit_tenant_max_queue=1,
                            admit_tenant_rate=50.0)
        report = run_storm(sched, opts, table=table)
        path = str(tmp_path / "replay.json")
        write_replay(path, sched, opts, report, minimized=False)
        with open(path) as f:
            assert check_storm_replay(json.load(f)) == []
        sched2, opts2 = load_replay(path)
        assert sched2 == sched
        assert opts2.admit_tenant_max_active == 3
        assert opts2.admit_tenant_max_queue == 1
        assert opts2.admit_tenant_rate == 50.0


@pytest.mark.slow
class TestWideSweep:
    @pytest.mark.parametrize("topology", ["single", "mesh", "fleet"])
    @pytest.mark.parametrize("seed", range(5))
    def test_seed_sweep(self, table, topology, seed):
        sched = generate_schedule(seed, topology)
        report = run_storm(sched, StormOptions(
            requests=24, concurrency=8), table=table)
        assert report.ok, report.violations


# ---------------------------------------------------------------------------
# replay determinism


class TestReplayDeterminism:
    def test_same_seed_same_outcomes(self, table):
        """Same seed + topology ⇒ identical schedule AND identical
        per-request outcomes. The schedule uses absorb-only error-mode
        faults (no timing-sensitive sheds), so every request completes
        with a deterministic digest both times."""
        sched = Schedule(seed=77, topology="single",
                         horizon_ms=800.0, events=[
                             StormEvent(at_ms=40.0,
                                        site="detect.dispatch",
                                        mode="error", dur_ms=400.0),
                             StormEvent(at_ms=150.0,
                                        kind="swap_table"),
                         ])
        opts = StormOptions(requests=12, concurrency=4)
        rep1 = run_storm(sched, opts, table=table)
        rep2 = run_storm(sched, opts, table=table)
        assert rep1.ok and rep2.ok, (rep1.violations, rep2.violations)
        assert [o.key() for o in rep1.outcomes] == \
            [o.key() for o in rep2.outcomes]
        assert all(o.status == "ok" for o in rep1.outcomes)


# ---------------------------------------------------------------------------
# minimization + replay artifacts


class TestMinimization:
    def test_planted_failure_minimizes_and_replays(self, table,
                                                   tmp_path):
        """ISSUE acceptance: a planted invariant violation (rpc.scan=
        error surfaces 500s to a directly-connected client — a fault
        class the single topology does NOT absorb) buried in three
        absorbable noise events minimizes to ≤ 2 events; the written
        replay artifact validates under obs.check and reproduces the
        failure deterministically."""
        sched = Schedule(seed=99, topology="single",
                         horizon_ms=800.0, events=[
                             StormEvent(at_ms=50.0,
                                        site="detect.dispatch",
                                        mode="slow", arg=10.0,
                                        dur_ms=400.0),
                             StormEvent(at_ms=80.0, site="rpc.scan",
                                        mode="error", dur_ms=0.0),
                             StormEvent(at_ms=120.0,
                                        site="detect.device_get",
                                        mode="error", dur_ms=300.0),
                             StormEvent(at_ms=200.0,
                                        kind="swap_table"),
                         ])
        opts = StormOptions(requests=10, concurrency=4,
                            artifact_dir=str(tmp_path))
        report = run_storm(sched, opts, table=table)
        assert not report.ok
        assert "no_lost_requests" in report.violations

        minimal, min_report, trials = minimize_schedule(
            sched, opts, table=table, oracle=report.oracle)
        assert len(minimal.events) <= 2, minimal.events
        assert any(e.site == "rpc.scan" for e in minimal.events)
        assert not min_report.ok
        assert trials > 0

        path = str(tmp_path / "storm-replay.json")
        write_replay(path, minimal, opts, min_report, minimized=True)
        # the artifact is a first-class graftwatch document
        assert check_file(path) == []
        sched2, opts2 = load_replay(path)
        assert sched2 == minimal
        opts2.artifact_dir = str(tmp_path)
        rep2 = run_storm(sched2, opts2, table=table)
        assert not rep2.ok
        assert sorted(rep2.violations) == sorted(min_report.violations)

    def test_replay_schema_validation(self):
        good = {"schema": "trivy-tpu-storm-replay/1",
                "schedule": {"seed": 1, "topology": "single",
                             "horizon_ms": 800.0,
                             "events": [{"at_ms": 1.0,
                                         "kind": "failpoint",
                                         "site": "rpc.scan",
                                         "mode": "error"}]},
                "load": {"requests": 4, "concurrency": 2,
                         "load_seed": 1},
                "violations": {}, "incident": None}
        assert check_storm_replay(good) == []
        bad = json.loads(json.dumps(good))
        bad["schedule"]["events"][0].pop("site")
        bad["schedule"]["events"].append({"at_ms": -3, "kind": "boom"})
        bad.pop("violations")
        problems = check_storm_replay(bad)
        assert any("without a site" in p for p in problems)
        assert any("unknown kind" in p for p in problems)
        assert any("bad at_ms" in p for p in problems)
        assert any("violations" in p for p in problems)


# ---------------------------------------------------------------------------
# strict exposition checker (the invariant engine's /metrics gate)


class TestExpositionCheck:
    def test_live_registry_payload_is_clean(self):
        METRICS.inc("trivy_tpu_scans_total")
        METRICS.observe("trivy_tpu_scan_latency_seconds", 0.02)
        assert check_exposition(METRICS.render()) == []

    def test_sample_before_type_flagged(self):
        text = ("foo_total 1\n"
                "# TYPE foo_total counter\n")
        assert any("without # TYPE" in p
                   for p in check_exposition(text))

    def test_non_cumulative_histogram_flagged(self):
        text = ("# TYPE h histogram\n"
                'h_bucket{le="1"} 5\n'
                'h_bucket{le="2"} 3\n'
                'h_bucket{le="+Inf"} 5\n'
                "h_sum 1\nh_count 5\n")
        assert any("not cumulative" in p for p in check_exposition(text))

    def test_count_inf_mismatch_flagged(self):
        text = ("# TYPE h histogram\n"
                'h_bucket{le="1"} 1\n'
                'h_bucket{le="+Inf"} 2\n'
                "h_sum 1\nh_count 3\n")
        assert any("!= +Inf bucket" in p
                   for p in check_exposition(text))

    def test_garbage_line_flagged(self):
        assert any("unparseable" in p
                   for p in check_exposition("!! not a sample\n"))


# ---------------------------------------------------------------------------
# satellite: SIGTERM/SIGINT graceful drain


def _post(base, route, doc, timeout=30, headers=None):
    req = urllib.request.Request(
        base + route, data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json",
                 **(headers or {})}, method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _scan_req(doc):
    return {"target": "t", "artifact_id": doc["DiffID"],
            "blob_ids": [doc["DiffID"]],
            "options": {"scanners": ["vuln"]}}


class TestGracefulDrain:
    def test_drain_under_load_completes_inflight_sheds_new(
            self, table):
        """The ISSUE scenario: drain while scans are mid-flight — the
        in-flight ones complete with correct results, NEW scans shed
        503 + Retry-After, and the accept loop closes only after the
        generation counts drain."""
        from trivy_tpu.server.listen import (drain_then_shutdown,
                                             serve_background)
        httpd, state = serve_background(
            "127.0.0.1", 0, table, cache_dir="",
            cache_backend="memory")
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        doc = request_doc(5, 0)
        try:
            _post(base, "/twirp/trivy.cache.v1.Cache/PutBlob",
                  {"diff_id": doc["DiffID"], "blob_info": doc})
            baseline = _post(
                base, "/twirp/trivy.scanner.v1.Scanner/Scan",
                _scan_req(doc))
            # slow handler so requests are reliably in flight
            FAILPOINTS.set("rpc.scan", "slow", 400.0)
            results, errors = [], []

            def scan_one():
                try:
                    results.append(_post(
                        base, "/twirp/trivy.scanner.v1.Scanner/Scan",
                        _scan_req(doc)))
                except Exception as e:   # noqa: BLE001 — asserted below
                    errors.append(e)

            workers = [threading.Thread(target=scan_one)
                       for _ in range(4)]
            for t in workers:
                t.start()
            time.sleep(0.1)   # all four are inside the slow handler
            drainer = threading.Thread(
                target=drain_then_shutdown, args=(httpd, state, 10.0))
            drainer.start()
            deadline = time.monotonic() + 5.0
            while not state.draining and time.monotonic() < deadline:
                time.sleep(0.005)
            assert state.draining
            # a NEW scan sheds 503 + Retry-After while draining
            with pytest.raises(urllib.error.HTTPError) as e:
                _post(base, "/twirp/trivy.scanner.v1.Scanner/Scan",
                      _scan_req(doc))
            assert e.value.code == 503
            assert int(e.value.headers.get("Retry-After")) >= 1
            assert json.loads(e.value.read())["code"] == "unavailable"
            # healthz reports the drain
            h = json.loads(urllib.request.urlopen(
                base + "/healthz", timeout=10).read())
            assert h["status"] == "draining"
            for t in workers:
                t.join(timeout=20.0)
            drainer.join(timeout=20.0)
            # nothing in flight was dropped, results exact
            assert errors == []
            assert len(results) == 4
            assert all(r == baseline for r in results)
            assert state.inflight == 0
        finally:
            FAILPOINTS.configure("")
            httpd.shutdown()
            httpd.server_close()
            state.close()

    def test_sigterm_triggers_drain_end_to_end(self, table):
        """A real SIGTERM through install_drain_handlers: the handler
        returns immediately, the drain runs on its own thread, and the
        accept loop stops."""
        from trivy_tpu.server.listen import (Handler,
                                             ServerState,
                                             install_drain_handlers)
        from http.server import ThreadingHTTPServer
        state = ServerState(table, cache_dir="",
                            cache_backend="memory")
        handler = type("Handler", (Handler,), {"state": state})
        httpd = ThreadingHTTPServer(("127.0.0.1", 0), handler)
        serve_thread = threading.Thread(target=httpd.serve_forever,
                                        daemon=True)
        serve_thread.start()
        old_term = signal.getsignal(signal.SIGTERM)
        old_int = signal.getsignal(signal.SIGINT)
        try:
            assert install_drain_handlers(httpd, state, 5.0)
            signal.raise_signal(signal.SIGTERM)
            serve_thread.join(timeout=10.0)
            assert not serve_thread.is_alive()
            assert state.draining
        finally:
            signal.signal(signal.SIGTERM, old_term)
            signal.signal(signal.SIGINT, old_int)
            httpd.server_close()
            state.close()

    def test_router_drain_sheds_new_requests(self, table):
        from trivy_tpu.fleet.router import (drain_router_then_shutdown,
                                            serve_router_background)
        from trivy_tpu.server.listen import serve_background
        rep_httpd, rep_state = serve_background(
            "127.0.0.1", 0, table, cache_dir="",
            cache_backend="memory")
        rep_url = f"http://127.0.0.1:{rep_httpd.server_address[1]}"
        router, rstate = serve_router_background(
            "127.0.0.1", 0, [rep_url])
        base = f"http://127.0.0.1:{router.server_address[1]}"
        try:
            rstate.begin_drain()
            with pytest.raises(urllib.error.HTTPError) as e:
                _post(base, "/twirp/trivy.scanner.v1.Scanner/Scan",
                      {"artifact_id": "sha256:0"})
            assert e.value.code == 503
            assert int(e.value.headers.get("Retry-After")) >= 1
            h = json.loads(urllib.request.urlopen(
                base + "/healthz", timeout=10).read())
            assert h["status"] == "draining"
            drainer = threading.Thread(
                target=drain_router_then_shutdown,
                args=(router, rstate, 5.0))
            drainer.start()
            drainer.join(timeout=10.0)
            assert not drainer.is_alive()
        finally:
            router.server_close()
            rstate.close()
            rep_httpd.shutdown()
            rep_httpd.server_close()
            rep_state.close()


# ---------------------------------------------------------------------------
# satellite: advisory-DB version identity


class TestDBVersionIdentity:
    def test_content_digest_deterministic_and_content_sensitive(self):
        t1 = storm_table()
        t2 = storm_table()
        t3 = storm_table(n_pkgs=17)
        assert t1.content_digest() == t2.content_digest()
        assert t1.content_digest().startswith("sha256:")
        assert t1.content_digest() != t3.content_digest()
        # cached: second call returns the same object fast
        assert t1.content_digest() is t1.content_digest()

    def test_healthz_and_scan_header_expose_db_version(self, table):
        from trivy_tpu.server.listen import serve_background
        httpd, state = serve_background(
            "127.0.0.1", 0, table, cache_dir="",
            cache_backend="memory")
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        try:
            h = json.loads(urllib.request.urlopen(
                base + "/healthz", timeout=10).read())
            assert h["db_version"] == table.content_digest()
            doc = request_doc(6, 0)
            _post(base, "/twirp/trivy.cache.v1.Cache/PutBlob",
                  {"diff_id": doc["DiffID"], "blob_info": doc})
            req = urllib.request.Request(
                base + "/twirp/trivy.scanner.v1.Scanner/Scan",
                data=json.dumps(_scan_req(doc)).encode(),
                headers={"Content-Type": "application/json"},
                method="POST")
            with urllib.request.urlopen(req, timeout=30) as r:
                assert r.headers.get("X-Trivy-DB-Version") == \
                    table.content_digest()
            # a hot swap to a different table re-stamps the version
            t2 = storm_table(n_pkgs=17)
            state.swap_table(t2)
            h = json.loads(urllib.request.urlopen(
                base + "/healthz", timeout=10).read())
            assert h["db_version"] == t2.content_digest()
        finally:
            httpd.shutdown()
            httpd.server_close()
            state.close()

    def test_router_counts_db_version_skew(self, table):
        """Two replicas serving DIFFERENT advisory tables behind one
        router: scans landing on both make the router observe
        disagreeing X-Trivy-DB-Version headers — warning + counter."""
        from trivy_tpu.fanal.cache import MemoryCache
        from trivy_tpu.fleet import serve_router_background
        from trivy_tpu.server.listen import serve_background
        t2 = storm_table(n_pkgs=17)
        shared = MemoryCache()
        servers = []
        for t in (table, t2):
            httpd, state = serve_background(
                "127.0.0.1", 0, t, cache_dir="", cache_backend=shared)
            servers.append((httpd, state,
                            f"http://127.0.0.1:"
                            f"{httpd.server_address[1]}"))
        router, rstate = serve_router_background(
            "127.0.0.1", 0, [s[2] for s in servers])
        base = f"http://127.0.0.1:{router.server_address[1]}"
        skew0 = METRICS.family_sum("trivy_tpu_fleet_db_version_skew_total")
        try:
            # one scan keyed to each replica's arc of the ring
            hit = set()
            for i in range(64):
                doc = request_doc(8, i)
                owner = rstate.ring.node_for(doc["DiffID"])
                if owner in hit:
                    continue
                hit.add(owner)
                _post(base, "/twirp/trivy.cache.v1.Cache/PutBlob",
                      {"diff_id": doc["DiffID"], "blob_info": doc})
                _post(base, "/twirp/trivy.scanner.v1.Scanner/Scan",
                      _scan_req(doc))
                if len(hit) == 2:
                    break
            assert len(hit) == 2
            assert METRICS.family_sum(
                "trivy_tpu_fleet_db_version_skew_total") > skew0
            versions = rstate.db_versions()
            assert len(set(versions.values())) == 2
            h = json.loads(urllib.request.urlopen(
                base + "/healthz", timeout=10).read())
            assert len(set(h["fleet"]["db_versions"].values())) == 2
        finally:
            router.shutdown()
            router.server_close()
            rstate.close()
            for httpd, state, _ in servers:
                httpd.shutdown()
                httpd.server_close()
                state.close()

    def test_agreeing_fleet_never_counts_skew(self, table):
        from trivy_tpu.fleet.router import RouterState
        skew0 = METRICS.family_sum("trivy_tpu_fleet_db_version_skew_total")
        st = RouterState(["http://a", "http://b"])
        try:
            st.note_db_version("http://a", "sha256:same")
            st.note_db_version("http://b", "sha256:same")
            st.note_db_version("http://a", "sha256:same")
            assert METRICS.family_sum(
                "trivy_tpu_fleet_db_version_skew_total") == skew0
            # a rollout flip counts ONCE per observed change
            st.note_db_version("http://b", "sha256:new")
            assert METRICS.family_sum(
                "trivy_tpu_fleet_db_version_skew_total") == skew0 + 1
            st.note_db_version("http://b", "sha256:new")
            assert METRICS.family_sum(
                "trivy_tpu_fleet_db_version_skew_total") == skew0 + 1
        finally:
            st.close()
