"""graftstorm — seeded multi-fault chaos schedules, a fleet-wide
invariant engine, and failing-schedule minimization.

PRs 4–7 each shipped hand-written, single-fault chaos drills. This
module replaces them with one engine in the FoundationDB/Jepsen
simulation-testing tradition, layered on the closed failpoint catalog:

  schedules    a seeded generator samples a timeline of fault events —
               failpoint arm/disarm (site, mode, timing, duration,
               overlap, including `detect.mesh:<slot>` family
               instances), replica kill/restart, and DB hot swaps —
               all derived from ONE integer seed, so any run is
               replayable byte-for-byte (same seed ⇒ same schedule,
               JSON-identical).
  harness      a runner stands up the real in-process topology
               (single server, mesh server, or router + N replicas via
               serve_background / serve_router_background), runs an
               unfaulted ORACLE pass, then drives a seeded concurrent
               scan load over HTTP while a driver thread executes the
               schedule against the live process.
  invariants   a registry of probes evaluated after the run: every
               request completed or was shed with a WELL-FORMED
               429/503/504 (none lost), completed results are
               bit-identical to the oracle, every breaker returns to
               closed after the faults clear (liveness), no surviving
               non-daemon threads, /metrics stays strict-exposition-
               parseable with shed-aware accounting, and a breaker
               opening produced a graftwatch incident file.
  minimization on invariant failure, the schedule is delta-debugged
               (drop events, then shorten windows) down to a minimal
               failing schedule, written with the captured incident as
               a replayable artifact (`--replay FILE` re-runs it;
               `python -m trivy_tpu.obs.check` validates it offline).

CLI:  python -m trivy_tpu.resilience.storm \
          --seed N --rounds K --topology {single,mesh,fleet}

Everything here is host-side orchestration (graftlint TPU106 lock
hygiene applies; TPU107/TPU108 keep the probes out of device code).
"""

from __future__ import annotations

import hashlib
import json
import os
import random
import tempfile
import threading
import time
import urllib.error
import urllib.request
from dataclasses import asdict, dataclass, field, replace

from ..log import get as _get_logger
from ..metrics import METRICS
from ..server import DB_VERSION_HEADER, TENANT_HEADER
from .breaker import GUARD
from .failpoints import FAILPOINTS

_log = _get_logger("resilience.storm")

TOPOLOGIES = ("single", "mesh", "fleet", "ingest")
REPLAY_SCHEMA = "trivy-tpu-storm-replay/1"

# fault menu per topology: ONLY faults the resilience stack is designed
# to absorb (host fallback, mesh shrink, router failover). rpc.scan
# error/flaky surface as 500s to a directly-connected client by design,
# so they are fleet-only — the router never relays a 5xx.
_SINGLE_FAULTS = (
    ("detect.dispatch", "error"), ("detect.dispatch", "hang"),
    ("detect.dispatch", "slow"), ("detect.dispatch", "flaky"),
    ("detect.device_get", "error"), ("detect.device_get", "flaky"),
    ("detect.compile", "error"), ("rpc.scan", "slow"),
    # graftfeed: a wedged/failed staged query upload must degrade to
    # the host join (the stage runs under its own watch)
    ("detect.query_upload", "error"), ("detect.query_upload", "hang"),
    ("detect.query_upload", "flaky"),
)
_MESH_FAULTS = (
    ("detect.mesh", "error"), ("detect.mesh", "hang"),
    ("detect.mesh", "flaky"),
)
_FLEET_FAULTS = (
    ("rpc.route", "error"), ("rpc.route", "flaky"),
    ("rpc.route", "slow"), ("rpc.scan", "error"),
    ("rpc.scan", "flaky"),
)
# graftmemo faults (fleet topology, where the shared result memo
# lives): a memo backend down must degrade to a plain re-detect —
# never a 5xx, never a stale-version result (the bit-identity and
# db_swap invariants would both catch the latter)
_MEMO_FAULTS = (
    ("memo.get", "error"), ("memo.get", "flaky"),
    ("memo.put", "error"), ("memo.put", "flaky"),
)
# fanald ingest faults (ingest topology only): the pipeline absorbs
# every one as an annotated partial result — plus the hostile_layer
# event kind, which swaps the load to a corrupt/bomb artifact variant,
# and the secrets lane: the ingest fixtures carry real tokens scanned
# through the DEVICE keyword engine (small-batch floor forced to 0),
# and a secret.prefilter fault must degrade that scan to the host
# engine bit-identically (the exact-match contract both paths share)
# with the shared detect breaker re-closing after settle.
# The graftbom sbom lane (odd request indices ride the ScanSBOM RPC)
# adds the server-side supervised document decode: sbom.parse faults
# must land as annotated partials on the parse stage — same contract,
# different ingress — and hostile windows swap the DOCUMENT for a
# truncated/component-bomb variant instead of the layer archive
_INGEST_FAULTS = (
    ("fanal.walk", "error"), ("fanal.walk", "hang"),
    ("fanal.walk", "flaky"),
    ("fanal.analyze", "error"), ("fanal.analyze", "hang"),
    ("fanal.analyze", "flaky"),
    ("secret.prefilter", "error"), ("secret.prefilter", "hang"),
    ("secret.prefilter", "flaky"),
    ("sbom.parse", "error"), ("sbom.parse", "hang"),
    ("sbom.parse", "flaky"),
)
HOSTILE_VARIANTS = ("truncated", "bomb")


# ---------------------------------------------------------------------------
# schedule grammar


@dataclass
class StormEvent:
    """One timeline entry. `at_ms` is the offset from load start;
    `dur_ms` bounds the armed window (0 = until the schedule ends).

    kinds:
      failpoint     arm `site=mode(arg[,seed])` at at_ms, clear at
                    at_ms+dur_ms. A `detect.mesh:<slot>` site names a
                    mesh SLOT (0-based position in the boot mesh); the
                    runner maps it to the actual device id, so the
                    schedule stays runtime-independent.
      kill_replica  shut replica `replica` down at at_ms, restart it on
                    the same port at at_ms+dur_ms (fleet only).
      swap_table    trigger a DB hot swap through the generation drain
                    on replica `replica` (0 outside fleet). Same table
                    content — the drill is the drain, not the data.
      db_swap       rolling advisory-DB UPGRADE: every server state
                    hot-swaps to the alternate table (different
                    content digest) in slot order while load flows —
                    redetectd sweeps the shared memo, responses must
                    match whichever oracle their X-Trivy-DB-Version
                    names, and the router's skew counter must go
                    quiet once the roll converges.
      hostile_layer (ingest only) scans issued in the window use the
                    `variant` hostile artifact (truncated gzip layer
                    or decompression bomb) instead of the clean one —
                    the fanald containment drill.
      host_loss     (mesh only) every `detect.mesh:<slot>` sharing
                    synthetic host `host` arms a hang-mode fault for
                    the window — the whole host dies at once. The
                    invariant beyond the usual set: meshguard answers
                    with ONE debounced shrink that re-factorizes
                    dp×db over the survivors, and the probe path
                    readmits the host after the window.
      adversarial_tenant
                    (any topology) at at_ms one hostile tenant
                    ("storm-adv") bursts `arg` extra requests at the
                    topology, all at once, while the paced victim
                    load flows. The runner arms per-tenant admission
                    quotas (graftfair) so the invariant beyond the
                    usual set — tenant_isolation — can hold: victims
                    never shed, flood overflow sheds are well-formed
                    429s with finite Retry-After, and every result
                    that does complete stays bit-identical.
    """
    at_ms: float
    kind: str = "failpoint"
    site: str = ""
    mode: str = ""
    arg: float = 0.0
    seed: int = 0
    dur_ms: float = 0.0
    replica: int = 0
    variant: str = ""
    host: int = 0

    def label(self) -> str:
        if self.kind == "failpoint":
            arg = "" if self.mode == "error" else f":{self.arg:g}"
            return (f"{self.site}={self.mode}{arg}"
                    f"@{self.at_ms:g}+{self.dur_ms:g}ms")
        if self.kind == "hostile_layer":
            return (f"hostile_layer({self.variant})"
                    f"@{self.at_ms:g}+{self.dur_ms:g}ms")
        if self.kind == "host_loss":
            return (f"host_loss(host={self.host})"
                    f"@{self.at_ms:g}+{self.dur_ms:g}ms")
        if self.kind == "adversarial_tenant":
            return (f"adversarial_tenant(n={self.arg:g})"
                    f"@{self.at_ms:g}ms")
        return f"{self.kind}[{self.replica}]@{self.at_ms:g}ms"


@dataclass
class Schedule:
    seed: int
    topology: str
    horizon_ms: float
    events: list[StormEvent] = field(default_factory=list)

    def to_json(self) -> dict:
        return {"seed": self.seed, "topology": self.topology,
                "horizon_ms": self.horizon_ms,
                "events": [asdict(e) for e in self.events]}

    @classmethod
    def from_json(cls, doc: dict) -> "Schedule":
        return cls(int(doc["seed"]), str(doc["topology"]),
                   float(doc["horizon_ms"]),
                   [StormEvent(**e) for e in doc.get("events", [])])


def generate_schedule(seed: int, topology: str, n_events: int = 4,
                      horizon_ms: float = 1500.0, mesh_devices: int = 4,
                      replicas: int = 3,
                      watchdog_ms: float = 50.0,
                      mesh_hosts: int = 2) -> Schedule:
    """Sample one fault timeline from `seed`. Deterministic: the same
    (seed, topology, knobs) always yields a JSON-identical schedule.
    Windows overlap by construction (starts land in the first 60% of
    the horizon, durations span 25–60% of it)."""
    if topology not in TOPOLOGIES:
        raise ValueError(f"unknown topology {topology!r} "
                         f"(known: {', '.join(TOPOLOGIES)})")
    rng = random.Random(seed)
    menu: list[tuple[str, str]] = list(_SINGLE_FAULTS)
    kinds = ["failpoint"] * 3 + ["swap_table"]
    if topology == "mesh":
        menu += list(_MESH_FAULTS) * 2     # mesh domains get airtime
        kinds += ["host_loss"]             # whole-host fault domains
    if topology == "fleet":
        menu += list(_FLEET_FAULTS) + list(_MEMO_FAULTS)
        kinds += ["kill_replica"] * 2 + ["db_swap"]
    if topology == "ingest":
        # ingest drills the fanald pipeline: stage faults plus
        # hostile-artifact windows; the device-side menu is replaced
        # (the load is dominated by client-side walks, not joins)
        menu = list(_INGEST_FAULTS) * 2 + [("rpc.scan", "slow")]
        kinds = ["failpoint"] * 3 + ["hostile_layer"] * 2 + \
            ["swap_table"]
    # graftfair: every topology can draw one adversarial-tenant flood
    # (at most one per schedule — a second flood tenant adds noise,
    # not coverage, and doubles the run's extra request volume)
    kinds = kinds + ["adversarial_tenant"]
    events: list[StormEvent] = []
    used_sites: set[str] = set()
    for _ in range(max(int(n_events), 1)):
        at = rng.uniform(0.0, horizon_ms * 0.6)
        dur = rng.uniform(horizon_ms * 0.25, horizon_ms * 0.6)
        kind = rng.choice(kinds)
        if kind == "adversarial_tenant":
            if any(e.kind == "adversarial_tenant" for e in events):
                continue
            # flood size: ingest requests are full client-side walks
            # (each one orders of magnitude heavier than a Scan RPC),
            # so its bursts stay small
            lo, hi = (4, 8) if topology == "ingest" else (8, 16)
            events.append(StormEvent(
                at_ms=round(at, 1), kind="adversarial_tenant",
                arg=float(rng.randrange(lo, hi + 1))))
            continue
        if kind == "hostile_layer":
            events.append(StormEvent(
                at_ms=round(at, 1), kind="hostile_layer",
                dur_ms=round(dur, 1),
                variant=HOSTILE_VARIANTS[
                    rng.randrange(len(HOSTILE_VARIANTS))]))
            continue
        if kind == "host_loss":
            # hang mode on every slot of the host: a watchdog trip is
            # the deterministic loss signal (error mode would need
            # fail_threshold repeats per device)
            events.append(StormEvent(
                at_ms=round(at, 1), kind="host_loss", mode="hang",
                arg=round(rng.uniform(watchdog_ms * 2.2,
                                      watchdog_ms * 4.0), 1),
                dur_ms=round(dur, 1),
                host=rng.randrange(max(mesh_hosts, 1))))
            continue
        if kind == "kill_replica":
            events.append(StormEvent(
                at_ms=round(at, 1), kind="kill_replica",
                dur_ms=round(dur, 1),
                replica=rng.randrange(max(replicas, 1))))
            continue
        if kind == "swap_table":
            events.append(StormEvent(
                at_ms=round(at, 1), kind="swap_table",
                replica=rng.randrange(max(replicas, 1))
                if topology == "fleet" else 0))
            continue
        if kind == "db_swap":
            events.append(StormEvent(at_ms=round(at, 1),
                                     kind="db_swap"))
            continue
        # one spec per site at a time: overlapping arms on one site
        # would overwrite each other and confuse minimization
        for _attempt in range(8):
            site, mode = menu[rng.randrange(len(menu))]
            if site == "detect.mesh":
                site = f"detect.mesh:{rng.randrange(max(mesh_devices, 1))}"
            if site not in used_sites:
                break
        if site in used_sites:
            continue
        used_sites.add(site)
        arg, spec_seed = 0.0, 0
        if mode == "hang":
            # must outlive the watchdog deadline to be a hang at all.
            # fanald sites (and the graftbom parse stage, which
            # watches with the same chaos-scaled deadline) watch with
            # the (longer) ingest layer deadline + grace, so their
            # hangs scale further out — the trip must be
            # deterministic, never a near-miss
            mult = (8.0, 12.0) if site.startswith("fanal.") \
                or site == "sbom.parse" else (2.2, 4.0)
            arg = round(rng.uniform(watchdog_ms * mult[0],
                                    watchdog_ms * mult[1]), 1)
        elif mode == "slow":
            arg = round(rng.uniform(5.0, 25.0), 1)
        elif mode == "flaky":
            arg = round(rng.uniform(0.1, 0.4), 3)
            spec_seed = rng.randrange(1 << 16)
        events.append(StormEvent(
            at_ms=round(at, 1), site=site, mode=mode, arg=arg,
            seed=spec_seed, dur_ms=round(dur, 1)))
    events.sort(key=lambda e: (e.at_ms, e.kind, e.site, e.replica))
    return Schedule(seed, topology, horizon_ms, events)


# ---------------------------------------------------------------------------
# seeded workload: a self-contained advisory table + scan request docs


def storm_table(n_pkgs: int = 16, seed: int = 604):
    """Small deterministic AdvisoryTable so the engine needs no
    fixture files: every package gets 1–3 alpine-style advisories with
    seeded fixed-version bounds."""
    from ..db.table import RawAdvisory, build_table
    rng = random.Random(seed)
    raw, details = [], {}
    for i in range(n_pkgs):
        name = f"storm-pkg-{i}"
        for j in range(rng.randrange(1, 4)):
            vid = f"CVE-2026-{i:03d}{j}"
            raw.append(RawAdvisory(
                source="alpine 3.17", ecosystem="alpine",
                pkg_name=name, vuln_id=vid,
                fixed_version=f"{1 + j}.{rng.randrange(10)}.0-r0",
                severity=rng.choice(("LOW", "MEDIUM", "HIGH"))))
            details[vid] = {"Title": f"storm planted bug {vid}",
                            "Severity": "HIGH"}
    return build_table(raw, details)


def alt_storm_table():
    """The db_swap event's upgrade target: same package namespace,
    different seeded advisory bounds — a DIFFERENT content digest
    whose scan results genuinely differ from storm_table()'s, so the
    post-swap oracle actually discriminates."""
    return storm_table(seed=605)


def request_doc(load_seed: int, idx: int, n_pkgs: int = 16) -> dict:
    """The idx-th scan request of a seeded load: a blob document whose
    DiffID doubles as the artifact id (PutBlob and Scan key to the
    same ring owner, the test_fleet convention)."""
    rng = random.Random((load_seed << 20) ^ idx)
    diff = "sha256:" + hashlib.sha256(
        f"storm|{load_seed}|{idx}".encode()).hexdigest()
    pkgs = []
    for _ in range(rng.randrange(1, 7)):
        k = rng.randrange(n_pkgs)
        ver = f"{rng.randrange(1, 4)}.{rng.randrange(10)}.0-r0"
        pkgs.append({"Name": f"storm-pkg-{k}", "Version": ver,
                     "SrcName": f"storm-pkg-{k}", "SrcVersion": ver})
    return {
        "SchemaVersion": 2, "DiffID": diff,
        "OS": {"Family": "alpine", "Name": "3.17.3"},
        "PackageInfos": [{"FilePath": "lib/apk/db/installed",
                          "Packages": pkgs}],
    }


def request_sbom_doc(load_seed: int, idx: int,
                     n_pkgs: int = 16) -> bytes:
    """The idx-th request's inventory as a CycloneDX document (the
    graftbom lane of the ingest drill): the SAME seeded package set
    request_doc() would put in a blob, exported the way
    encode_cyclonedx writes alpine packages — so an sbom-lane scan
    detects against the same advisories the archive lane would."""
    blob = request_doc(load_seed, idx, n_pkgs)
    comps = []
    for p in blob["PackageInfos"][0]["Packages"]:
        purl = (f"pkg:apk/alpine/{p['Name']}@{p['Version']}"
                f"?distro=3.17.3")
        comps.append({
            "type": "library",
            "bom-ref": purl,
            "name": p["Name"], "version": p["Version"],
            "purl": purl,
            "properties": [
                {"name": "aquasecurity:trivy:PkgType",
                 "value": "alpine"},
                {"name": "aquasecurity:trivy:SrcName",
                 "value": p["SrcName"]},
                {"name": "aquasecurity:trivy:SrcVersion",
                 "value": p["SrcVersion"]},
            ],
        })
    doc = {
        "bomFormat": "CycloneDX", "specVersion": "1.5",
        "serialNumber": f"urn:uuid:storm-sbom-{load_seed}-{idx}",
        "version": 1,
        "metadata": {"component": {
            "type": "operating-system", "name": "alpine",
            "version": "3.17.3",
            "properties": [{"name": "aquasecurity:trivy:Type",
                            "value": "alpine"}]}},
        "components": comps,
    }
    return json.dumps(doc, sort_keys=True).encode()


def build_sbom_document(load_seed: int, idx: int, variant: str,
                        max_components: int = 64) -> bytes:
    """clean | truncated (mid-token JSON cut → deterministic
    `malformed` annotation) | bomb (component-count flood past the
    drill's budget → clamped prefix decode + `budget.components`)."""
    raw = request_sbom_doc(load_seed, idx)
    if variant == "clean":
        return raw
    if variant == "truncated":
        return raw[:48]
    doc = json.loads(raw)
    base = doc["components"]
    flood = []
    k = 0
    while len(flood) <= max_components * 8:
        for c in base:
            c2 = dict(c)
            c2["bom-ref"] = f"{c['bom-ref']}#{k}"
            k += 1
            flood.append(c2)
    doc["components"] = flood
    return json.dumps(doc, sort_keys=True).encode()


# ---------------------------------------------------------------------------
# options, outcomes, report


@dataclass
class StormOptions:
    """Runner knobs (CLI flags of the same names)."""
    requests: int = 24
    concurrency: int = 8
    load_seed: int = 0          # 0 = derived from the schedule seed
    replicas: int = 3           # fleet width
    mesh_devices: int = 4
    mesh_db_shards: int = 2
    mesh_hosts: int = 2         # synthetic host fault domains (mesh)
    watchdog_ms: float = 50.0   # graftguard dispatch deadline
    breaker_reset_ms: float = 150.0
    admit_max_active: int = 0   # 0 = unbounded (no admission sheds)
    admit_max_queue: int = 8
    # graftfair per-tenant quotas (0/0.0 = disarmed). When a schedule
    # carries an adversarial_tenant event and none of these are set,
    # run_storm derives victim-safe defaults: tenant_max_active =
    # concurrency (victims run ≤1 in-flight per worker, so they can
    # NEVER trip their own cap — zero victim sheds is structural),
    # tenant_max_queue small (the flood's burst overflows as 429s)
    admit_tenant_max_active: int = 0
    admit_tenant_max_queue: int = 0
    admit_tenant_rate: float = 0.0
    settle_s: float = 8.0       # post-schedule liveness window
    request_timeout_s: float = 30.0
    artifact_dir: str = ""      # incident/replay dir ("" = tmpdir)
    # graftcost: distinct tenants the load round-robins through via
    # X-Trivy-Tenant (request idx % tenants). 1 = untenanted load
    # (everything lands in "default"); the tenant mix is recorded in
    # replay artifacts so a failing schedule replays the same mix
    tenants: int = 1


@dataclass
class Outcome:
    idx: int
    status: str          # "ok" | "shed" | "lost"
    code: int = 0
    digest: str = ""
    latency_ms: float = 0.0
    detail: str = ""
    well_formed: bool = True
    # fanald: the response carried ingest degradation annotations
    # (a deterministic partial result) — excluded from the oracle
    # bit-identity probe, held to the annotation contract instead
    partial: bool = False
    # the X-Trivy-DB-Version the answering replica stamped: under a
    # db_swap schedule, the digest must match the ORACLE THIS HEADER
    # NAMES (a v2-stamped response carrying v1 hits is exactly the
    # mixing the memo's version keying forbids)
    db_version: str = ""

    def key(self) -> tuple:
        return (self.idx, self.status, self.code, self.digest)


@dataclass
class StormReport:
    schedule: Schedule
    outcomes: list[Outcome]
    oracle: dict[int, str]
    violations: dict[str, list[str]]
    incident_dir: str = ""
    duration_s: float = 0.0
    # adversarial_tenant schedules: the flood's own outcomes, kept
    # separate from the victim load's (see RunContext.flood_outcomes)
    flood_outcomes: list[Outcome] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.violations

    def sheds(self) -> int:
        return sum(1 for o in self.outcomes if o.status == "shed")

    def p99_ms(self) -> float:
        lats = sorted(o.latency_ms for o in self.outcomes
                      if o.status == "ok")
        if not lats:
            return 0.0
        return lats[min(len(lats) - 1, int(len(lats) * 0.99))]

    def summary(self) -> dict:
        out = {
            "seed": self.schedule.seed,
            "topology": self.schedule.topology,
            "events": [e.label() for e in self.schedule.events],
            "requests": len(self.outcomes),
            "ok": self.ok,
            "sheds": self.sheds(),
            "p99_ms": round(self.p99_ms(), 2),
            "violations": self.violations,
            "duration_s": round(self.duration_s, 2),
        }
        if self.flood_outcomes:
            out["flood"] = {
                "requests": len(self.flood_outcomes),
                "sheds": sum(1 for o in self.flood_outcomes
                             if o.status == "shed")}
        return out


def canonical_digest(doc: dict) -> str:
    return hashlib.sha256(json.dumps(
        doc, sort_keys=True, separators=(",", ":")).encode()).hexdigest()


def tenant_for(opts: StormOptions, idx: int) -> str:
    """The idx-th load request's tenant id ("" = no header →
    "default"): a deterministic round-robin over `opts.tenants`
    synthetic tenants, so replays keep the same mix."""
    if opts.tenants <= 1:
        return ""
    return f"storm-t{idx % opts.tenants}"


# ---------------------------------------------------------------------------
# topologies


def _post(base: str, route: str, doc: dict, timeout: float,
          headers: dict | None = None):
    """→ (status, headers, parsed-json body). Raises on transport
    errors; HTTP error statuses are returned, not raised."""
    req = urllib.request.Request(
        base + route, data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, dict(r.headers), json.loads(r.read())
    except urllib.error.HTTPError as e:
        body = e.read()
        try:
            parsed = json.loads(body) if body else {}
        except json.JSONDecodeError:
            parsed = {"_raw": body.decode(errors="replace")[:200]}
        return e.code, dict(e.headers), parsed


class _Topology:
    """Common surface the runner drives: a scan URL, schedule-event
    application, metrics endpoints, and teardown."""

    kind = ""

    def __init__(self, table, opts: StormOptions):
        self.table = table
        self.opts = opts
        # the db_swap event's upgrade target (a different content
        # digest); run_storm computes the post-swap oracle against it
        self.table2 = alt_storm_table()
        self.db_swapped = False

    # the base URL scans go to (router for fleet, server otherwise)
    url: str = ""
    # run_storm pre-pushes the seeded blob docs (PutBlob) when True;
    # the ingest topology pushes per-request instead (its blobs come
    # out of the fanald walk, not the seeded docs)
    push_blobs: bool = True

    def metrics_urls(self) -> list[str]:
        return [self.url]

    def server_states(self) -> list:
        raise NotImplementedError

    def do_request(self, idx: int, doc: dict, timeout: float,
                   tenant: str = "") -> Outcome:
        """Issue the idx-th load request. The default is one Scan RPC
        over the pre-pushed blob; the ingest topology overrides with
        the full client-side walk → PutBlob → Scan flow."""
        o = _scan_once(self.url, doc, timeout, tenant=tenant)
        o.idx = idx
        return o

    def apply(self, ev: StormEvent) -> None:
        """Arm one schedule event against the live topology."""
        if ev.kind == "failpoint":
            site = self.resolve_site(ev.site)
            if site:
                FAILPOINTS.set(site, ev.mode, ev.arg, seed=ev.seed)
        elif ev.kind == "swap_table":
            self.swap(ev.replica)
        elif ev.kind == "db_swap":
            self.db_swap()
        elif ev.kind == "kill_replica":
            self.kill(ev.replica)
        elif ev.kind == "hostile_layer":
            self.push_hostile(ev.variant)
        elif ev.kind == "host_loss":
            for site in self.host_sites(ev.host):
                FAILPOINTS.set(site, ev.mode or "hang",
                               ev.arg, seed=ev.seed)
        elif ev.kind == "adversarial_tenant":
            # the flood is traffic, not topology state: run_storm's
            # load phase spawns the burst workers against the same
            # epoch (they need the request docs and the outcome
            # collection, which live there) — nothing to arm here
            pass

    def revert(self, ev: StormEvent) -> None:
        """Disarm one event at the end of its window."""
        if ev.kind == "failpoint":
            site = self.resolve_site(ev.site)
            if site:
                FAILPOINTS.clear(site)
        elif ev.kind == "kill_replica":
            self.restart(ev.replica)
        elif ev.kind == "hostile_layer":
            self.pop_hostile(ev.variant)
        elif ev.kind == "host_loss":
            for site in self.host_sites(ev.host):
                FAILPOINTS.clear(site)

    def host_sites(self, host: int) -> list[str]:
        """→ the `detect.mesh:<id>` sites of every device on synthetic
        host `host` ([] outside the mesh topology — the event drops)."""
        return []

    def push_hostile(self, variant: str) -> None:
        pass

    def pop_hostile(self, variant: str) -> None:
        pass

    def resolve_site(self, site: str) -> str:
        """Map `detect.mesh:<slot>` to the runtime device id;
        passthrough otherwise. '' drops the event (site not
        applicable to this topology instance)."""
        return site

    def swap(self, replica: int) -> None:
        states = self.server_states()
        if states:
            states[replica % len(states)].swap_table(self.table)

    def db_swap(self) -> None:
        """Rolling DB upgrade under load: every live server state
        hot-swaps to the alternate table in slot order (each swap
        triggers that replica's redetectd sweep when a memo is
        wired)."""
        self.db_swapped = True
        for st in self.server_states():
            st.swap_table(self.table2)

    def kill(self, replica: int) -> None:
        pass

    def restart(self, replica: int) -> None:
        pass

    def settled(self) -> list[str]:
        """→ [] once every breaker/fault-domain is closed again."""
        problems = []
        if GUARD.breaker.state_name() != "closed":
            problems.append(
                f"backend breaker {GUARD.breaker.state_name()}")
        return problems

    def close(self) -> None:
        raise NotImplementedError


class SingleTopology(_Topology):
    kind = "single"

    def __init__(self, table, opts: StormOptions, mesh_opts=None,
                 sbom_opts=None):
        super().__init__(table, opts)
        from ..resilience import AdmissionOptions
        from ..server.listen import serve_background
        admission = AdmissionOptions(
            max_active=opts.admit_max_active,
            max_queue=opts.admit_max_queue,
            tenant_max_active=opts.admit_tenant_max_active,
            tenant_max_queue=opts.admit_tenant_max_queue,
            tenant_rate=opts.admit_tenant_rate)
        self.httpd, self.state = serve_background(
            "127.0.0.1", 0, table, cache_dir="",
            cache_backend="memory", admission=admission,
            mesh_opts=mesh_opts, sbom_opts=sbom_opts)
        self.url = f"http://127.0.0.1:{self.httpd.server_address[1]}"

    def server_states(self):
        return [self.state]

    def resolve_site(self, site: str) -> str:
        return "" if site.startswith("detect.mesh:") else site

    def close(self) -> None:
        self.httpd.shutdown()
        self.httpd.server_close()
        self.state.close()


class MeshTopology(SingleTopology):
    kind = "mesh"

    def __init__(self, table, opts: StormOptions):
        from ..server.listen import MeshOptions
        super().__init__(table, opts, mesh_opts=MeshOptions(
            devices=opts.mesh_devices, db_shards=opts.mesh_db_shards,
            min_devices=1, rebuild_cooldown_ms=20.0,
            # the per-device watch deadline: a schedule's mesh hang
            # (arg > 2× watchdog_ms by construction) must TRIP the
            # domain, not read as mere slowness
            probe_timeout_ms=opts.watchdog_ms,
            # synthetic host fault domains: devices split into
            # contiguous host blocks so host_loss events can kill a
            # whole host's worth of domains at once, with a window
            # short enough that the ONE debounced rebuild lands
            # inside the schedule horizon
            hosts=opts.mesh_hosts, host_loss_window_ms=100.0))
        # fast readmission so the liveness invariant settles in-window
        self.state.mesh_guard.opts.probe_interval_ms = 20.0
        self.state.mesh_guard.registry.reset_timeout_s = \
            opts.breaker_reset_ms / 1e3

    def resolve_site(self, site: str) -> str:
        if site.startswith("detect.mesh:"):
            slot = int(site.split(":", 1)[1])
            ids = self.state.mesh_guard.all_ids
            from .meshguard import mesh_site
            return mesh_site(ids[slot % len(ids)])
        return site

    def host_sites(self, host: int) -> list[str]:
        """Slots sharing synthetic host `host` (the contiguous-block
        rule of parallel.multihost.host_assignments), mapped to their
        runtime device sites."""
        n = max(self.opts.mesh_devices, 1)
        hosts = max(self.opts.mesh_hosts, 1)
        return [self.resolve_site(f"detect.mesh:{slot}")
                for slot in range(n)
                if slot * hosts // n == host % hosts]

    def settled(self) -> list[str]:
        problems = super().settled()
        guard = self.state.mesh_guard
        lost = guard.lost_ids()
        if lost:
            problems.append(f"mesh devices still lost: {lost}")
        for dev, st in guard.status()["breakers"].items():
            if st["state"] != "closed":
                problems.append(f"mesh device {dev} breaker "
                                f"{st['state']}")
        return problems


class FleetTopology(_Topology):
    kind = "fleet"

    def __init__(self, table, opts: StormOptions):
        from ..fanal.cache import MemoryCache
        from ..fleet import (ReplicaOptions, RouterOptions,
                             serve_router_background)
        from ..fleet.memo import MemoryMemo
        from ..resilience import RetryPolicy
        super().__init__(table, opts)
        # rolling db_swap: restarts must come back on whatever table
        # the fleet is CURRENTLY rolling toward, not the boot table
        self.active_table = table
        # one shared in-process cache: a failover Scan finds its blobs
        # wherever it lands (the graftfleet redis/s3 contract, without
        # a socket in the loop)
        self.shared_cache = MemoryCache()
        # one shared result memo: the graftmemo contract under chaos —
        # a layer detected by any replica is a memo hit on all of
        # them, per db_version; memo.get/memo.put faults must degrade
        # to plain re-detects
        self.shared_memo = MemoryMemo()
        self.replicas: list = []     # slot → (httpd, state, url) | None
        self.ports: list[int] = []
        for _ in range(opts.replicas):
            self.replicas.append(None)
            self.ports.append(0)
        for slot in range(opts.replicas):
            self._start(slot)
        urls = [entry[2] for entry in self.replicas]
        self.router, self.router_state = serve_router_background(
            "127.0.0.1", 0, urls,
            RouterOptions(
                retry=RetryPolicy(attempts=4, base_delay_s=0.01,
                                  max_delay_s=0.05, budget_s=5.0),
                replica=ReplicaOptions(
                    fail_threshold=2,
                    reset_timeout_ms=opts.breaker_reset_ms,
                    probe_interval_ms=50.0,
                    probe_timeout_ms=2000.0)))
        self.url = f"http://127.0.0.1:{self.router.server_address[1]}"

    def _start(self, slot: int) -> None:
        from ..resilience import AdmissionOptions
        from ..server.listen import serve_background
        httpd, state = serve_background(
            "127.0.0.1", self.ports[slot], self.active_table,
            cache_dir="",
            cache_backend=self.shared_cache,
            memo_backend=self.shared_memo,
            admission=AdmissionOptions(
                max_active=self.opts.admit_max_active,
                max_queue=self.opts.admit_max_queue,
                tenant_max_active=self.opts.admit_tenant_max_active,
                tenant_max_queue=self.opts.admit_tenant_max_queue,
                tenant_rate=self.opts.admit_tenant_rate))
        url = f"http://127.0.0.1:{httpd.server_address[1]}"
        self.replicas[slot] = (httpd, state, url)
        self.ports[slot] = httpd.server_address[1]

    def server_states(self):
        return [entry[1] for entry in self.replicas
                if entry is not None]

    def metrics_urls(self) -> list[str]:
        return [self.url] + [entry[2] for entry in self.replicas
                             if entry is not None]

    def resolve_site(self, site: str) -> str:
        return "" if site.startswith("detect.mesh:") else site

    def swap(self, replica: int) -> None:
        entry = self.replicas[replica % len(self.replicas)]
        if entry is not None:
            entry[1].swap_table(self.active_table)

    def db_swap(self) -> None:
        self.db_swapped = True
        self.active_table = self.table2
        for entry in self.replicas:
            if entry is not None:
                entry[1].swap_table(self.table2)

    def kill(self, replica: int) -> None:
        slot = replica % len(self.replicas)
        entry = self.replicas[slot]
        if entry is None:
            return
        httpd, state, _url = entry
        self.replicas[slot] = None
        httpd.shutdown()
        httpd.server_close()
        state.close()

    def restart(self, replica: int) -> None:
        slot = replica % len(self.replicas)
        if self.replicas[slot] is None:
            self._start(slot)

    def settled(self) -> list[str]:
        problems = super().settled()
        lost = self.router_state.supervisor.lost()
        if lost:
            problems.append(f"replicas still lost: {lost}")
        return problems

    def close(self) -> None:
        self.router.shutdown()
        self.router.server_close()
        self.router_state.close()
        for slot in range(len(self.replicas)):
            self.kill(slot)


class IngestTopology(SingleTopology):
    """fanald containment drill: even-indexed load requests run the
    FULL client-side archive flow — ImageArchiveArtifact through the
    supervised pipeline (small budgets), blob push, Scan RPC — against
    one in-process server; odd-indexed requests ride the graftbom lane
    (the same seeded inventory as a CycloneDX document through the
    ScanSBOM RPC, decoded server-side under the supervised parse
    stage). Schedule faults hit the pipeline's
    `fanal.walk`/`fanal.analyze` sites and the sbom lane's
    `sbom.parse`; `hostile_layer` windows swap the archive for a
    truncated-gzip or decompression-bomb variant and the sbom
    document for a truncated-JSON or component-bomb one. The contract
    under drill: zero 5xx, every affected scan a deterministic
    ANNOTATED partial, ingest breakers re-closed once the faults
    clear."""

    kind = "ingest"
    push_blobs = False
    sbom_lane = True

    def __init__(self, table, opts: StormOptions, load_seed: int = 0):
        from ..sbom.artifact import SBOMOptions
        w = opts.watchdog_ms
        # graftbom lane budgets, chaos-scaled like the ingest budgets
        # below: the parse watch (deadline + 50% grace) must lose to a
        # schedule hang (≥ 8× watchdog by construction) and the bomb
        # document (~8× the component cap) must trip the count budget
        self._sbom_cap = 64
        self.sbom_opts = SBOMOptions(
            max_doc_bytes=1 << 20, max_components=self._sbom_cap,
            parse_deadline_ms=w * 4.0)
        super().__init__(table, opts, sbom_opts=self.sbom_opts)
        from ..fanal.pipeline import IngestOptions
        # budgets sized against the drill fixtures: the bomb variant
        # (zeros expanding ~1000×) must trip the ratio guard, hang
        # faults (≥ 8× watchdog by schedule construction) must outlive
        # the walk watch (deadline + 50% grace)
        self.ingest_opts = IngestOptions(
            walkers=2, analyzers=2,
            max_file_bytes=1 << 20, max_layer_bytes=1 << 20,
            max_members=5000, layer_deadline_ms=w * 4.0,
            max_inflight_bytes=4 << 20, max_ratio=50.0,
            ratio_floor=64 << 10)
        # ONE shared secret scanner with the small-batch floor forced
        # to 0: every request's token file goes through the DEVICE
        # keyword engine, so an armed `secret.prefilter` failpoint
        # genuinely fires (per-layer fixture bytes never cross the
        # production 2 MiB floor) and degrades to the host engine
        # bit-identically. Shared on purpose — concurrent scans reuse
        # one bank and one jit cache, like a server process would.
        # The bank is cut to the two rules the fixture plants: the
        # drill needs the device path, the failpoint, and host parity
        # — not all 86 rules — and the full bank's jnp scan on a CPU
        # test host (~0.7 s/launch) would outlive the chaos-tuned
        # watchdog on EVERY scan, turning the whole run into breaker
        # churn with nothing armed.
        from ..secret import SecretScanner
        from ..secret.rules import BUILTIN_RULES
        self.secret_scanner = SecretScanner(
            rules=[r for r in BUILTIN_RULES
                   if r.id in ("github-pat", "aws-access-key-id")],
            small_batch_bytes=0)
        # absorb the one-time jit compile OUTSIDE any watch: the first
        # request's prefilter would otherwise spend seconds compiling
        # under the 50 ms chaos watchdog and trip the shared breaker
        # before the schedule even starts
        self.secret_scanner._keyword_masks_device([b"warmup " * 8])
        # LIFO of armed hostile windows: overlapping windows must not
        # clobber each other (the earlier window's revert would
        # otherwise clear a later, still-armed one). Mutated only by
        # the single schedule-driver thread; workers read it.
        self._hostile_stack: list = []
        self._fixture_dir = tempfile.mkdtemp(prefix="storm-ingest-")
        self._paths: dict = {}
        from ..fanal.fixtures import gz_bytes, sha256_hex, tar_bytes
        # the bomb layer is idx-independent; build its blob once
        bomb_tar = tar_bytes({"filler/zeros.bin": b"\0" * (4 << 20)})
        self._bomb = (gz_bytes(bomb_tar), sha256_hex(bomb_tar))
        for i in range(opts.requests):
            doc = request_doc(load_seed, i)
            for variant in ("clean",) + HOSTILE_VARIANTS:
                p = os.path.join(self._fixture_dir,
                                 f"img-{i}-{variant}.tar")
                build_ingest_archive(p, doc, variant, self._bomb)
                self._paths[(i, variant)] = p
        # graftbom lane documents (odd request indices): the same
        # seeded inventories as CycloneDX bytes, with hostile-window
        # variants swapping the DOCUMENT rather than the layer archive
        self._sbom_docs = {
            (i, variant): build_sbom_document(
                load_seed, i, variant, self._sbom_cap)
            for i in range(1, opts.requests, 2)
            for variant in ("clean",) + HOSTILE_VARIANTS}

    def push_hostile(self, variant: str) -> None:
        self._hostile_stack.append(variant)

    def pop_hostile(self, variant: str) -> None:
        stack = list(self._hostile_stack)
        if variant in stack:
            stack.reverse()
            stack.remove(variant)
            stack.reverse()
            self._hostile_stack = stack

    def do_request(self, idx: int, doc: dict, timeout: float,
                   tenant: str = "") -> Outcome:
        from ..fanal.artifact import ImageArchiveArtifact
        from ..fanal.cache import MemoryCache
        stack = self._hostile_stack
        variant = stack[-1] if stack else "clean"
        if idx % 2:
            return self._do_sbom_request(idx, timeout, tenant, variant)
        path = self._paths.get((idx, variant)) \
            or self._paths[(idx, "clean")]
        cache = MemoryCache()
        t0 = time.perf_counter()
        try:
            art = ImageArchiveArtifact(path, cache,
                                       scanners=("vuln", "secret"),
                                       secret_scanner=self.secret_scanner,
                                       ingest=self.ingest_opts)
            ref = art.inspect()
        except Exception as e:  # noqa: BLE001 — containment breach
            return Outcome(idx, "lost",
                           detail=f"ingest raised "
                                  f"{type(e).__name__}: {e}"[:160])
        partial = any((cache.blobs.get(b) or {}).get("IngestErrors")
                      for b in ref.blob_ids)
        try:
            for b in ref.blob_ids:
                code, _, body = _post(
                    self.url, "/twirp/trivy.cache.v1.Cache/PutBlob",
                    {"diff_id": b, "blob_info": cache.blobs[b]},
                    timeout=timeout)
                if code != 200:
                    return _classify(idx, code, {}, body,
                                     (time.perf_counter() - t0) * 1e3)
            code, headers, body = _post(
                self.url, "/twirp/trivy.scanner.v1.Scanner/Scan",
                {"target": f"ingest-{idx}", "artifact_id": ref.id,
                 "blob_ids": ref.blob_ids,
                 "options": {"scanners": ["vuln", "secret"]}},
                timeout=timeout,
                headers={"X-Trivy-Deadline-Ms":
                         str(int(timeout * 1e3)),
                         **({TENANT_HEADER: tenant}
                            if tenant else {})})
        except (urllib.error.URLError, OSError, TimeoutError) as e:
            return Outcome(idx, "lost",
                           latency_ms=(time.perf_counter() - t0) * 1e3,
                           detail=f"{type(e).__name__}: {e}"[:160])
        o = _classify(idx, code, headers, body,
                      (time.perf_counter() - t0) * 1e3)
        o.idx = idx
        o.partial = partial
        if variant != "clean":
            o.detail = (o.detail + f" variant={variant}").strip()
            if o.status == "ok" and not o.partial:
                # a hostile artifact MUST degrade annotated — a clean-
                # looking result off a truncated/bomb layer means the
                # containment silently under-reported
                o.well_formed = False
                o.detail = (f"hostile variant {variant} yielded no "
                            f"ingest annotation")
        return o

    def _do_sbom_request(self, idx: int, timeout: float, tenant: str,
                         variant: str) -> Outcome:
        """The graftbom lane: ship the (possibly hostile) document
        through the ScanSBOM RPC — the server runs the supervised
        decode, so sbom.parse faults and document bombs land on ITS
        parse stage. Same containment contract as the archive lane:
        zero 5xx, hostile input always an annotated partial."""
        import base64

        from ..sbom.artifact import doc_digest
        raw = self._sbom_docs[(idx, variant)]
        t0 = time.perf_counter()
        try:
            code, headers, body = _post(
                self.url,
                "/twirp/trivy.scanner.v1.Scanner/ScanSBOM",
                {"target": f"sbom-{idx}",
                 "artifact_id": doc_digest(raw),
                 "kind": "cyclonedx",
                 "document": base64.b64encode(raw).decode(),
                 "options": {"scanners": ["vuln"]}},
                timeout=timeout,
                headers={"X-Trivy-Deadline-Ms":
                         str(int(timeout * 1e3)),
                         **({TENANT_HEADER: tenant}
                            if tenant else {})})
        except (urllib.error.URLError, OSError, TimeoutError) as e:
            return Outcome(idx, "lost",
                           latency_ms=(time.perf_counter() - t0) * 1e3,
                           detail=f"{type(e).__name__}: {e}"[:160])
        o = _classify(idx, code, headers, body,
                      (time.perf_counter() - t0) * 1e3)
        o.idx = idx
        # the parse stage's degradations surface as the report's
        # "ingest" result (the same shape the archive lane's partial
        # blobs produce server-side)
        o.partial = isinstance(body, dict) and any(
            r.get("Class") == "ingest"
            for r in body.get("results") or [])
        if variant != "clean":
            o.detail = (o.detail + f" variant={variant}").strip()
            if o.status == "ok" and not o.partial:
                o.well_formed = False
                o.detail = (f"hostile sbom variant {variant} yielded "
                            f"no parse annotation")
        return o

    def settled(self) -> list[str]:
        problems = super().settled()
        from ..fanal.pipeline import INGEST
        problems.extend(INGEST.settled())
        return problems

    def close(self) -> None:
        super().close()
        import shutil
        shutil.rmtree(self._fixture_dir, ignore_errors=True)


def build_ingest_archive(path: str, doc: dict, variant: str,
                         bomb: tuple | None = None) -> None:
    """Write one docker-save archive for the ingest drill (layout via
    the shared `fanal.fixtures` builders): an alpine os-release layer,
    an apk-db layer carrying the request doc's storm-pkg set, and a
    padding layer. Variants:

      clean      well-formed, 3 gzipped layers
      truncated  the apk layer's gzip blob cut mid-stream (the walk
                 hits EOFError → deterministic `layer_error` partial)
      bomb       an extra layer of highly-compressible zeros that
                 trips the decompression-ratio guard mid-stream
    """
    from ..fanal.fixtures import (gz_bytes, sha256_hex, tar_bytes,
                                  write_docker_archive)
    pkgs = doc["PackageInfos"][0]["Packages"]
    blocks = [f"P:{p['Name']}\nV:{p['Version']}\nA:x86_64\n"
              f"o:{p['Name']}\nL:MIT\n" for p in pkgs]
    apk_db = ("\n".join(blocks) + "\n").encode()
    os_release = (b'NAME="Alpine Linux"\nID=alpine\n'
                  b'VERSION_ID=3.17.3\n')
    # the secrets lane: a per-request token file (the doc's pkg set
    # salts the content so per-request responses differ) scanned
    # through the DEVICE keyword engine by IngestTopology's shared
    # small_batch_bytes=0 scanner — the `secret.prefilter` fault
    # window degrades exactly this scan
    secret_cfg = (
        f"# storm secrets lane ({pkgs[0]['Name']})\n"
        f"github_token = ghp_{'a' * 36}\n"
        f"aws_access_key_id = \"AKIA{'Z' * 16}\" \n").encode()
    layer_tars = [
        tar_bytes({"etc/os-release": os_release}),
        tar_bytes({"lib/apk/db/installed": apk_db,
                   "app/config.txt": secret_cfg}),
        tar_bytes({"usr/share/doc/pad.txt": b"pad " * 256}),
    ]
    blobs = [gz_bytes(t) for t in layer_tars]
    diff_ids = ["sha256:" + sha256_hex(t) for t in layer_tars]
    if variant == "truncated":
        blobs[1] = blobs[1][:max(len(blobs[1]) // 2, 20)]
    elif variant == "bomb" and bomb is not None:
        blobs.append(bomb[0])
        diff_ids.append("sha256:" + bomb[1])
    write_docker_archive(path, blobs, diff_ids,
                         repo_tag=f"storm/ingest:{variant}")


def build_topology(table, schedule: Schedule,
                   opts: StormOptions) -> _Topology:
    if schedule.topology == "single":
        return SingleTopology(table, opts)
    if schedule.topology == "mesh":
        return MeshTopology(table, opts)
    if schedule.topology == "fleet":
        return FleetTopology(table, opts)
    if schedule.topology == "ingest":
        return IngestTopology(table, opts,
                              load_seed=opts.load_seed
                              or schedule.seed)
    raise ValueError(f"unknown topology {schedule.topology!r}")


# ---------------------------------------------------------------------------
# strict exposition check — ONE definition of "strict", shared with the
# tier-1 gate (tests/helpers.py re-exports the same parser)


def check_exposition(text: str) -> list[str]:
    """Validate one /metrics payload under the strict exposition
    parser (obs.exposition: TYPE-before-sample, label escaping,
    histogram cumulativity, +Inf == _count); → [] when clean."""
    from ..obs.exposition import parse_exposition
    try:
        parse_exposition(text)
    except ValueError as e:
        return [str(e)]
    return []


# ---------------------------------------------------------------------------
# invariant registry

INVARIANTS: dict = {}


def invariant(name: str):
    def deco(fn):
        INVARIANTS[name] = fn
        return fn
    return deco


@dataclass
class RunContext:
    """Everything the invariant probes see after one run."""
    schedule: Schedule
    opts: StormOptions
    outcomes: list[Outcome]
    oracle: dict[int, str]
    settle_problems: list[str]
    leaked_threads: list[str]
    metrics: dict[str, str]            # url → /metrics text
    shed_counter_delta: float
    breaker_opens: int                 # breaker_open events in-window
    incident_files: list[str]
    incident_dir: str
    # db_swap: the rolling-upgrade probes. `oracle2` is the post-swap
    # oracle (None when the schedule never swapped); v1/v2 are the
    # before/after table digests; skew_settle_delta counts skew
    # increments observed AFTER the fleet's version view converged
    db_swap: bool = False
    oracle2: "dict[int, str] | None" = None
    v1: str = ""
    v2: str = ""
    skew_settle_delta: float = 0.0
    requests: int = 0
    # graftcost conservation: this run's DELTAS of the graftprof
    # ledger totals vs the tenant-attributed totals (ledger/attributed
    # per axis, plus the reconciliation verdicts) — filled after
    # teardown, when every handler thread has settled its ledger
    cost_conservation: dict = field(default_factory=dict)
    # graftfair adversarial_tenant: the flood's own outcomes (kept out
    # of `outcomes` — the victim invariants must see ONLY the paced
    # load) and the oracle pass's per-request latencies, the victim
    # p99's solo baseline ({} when the oracle was passed in, e.g.
    # minimization trials — the latency probe is then vacuous)
    adversarial: bool = False
    flood_outcomes: list = field(default_factory=list)
    oracle_lat: dict = field(default_factory=dict)


@invariant("no_lost_requests")
def _inv_lost(ctx: RunContext) -> list[str]:
    out = []
    for o in ctx.outcomes:
        if o.status == "lost":
            out.append(f"request {o.idx}: {o.code or 'conn'} "
                       f"{o.detail}")
        elif o.status == "shed" and not o.well_formed:
            out.append(f"request {o.idx}: malformed shed "
                       f"({o.code}: {o.detail})")
        elif o.status == "ok" and not o.well_formed:
            # ingest drill: a hostile artifact that produced a
            # clean-looking 200 silently under-reported
            out.append(f"request {o.idx}: {o.detail}")
    return out


@invariant("bit_identity")
def _inv_identity(ctx: RunContext) -> list[str]:
    out = []
    for o in ctx.outcomes:
        if o.status != "ok" or o.partial:
            # annotated partials are the fanald degradation contract,
            # not drift — no_lost_requests holds them to annotation
            # well-formedness instead
            continue
        if ctx.db_swap:
            # rolling upgrade: a response must match the oracle its
            # OWN X-Trivy-DB-Version names — old hits under the new
            # header (or vice versa) is version mixing, exactly what
            # the memo's (blob, db_version) keying forbids
            if o.db_version == ctx.v2:
                want = (ctx.oracle2 or {}).get(o.idx)
                if want is not None and o.digest != want:
                    out.append(f"request {o.idx}: v2-stamped result "
                               f"drifted from the post-swap oracle")
            elif o.db_version == ctx.v1:
                want = ctx.oracle.get(o.idx)
                if want is not None and o.digest != want:
                    out.append(f"request {o.idx}: v1-stamped result "
                               f"drifted from the pre-swap oracle")
            else:
                out.append(f"request {o.idx}: unknown "
                           f"X-Trivy-DB-Version "
                           f"{o.db_version[:19]!r}")
            continue
        want = ctx.oracle.get(o.idx)
        if want is not None and o.digest != want:
            out.append(f"request {o.idx}: result drifted from the "
                       f"unfaulted oracle")
    return out


@invariant("db_swap_converged")
def _inv_db_swap(ctx: RunContext) -> list[str]:
    """db_swap schedules only: after settle the fleet must be fully
    on the new table (complete post-swap oracle) and the skew counter
    quiet — a rolling upgrade that never converges is the split-brain
    the version identity machinery exists to catch."""
    if not ctx.db_swap:
        return []
    out = []
    if ctx.oracle2 is None or len(ctx.oracle2) < ctx.requests:
        missing = ctx.requests - len(ctx.oracle2 or {})
        out.append(f"post-swap oracle incomplete: {missing} "
                   f"request(s) failed against the settled, "
                   f"fully-rolled topology")
    if ctx.skew_settle_delta > 0:
        out.append(f"db-version skew counter moved "
                   f"{ctx.skew_settle_delta:g} time(s) after settle "
                   f"— the rolling swap never converged")
    return out


@invariant("breakers_reclose")
def _inv_liveness(ctx: RunContext) -> list[str]:
    return list(ctx.settle_problems)


@invariant("no_leaked_threads")
def _inv_threads(ctx: RunContext) -> list[str]:
    return [f"surviving non-daemon thread {n}"
            for n in ctx.leaked_threads]


@invariant("metrics_wellformed")
def _inv_metrics(ctx: RunContext) -> list[str]:
    out = []
    for url, text in ctx.metrics.items():
        if text is None:
            out.append(f"{url}/metrics unreachable after the run")
            continue
        for p in check_exposition(text):
            out.append(f"{url}: {p}")
    # shed-aware accounting: sheds a directly-connected client saw
    # must show up in the server's shed counter (fleet sheds may be
    # router-minted, so only the direct topologies assert the delta)
    client_sheds = sum(1 for o in ctx.outcomes if o.status == "shed")
    if ctx.schedule.topology != "fleet" and client_sheds \
            and ctx.shed_counter_delta <= 0:
        out.append(f"{client_sheds} client-visible sheds but "
                   f"trivy_tpu_requests_shed_total never moved")
    return out


@invariant("incident_on_breaker_open")
def _inv_incident(ctx: RunContext) -> list[str]:
    if ctx.breaker_opens and not ctx.incident_files:
        return [f"{ctx.breaker_opens} breaker opening(s) but no "
                f"incident file in {ctx.incident_dir}"]
    return []


@invariant("tenant_isolation")
def _inv_tenant_isolation(ctx: RunContext) -> list[str]:
    """adversarial_tenant schedules only (vacuous otherwise): one
    hostile tenant's burst must not degrade anyone else. Victims
    (the paced load) never shed — the flood tenant's quota caps, not
    victim starvation, absorb the burst; every flood overflow is a
    well-formed 429 the flooder can back off on; and whatever DOES
    complete — victim or flood — stays bit-identical (bit_identity
    covers the victims; the flood's completions are held to the same
    oracle here). When the flood is the schedule's ONLY event, the
    victim p99 must stay within 3x the solo (oracle-pass) baseline —
    under combined fault windows the latency bound belongs to the
    faults, not the flood, so it is skipped."""
    if not ctx.adversarial:
        return []
    out = []
    for o in ctx.outcomes:
        if o is not None and o.status == "shed":
            out.append(f"victim request {o.idx} shed ({o.code}) "
                       f"under the tenant flood")
    others = [e for e in ctx.schedule.events
              if e.kind != "adversarial_tenant"]
    lats = sorted(o.latency_ms for o in ctx.outcomes
                  if o is not None and o.status == "ok")
    base = sorted(ctx.oracle_lat.values())
    if not others and lats and base:
        p99 = lats[min(len(lats) - 1, int(len(lats) * 0.99))]
        b99 = base[min(len(base) - 1, int(len(base) * 0.99))]
        # 3x the solo baseline, floored: a sub-ms baseline would turn
        # ordinary CI scheduler jitter into a violation
        bound = max(3.0 * b99, 300.0)
        if p99 > bound:
            out.append(f"victim p99 {p99:.0f}ms exceeds {bound:.0f}ms "
                       f"(3x solo baseline {b99:.0f}ms)")
    for o in ctx.flood_outcomes:
        if o.status == "shed":
            if not o.well_formed:
                out.append(f"flood request {o.idx}: malformed shed "
                           f"({o.code}: {o.detail})")
            elif o.code != 429 and not ctx.breaker_opens:
                out.append(f"flood request {o.idx}: {o.code} shed "
                           f"with no breaker opening — quota "
                           f"overflow must be a 429")
        elif o.status == "ok":
            if o.partial:
                continue
            want = ctx.oracle.get(o.idx)
            if want is not None and o.digest != want \
                    and not ctx.db_swap:
                out.append(f"flood request {o.idx}: completed result "
                           f"drifted from the unfaulted oracle")
        else:
            out.append(f"flood request {o.idx}: "
                       f"{o.code or 'conn'} {o.detail}")
    return out


@invariant("cost_conservation")
def _inv_cost(ctx: RunContext) -> list[str]:
    """graftcost headline: across the whole run — faults, failovers,
    sheds, warmup and all — the device ms and conserved transfer
    bytes the graftprof ledger measured must equal what the tenant
    rows (plus the SYSTEM tenant) were charged. A leak means work
    nobody was billed for; an excess means double-counting."""
    out = []
    for axis in ("device_ms", "transfer_bytes"):
        rec = ctx.cost_conservation.get(axis)
        if rec and not rec.get("ok"):
            out.append(
                f"{axis}: ledger moved {rec['ledger']:g} but "
                f"attribution moved {rec['attributed']:g} "
                f"(leak or double count)")
    return out


# ---------------------------------------------------------------------------
# the runner


def _nondaemon_threads() -> dict[int, str]:
    return {t.ident: t.name for t in threading.enumerate()
            if not t.daemon and t.ident is not None}


def _cost_totals() -> dict:
    """Current absolute totals of both conservation sides: the
    graftprof ledger (measured) and the tenant attribution (charged).
    run_storm snapshots before the run and diffs after teardown, so
    the cost_conservation invariant sees only THIS run's movement."""
    from ..obs import cost as _cost
    from ..obs.perf import LEDGER
    agg = LEDGER.aggregate()
    att = _cost.TENANTS.totals()
    return {
        "ledger_ms": float(agg.get("device_ms_total", 0.0)),
        "ledger_bytes": float(sum(
            int(agg.get("transfer_bytes", {}).get(p, 0))
            for p in _cost.CONSERVED_TRANSFER_PATHS)),
        "att_ms": att["device_ms"],
        "att_bytes": att["transfer_bytes"],
    }


def _conservation_deltas(base: dict, timeout_s: float = 2.0) -> dict:
    """→ the run's {device_ms, transfer_bytes} conservation record.
    Handler threads settle their ledgers right after the response is
    written, so attribution can trail the last response by a beat —
    poll until both axes reconcile (or the timeout makes the
    discrepancy the invariant's problem)."""
    def _ok(a: float, b: float, abs_tol: float) -> bool:
        return abs(a - b) <= max(abs_tol, 0.01 * max(a, b))

    deadline = time.monotonic() + timeout_s
    while True:
        cur = _cost_totals()
        d_lms = cur["ledger_ms"] - base["ledger_ms"]
        d_ams = cur["att_ms"] - base["att_ms"]
        d_lb = cur["ledger_bytes"] - base["ledger_bytes"]
        d_ab = cur["att_bytes"] - base["att_bytes"]
        ok_ms = _ok(d_lms, d_ams, 0.5)
        ok_b = _ok(d_lb, d_ab, 4096.0)
        if (ok_ms and ok_b) or time.monotonic() >= deadline:
            return {
                "device_ms": {"ledger": round(d_lms, 3),
                              "attributed": round(d_ams, 3),
                              "ok": ok_ms},
                "transfer_bytes": {"ledger": int(d_lb),
                                   "attributed": int(d_ab),
                                   "ok": ok_b},
            }
        time.sleep(0.02)


class _ScheduleDriver(threading.Thread):
    """Executes arm/revert actions at their offsets from the shared
    epoch `t0` (the load workers pace their requests against the same
    epoch, so schedule windows genuinely overlap the traffic). When
    the load drains early, `flush()` runs every remaining action
    immediately (a kill without its restart would fail the liveness
    probe for no interesting reason)."""

    def __init__(self, topo: _Topology, schedule: Schedule,
                 t0: float):
        super().__init__(name="storm-driver", daemon=True)
        actions: list[tuple[float, int, StormEvent, str]] = []
        for n, ev in enumerate(schedule.events):
            actions.append((ev.at_ms, n, ev, "apply"))
            if ev.kind in ("kill_replica", "hostile_layer",
                           "host_loss") or (
                    ev.kind == "failpoint" and ev.dur_ms > 0):
                end = ev.at_ms + (ev.dur_ms or schedule.horizon_ms)
                actions.append((end, n, ev, "revert"))
        actions.sort(key=lambda a: (a[0], a[1]))
        self._actions = actions
        self._topo = topo
        self._cursor = 0
        self._cv = threading.Condition()
        self._flushed = False
        self.t0 = t0

    def run(self) -> None:
        while True:
            with self._cv:
                if self._cursor >= len(self._actions):
                    return
                at_ms, _, ev, op = self._actions[self._cursor]
                if self._flushed:
                    wait = 0.0
                else:
                    wait = at_ms / 1e3 - (time.monotonic() - self.t0)
                if wait > 0:
                    self._cv.wait(timeout=min(wait, 0.05))
                    continue
                self._cursor += 1
            self._fire(ev, op)

    def _fire(self, ev: StormEvent, op: str) -> None:
        _log.info("storm: %s %s", op, ev.label())
        try:
            if op == "apply":
                self._topo.apply(ev)
            else:
                self._topo.revert(ev)
        except Exception:
            _log.exception("storm: %s %s failed", op, ev.label())

    def flush(self) -> None:
        with self._cv:
            self._flushed = True
            self._cv.notify()
        self.join(timeout=30.0)


def _classify(idx: int, code: int, headers: dict, body,
              latency_ms: float) -> Outcome:
    if 200 <= code < 300:
        return Outcome(idx, "ok", code, canonical_digest(body),
                       latency_ms,
                       db_version=headers.get(DB_VERSION_HEADER)
                       or "")
    if code in (429, 503):
        well = True
        detail = ""
        try:
            ra = float(headers.get("Retry-After") or "")
            if ra < 1.0:
                well, detail = False, f"Retry-After {ra} < 1"
        except ValueError:
            well, detail = False, "missing/unparseable Retry-After"
        if not isinstance(body, dict) or body.get("code") not in (
                "resource_exhausted", "unavailable"):
            well, detail = False, f"bad shed body {body!r}"[:120]
        return Outcome(idx, "shed", code, latency_ms=latency_ms,
                       detail=detail, well_formed=well)
    if code == 504:
        well = isinstance(body, dict) and \
            body.get("code") == "deadline_exceeded"
        return Outcome(idx, "shed", code, latency_ms=latency_ms,
                       detail="" if well else f"bad 504 body {body!r}",
                       well_formed=well)
    return Outcome(idx, "lost", code, latency_ms=latency_ms,
                   detail=str(body)[:160])


def _scan_once(url: str, doc: dict, timeout: float,
               tenant: str = "") -> Outcome:
    diff = doc["DiffID"]
    t0 = time.perf_counter()
    try:
        code, headers, body = _post(
            url, "/twirp/trivy.scanner.v1.Scanner/Scan",
            {"target": diff[:19], "artifact_id": diff,
             "blob_ids": [diff], "options": {"scanners": ["vuln"]}},
            timeout=timeout,
            headers={"X-Trivy-Deadline-Ms": str(int(timeout * 1e3)),
                     **({TENANT_HEADER: tenant} if tenant else {})})
    except (urllib.error.URLError, OSError, TimeoutError) as e:
        return Outcome(-1, "lost",
                       latency_ms=(time.perf_counter() - t0) * 1e3,
                       detail=f"{type(e).__name__}: {e}"[:160])
    return _classify(-1, code, headers, body,
                     (time.perf_counter() - t0) * 1e3)


def run_storm(schedule: Schedule, opts: StormOptions | None = None,
              table=None, oracle: dict[int, str] | None = None
              ) -> StormReport:
    """Stand up the topology, run the oracle pass (unless given), push
    the blobs, drive the concurrent load while the schedule executes,
    settle, tear down, evaluate every invariant probe."""
    opts = opts or StormOptions()
    if table is None:
        table = storm_table()
    # graftfair: an adversarial_tenant schedule needs armed per-tenant
    # quotas to mean anything. When the caller set none, derive
    # victim-safe defaults — active cap = concurrency (each victim
    # worker holds ≤1 request in flight, so victims structurally
    # cannot trip their own tenant cap even when a fault window
    # stalls them) and a small queue cap the burst overflows past
    adv_events = [ev for ev in schedule.events
                  if ev.kind == "adversarial_tenant"]
    if adv_events and not (opts.admit_tenant_max_active
                           or opts.admit_tenant_max_queue
                           or opts.admit_tenant_rate):
        opts = replace(
            opts,
            admit_tenant_max_active=max(2, opts.concurrency),
            admit_tenant_max_queue=max(1, opts.concurrency // 4))
    load_seed = opts.load_seed or schedule.seed
    docs = [request_doc(load_seed, i) for i in range(opts.requests)]

    # per-run incident capture (the invariant needs to see THIS run's
    # files); RECORDER is process-global, so save/restore its config
    from ..obs.recorder import RECORDER
    run_dir = tempfile.mkdtemp(
        prefix=f"storm-{schedule.topology}-{schedule.seed}-",
        dir=opts.artifact_dir or None)
    saved = (RECORDER.incident_dir, RECORDER.incident_cooldown_s)
    saved_guard = (GUARD.dispatch_timeout_s,
                   GUARD.breaker.fail_threshold,
                   GUARD.breaker.reset_timeout_s)
    RECORDER.configure(incident_dir=run_dir, incident_cooldown_s=0.05)
    FAILPOINTS.configure("")
    GUARD.breaker.reset()
    GUARD.configure(dispatch_timeout_s=opts.watchdog_ms / 1e3,
                    fail_threshold=3,
                    reset_timeout_s=opts.breaker_reset_ms / 1e3)
    # fanald ingest domains share the run's fast reset window (and are
    # force-closed around the run like the backend breaker)
    from ..fanal.pipeline import INGEST
    saved_ingest = (INGEST.registry.fail_threshold,
                    INGEST.registry.reset_timeout_s)
    INGEST.configure(fail_threshold=3,
                     reset_timeout_s=opts.breaker_reset_ms / 1e3)
    INGEST.reset_for_tests()
    baseline_threads = _nondaemon_threads()
    shed0 = METRICS.get("trivy_tpu_requests_shed_total")
    events0 = len(RECORDER.events())
    cost0 = _cost_totals()
    t_run0 = time.perf_counter()

    topo = build_topology(table, schedule, opts)
    try:
        # blobs first (faults start with the load, not the setup)
        if topo.push_blobs:
            for doc in docs:
                code, _, body = _post(
                    topo.url, "/twirp/trivy.cache.v1.Cache/PutBlob",
                    {"diff_id": doc["DiffID"], "blob_info": doc},
                    timeout=opts.request_timeout_s)
                if code != 200:
                    raise RuntimeError(f"storm setup: PutBlob → "
                                       f"{code} {body}")
        oracle_lat: dict[int, float] = {}
        if oracle is None:
            oracle = {}
            for i, doc in enumerate(docs):
                o = topo.do_request(i, doc, opts.request_timeout_s)
                if o.status != "ok":
                    raise RuntimeError(
                        f"storm oracle pass failed on request {i}: "
                        f"{o.status} {o.code} {o.detail}")
                oracle[i] = o.digest
                # the serial unfaulted pass doubles as the victim
                # latency baseline for tenant_isolation
                oracle_lat[i] = o.latency_ms

        # the storm pass: concurrent load + schedule driver, all paced
        # against one epoch. Requests spread across ~85% of the
        # horizon so the schedule's windows overlap real traffic —
        # warm-compile runs would otherwise drain the whole load
        # before the first event fires, and the storm would test
        # nothing. Pacing is a deterministic function of the request
        # index (replay keeps the same arrival plan).
        outcomes: list = [None] * len(docs)
        t0 = time.monotonic() + 0.02
        span_s = schedule.horizon_ms * 0.85 / 1e3
        driver = _ScheduleDriver(topo, schedule, t0)

        def worker(ids):
            for i in ids:
                delay = t0 + (i / max(len(docs), 1)) * span_s \
                    - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                try:
                    o = topo.do_request(i, docs[i],
                                        opts.request_timeout_s,
                                        tenant=tenant_for(opts, i))
                except Exception as e:  # noqa: BLE001 — a surprise
                    # (e.g. a 200 with a truncated body) is exactly a
                    # lost request; the invariant engine must REPORT
                    # it, not die on a None outcome
                    o = Outcome(i, "lost",
                                detail=f"{type(e).__name__}: {e}"[:160])
                o.idx = i
                outcomes[i] = o

        threads = [threading.Thread(
            target=worker, name=f"storm-load-{k}",
            args=(range(k, len(docs), opts.concurrency),))
            for k in range(opts.concurrency)]

        # adversarial_tenant floods: one thread per flood request, all
        # released at the event's offset against the shared epoch —
        # the sharpest burst the hostile tenant can mount. Flood
        # outcomes are collected separately: the victim invariants
        # must never see them, tenant_isolation holds them to the
        # well-formed-429 + bit-identity contract.
        flood_outcomes: list[Outcome] = []
        flood_lock = threading.Lock()

        def flood_worker(ev: StormEvent, j: int) -> None:
            delay = t0 + ev.at_ms / 1e3 - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            base = j % len(docs)
            try:
                o = topo.do_request(base, docs[base],
                                    opts.request_timeout_s,
                                    tenant="storm-adv")
            except Exception as e:  # noqa: BLE001 — same contract as
                # the victim workers: surprises become reportable
                # lost outcomes, never a dead thread
                o = Outcome(base, "lost",
                            detail=f"{type(e).__name__}: {e}"[:160])
            o.idx = base
            with flood_lock:
                flood_outcomes.append(o)

        flood_threads = [
            threading.Thread(target=flood_worker,
                             name=f"storm-flood-{n}-{j}",
                             args=(ev, j), daemon=True)
            for n, ev in enumerate(adv_events)
            for j in range(max(1, int(ev.arg)))]

        driver.start()
        for t in threads + flood_threads:
            t.start()
        for t in threads:
            t.join()
        for t in flood_threads:
            t.join(timeout=opts.request_timeout_s + 5.0)
        driver.flush()
        FAILPOINTS.configure("")   # safety net past driver bugs

        # settle: faults cleared — every breaker must find its way
        # back to closed (liveness). Serial probe scans admit the
        # half-open device probe; mesh/fleet readmission loops run on
        # their own maintenance threads.
        # the chaos-tuned watchdog (50 ms — hang faults must trip
        # fast) is wrong for settle: a solo probe's dispatch can
        # legitimately pay a cold-shape compile or post-fallback CPU
        # contention, and tripping the breaker on THAT defeats the
        # very probes that prove liveness. Faults are cleared; settle
        # asks "does the device path recover", not "is it fast" — so
        # probe under the caller's original deadline.
        GUARD.configure(dispatch_timeout_s=saved_guard[0])
        settle_deadline = time.monotonic() + opts.settle_s
        time.sleep(opts.breaker_reset_ms / 1e3)
        settle_problems = topo.settled()
        probe_n = 0
        while settle_problems and time.monotonic() < settle_deadline:
            # probe with docs[0]'s CONTENT under a fresh DiffID: a
            # shared-memo topology serves the original doc as a memo
            # hit (no device dispatch at all), which can never admit
            # the half-open probe. Same content = warm shape; new
            # blob id = guaranteed memo miss = a real dispatch.
            probe_n += 1
            probe = dict(docs[0])
            probe["DiffID"] = f"sha256:{0x5e771e0000 + probe_n:064x}"
            if topo.push_blobs:
                _post(topo.url,
                      "/twirp/trivy.cache.v1.Cache/PutBlob",
                      {"diff_id": probe["DiffID"],
                       "blob_info": probe},
                      timeout=opts.request_timeout_s)
            topo.do_request(0, probe, opts.request_timeout_s)
            if getattr(topo, "sbom_lane", False):
                # the parse stage's half-open probe only admits
                # through a ScanSBOM decode — the archive probe above
                # never touches the sbom lane's breaker
                topo.do_request(1, probe, opts.request_timeout_s)
            time.sleep(0.05)
            settle_problems = topo.settled()

        # db_swap epilogue: (a) the post-swap oracle — a settled,
        # fully-rolled topology must answer every request cleanly
        # under the new table (this pass also converges the router's
        # per-replica version view); (b) the skew counter must then be
        # QUIET across a second full pass — any further movement means
        # the roll never converged
        oracle2 = None
        skew_settle_delta = 0.0
        if topo.db_swapped:
            oracle2 = {}
            for i, doc in enumerate(docs):
                o = topo.do_request(i, doc, opts.request_timeout_s)
                if o.status == "ok":
                    oracle2[i] = o.digest
            skew0 = METRICS.family_sum(
                "trivy_tpu_fleet_db_version_skew_total")
            for i, doc in enumerate(docs):
                topo.do_request(i, doc, opts.request_timeout_s)
            skew_settle_delta = METRICS.family_sum(
                "trivy_tpu_fleet_db_version_skew_total") - skew0

        metrics: dict = {}
        for url in topo.metrics_urls():
            try:
                with urllib.request.urlopen(url + "/metrics",
                                            timeout=10) as r:
                    metrics[url] = r.read().decode()
            except (urllib.error.URLError, OSError):
                metrics[url] = None
    finally:
        try:
            topo.close()
        finally:
            FAILPOINTS.configure("")
            GUARD.configure(dispatch_timeout_s=saved_guard[0],
                            fail_threshold=saved_guard[1],
                            reset_timeout_s=saved_guard[2])
            GUARD.breaker.reset()
            INGEST.configure(fail_threshold=saved_ingest[0],
                             reset_timeout_s=saved_ingest[1])
            INGEST.reset_for_tests()
            RECORDER.configure(incident_dir=saved[0],
                               incident_cooldown_s=saved[1])

    # conservation read AFTER teardown: every handler thread has
    # settled, warmup/probe work has landed in SYSTEM — the two sides
    # must now agree for this run's deltas
    cost_deltas = _conservation_deltas(cost0)

    # leaked threads: everything the run created must be gone
    leak_deadline = time.monotonic() + 10.0
    leaked = {}
    while time.monotonic() < leak_deadline:
        leaked = {i: n for i, n in _nondaemon_threads().items()
                  if i not in baseline_threads}
        if not leaked:
            break
        time.sleep(0.05)

    breaker_opens = sum(
        1 for ev in RECORDER.events()[events0:]
        if ev.get("kind") == "breaker_open")
    try:
        incident_files = sorted(
            n for n in os.listdir(run_dir) if n.endswith(".json"))
    except OSError:
        incident_files = []

    ctx = RunContext(
        schedule=schedule, opts=opts, outcomes=outcomes,
        oracle=oracle, settle_problems=settle_problems,
        leaked_threads=sorted(leaked.values()), metrics=metrics,
        shed_counter_delta=METRICS.get(
            "trivy_tpu_requests_shed_total") - shed0,
        breaker_opens=breaker_opens, incident_files=incident_files,
        incident_dir=run_dir,
        db_swap=topo.db_swapped, oracle2=oracle2,
        v1=table.content_digest(),
        v2=topo.table2.content_digest(),
        skew_settle_delta=skew_settle_delta,
        requests=len(docs),
        cost_conservation=cost_deltas,
        adversarial=bool(adv_events),
        flood_outcomes=flood_outcomes,
        oracle_lat=oracle_lat)
    violations = {}
    for name, probe in INVARIANTS.items():
        msgs = probe(ctx)
        if msgs:
            violations[name] = msgs
    return StormReport(schedule=schedule, outcomes=outcomes,
                       oracle=oracle, violations=violations,
                       incident_dir=run_dir,
                       duration_s=time.perf_counter() - t_run0,
                       flood_outcomes=flood_outcomes)


# ---------------------------------------------------------------------------
# minimization: delta-debug a failing schedule


def minimize_schedule(schedule: Schedule, opts: StormOptions,
                      table=None, oracle: dict[int, str] | None = None,
                      max_trials: int = 24
                      ) -> tuple[Schedule, StormReport, int]:
    """Shrink a failing schedule to a minimal one that still fails:
    greedy event drops to a fixpoint, then window halving. → (minimal
    schedule, its failing report, trials spent). The caller supplies
    the oracle so trials never re-run the unfaulted pass."""
    if table is None:
        table = storm_table()
    trials = 0
    last_fail: StormReport | None = None

    def fails(evts: list[StormEvent]) -> bool:
        nonlocal trials, last_fail
        if trials >= max_trials:
            return False
        trials += 1
        rep = run_storm(replace(schedule, events=evts), opts,
                        table=table, oracle=oracle)
        if not rep.ok:
            last_fail = rep
        return not rep.ok

    events = list(schedule.events)
    i = 0
    while i < len(events) and len(events) > 1:
        cand = events[:i] + events[i + 1:]
        if fails(cand):
            events = cand        # dropped; retry the same position
        else:
            i += 1
    for i, ev in enumerate(list(events)):
        while ev.dur_ms >= 100.0 and trials < max_trials:
            shorter = replace(ev, dur_ms=round(ev.dur_ms / 2, 1))
            if fails(events[:i] + [shorter] + events[i + 1:]):
                ev = shorter
                events[i] = ev
            else:
                break
    minimal = replace(schedule, events=events)
    if last_fail is None or last_fail.schedule.events != events:
        # re-run the exact minimal schedule so the report matches it
        last_fail = run_storm(minimal, opts, table=table,
                              oracle=oracle)
    return minimal, last_fail, trials


# ---------------------------------------------------------------------------
# replay artifacts


def write_replay(path: str, schedule: Schedule, opts: StormOptions,
                 report: StormReport, minimized: bool) -> str:
    """Write the replayable failing-schedule artifact: schedule, load
    parameters, violations, and the newest captured incident (obs.check
    validates the whole document offline)."""
    incident = None
    for name in reversed(sorted(
            os.listdir(report.incident_dir))
            if os.path.isdir(report.incident_dir) else []):
        if name.endswith(".json"):
            try:
                with open(os.path.join(report.incident_dir, name)) as f:
                    incident = json.load(f)
                break
            except (OSError, json.JSONDecodeError):
                continue
    doc = {
        "schema": REPLAY_SCHEMA,
        "schedule": schedule.to_json(),
        "load": {
            "requests": opts.requests,
            "concurrency": opts.concurrency,
            "load_seed": opts.load_seed or schedule.seed,
            "admit_max_active": opts.admit_max_active,
            "admit_max_queue": opts.admit_max_queue,
            "admit_tenant_max_active": opts.admit_tenant_max_active,
            "admit_tenant_max_queue": opts.admit_tenant_max_queue,
            "admit_tenant_rate": opts.admit_tenant_rate,
            "watchdog_ms": opts.watchdog_ms,
            "breaker_reset_ms": opts.breaker_reset_ms,
            "replicas": opts.replicas,
            "mesh_devices": opts.mesh_devices,
            "mesh_hosts": opts.mesh_hosts,
            "tenants": opts.tenants,
        },
        "violations": report.violations,
        "minimized": minimized,
        "incident": incident,
    }
    tmp = path + ".tmp"
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1)
    os.replace(tmp, path)
    return path


def load_replay(path: str) -> tuple[Schedule, StormOptions]:
    with open(path) as f:
        doc = json.load(f)
    if doc.get("schema") != REPLAY_SCHEMA:
        raise ValueError(f"{path}: not a storm replay artifact "
                         f"(schema {doc.get('schema')!r})")
    schedule = Schedule.from_json(doc["schedule"])
    load = doc.get("load", {})
    opts = StormOptions(
        requests=int(load.get("requests", 24)),
        concurrency=int(load.get("concurrency", 8)),
        load_seed=int(load.get("load_seed", 0)),
        admit_max_active=int(load.get("admit_max_active", 0)),
        admit_max_queue=int(load.get("admit_max_queue", 8)),
        admit_tenant_max_active=int(
            load.get("admit_tenant_max_active", 0)),
        admit_tenant_max_queue=int(
            load.get("admit_tenant_max_queue", 0)),
        admit_tenant_rate=float(load.get("admit_tenant_rate", 0.0)),
        watchdog_ms=float(load.get("watchdog_ms", 50.0)),
        breaker_reset_ms=float(load.get("breaker_reset_ms", 150.0)),
        replicas=int(load.get("replicas", 3)),
        mesh_devices=int(load.get("mesh_devices", 4)),
        mesh_hosts=int(load.get("mesh_hosts", 2)),
        tenants=int(load.get("tenants", 1)))
    return schedule, opts


# ---------------------------------------------------------------------------
# CLI


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m trivy_tpu.resilience.storm",
        description="graftstorm: seeded multi-fault chaos schedules "
                    "against the in-process scan topology, with an "
                    "invariant engine and failing-schedule "
                    "minimization")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rounds", type=int, default=1,
                    help="schedules to run (round r uses seed+r)")
    ap.add_argument("--topology", choices=TOPOLOGIES, default="single")
    ap.add_argument("--events", type=int, default=4,
                    help="fault events per schedule")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--concurrency", type=int, default=8)
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--mesh-devices", type=int, default=4)
    ap.add_argument("--mesh-hosts", type=int, default=2,
                    help="synthetic host fault domains on the mesh "
                         "topology (host_loss events kill one host's "
                         "worth of device domains at once)")
    ap.add_argument("--admit-max-active", type=int, default=0)
    ap.add_argument("--admit-tenant-max-active", type=int, default=0,
                    help="graftfair per-tenant active cap (0 = "
                         "disarmed; adversarial_tenant schedules "
                         "derive victim-safe defaults when none of "
                         "the tenant quota flags are set)")
    ap.add_argument("--admit-tenant-max-queue", type=int, default=0)
    ap.add_argument("--admit-tenant-rate", type=float, default=0.0,
                    help="per-tenant admission rate (req/s token "
                         "bucket; 0 = disarmed)")
    ap.add_argument("--tenants", type=int, default=1,
                    help="distinct X-Trivy-Tenant ids the load "
                         "round-robins through (graftcost tenant mix; "
                         "1 = untenanted)")
    ap.add_argument("--artifact-dir", default="",
                    help="where failing-schedule replay artifacts and "
                         "incident snapshots land (default: a tmpdir)")
    ap.add_argument("--replay", default="", metavar="FILE",
                    help="re-run a previously written failing-schedule "
                         "artifact instead of generating schedules")
    ap.add_argument("--no-minimize", action="store_true",
                    help="on failure, skip delta-debugging the "
                         "schedule down to a minimal one")
    ap.add_argument("--virtual-devices", type=int, default=0,
                    help="force N virtual CPU devices before jax "
                         "loads (mesh topology without a real "
                         "multi-chip backend)")
    args = ap.parse_args(argv)

    if args.virtual_devices:
        import sys
        if "jax" not in sys.modules:
            flags = os.environ.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" not in flags:
                os.environ["XLA_FLAGS"] = (
                    flags + " --xla_force_host_platform_device_count="
                    f"{args.virtual_devices}").strip()

    table = storm_table()
    if args.replay:
        schedule, opts = load_replay(args.replay)
        if args.artifact_dir:
            opts.artifact_dir = args.artifact_dir
        report = run_storm(schedule, opts, table=table)
        print(json.dumps(report.summary()))
        return 0 if report.ok else 1

    opts = StormOptions(
        requests=args.requests, concurrency=args.concurrency,
        replicas=args.replicas, mesh_devices=args.mesh_devices,
        mesh_hosts=args.mesh_hosts,
        admit_max_active=args.admit_max_active,
        admit_tenant_max_active=args.admit_tenant_max_active,
        admit_tenant_max_queue=args.admit_tenant_max_queue,
        admit_tenant_rate=args.admit_tenant_rate,
        artifact_dir=args.artifact_dir, tenants=args.tenants)
    for r in range(args.rounds):
        seed = args.seed + r
        schedule = generate_schedule(
            seed, args.topology, n_events=args.events,
            mesh_devices=args.mesh_devices, replicas=args.replicas,
            watchdog_ms=opts.watchdog_ms,
            mesh_hosts=args.mesh_hosts)
        report = run_storm(schedule, opts, table=table)
        print(json.dumps(report.summary()))
        if report.ok:
            continue
        if not args.no_minimize:
            minimal, report, trials = minimize_schedule(
                schedule, opts, table=table, oracle=report.oracle)
            print(json.dumps({"minimized": minimal.to_json(),
                              "trials": trials,
                              "violations": report.violations}))
            schedule = minimal
        out = os.path.join(
            args.artifact_dir or report.incident_dir,
            f"storm-replay-{args.topology}-{seed}.json")
        write_replay(out, schedule, opts, report,
                     minimized=not args.no_minimize)
        print(json.dumps({"replay_artifact": out}))
        return 1
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
