"""Scan server.

Mirrors pkg/rpc/server/listen.go: one HTTP mux exposing the Scanner and
Cache services plus /healthz and /version, with optional token auth
(Trivy-Token header) and a hot-swappable advisory table (the reference
drains in-flight requests around a DB reload, listen.go:129-192; here a
lock swap suffices because the table is immutable once built).

Routes speak both Twirp encodings (POST /twirp/<svc>/<Method>): JSON
bodies with proto field names, and application/protobuf binary for
drop-in Go clients (rpc/scanner/service.proto, rpc/cache/service.proto,
handwritten codec in protowire.py). Batches accumulate per request;
every Scan
request runs the batched device join over all its target's packages at
once (SURVEY.md §2.7 P4/P5)."""

from __future__ import annotations

import json
import threading
import time
from dataclasses import dataclass
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from .. import __version__, types as T
from ..fanal.cache import blob_from_json
from ..log import get as _get_logger
from ..obs import SLO, device_status, new_trace, span
from ..obs import cost as _cost
from ..obs.perf import debug_perf_payload, debug_profile_payload
from ..obs.recorder import (debug_incidents_payload,
                            debug_traces_payload)
from ..resilience import (AdmissionQueue, Deadline, GUARD, Shed,
                          failpoint)
from ..scanner import LocalScanner
# wire-header names live in the package __init__ so the CLIENT can
# import them without pulling in this module's server stack;
# re-exported here for the existing `listen.TOKEN_HEADER` readers
from . import (COST_HEADER, DB_VERSION_HEADER,  # noqa: F401
               DEADLINE_HEADER, PARENT_SPAN_HEADER, ROUTE_DESCRIPTORS,
               TENANT_HEADER, TOKEN_HEADER, TRACE_HEADER)

_log = _get_logger("server")


@dataclass
class MeshOptions:
    """Mesh-mode knobs (server flags --mesh-devices, --mesh-db-shards,
    --mesh-min-devices, --mesh-rebuild-cooldown-ms,
    --mesh-probe-timeout-ms, --mesh-hosts,
    --mesh-host-loss-window-ms). devices=0 keeps the single-chip
    path."""
    devices: int = 0          # mesh size; 0 = single-chip detect path
    db_shards: int = 1        # preferred db width (shrink re-fits it)
    min_devices: int = 1      # survivors below this → host join
    rebuild_cooldown_ms: float = 1000.0
    probe_timeout_ms: float = 5000.0
    # host fault domains: 0 = real per-device process_index (multi-
    # host jobs); > 1 = synthetic contiguous blocks for drills on a
    # single-process platform. Domains only engage when the mapping
    # actually spans ≥ 2 hosts — a single-host mesh keeps the plain
    # per-chip behavior.
    hosts: int = 0
    host_loss_window_ms: float = 250.0


class ServerState:
    def __init__(self, table, cache_dir: str, token: str = "",
                 cache_backend: str = "fs", detect_opts=None,
                 admission=None, mesh_opts: MeshOptions | None = None,
                 memo_backend="", redetect_opts=None, sbom_opts=None):
        from ..detect.sched import SchedOptions
        from ..fanal.cache import open_cache
        # one backend-selection path (fanal.cache.open_cache) shared
        # with the CLI: fs | memory | redis:// | s3:// — the shared
        # backends are what make a replica fleet cache-coherent
        self.cache = open_cache(cache_backend, cache_dir)
        # graftmemo: content-addressed detection-result memo (same
        # backend grammar; "" = disabled). On a shared backend a blob
        # detected by any replica is a memo hit on all of them.
        from ..fleet.memo import open_memo
        self.memo = open_memo(memo_backend, cache_dir)
        self.token = token
        self._lock = threading.Lock()
        # server mode runs detectd by default: concurrent RPCs'
        # prepared batches coalesce into shared device dispatches
        # (detect/sched.py; --detect-* flags tune or disable it)
        self.detect_opts = detect_opts if detect_opts is not None \
            else SchedOptions()
        # graftbom: parse budgets/deadline for ScanSBOM document
        # decodes (SBOMOptions; None → defaults). Chaos drills tighten
        # the parse deadline the way they tighten ingest budgets.
        self.sbom_opts = sbom_opts
        # graftguard admission: bounded deadline-aware Scan queue
        # (--admit-* flags; unbounded by default). The breaker reference
        # picks the shed code — 503 while the device is down, 429 else
        self.admission = AdmissionQueue(admission,
                                        breaker=GUARD.breaker)
        self._table = table
        # advisory-DB version identity: the serving table's content
        # digest, stamped on every Scan response and in /healthz so a
        # mid-rollout fleet's skew is observable (the router counts
        # disagreements). Plain str attribute — handler reads need no
        # lock; swap_table re-stamps it when a new table installs.
        self.db_version = table.content_digest()
        # rolling-upgrade observability: the version this replica
        # served BEFORE its last hot swap, and when the swap landed —
        # /healthz surfaces both so an operator can tell which side of
        # a rolling fleet upgrade each replica is on (the skew counter
        # says the fleet disagrees; these say who moved, and when)
        self.db_previous_version = ""
        self.db_swapped_at = ""
        # graceful drain (SIGTERM/SIGINT): once draining, Scan sheds
        # 503 + Retry-After while in-flight requests finish through
        # the generation drain — a restart mid-load completes what the
        # admission queue holds instead of dropping it
        self._draining = False
        self.drain_retry_after_s = 5.0
        # meshguard: mesh mode shards the detect join over a device
        # mesh with per-device fault domains. Device loss shrinks the
        # mesh to the survivors (grow on readmission) through the
        # swap_table generation drain below, instead of dropping the
        # whole backend to the host fallback.
        self.mesh_guard = None
        self._mesh = None
        self._mesh_devices = []
        self._mesh_db_shards = 1
        # the latest breaker-recovery rebuild thread; close() joins it
        # (bounded) so a recovery swap can't outlive the server
        self._recover_thread: threading.Thread | None = None
        if mesh_opts is not None and mesh_opts.devices:
            import jax

            from ..parallel.mesh import mesh_from_devices
            from ..parallel.multihost import host_assignments
            from ..resilience import MeshGuard, MeshGuardOptions
            n = mesh_opts.devices
            devs = jax.devices()
            self._mesh_devices = list(devs if n < 0 else devs[:n])
            self._mesh_db_shards = mesh_opts.db_shards
            self._mesh = mesh_from_devices(self._mesh_devices,
                                           mesh_opts.db_shards)
            # host fault domains engage only when the mapping spans
            # ≥ 2 hosts — a single-host mesh must keep the prompt
            # per-chip shrink (no host-loss hold on every loss)
            host_of = host_assignments(self._mesh_devices,
                                       synthetic_hosts=mesh_opts.hosts)
            if len(set(host_of.values())) < 2:
                host_of = None
            self.mesh_guard = MeshGuard(
                [int(d.id) for d in self._mesh_devices],
                MeshGuardOptions(
                    min_devices=mesh_opts.min_devices,
                    rebuild_cooldown_ms=mesh_opts.rebuild_cooldown_ms,
                    probe_timeout_ms=mesh_opts.probe_timeout_ms,
                    host_loss_window_ms=mesh_opts.host_loss_window_ms),
                probe=self._mesh_probe, host_of=host_of)
        self._scanner = LocalScanner(self.cache, table,
                                     sched=self.detect_opts,
                                     mesh=self._mesh,
                                     mesh_guard=self.mesh_guard,
                                     memo=self.memo)
        # redetectd: on a DB hot swap, sweep the memo's known blobs
        # through the pure detect path in the background so fresh
        # entries exist under the new db_version before users rescan
        self.redetect = None
        if self.memo is not None:
            from ..detect.redetect import RedetectDaemon
            self.redetect = RedetectDaemon(
                self.memo, self.cache, self.admission,
                self.scanner_with_version, redetect_opts,
                track=(self.request_started,
                       self.request_finished))
        self._inflight = 0
        self._closed = False
        # scanner generations: a request started under generation g
        # may hold that generation's scanner for its whole lifetime, so
        # a swapped-out scanner is closeable exactly when its
        # generation's active count drains — not on the GLOBAL count,
        # which under sustained traffic never reaches zero
        self._gen = 0
        self._gen_active = {0: 0}
        # breaker recovery (half-open probe succeeded): rebuild the
        # detector through the swap_table generation drain — a fresh
        # engine re-ships its device arrays onto the recovered backend
        # and no in-flight request is ever force-killed. The rebuild
        # runs on its own thread: listeners fire from whatever thread
        # recorded the probe's success, which must not absorb a
        # multi-second scanner build
        GUARD.breaker.on_recovery(self._recover)
        # meshguard rebuilds ride the same drain (they run on the
        # coordinator's maintenance thread, already off the hot path)
        if self.mesh_guard is not None:
            self.mesh_guard.on_rebuild(self._mesh_rebuild)

    def _mesh_probe(self, dev_id) -> None:
        """Readmission probe body (meshguard runs it under the
        device's own watch, after its failpoint site): one real tiny
        op on the lost device — a dead chip fails or wedges right
        here, a recovered one completes and closes its domain."""
        import jax
        import numpy as np
        dev = next(d for d in self._mesh_devices
                   if int(d.id) == int(dev_id))
        jax.device_put(np.zeros(8, np.int32), dev).block_until_ready()

    def _mesh_rebuild(self, active_ids, reason: str) -> None:
        """meshguard rebuild callback: re-mesh the survivors (largest
        valid dp×db factorization), re-shard the table, and swap the
        detector through the generation drain — in-flight scans finish
        on the old mesh while new requests land on the rebuilt one.
        Zero survivors swaps in the host-join degraded detector."""
        with self._lock:
            if self._closed:
                return
        from ..parallel.mesh import mesh_from_devices
        ids = {int(i) for i in active_ids}
        devs = [d for d in self._mesh_devices if int(d.id) in ids]
        mesh = mesh_from_devices(devs, self._mesh_db_shards) \
            if devs else "host"
        _log.warning("meshguard: %s rebuild → swapping %s-device mesh "
                     "via generation drain", reason,
                     len(devs) if devs else "host-join (0)")
        try:
            # _KEEP_TABLE: a DB hot swap racing this rebuild must not
            # be reverted to a snapshotted (stale) advisory table
            self.swap_table(ServerState._KEEP_TABLE, mesh=mesh)
        except Exception:
            _log.exception("meshguard: %s rebuild swap failed", reason)

    def _recover(self) -> None:
        with self._lock:
            if self._closed:
                return
        _log.warning("graftguard: device recovered; rebuilding "
                     "detector via swap_table")
        t = threading.Thread(target=self._recover_swap,
                             name="graftguard-recover", daemon=True)
        with self._lock:
            self._recover_thread = t
        t.start()

    def _recover_swap(self) -> None:
        try:
            # rebuild with whatever table/mesh are CURRENT at install
            # time — a hot swap racing the recovery must not be undone
            self.swap_table(ServerState._KEEP_TABLE)
        except Exception:
            _log.exception("graftguard: recovery swap failed")

    def request_started(self) -> int:
        """→ the scanner generation this request runs under; pass it
        back to request_finished."""
        with self._lock:
            self._inflight += 1
            self._gen_active[self._gen] += 1
            return self._gen

    def request_finished(self, gen: int | None = None) -> None:
        with self._lock:
            self._inflight -= 1
            g = self._gen if gen is None else gen
            self._gen_active[g] -= 1
            if g != self._gen and not self._gen_active[g]:
                del self._gen_active[g]

    @property
    def scanner(self) -> LocalScanner:
        with self._lock:
            return self._scanner

    def scanner_with_version(self) -> "tuple[LocalScanner, str]":
        """Scanner AND the digest of the table it serves, captured
        under one lock hold — a hot swap landing mid-scan must not
        stamp the NEW table's version on a result the OLD table
        produced (the router's skew accounting trusts the header)."""
        with self._lock:
            return self._scanner, self.db_version

    @property
    def inflight(self) -> int:
        with self._lock:
            return self._inflight

    @property
    def draining(self) -> bool:
        return self._draining

    def begin_drain(self, retry_after_s: float | None = None) -> None:
        """Stop admitting Scans: subsequent requests shed 503 +
        Retry-After while in-flight ones keep running."""
        with self._lock:
            if retry_after_s is not None:
                self.drain_retry_after_s = retry_after_s
            self._draining = True
        # a draining replica is leaving: its redetect sweep is work
        # for a process that won't serve the result — cancel it so the
        # drain window belongs entirely to in-flight user requests
        if self.redetect is not None:
            self.redetect.cancel()

    def drain(self, timeout_s: float) -> bool:
        """Wait (bounded) for every in-flight request to finish — the
        same generation counts the swap drain trusts. → True when the
        server went quiescent, False when the deadline expired with
        requests still running."""
        deadline = time.monotonic() + max(timeout_s, 0.0)
        while True:
            with self._lock:
                if self._inflight == 0:
                    return True
            if time.monotonic() >= deadline:
                return False
            time.sleep(0.02)

    def close(self) -> None:
        """Server shutdown: join the scanner's detectd + engine worker
        threads (idempotent)."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            scanner = self._scanner
        GUARD.breaker.remove_recovery(self._recover)
        if self.redetect is not None:
            self.redetect.close()
        if self.mesh_guard is not None:
            self.mesh_guard.remove_rebuild(self._mesh_rebuild)
            self.mesh_guard.close()
        t = self._recover_thread
        if t is not None and t.is_alive():
            t.join(timeout=10.0)
        scanner.close()

    # "keep the current value" sentinels: a DB hot swap keeps the
    # mesh, a meshguard rebuild / breaker recovery keeps the table —
    # each must re-read the CURRENT other half at build time AND
    # re-check it at install time, or a swap racing a rebuild would
    # silently resurrect the half its caller never meant to change
    # (stale mesh with a lost device, or a stale advisory table)
    _KEEP_MESH = object()
    _KEEP_TABLE = object()

    def swap_table(self, table, mesh=_KEEP_MESH) -> None:
        """DB hot swap (reference listen.go dbWorker). Also the
        meshguard shrink/grow path: `mesh` swaps the detect mesh under
        the same generation drain."""
        keep_mesh = mesh is ServerState._KEEP_MESH
        keep_table = table is ServerState._KEEP_TABLE
        while True:
            with self._lock:
                build_mesh = self._mesh if keep_mesh else mesh
                build_table = self._table if keep_table else table
            # build (and, with --detect-warmup, XLA-warm) the new
            # scanner OUTSIDE the lock: construction can take seconds
            # and every handler blocks on request_started behind it
            new_scanner = LocalScanner(self.cache, build_table,
                                       sched=self.detect_opts,
                                       mesh=build_mesh,
                                       mesh_guard=self.mesh_guard,
                                       memo=self.memo)
            # digest outside the lock too (first computation walks the
            # whole table); cached on the table object afterwards
            new_version = build_table.content_digest()
            with self._lock:
                # close() may have run while the scanner was building
                # (a meshguard rebuild races server shutdown):
                # installing now would strand a never-closed scanner
                # whose non-daemon workers hang process exit
                if self._closed:
                    outcome = "aborted"
                elif (keep_mesh and self._mesh is not build_mesh) or \
                        (keep_table and self._table
                         is not build_table):
                    # a concurrent swap changed the kept half
                    # mid-build: installing the snapshot would undo it
                    outcome = "stale"
                else:
                    outcome = "installed"
                    old_scanner = self._scanner
                    old_gen = self._gen
                    self._gen += 1
                    self._gen_active.setdefault(self._gen, 0)
                    if not self._gen_active[old_gen]:
                        del self._gen_active[old_gen]
                    self._scanner = new_scanner
                    self._table = build_table
                    self._mesh = build_mesh
                    version_changed = new_version != self.db_version
                    if version_changed:
                        # rolling-upgrade breadcrumbs for /healthz:
                        # what this replica served before, and when
                        # the swap landed
                        self.db_previous_version = self.db_version
                        self.db_swapped_at = time.strftime(
                            "%Y-%m-%dT%H:%M:%SZ", time.gmtime())
                    self.db_version = new_version
            if outcome == "aborted":
                new_scanner.close()
                return
            if outcome == "stale":
                _log.warning("swap: mesh/table changed during scanner "
                             "build; rebuilding against fresh state")
                new_scanner.close()
                continue
            break
        # redetectd: a swap that changed the advisory-DB identity
        # kicks the background memo sweep — fresh entries publish
        # under the new db_version while old-version entries simply
        # stop being addressed (a mesh rebuild / breaker recovery
        # keeps the table, so it never sweeps)
        if version_changed and self.redetect is not None \
                and not self._draining:
            self.redetect.schedule(new_version)
            # begin_drain may have raced in between the check and the
            # schedule — its cancel would have found no sweep to stop.
            # Re-check and cancel so a draining replica never runs a
            # fresh sweep against its own in-flight user requests.
            with self._lock:
                draining = self._draining
            if draining:
                self.redetect.cancel()
        # the swapped-in table's object graph (~1M small objects for a
        # full trivy-db) is immutable; freezing it out of the cyclic
        # collector keeps gen2 passes from stalling in-flight scans.
        # unfreeze first: the PREVIOUS swap's frozen set must rejoin
        # the collector or every swap would leak one table's worth of
        # uncollectable objects. Freeze only in a quiescent window —
        # freezing while requests are in flight would pin their
        # transient buffers (and any cyclic garbage among them)
        # forever if no later swap unfreezes them.
        import gc
        gc.unfreeze()
        # the expensive full collect over the just-unfrozen table graph
        # runs OUTSIDE the lock — request_started/finished must never
        # block behind a multi-hundred-ms gen2 pass (healthz probes!)
        gc.collect()
        deadline = time.monotonic() + 2.0
        froze = False
        while time.monotonic() < deadline and \
                not (froze and old_scanner is None):
            with self._lock:
                drained = not self._gen_active.get(old_gen)
                if not froze and self._inflight == 0:
                    # young-gen sweep inside the window: requests that
                    # finished during the wait leave fresh cyclic
                    # garbage that must die before freeze pins it;
                    # gen-1 collects are cheap enough to hold the lock
                    gc.collect(1)
                    gc.freeze()
                    froze = True
            if drained and old_scanner is not None:
                # no request started before the swap is still running:
                # nothing can hold the old scanner, so its executors
                # join without breaking an in-flight detect (the
                # pre-close() leak: every swap stranded the old
                # engine's threads forever)
                old_scanner.close()
                old_scanner = None
            if not (froze and old_scanner is None):
                time.sleep(0.01)
        # old generation still busy (a long scan straddles the swap):
        # retire its scanner from a waiter thread once its LAST request
        # drains — never force-close, that would yank the executors out
        # from under the running detect. An un-frozen swap just means
        # gen2 GC passes stay slower until the next swap.
        if old_scanner is not None:
            self._close_when_idle(old_scanner, old_gen)

    def _close_when_idle(self, scanner: LocalScanner,
                         gen: int) -> None:
        def waiter():
            deadline = time.monotonic() + 600.0
            while time.monotonic() < deadline:
                with self._lock:
                    if not self._gen_active.get(gen):
                        break
                time.sleep(0.05)
            else:
                _log.warning(
                    "swap: generation %d still busy after 600s; "
                    "leaking its scanner workers", gen)
                return
            scanner.close()
        # lint: allow(TPU112) reason=detached by design so the swap path never blocks; the waiter self-bounds at 600s and then deliberately leaks the busy generation
        threading.Thread(target=waiter, name="swap-close",
                         daemon=True).start()


def _result_to_json(res: T.Result) -> dict:
    return res.to_json()


class ScanServer(ThreadingHTTPServer):
    # graftfair: the TCP accept backlog must exceed any burst the
    # admission layer is meant to judge — with the http.server default
    # (5), a flooding tenant's connections die as kernel RSTs before
    # the quota layer can mint its well-formed 429 + Retry-After
    request_queue_size = 128


class Handler(BaseHTTPRequestHandler):
    state: ServerState = None  # set by serve()
    protocol_version = "HTTP/1.1"
    _trace_id = ""  # per-request; set by do_POST before dispatch
    _db_version = ""  # stamped on Scan responses only (X-Trivy-DB-Version)

    def log_message(self, *args):
        pass

    def _json(self, code: int, payload: dict):
        with span("server.encode"):
            self._send(code, "application/json",
                       json.dumps(payload).encode())

    def _send(self, code: int, ctype: str, body: bytes):
        """Headers, then the body: the one writer of POST replies and
        JSON GET replies."""
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        if self._trace_id:
            self.send_header(TRACE_HEADER, self._trace_id)
        if self._db_version:
            self.send_header(DB_VERSION_HEADER, self._db_version)
        # graftcost: the per-request cost split rides every response
        # produced inside a request ledger (do_POST installs one);
        # GET surfaces have no ledger and stamp nothing
        led = _cost.active()
        if led is not None:
            self.send_header(COST_HEADER, led.header_json())
        self.end_headers()
        self.wfile.write(body)

    def _twirp_error(self, code: int, twirp_code: str, msg: str):
        self._json(code, {"code": twirp_code, "msg": msg})

    def do_GET(self):
        st = self.state
        # clear any trace id a previous POST on this keep-alive
        # connection stamped on the handler instance — a health probe
        # must not echo an unrelated scan's id
        self._trace_id = ""
        self._db_version = ""
        gen = st.request_started()
        try:
            self._do_get()
        finally:
            st.request_finished(gen)

    def _do_get(self):
        if self.path.startswith(("/debug/traces", "/debug/incidents",
                                 "/debug/perf", "/debug/profile",
                                 "/debug/costs")):
            # unlike /healthz//metrics (liveness/scrape surfaces), the
            # debug buffers carry scan detail — file paths in analyzer
            # spans, other tenants' trace ids — so a configured token
            # gates them exactly like the POST surface; /debug/profile
            # additionally COSTS (it runs the profiler against live
            # traffic), which is exactly what a token should gate
            if self.state.token and \
                    self.headers.get(TOKEN_HEADER) != self.state.token:
                return self._twirp_error(401, "unauthenticated",
                                         "invalid token")
            if self.path.startswith("/debug/traces"):
                return self._json(200, debug_traces_payload(self.path))
            if self.path.startswith("/debug/perf"):
                return self._json(200, debug_perf_payload())
            if self.path.startswith("/debug/profile"):
                code, payload = debug_profile_payload(self.path)
                return self._json(code, payload)
            if self.path.startswith("/debug/costs"):
                # graftcost: per-tenant totals + the conservation
                # reconciliation (replica-local; the fleet router
                # serves the fleet-wide variant from relayed headers)
                return self._json(200, _cost.debug_costs_payload())
            return self._json(200, debug_incidents_payload())
        if self.path == "/healthz":
            # plain `ok` stays the fast path for probes that ask for
            # it (kubelet-style `Accept: text/plain`); everything else
            # gets the device-backend status as JSON. Neither path
            # touches jax — the status is the cached view the detect
            # engine stamps on its dispatch path (obs.device).
            accept = self.headers.get("Accept") or ""
            if "text/plain" in accept:
                body = b"ok"
                self.send_response(200)
                self.send_header("Content-Type", "text/plain")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            else:
                from ..fanal.pipeline import INGEST
                resilience = {
                    **GUARD.status(),
                    "admission": self.state.admission.snapshot(),
                    # fanald: per-stage ingest breaker states, partial-
                    # scan and budget-trip counters — the degradation
                    # contract's observable face (a scan that returned
                    # an annotated partial shows up here, never as a
                    # 5xx)
                    "ingest": INGEST.status(),
                }
                # meshguard: per-device breaker states, lost set, and
                # the shrink/grow rebuild counters
                if self.state.mesh_guard is not None:
                    resilience["mesh"] = self.state.mesh_guard.status()
                payload = {
                    "status": "draining" if self.state.draining
                    else "ok",
                    # advisory-DB identity: replicas of one fleet must
                    # agree, or bit-identical failover is a lie — the
                    # router's probe reads this field. The previous
                    # version + swap timestamp make a rolling upgrade
                    # observable per replica.
                    "db_version": self.state.db_version,
                    "db_previous_version":
                        self.state.db_previous_version,
                    "db_swapped_at": self.state.db_swapped_at,
                    "device": device_status(),
                    # graftguard: breaker state, watchdog last-probe
                    # age, shed/fallback counters, admission snapshot
                    "resilience": resilience,
                    # graftwatch: per-objective burn rates over the
                    # sliding windows (export() also refreshes the
                    # burn-rate gauges, so /healthz and /metrics agree)
                    "slo": SLO.export(),
                    # graftcost: per-tenant scan counts + headline cost
                    # split (bounded rows — the top-K clamp already
                    # ran). graftfair adds the `qos` view: per-tenant
                    # admission quota state and tenant-labelled SLO
                    # burn rates, bounded by the same clamp
                    "tenants": {
                        **_cost.TENANTS.healthz_block(),
                        "qos": {
                            "quotas": resilience["admission"].get(
                                "tenant_quotas"),
                            "admission": resilience["admission"].get(
                                "tenants", {}),
                            "burn_rates": SLO.tenant_burn_rates(),
                        },
                    },
                }
                # graftmemo: backend + known-blob count, and the
                # redetectd sweep's progress (phase, done/total,
                # target db_version)
                if self.state.memo is not None:
                    memo = self.state.memo.status()
                    if self.state.redetect is not None:
                        memo["sweep"] = self.state.redetect.status()
                    payload["memo"] = memo
                self._json(200, payload)
        elif self.path == "/version":
            self._json(200, {"Version": __version__})
        elif self.path == "/metrics":
            from ..metrics import METRICS
            # burn-rate gauges are window functions of the SLO event
            # store — recompute at scrape time so they are current
            SLO.export()
            body = METRICS.render().encode()
            self.send_response(200)
            self.send_header("Content-Type",
                             "text/plain; version=0.0.4")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        else:
            self._twirp_error(404, "not_found", self.path)

    def _proto(self, code: int, payload: dict, desc: str):
        from .protowire import encode_msg
        with span("server.encode"):
            self._send(code, "application/protobuf",
                       encode_msg(payload, desc))

    def _reply(self, payload: dict, desc: str):
        """Encode the response in the request's encoding (Twirp
        requires responses to match the request content type)."""
        if self._is_proto:
            return self._proto(200, payload, desc)
        return self._json(200, payload)

    # request-message descriptor per route (binary Twirp); the map
    # itself lives in the package __init__ so the fleet router shares
    # it without importing this module's server stack
    _ROUTES = ROUTE_DESCRIPTORS

    def do_POST(self):
        st = self.state
        self._db_version = ""
        gen = st.request_started()
        # per-RPC trace stamp: reuse the client's id when forwarded,
        # mint one otherwise; every span/log line below inherits it.
        # The forwarded parent-span id (router hop or client span)
        # parents this fragment's root, so obs.collect stitches one
        # tree across processes
        tid = self.headers.get(TRACE_HEADER) or ""
        parent = self.headers.get(PARENT_SPAN_HEADER) or ""
        # graftcost: one request-scoped ledger per RPC, keyed by the
        # relayed tenant header (client --tenant; router forwards it;
        # absent → "default"). Every seam below — admission queue,
        # detectd apportionment, fanald ingest, secrets, memo — charges
        # this ledger through the contextvar; settle folds it into the
        # tenant aggregate once the response is on the wire.
        # graftfair: the raw header is attacker-controlled, so it is
        # syntactically clamped HERE — before it can mint a ledger,
        # quota state, or a metric label anywhere downstream
        tenant = _cost.normalize_tenant(self.headers.get(TENANT_HEADER))
        try:
            with new_trace(tid or None, parent_id=parent or None) as tid:
                self._trace_id = tid
                with _cost.request_ledger(tenant) as led:
                    try:
                        with span("server.rpc", route=self.path,
                                  tenant=tenant):
                            self._do_post(st)
                    finally:
                        _cost.TENANTS.settle(led, led.outcome)
        finally:
            st.request_finished(gen)

    def _do_post(self, st):
        if st.token and self.headers.get(TOKEN_HEADER) != st.token:
            return self._twirp_error(401, "unauthenticated", "invalid token")
        ctype = (self.headers.get("Content-Type") or "").split(";")[0]
        self._is_proto = ctype in ("application/protobuf",
                                   "application/x-protobuf")
        route = self.path
        desc = self._ROUTES.get(route)
        try:
            with span("server.decode"):
                length = int(self.headers.get("Content-Length", "0"))
                body = self.rfile.read(length)
                if not self._is_proto:
                    req = json.loads(body or b"{}")
                elif desc is not None:
                    from .protowire import decode_msg
                    req = decode_msg(body, desc)
        except (ValueError, json.JSONDecodeError):
            return self._twirp_error(400, "malformed", "bad body")
        if self._is_proto and desc is None:
            return self._twirp_error(404, "bad_route", route)

        try:
            if route == "/twirp/trivy.scanner.v1.Scanner/Scan":
                return self._scan_admitted(req)
            if route == "/twirp/trivy.scanner.v1.Scanner/ScanSBOM":
                return self._scan_admitted(req, sbom=True)
            if route == "/twirp/trivy.cache.v1.Cache/PutArtifact":
                st.cache.put_artifact(req.get("artifact_id", ""),
                                      req.get("artifact_info") or {})
                return self._reply({}, "Empty")
            if route == "/twirp/trivy.cache.v1.Cache/PutBlob":
                with span("server.decode"):
                    blob_j = req.get("blob_info") or {}
                    if self._is_proto:
                        from .convert import proto_blob_to_json
                        blob_j = proto_blob_to_json(blob_j)
                    blob = blob_from_json(blob_j)
                st.cache.put_blob(req.get("diff_id", ""), blob)
                return self._reply({}, "Empty")
            if route == "/twirp/trivy.cache.v1.Cache/MissingBlobs":
                missing_artifact, missing = st.cache.missing_blobs(
                    req.get("artifact_id", ""), req.get("blob_ids") or [])
                return self._reply({
                    "missing_artifact": missing_artifact,
                    "missing_blob_ids": missing,
                }, "MissingBlobsResponse")
            if route == "/twirp/trivy.cache.v1.Cache/DeleteBlobs":
                return self._reply({}, "Empty")
            return self._twirp_error(404, "bad_route", route)
        except KeyError as e:
            return self._twirp_error(400, "invalid_argument", str(e))
        except Exception as e:  # noqa: BLE001 — server must not die
            return self._twirp_error(500, "internal", f"{type(e).__name__}: {e}")

    def _shed_response(self, s: Shed):
        """429/503 + Retry-After: the admission queue rejected the
        scan. Twirp-style JSON body so clients surface a reason."""
        body = json.dumps({
            "code": "resource_exhausted" if s.http_code == 429
            else "unavailable",
            "msg": f"scan shed: {s.reason}",
        }).encode()
        self.send_response(s.http_code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Retry-After",
                         str(max(1, int(s.retry_after_s + 0.999))))
        self.send_header("Content-Length", str(len(body)))
        if self._trace_id:
            self.send_header(TRACE_HEADER, self._trace_id)
        # a shed response still tells the tenant what it cost them:
        # pure queue ms (the router's fleet aggregator sums it across
        # the failover hops that eventually served the scan)
        led = _cost.active()
        if led is not None:
            self.send_header(COST_HEADER, led.header_json())
        self.end_headers()
        self.wfile.write(body)

    def _scan_admitted(self, req: dict, sbom: bool = False):
        """Scan behind graftguard admission: bounded concurrency,
        bounded queue time, per-request deadline from
        X-Trivy-Deadline-Ms — a handler thread is never parked past
        the point its client has given up. ScanSBOM (`sbom=True`)
        shares every seam — admission, shed accounting, rpc.scan
        failpoint, SLO, cost settle — and differs only in the decode
        step ahead of the Scan tail."""
        st = self.state
        if st.draining:
            # graceful drain: no NEW scans once the shutdown signal
            # landed — shed exactly like admission overload so clients
            # back off to another replica (or retry after the restart)
            from ..metrics import METRICS
            s = Shed("server draining", 503, st.drain_retry_after_s)
            METRICS.inc("trivy_tpu_requests_shed_total")
            led = _cost.active()
            SLO.observe_scan(
                0.0, "shed",
                tenant=_cost.TENANTS.resolve(led.tenant)
                if led is not None else None)
            if led is not None:
                led.outcome = "shed"
            _log.warning("scan shed (draining): 503 Retry-After=%ds",
                         int(s.retry_after_s))
            return self._shed_response(s)
        deadline = None
        hdr = self.headers.get(DEADLINE_HEADER)
        if hdr:
            try:
                deadline = Deadline(max(float(hdr), 0.0) / 1e3)
            except ValueError:
                deadline = None  # unparseable header: no deadline
        led = _cost.active()
        # graftfair: quota state keys on the CLAMPED aggregator label,
        # never the raw header — a cardinality bomb of distinct names
        # folds into "other" and shares ONE bucket. System work (no
        # ledger installed) passes tenant=None and is quota-exempt
        qlabel = (_cost.TENANTS.resolve(led.tenant)
                  if led is not None else None)
        # graftcost: time parked in the admission queue is queue ms —
        # kept distinct from service ms so a tenant whose scans are
        # QUEUED reads differently from one whose scans are SLOW.
        # Charged on the shed path too (the wait really happened)
        t_adm = time.perf_counter()
        try:
            with span("server.admission"):
                st.admission.admit(deadline, tenant=qlabel)
        except Shed as s:
            _cost.charge_queue_ms(
                (time.perf_counter() - t_adm) * 1e3, ledger=led)
            if led is not None:
                led.outcome = "shed"
            _log.warning("scan shed (%s): %d Retry-After=%ds",
                         s.reason, s.http_code, int(s.retry_after_s))
            # shed-aware SLO accounting: a 429/503 is load the
            # deployment refused on purpose — availability's
            # denominator grows, its error count does not
            SLO.observe_scan(0.0, "shed", tenant=qlabel)
            return self._shed_response(s)
        _cost.charge_queue_ms((time.perf_counter() - t_adm) * 1e3,
                              ledger=led)
        try:
            failpoint("rpc.scan")
            if sbom:
                return self._scan_sbom(req)
            return self._scan(req)
        except KeyError:
            raise   # 400 invalid_argument: the client's error
        except Exception:
            if led is not None:
                led.outcome = "error"
            SLO.observe_scan(
                0.0, "error",
                tenant=_cost.TENANTS.resolve(led.tenant)
                if led is not None else None)
            raise
        finally:
            st.admission.release(tenant=qlabel)

    def _scan_sbom(self, req: dict):
        """graftbom ingress: one supervised decode into a content-
        addressed blob, then the UNCHANGED Scan tail. inspect() never
        raises for the document's fault — hostile input lands as an
        annotated partial result, not a 5xx, and not a breaker
        charge. The client-stamped artifact_id only steered router
        affinity; the blob identity is the server-computed document
        digest either way (the two agree for honest clients)."""
        import base64

        from ..sbom.artifact import SBOMArtifact
        raw = req.get("document") or b""
        if isinstance(raw, str):
            # JSON-mode bodies carry the document base64-encoded;
            # fall back to literal text for hand-rolled callers
            try:
                raw = base64.b64decode(raw, validate=True)
            except (ValueError, TypeError):
                raw = raw.encode()
        ref = SBOMArtifact(raw, self.state.cache,
                           name=req.get("target", ""),
                           opts=self.state.sbom_opts).inspect()
        return self._scan({
            "target": req.get("target", "") or ref.name,
            "artifact_id": ref.id,
            "blob_ids": ref.blob_ids,
            "options": req.get("options") or {},
        })

    def _scan(self, req: dict):
        import time

        from ..metrics import METRICS
        opts_j = req.get("options") or {}
        opts = T.ScanOptions(
            scanners=tuple(opts_j.get("scanners") or ("vuln",)),
            pkg_types=tuple(opts_j.get("vuln_type") or ("os", "library")),
            list_all_packages=bool(opts_j.get("list_all_packages")),
        )
        t0 = time.perf_counter()
        # scanner + db version captured together: the header must name
        # the table that produced THIS answer, even when a hot swap
        # lands mid-scan (the reply helpers stamp it)
        scanner, self._db_version = self.state.scanner_with_version()
        results, os_info = scanner.scan(
            req.get("target", ""), req.get("artifact_id", ""),
            req.get("blob_ids") or [], opts)
        elapsed = time.perf_counter() - t0
        METRICS.inc("trivy_tpu_scans_total")
        METRICS.inc("trivy_tpu_scan_seconds_total", elapsed)
        METRICS.observe("trivy_tpu_scan_latency_seconds", elapsed)
        led = _cost.active()
        if led is not None:
            led.outcome = "ok"
        # per-tenant burn window keyed by the CLAMPED label — raw
        # header values never become metric labels
        SLO.observe_scan(
            elapsed, "ok",
            tenant=_cost.TENANTS.resolve(led.tenant)
            if led is not None else None)
        _log.debug("scan %s: %d results in %.1fms",
                   req.get("target", ""), len(results), elapsed * 1e3)
        # one server.encode span: the response build, its bytes and
        # the write
        with span("server.encode"):
            if self._is_proto:
                from .convert import results_to_proto
                from .protowire import encode_msg
                return self._send(200, "application/protobuf", encode_msg(
                    results_to_proto(results, os_info), "ScanResponse"))
            self._send(200, "application/json", json.dumps({
                "os": {"family": os_info.family, "name": os_info.name,
                       "eosl": os_info.eosl},
                "results": [_result_to_json(r) for r in results],
            }).encode())


def drain_then_shutdown(httpd, state: ServerState,
                        grace_s: float = 10.0) -> None:
    """Graceful shutdown: stop admitting Scans (503 + Retry-After),
    wait (bounded) for in-flight requests to finish through the
    generation counts, then stop the accept loop. serve() wires
    SIGTERM/SIGINT here — a restart mid-load completes what the
    admission queue holds instead of dropping it. Runs off the signal
    handler on its own thread; callers under test drive it directly."""
    _log.warning("drain: admission stopped; waiting up to %.1fs for "
                 "%d in-flight request(s)", grace_s, state.inflight)
    state.begin_drain()
    if not state.drain(grace_s):
        _log.warning("drain: grace period expired with %d request(s) "
                     "still in flight; shutting down anyway",
                     state.inflight)
    httpd.shutdown()


def install_drain_handlers(httpd, state, grace_s: float) -> bool:
    """SIGTERM/SIGINT → graceful drain (main thread only — background
    servers in tests own their shutdown). → True when installed."""
    import signal

    def _on_signal(signum, frame):
        # the handler must return immediately; the drain wait runs on
        # its own thread and ends by stopping the accept loop
        # lint: allow(TPU112) reason=signal-time drain thread; the process is exiting and the drain ends by stopping the accept loop the main thread sits in
        threading.Thread(target=drain_then_shutdown,
                         args=(httpd, state, grace_s),
                         name="graceful-drain", daemon=True).start()

    try:
        signal.signal(signal.SIGTERM, _on_signal)
        signal.signal(signal.SIGINT, _on_signal)
        return True
    except ValueError:
        return False   # not the main thread


def serve(host: str, port: int, table, cache_dir: str, token: str = "",
          ready_event: threading.Event | None = None,
          cache_backend: str = "fs", trace_path: str = "",
          detect_opts=None, admission=None, mesh_opts=None,
          drain_grace_s: float = 10.0, memo_backend="",
          redetect_opts=None, sbom_opts=None):
    """`trace_path` arms graftscope recording for the server's
    lifetime and dumps the Chrome trace-event JSON there on shutdown
    (the CLI's `server --trace FILE`). `detect_opts` (SchedOptions)
    tunes detectd — coalesce wait, in-flight pair bound, warmup;
    `admission` (AdmissionOptions) bounds the graftguard scan queue;
    `mesh_opts` (MeshOptions) shards detection over a device mesh with
    meshguard per-device fault domains; `drain_grace_s` bounds the
    SIGTERM/SIGINT graceful drain (--drain-grace-ms)."""
    if trace_path:
        from ..obs import COLLECTOR
        COLLECTOR.enable()
    state = ServerState(table, cache_dir, token, cache_backend,
                        detect_opts=detect_opts, admission=admission,
                        mesh_opts=mesh_opts, memo_backend=memo_backend,
                        redetect_opts=redetect_opts,
                        sbom_opts=sbom_opts)
    # per-server Handler subclass: `state` must not live on the shared
    # base class, or two in-process replicas (the fleet tests/bench)
    # would serve each other's caches and scanners
    handler = type("Handler", (Handler,), {"state": state})
    httpd = ScanServer((host, port), handler)
    install_drain_handlers(httpd, state, drain_grace_s)
    if ready_event is not None:
        ready_event.set()
    try:
        httpd.serve_forever()
    finally:
        httpd.server_close()
        state.close()
        if trace_path:
            from ..obs import COLLECTOR, write_chrome_trace
            COLLECTOR.disable()
            write_chrome_trace(trace_path)
            _log.warning("graftscope trace written to %s", trace_path)
    return httpd


def serve_background(host: str, port: int, table, cache_dir: str,
                     token: str = "", cache_backend: str = "fs",
                     detect_opts=None, admission=None, mesh_opts=None,
                     memo_backend="", redetect_opts=None,
                     sbom_opts=None):
    """Start in a daemon thread; returns (httpd, state) once listening.
    Callers own shutdown: `httpd.shutdown()` then `state.close()` (the
    detect engine's worker threads are non-daemon). `cache_backend`
    picks the fanal cache (fs | memory | redis:// | s3://) — fleet
    tests and the bench point several replicas at one shared
    redis/s3 URL."""
    state = ServerState(table, cache_dir, token, cache_backend,
                        detect_opts=detect_opts,
                        admission=admission,
                        mesh_opts=mesh_opts,
                        memo_backend=memo_backend,
                        redetect_opts=redetect_opts,
                        sbom_opts=sbom_opts)
    handler = type("Handler", (Handler,), {"state": state})
    httpd = ScanServer((host, port), handler)
    # lint: allow(TPU112) reason=serve loop exits when the caller runs httpd.shutdown() (documented caller-owned shutdown contract)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    return httpd, state
