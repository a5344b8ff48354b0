"""Local scan driver: the orchestration the reference runs per target in
pkg/scanner/local/scan.go — ApplyLayers → OS/lang-package detection →
FillInfo → result assembly. Detection runs as batched device joins.

This object is the third `scanner.Driver` implementation the survey calls
for (pkg/scanner/scan.go:131-134): same (target, artifact_id, blob_ids,
options) → (results, os) contract, but the inner loops are TPU programs.
"""

from __future__ import annotations

import datetime as dt
from typing import Optional

from typing import TYPE_CHECKING

from . import types as T
from .db.table import AdvisoryTable
from .detect.engine import BatchDetector
from .detect.fill import fill_info
from .detect.langpkg import LangpkgScanner
from .detect.ospkg import OspkgScanner
from .fanal.applier import apply_layers
from .obs import cost as _cost
from .obs import ensure_trace, span

if TYPE_CHECKING:
    from .detect.sched import SchedOptions


class LocalScanner:
    def __init__(self, cache, table: AdvisoryTable,
                 sched: "SchedOptions | None" = None,
                 mesh=None, mesh_guard=None, memo=None):
        self.cache = cache
        self.table = table
        # graftmemo: content-addressed detection-result memo (an open
        # fleet.memo.MemoStore, shared across replicas on a common
        # backend). Per scan unit, a (blob digest, db_version) entry
        # replays the stored hits instead of dispatching the device
        # join; misses detect normally and publish their result.
        self.memo = memo
        # mesh mode (server --mesh-devices): the detect step shards
        # over a dp×db device mesh, supervised per-device by meshguard.
        # `mesh="host"` is the zero-survivor degraded detector — same
        # surface, every join host-side — so the meshguard grow path
        # can swap a real mesh back in through the same drain.
        if mesh is not None:
            from .parallel.mesh import MeshDetector
            self.detector = MeshDetector(
                table, None if mesh == "host" else mesh,
                guard=mesh_guard)
        else:
            self.detector = BatchDetector(table)
        # detectd: when the owner passes SchedOptions (the scan server
        # does by default), detection routes through the shared
        # coalescing scheduler so concurrent requests merge into
        # shared device dispatches
        self.sched = None
        if sched is not None and sched.enabled:
            from .detect.sched import DispatchScheduler
            self.sched = DispatchScheduler(self.detector, sched)
            if sched.warmup:
                self.detector.warmup(sched.warmup_max_pairs)
        self.ospkg = OspkgScanner(self.detector)
        self.langpkg = LangpkgScanner(self.detector)

    def close(self) -> None:
        """Join detectd and the detector's worker threads (idempotent).
        Owners that replace or retire a scanner (ServerState.swap_table,
        server shutdown) must call this — the pools' threads are
        non-daemon."""
        if self.sched is not None:
            self.sched.close()
        self.detector.close()

    def scan(self, target: str, artifact_id: str, blob_ids: list[str],
             options: Optional[T.ScanOptions] = None,
             now: Optional[dt.datetime] = None
             ) -> tuple[list[T.Result], T.OS]:
        return self.scan_many([(target, artifact_id, blob_ids)],
                              options, now)[0]

    def scan_many(self, items: list[tuple[str, str, list[str]]],
                  options: Optional[T.ScanOptions] = None,
                  now: Optional[dt.datetime] = None
                  ) -> list[tuple[list[T.Result], T.OS]]:
        """Scan many targets with ONE pipelined device dispatch.

        Every target's OS-package and per-application query batches are
        prepared host-side first, then a single detect_many call
        overlaps host prep, device joins, and transfers across ALL
        targets — the cross-image batching the k8s cluster sweep uses
        where the reference loops runner.ScanImage per image
        (pkg/k8s/scanner/scanner.go:163-175)."""
        # one trace per scan call (unless the server already stamped a
        # per-RPC id): every span and log line below carries it
        with ensure_trace(), span("scan", targets=len(items)):
            return self._scan_many_traced(items, options, now)

    def _scan_many_traced(self, items, options, now):
        options = options or T.ScanOptions()
        details = []
        item_blobs = []   # per item: the fetched BlobInfos (graftmemo
        # attribution reads them; order matches the item's blob_ids)
        with span("scan.apply_layers", targets=len(items)):
            for target, artifact_id, blob_ids in items:
                blobs = []
                for bid in blob_ids:
                    blob = self.cache.get_blob(bid)
                    if blob is None:
                        raise KeyError(f"missing blob {bid} in cache "
                                       f"(artifact {artifact_id})")
                    blobs.append(blob)
                detail = apply_layers(blobs)
                # OS-independent packages without a detected OS report
                # Family "none" (reference local/scan.go:66-71)
                if not detail.os.detected and detail.packages:
                    detail.os = T.OS(family=T.OSFamily.NONE)
                # dev dependencies are removed unless --include-dev-deps
                # (reference local/scan.go:109-111 excludeDevDeps)
                if not options.include_dev_deps:
                    for app in detail.applications:
                        app.packages = [p for p in app.packages
                                        if not p.dev]
                details.append(detail)
                item_blobs.append(blobs)

        # phase 1: build every query batch (host)
        units = []    # (item_idx, "os" | app, finish)
        batches = []
        with span("scan.build_queries") as sp:
            if T.Scanner.VULN in options.scanners:
                for idx, detail in enumerate(details):
                    if detail.os.detected and "os" in options.pkg_types:
                        qs, fin = self.ospkg.prepare(
                            detail.os, detail.repository,
                            detail.packages, now=now)
                        if fin is not None:  # family supported
                            units.append((idx, "os", fin))
                            batches.append(qs)
                    if "library" in options.pkg_types:
                        for app in sorted(detail.applications,
                                          key=lambda a: (a.file_path,
                                                         a.type)):
                            qs, fin = self.langpkg.prepare_app(app)
                            units.append((idx, app, fin))
                            batches.append(qs)
            sp.attrs.update(batches=len(batches),
                            queries=sum(len(b) for b in batches))

        # graftmemo: per unit, an attributable (blob digest,
        # db_version) entry whose query digest matches replays its
        # stored hits — the device join runs only for the live
        # remainder, and live results publish back so the next scan
        # (on any replica sharing the backend) hits. A degraded memo
        # backend silently falls back to a full live dispatch.
        session = None
        replayed: dict[int, list] = {}
        store_tokens: dict[int, tuple] = {}
        if self.memo is not None and units:
            from .fleet.memo import MemoSession
            session = MemoSession(self.memo,
                                  self.table.content_digest())
            with span("scan.memo", units=len(units)) as sp:
                for u_i, ((idx, unit, _fin), qs) in enumerate(
                        zip(units, batches)):
                    hits, token = session.consult(
                        unit, qs, details[idx], item_blobs[idx],
                        items[idx][2])
                    if hits is not None:
                        replayed[u_i] = hits
                    elif token is not None:
                        store_tokens[u_i] = token
                sp.attrs.update(replayed=len(replayed))
                # graftcost: memo replays are work AVOIDED — priced
                # per replayed unit's query count at the EWMA device
                # exchange rate (an estimate, kept out of the
                # conservation sums) and credited to this tenant
                if replayed:
                    _cost.note_work_avoided(
                        sum(len(batches[i]) for i in replayed))

        # phase 2: one pipelined dispatch across all live targets
        # (device). Server mode routes through detectd so concurrent
        # requests coalesce, recorded or not: a trace times the
        # program that serves.
        hit_lists: list = [replayed.get(i) for i in range(len(batches))]
        live = [i for i in range(len(batches)) if i not in replayed]
        if live:
            from .resilience import GUARD
            live_batches = [batches[i] for i in live]
            with span("scan.detect", batches=len(live_batches)):
                # a blameless caller (redetectd's background replay)
                # takes the direct path too: merging its queries into
                # a live detectd dispatch would make live traffic
                # share fate — and breaker charges — with guest work
                if self.sched is not None \
                        and not GUARD.blameless_active():
                    live_hits = self.sched.detect_many(live_batches)
                else:
                    live_hits = self.detector.detect_many(live_batches)
            for u_i, hits in zip(live, live_hits):
                hit_lists[u_i] = hits
        if session is not None:
            for u_i, token in store_tokens.items():
                session.record(token, hit_lists[u_i])
            session.flush()

        # phase 3: assemble per-target results (host)
        with span("scan.assemble_results"):
            vuln_results: dict[int, list[T.Result]] = {}
            for (idx, unit, finish), hits in zip(units, hit_lists):
                target = items[idx][0]
                detail = details[idx]
                if unit == "os":
                    vulns, eosl = finish(hits)
                    if eosl:
                        detail.os.eosl = True
                    # a supported, detected OS always yields a result —
                    # even with zero packages (ospkg/scan.go:42-69)
                    keep = True
                    res = self._vuln_result(
                        vulns,
                        target=f"{target} ({detail.os.family} "
                               f"{detail.os.name})",
                        clazz=T.ResultClass.OS_PKGS,
                        rtype=detail.os.family,
                        packages=detail.packages, options=options)
                else:
                    app = unit
                    vulns = finish(hits)
                    keep = bool(vulns) or options.list_all_packages
                    res = self._vuln_result(
                        vulns,
                        target=app.file_path or
                        PKG_TARGETS.get(app.type, app.type),
                        clazz=T.ResultClass.LANG_PKGS, rtype=app.type,
                        packages=app.packages, options=options)
                if keep:
                    vuln_results.setdefault(idx, []).append(res)

            return [
                self._finish_item(items[idx][0], details[idx],
                                  vuln_results.get(idx, []), options)
                for idx in range(len(items))
            ]

    def _vuln_result(self, vulns, target: str, clazz, rtype,
                     packages, options: T.ScanOptions) -> T.Result:
        """Shared result assembly: FillInfo enrichment, severity sort,
        optional package listing."""
        fill_info(vulns, self.table.details)
        vulns.sort(key=_vuln_sort_key)
        res = T.Result(target=target, clazz=clazz, type=rtype,
                       vulnerabilities=vulns)
        if options.list_all_packages:
            res.packages = sorted(packages,
                                  key=lambda p: (p.name, p.version))
        return res

    def _finish_item(self, target: str, detail, results: list[T.Result],
                     options: T.ScanOptions
                     ) -> tuple[list[T.Result], T.OS]:
        os_info = detail.os

        # fanald partial-result degradation: a layer the ingest
        # pipeline had to degrade (budget trip, hostile input, stage
        # timeout) carries structured annotations — surface them as a
        # dedicated result so the report says WHAT is missing and why
        # instead of silently under-reporting (same contract /healthz
        # exposes process-wide)
        if detail.ingest_errors:
            results.append(T.Result(
                target="Ingest Degradations",
                clazz=T.ResultClass.INGEST,
                ingest_errors=list(detail.ingest_errors),
            ))

        if T.Scanner.MISCONF in options.scanners or \
                "config" in options.scanners:  # raw "config" kept for
            # callers bypassing cli.normalize_scanners (server RPC)
            for mc in detail.misconfigurations:
                if not mc.failures and not mc.successes and \
                        not mc.exceptions:
                    continue
                results.append(T.Result(
                    target=mc.file_path,
                    clazz=T.ResultClass.CONFIG,
                    type=mc.file_type,
                    misconf_summary=T.MisconfSummary(
                        successes=mc.successes,
                        failures=len(mc.failures),
                        exceptions=mc.exceptions),
                    misconfigurations=sorted(
                        mc.failures, key=lambda f: (f.id, f.message)),
                ))

        if T.Scanner.SECRET in options.scanners:
            for sec in detail.secrets:
                results.append(T.Result(
                    target=sec.file_path,
                    clazz=T.ResultClass.SECRET,
                    secrets=sec.findings,
                ))

        if T.Scanner.LICENSE in options.scanners:
            # reference scanLicenses (local/scan.go:280-360): one
            # result per group, emitted even when empty
            from .licensing import scan_license_name
            os_lics = []
            for pkg in detail.packages:
                for lic in pkg.licenses:
                    cat, sev = scan_license_name(lic)
                    os_lics.append(T.DetectedLicense(
                        severity=sev, category=cat, pkg_name=pkg.name,
                        name=lic, confidence=1.0))
            results.append(T.Result(
                target="OS Packages", clazz=T.ResultClass.LICENSE,
                licenses=os_lics))
            for app in detail.applications:
                lang = []
                for lib in app.packages:
                    for lic in lib.licenses:
                        cat, sev = scan_license_name(lic)
                        lang.append(T.DetectedLicense(
                            severity=sev, category=cat,
                            pkg_name=lib.name, name=lic,
                            file_path=lib.file_path or app.file_path,
                            confidence=1.0))
                results.append(T.Result(
                    target=app.file_path or
                    PKG_TARGETS.get(app.type, app.type),
                    clazz=T.ResultClass.LICENSE, licenses=lang))
            results.append(T.Result(
                target="Loose File License(s)",
                clazz=T.ResultClass.LICENSE_FILE,
                licenses=sorted(detail.licenses,
                                key=lambda l: (l.file_path, l.name)),
            ))

        # extension-module post-scan hooks (reference post.Scan at
        # pkg/scanner/local/scan.go:162; custom resources travel as a
        # ClassCustom result like module.go PostScan:478)
        from .module import apply_post_scan, loaded_modules
        if loaded_modules():
            if detail.custom_resources:
                results.append(T.Result(
                    target="Custom",
                    clazz=T.ResultClass.CUSTOM,
                    custom_resources=detail.custom_resources,
                ))
            results = apply_post_scan(results)

        return results, os_info


# friendly targets for aggregated individual-package results
# (reference pkg/scanner/langpkg/scan.go:15-23)
PKG_TARGETS = {
    "python-pkg": "Python", "conda-pkg": "Conda", "gemspec": "Ruby",
    "node-pkg": "Node.js", "jar": "Java", "k8s": "Kubernetes",
    "kubernetes": "Kubernetes",
}


def _vuln_sort_key(v: T.DetectedVulnerability):
    """(pkg name, installed version, severity desc, vuln id, pkg path) —
    reference types.BySeverity (pkg/types/vulnerability.go:42-58)."""
    sev = T.SEVERITIES.index(v.severity) if v.severity in T.SEVERITIES else 0
    return (v.pkg_name, v.installed_version, -sev, v.vulnerability_id,
            v.pkg_path)
