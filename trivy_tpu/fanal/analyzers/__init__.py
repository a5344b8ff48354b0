"""Analyzer registry & dispatch.

Mirrors pkg/fanal/analyzer/analyzer.go: each analyzer declares the paths
it needs (`required`) and produces a partial AnalysisResult; the group
merges results. Analyzer versions participate in cache keys so cached
blobs invalidate when an analyzer changes
(pkg/fanal/cache/key.go:18-60)."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from ... import types as T


@dataclass
class AnalysisResult:
    os: Optional[T.OS] = None
    repository: Optional[T.Repository] = None
    package_infos: list = field(default_factory=list)
    applications: list = field(default_factory=list)
    misconfigurations: list = field(default_factory=list)
    secrets: list = field(default_factory=list)
    licenses: list = field(default_factory=list)
    # files owned by the OS package manager; consumed by the system-file
    # filter post-handler (reference analyzer.AnalysisResult
    # SystemInstalledFiles)
    system_installed_files: list = field(default_factory=list)
    build_info: object = None  # Red Hat content sets / nvr+arch
    custom_resources: list = field(default_factory=list)  # module output
    # path → sha256 digest of unpackaged executables; consumed by the
    # unpackaged-Rekor post-handler (reference AnalysisResult.Digests)
    digests: dict = field(default_factory=dict)

    def merge(self, other: "AnalysisResult"):
        if other is None:
            return
        if other.os is not None:
            if self.os is None:
                self.os = other.os
            else:
                self.os.merge(other.os)
        if other.repository is not None:
            self.repository = other.repository
        self.package_infos.extend(other.package_infos)
        self.applications.extend(other.applications)
        self.misconfigurations.extend(other.misconfigurations)
        self.secrets.extend(other.secrets)
        self.licenses.extend(other.licenses)
        self.system_installed_files.extend(other.system_installed_files)
        self.custom_resources.extend(other.custom_resources)
        self.digests.update(other.digests)
        if other.build_info is not None:
            if self.build_info is None:
                self.build_info = other.build_info
            else:  # merge content sets with nvr/arch (analyzer.go Merge)
                bi, obi = self.build_info, other.build_info
                bi.content_sets = bi.content_sets or obi.content_sets
                bi.nvr = bi.nvr or obi.nvr
                bi.arch = bi.arch or obi.arch


class Analyzer:
    """Base: subclasses set `name` and `version` and implement
    required(path) / analyze(path, content)."""
    name = "base"
    version = 1

    def required(self, path: str, size: int = -1) -> bool:
        raise NotImplementedError

    def analyze(self, path: str, content: bytes) -> Optional[AnalysisResult]:
        raise NotImplementedError


class PostAnalyzer:
    """Multi-file analyzer run after the walk over all collected files
    (reference pkg/fanal/analyzer PostAnalyzer over a composite FS) —
    used where one result needs several files, e.g. a terraform
    module."""
    name = "base-post"
    version = 1

    def required(self, path: str, size: int = -1) -> bool:
        raise NotImplementedError

    def post_analyze(self, files: dict) -> Optional[AnalysisResult]:
        raise NotImplementedError


_REGISTRY: dict[str, type] = {}
_POST_REGISTRY: dict[str, type] = {}


def register(cls):
    _REGISTRY[cls.name] = cls
    return cls


def register_post(cls):
    _POST_REGISTRY[cls.name] = cls
    return cls


def all_analyzers() -> dict[str, type]:
    _ensure_loaded()
    return dict(_REGISTRY)


# extension modules (trivy_tpu.module) — WASM-analyzer analog; the
# loaded set participates in dispatch and cache-key versions exactly
# like built-in analyzers (reference pkg/module Register hooks into the
# analyzer registry)
_MODULE_ANALYZERS: list = []


def set_module_analyzers(mods: list) -> None:
    global _MODULE_ANALYZERS
    _MODULE_ANALYZERS = list(mods)


def _ensure_loaded():
    from . import (apk, binaries, dpkg, executable,  # noqa: F401
                   license_file, lockfiles, lockfiles_extra, misconf,
                   os_release, python, redhat, rpm, sbom)


# analyzers that are opt-in everywhere (reference: license scanning is
# behind --license-full); excluded from EVERY AnalyzerGroup unless the
# caller lists them in `enabled`
OPTIN_ANALYZERS = ("license-file", "executable")


class AnalyzerGroup:
    def __init__(self, disabled: tuple = (), enabled: tuple = (),
                 file_patterns: tuple = ()):
        _ensure_loaded()
        off = set(disabled) | (set(OPTIN_ANALYZERS) - set(enabled))
        self.analyzers = [cls() for name, cls in sorted(_REGISTRY.items())
                          if name not in off]
        self.post_analyzers = [
            cls() for name, cls in sorted(_POST_REGISTRY.items())
            if name not in off]
        # --file-patterns "analyzer:regex": a matching path is routed
        # to that analyzer even when its own required() declines
        # (reference analyzer.go:321-341, filePatternMatch:508-515)
        import re as _re
        self._patterns: dict[str, list] = {}
        for raw in file_patterns or ():
            name, sep, pattern = str(raw).partition(":")
            if not sep:
                raise ValueError(
                    f"invalid file pattern {raw!r} "
                    '(expected "analyzerType:regex")')
            try:
                rx = _re.compile(pattern)
            except _re.error as e:
                raise ValueError(
                    f"invalid file pattern regex {pattern!r}: {e}") \
                    from e
            self._patterns.setdefault(name, []).append(rx)

    def _wants(self, a, path: str, size: int) -> bool:
        if any(rx.search(path) for rx in
               self._patterns.get(a.name, ())):
            return True
        return a.required(path, size)

    def versions(self) -> dict[str, int]:
        """name → version, for cache keys."""
        out = {a.name: a.version for a in self.analyzers}
        out.update({a.name: a.version for a in self.post_analyzers})
        out.update({f"module:{m.name}": m.version
                    for m in _MODULE_ANALYZERS})
        return out

    def required(self, path: str, size: int = -1) -> bool:
        return any(self._wants(a, path, size) for a in self.analyzers) \
            or any(m.required(path) for m in _MODULE_ANALYZERS)

    def post_required(self, path: str, size: int = -1) -> bool:
        return any(self._wants(a, path, size)
                   for a in self.post_analyzers)

    def analyze_file(self, path: str, content: bytes,
                     result: AnalysisResult) -> None:
        # graftwatch attribution: one span per analyzer DISPATCH (an
        # analyzer that actually ran on this file), not per candidate
        # — required() gates keep the span count proportional to real
        # work, and a per-phase breakdown aggregates these into an
        # analyzer_ms phase
        from ...obs import span
        for a in self.analyzers:
            if self._wants(a, path, len(content)):
                with span("fanal.analyze", analyzer=a.name,
                          path=path, bytes=len(content)):
                    r = a.analyze(path, content)
                if r is not None:
                    result.merge(r)
        for m in _MODULE_ANALYZERS:
            if m.required(path):
                try:
                    with span("fanal.analyze",
                              analyzer=f"module:{m.name}", path=path):
                        data = m.analyze(path, content)
                except Exception:
                    continue
                if data:
                    result.custom_resources.append({
                        "Type": m.name, "FilePath": path,
                        "Data": data})

    def analyze_batch(self, files: list, on_error=None) -> list:
        """Batched dispatch for the fanald pipeline: ONE pass per
        analyzer (file-kind) over many files — detectd's coalescing
        pattern applied to ingest, so a 10k-file layer costs one
        required()-routing sweep per analyzer instead of one analyzer
        sweep per file. `files` is [(path, content)]; → a per-file
        AnalysisResult (or None), each file's partial results merged
        in analyzer-registry order — merging the returned list in file
        order is therefore bit-identical to calling analyze_file per
        file in that order (AnalysisResult.merge is associative over
        that grouping).

        `on_error(analyzer_name, path, exc)` receives per-analyzer
        failures on hostile content (the pipeline annotates them and
        keeps the rest of the batch); without it they propagate, the
        serial analyze_file contract."""
        from ...obs import span
        results: list = [None] * len(files)

        def _merge(i, r):
            if r is None:
                return
            if results[i] is None:
                results[i] = AnalysisResult()
            results[i].merge(r)

        for a in self.analyzers:
            wanted = [(i, p, c) for i, (p, c) in enumerate(files)
                      if self._wants(a, p, len(c))]
            if not wanted:
                continue
            with span("fanal.analyze", analyzer=a.name, batched=True,
                      files=len(wanted),
                      bytes=sum(len(c) for _, _, c in wanted)):
                for i, p, c in wanted:
                    try:
                        _merge(i, a.analyze(p, c))
                    except Exception as e:  # noqa: BLE001 — contained
                        if on_error is None:
                            raise
                        on_error(a.name, p, e)
        for m in _MODULE_ANALYZERS:
            wanted = [(i, p, c) for i, (p, c) in enumerate(files)
                      if m.required(p)]
            if not wanted:
                continue
            with span("fanal.analyze", analyzer=f"module:{m.name}",
                      batched=True, files=len(wanted)):
                for i, p, c in wanted:
                    try:
                        data = m.analyze(p, c)
                    except Exception:
                        continue
                    if data:
                        if results[i] is None:
                            results[i] = AnalysisResult()
                        results[i].custom_resources.append({
                            "Type": m.name, "FilePath": p,
                            "Data": data})
        return results

    def post_analyze(self, files: dict,
                     result: AnalysisResult) -> None:
        if not files:
            return
        from ...obs import span
        for a in self.post_analyzers:
            subset = {p: c for p, c in files.items()
                      if self._wants(a, p, -1)}
            if subset:
                with span("fanal.analyze", analyzer=a.name,
                          post=True, files=len(subset)):
                    r = a.post_analyze(subset)
                if r is not None:
                    result.merge(r)


# analyzer groups disabled per target kind (reference run.go:167-224:
# image disables lockfiles; fs disables individual-package + SBOM;
# rootfs disables lockfiles; repo disables OS + individual + SBOM;
# const.go TypeIndividualPkgs / TypeLockfiles / TypeOSes)
INDIVIDUAL_PKG_ANALYZERS = ("gemspec", "node-pkg", "conda-pkg",
                            "python-pkg", "gobinary", "jar", "rustbinary")
LOCKFILE_ANALYZERS = ("bundler", "npm", "yarn", "pnpm", "pip", "pipenv",
                      "poetry", "gomod", "pom", "conan",
                      "gradle-lockfile", "cocoapods", "swift", "pub",
                      "mix-lock")
OS_ANALYZERS = ("os-release", "alpine", "amazonlinux", "mariner",
                "debian", "redhatbase", "ubuntu", "apk", "dpkg", "rpm",
                "rpmqa", "apk-repo", "redhat-content-manifest",
                "redhat-dockerfile")
