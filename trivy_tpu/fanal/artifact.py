"""Artifacts: things that can be inspected into (artifact_id, blob_ids)
with per-blob analysis memoized in the cache.

Mirrors pkg/fanal/artifact: image archives (docker-save tarballs,
artifact/image/archive path), local filesystems (artifact/local/fs.go).
Daemon/registry image sources are host-IO plumbing added later; archives
are the benchmarkable ingest path (BASELINE.md config 3 uses tarballs)."""

from __future__ import annotations

import gzip
import hashlib
import io
import json
import os
import tarfile
from dataclasses import dataclass, field
from typing import Optional

from .. import types as T
from ..obs import span
from .analyzers import AnalyzerGroup
from .cache import cache_key
from .walker import DEFAULT_SECRET_CONFIG, blob_info, walk_fs, walk_layer_tar


@dataclass
class ArtifactReference:
    name: str
    type: str
    id: str
    blob_ids: list
    image_metadata: Optional[T.Metadata] = None
    secret_files: dict = field(default_factory=dict)  # blob_id → [(path, bytes)]


class _ImageInspectMixin:
    """Shared image-source assembly: cache keys (analyzer versions +
    custom-check fingerprints), the missing-layer walk, and metadata —
    used by docker-archive, OCI-layout, and streaming-registry paths
    so cache/secret handling cannot drift between them."""

    @staticmethod
    def _created_by(config: dict, diff_ids: list) -> list:
        history = [h for h in config.get("history", [])
                   if not h.get("empty_layer")]
        created_by = [h.get("created_by", "") for h in history]
        return created_by + [""] * (len(diff_ids) - len(created_by))

    def _image_keys(self, image_id: str, diff_ids: list):
        versions = self.group.versions()
        opts = {"scanners": sorted(self.scanners)}
        # skip filters change blob content → they are part of the key
        # (reference artifact option hashing)
        from .walker import normalize_skip_globs
        sf = normalize_skip_globs(getattr(self, "skip_files", ()))
        sd = normalize_skip_globs(getattr(self, "skip_dir_globs", ()))
        if sf:
            opts["skip_files"] = sorted(sf)
        if sd:
            opts["skip_dirs"] = sorted(sd)
        from ..misconf import custom_checks_fingerprint
        fp = custom_checks_fingerprint()
        if fp:
            opts["config_checks"] = fp
        return (cache_key(image_id, versions, opts),
                [cache_key(d, versions, opts) for d in diff_ids])

    def _missing_blobs(self, artifact_id: str, blob_ids: list):
        """Cache check with attribution: layer-cache hits short-circuit
        the walk entirely, which at production traffic is the
        difference between re-analyzing a base image and skipping it —
        the span makes that decision visible per artifact."""
        with span("fanal.cache_check", blobs=len(blob_ids)) as sp:
            missing_artifact, missing = self.cache.missing_blobs(
                artifact_id, blob_ids)
            sp.attrs.update(hits=len(blob_ids) - len(missing),
                            misses=len(missing))
            return missing_artifact, missing

    def _ingest_options(self):
        from .pipeline import default_ingest
        return getattr(self, "ingest", None) or default_ingest()

    def _walk_missing_layers(self, diff_ids, blob_ids, created_by,
                             missing, open_layer,
                             layer_digests=None,
                             stream_open=None) -> dict:
        """open_layer(i) → context manager yielding a layer tarfile
        (the serial parity-oracle path). When the fanald pipeline is
        enabled and the source provides `stream_open(i)` — a
        THREAD-SAFE context manager yielding a pipeline.LayerStream —
        missing layers walk concurrently through the supervised
        pipeline instead. `blob_ids` is edited in place for partial
        layers (see _walk_missing_pipelined)."""
        ingest = self._ingest_options()
        if ingest.enabled and stream_open is not None:
            return self._walk_missing_pipelined(
                ingest, diff_ids, blob_ids, created_by, missing,
                stream_open, layer_digests)
        secret_files: dict = {}
        want_secrets = "secret" in self.scanners
        for i, (diff_id, blob_id, cb) in enumerate(
                zip(diff_ids, blob_ids, created_by)):
            if blob_id not in missing:
                continue
            # one span per LAYER walk: the archive-e2e breakdown needs
            # per-layer attribution (layer sizes are wildly skewed in
            # real images — one fat layer dominates the walk)
            with span("fanal.layer_walk", layer=i,
                      diff_id=diff_id) as sp:
                with open_layer(i) as layer_tf:
                    scan = walk_layer_tar(
                        layer_tf, self.group,
                        collect_secrets=want_secrets,
                        secret_config_path=self.secret_config_path,
                        skip_files=getattr(self, "skip_files", ()),
                        skip_dir_globs=getattr(self, "skip_dir_globs",
                                               ()))
                bi = blob_info(scan, diff_id=diff_id, created_by=cb)
                sp.attrs.update(
                    packages=sum(len(p.packages)
                                 for p in bi.package_infos),
                    applications=len(bi.applications))
                if layer_digests:
                    bi.digest = layer_digests[i]
                if want_secrets and scan.secret_files:
                    secret_files[blob_id] = scan.secret_files
                    bi.secrets = self.secret_scanner.scan_files(
                        scan.secret_files)
                self.cache.put_blob(blob_id, bi)
        return secret_files

    def _walk_missing_pipelined(self, ingest, diff_ids, blob_ids,
                                created_by, missing, stream_open,
                                layer_digests) -> dict:
        """fanald: walk every missing layer through the supervised
        streaming pipeline. Complete layers cache under their
        canonical blob id exactly like the serial path; a PARTIAL
        layer caches only under a deterministic salted id
        (pipeline.partial_blob_id) and `blob_ids` is rewritten in
        place to point at it — the canonical key stays missing, so the
        next scan re-walks instead of serving the degraded result
        forever."""
        from .pipeline import (IngestPipeline, LayerTask,
                               partial_blob_id)
        want_secrets = "secret" in self.scanners
        tasks = []
        for i, (diff_id, blob_id, cb) in enumerate(
                zip(diff_ids, blob_ids, created_by)):
            if blob_id not in missing:
                continue
            tasks.append(LayerTask(
                idx=i, diff_id=diff_id, blob_id=blob_id,
                created_by=cb,
                open_stream=(lambda i=i: stream_open(i))))
        if not tasks:
            return {}
        secret_files: dict = {}
        pipe = IngestPipeline(
            self.group, ingest, collect_secrets=want_secrets,
            secret_config_path=self.secret_config_path,
            skip_files=getattr(self, "skip_files", ()),
            skip_dir_globs=getattr(self, "skip_dir_globs", ()))
        from .pipeline import IngestIntegrityError
        try:
            with span("fanal.pipeline", layers=len(tasks)) as sp:
                scans = pipe.run(tasks)
                sp.attrs.update(partial=sum(
                    1 for s in scans.values() if s.partial))
        except IngestIntegrityError as e:
            # surface the original failure (OCIError digest mismatch)
            # exactly like the serial path; nothing was cached
            raise (e.__cause__ or e) from None
        finally:
            pipe.close()
        # finalize in layer order (deterministic output + cache puts)
        finalized = []
        for t in tasks:
            scan = scans[t.idx]
            bi = blob_info(scan, diff_id=t.diff_id,
                           created_by=t.created_by)
            if layer_digests:
                bi.digest = layer_digests[t.idx]
            blob_id = t.blob_id
            if scan.partial:
                blob_id = partial_blob_id(t.blob_id, bi.ingest_errors)
                blob_ids[t.idx] = blob_id
            finalized.append((scan, bi, blob_id))
        # coalesced secrets lane: every missing layer's secret files go
        # through ONE scan_files_many call — one device prefilter
        # launch for the whole image (detectd's coalescing move),
        # where the per-layer calls this replaces rarely crossed the
        # engine's small-batch floor. Per-layer results come back in
        # layer order, bit-identical to per-layer scan_files calls by
        # construction.
        with_secrets = [f for f in finalized
                        if want_secrets and f[0].secret_files]
        if with_secrets:
            per_layer = self.secret_scanner.scan_files_many(
                [scan.secret_files for scan, _bi, _b in with_secrets])
            for (scan, bi, blob_id), secs in zip(with_secrets,
                                                 per_layer):
                secret_files[blob_id] = scan.secret_files
                bi.secrets = secs
        for _scan, bi, blob_id in finalized:
            self.cache.put_blob(blob_id, bi)
        return secret_files

    def _put_artifact_info(self, artifact_id: str, config: dict):
        self.cache.put_artifact(artifact_id, {
            "SchemaVersion": 2,
            "Architecture": config.get("architecture", ""),
            "Created": config.get("created", ""),
            "OS": config.get("os", ""),
        })


class ImageArchiveArtifact(_ImageInspectMixin):
    """docker-save / OCI-archive tarball."""

    def __init__(self, path: str, cache, group: Optional[AnalyzerGroup] = None,
                 scanners: tuple = ("vuln",), secret_scanner=None,
                 secret_config_path: str = DEFAULT_SECRET_CONFIG,
                 skip_files: tuple = (), skip_dirs: tuple = (),
                 ingest=None):
        self.path = path
        self.cache = cache
        self.group = group or AnalyzerGroup()
        self.scanners = scanners
        self.secret_scanner = secret_scanner
        self.secret_config_path = secret_config_path
        self.skip_files = tuple(skip_files)
        self.skip_dir_globs = tuple(skip_dirs)
        # fanald knobs (pipeline.IngestOptions); None = process default
        self.ingest = ingest
        if "secret" in scanners and secret_scanner is None:
            from ..secret import SecretScanner
            self.secret_scanner = SecretScanner()

    def inspect(self) -> ArtifactReference:
        with span("fanal.inspect"), tarfile.open(self.path) as tf:
            names = tf.getnames()
            if "manifest.json" in names:
                return self._inspect_docker_archive(tf)
            if "index.json" in names:
                return self._inspect_oci_layout(tf)
            raise ValueError(f"{self.path}: not a docker/oci image archive")

    def image_digest(self) -> str:
        """sha256 of the raw image config — the digest cosign signs
        attestations against (used by the remote-SBOM rekor shortcut,
        reference pkg/fanal/artifact/image/remote_sbom.go)."""
        with tarfile.open(self.path) as tf:
            names = tf.getnames()
            if "manifest.json" in names:
                manifest = json.load(tf.extractfile("manifest.json"))[0]
                raw = tf.extractfile(manifest["Config"]).read()
                return "sha256:" + hashlib.sha256(raw).hexdigest()
            if "index.json" in names:
                index = json.load(tf.extractfile("index.json"))
                digest = index["manifests"][0]["digest"]
                return digest
        raise ValueError(f"{self.path}: not an image archive")

    # --- docker-save format ---

    def _inspect_docker_archive(self, tf: tarfile.TarFile):
        import contextlib

        manifest = json.load(tf.extractfile("manifest.json"))[0]
        config = json.load(tf.extractfile(manifest["Config"]))
        diff_ids = config.get("rootfs", {}).get("diff_ids", [])
        layer_paths = manifest.get("Layers", [])
        created_by = self._created_by(config, diff_ids)
        image_id = "sha256:" + hashlib.sha256(
            json.dumps(config, sort_keys=True).encode()).hexdigest()
        artifact_id, blob_ids = self._image_keys(image_id, diff_ids)
        missing_artifact, missing = self._missing_blobs(
            artifact_id, blob_ids)

        @contextlib.contextmanager
        def open_layer(i):
            data = tf.extractfile(layer_paths[i]).read()
            if data[:2] == b"\x1f\x8b":
                data = gzip.decompress(data)
            with tarfile.open(fileobj=io.BytesIO(data)) as layer_tf:
                yield layer_tf

        def stream_open(i):
            # fanald: own outer handle per call (thread-safe); the
            # compressed blob streams straight off the archive, and
            # the decompressed spool is budget-bounded
            from .pipeline import archive_member_stream
            return archive_member_stream(self.path, layer_paths[i])

        secret_files = self._walk_missing_layers(
            diff_ids, blob_ids, created_by, missing, open_layer,
            stream_open=stream_open)

        metadata = T.Metadata(
            image_id=image_id,
            diff_ids=diff_ids,
            repo_tags=manifest.get("RepoTags") or [],
            image_config=config,
        )
        if missing_artifact:
            self._put_artifact_info(artifact_id, config)
        name = self.path
        if metadata.repo_tags:
            name = metadata.repo_tags[0]
        return ArtifactReference(
            name=name, type=T.ArtifactType.CONTAINER_IMAGE, id=artifact_id,
            blob_ids=blob_ids, image_metadata=metadata,
            secret_files=secret_files)

    # --- OCI image layout ---

    def _inspect_oci_layout(self, tf: tarfile.TarFile):
        import contextlib

        index = json.load(tf.extractfile("index.json"))
        mdesc = index["manifests"][0]
        manifest = json.load(tf.extractfile(_blob_path(mdesc["digest"])))
        config = json.load(tf.extractfile(
            _blob_path(manifest["config"]["digest"])))
        diff_ids = config.get("rootfs", {}).get("diff_ids", [])
        created_by = self._created_by(config, diff_ids)
        image_id = manifest["config"]["digest"]
        artifact_id, blob_ids = self._image_keys(image_id, diff_ids)
        missing_artifact, missing = self._missing_blobs(
            artifact_id, blob_ids)
        layer_digests = [ld["digest"] for ld in manifest["layers"]]

        @contextlib.contextmanager
        def open_layer(i):
            data = tf.extractfile(_blob_path(layer_digests[i])).read()
            if data[:2] == b"\x1f\x8b":
                data = gzip.decompress(data)
            with tarfile.open(fileobj=io.BytesIO(data)) as layer_tf:
                yield layer_tf

        def stream_open(i):
            from .pipeline import archive_member_stream
            return archive_member_stream(
                self.path, _blob_path(layer_digests[i]))

        secret_files = self._walk_missing_layers(
            diff_ids, blob_ids, created_by, missing, open_layer,
            layer_digests=layer_digests, stream_open=stream_open)

        metadata = T.Metadata(image_id=image_id, diff_ids=diff_ids,
                              image_config=config)
        if missing_artifact:
            self._put_artifact_info(artifact_id, config)
        return ArtifactReference(
            name=self.path, type=T.ArtifactType.CONTAINER_IMAGE,
            id=artifact_id, blob_ids=blob_ids, image_metadata=metadata,
            secret_files=secret_files)


def _blob_path(digest: str) -> str:
    algo, hexd = digest.split(":", 1)
    return f"blobs/{algo}/{hexd}"


class _SingleBlobArtifact:
    """Shared assembly for sources that squash to ONE synthetic blob
    (filesystem trees and VM disk images): walk → blob info → secret
    scan → content-addressed cache key → cache put."""

    def __init__(self, target: str, cache,
                 group: Optional[AnalyzerGroup] = None,
                 scanners: tuple = ("vuln",), secret_scanner=None,
                 secret_config_path: str = DEFAULT_SECRET_CONFIG):
        self.target = target
        self.cache = cache
        self.group = group or AnalyzerGroup()
        self.scanners = scanners
        self.secret_scanner = secret_scanner
        self.secret_config_path = secret_config_path
        if "secret" in scanners and secret_scanner is None:
            from ..secret import SecretScanner
            self.secret_scanner = SecretScanner()

    def _walk(self):  # pragma: no cover — subclasses implement
        raise NotImplementedError

    def _name(self) -> str:
        return self.target

    ARTIFACT_TYPE = T.ArtifactType.FILESYSTEM

    def inspect(self) -> ArtifactReference:
        scan = self._walk()
        bi = blob_info(scan)
        if "secret" in self.scanners and scan.secret_files:
            bi.secrets = self.secret_scanner.scan_files(scan.secret_files)
        blob_id = cache_key(self._content_id(bi), self.group.versions(),
                            {"scanners": sorted(self.scanners)})
        self.cache.put_blob(blob_id, bi)
        self.cache.put_artifact(blob_id, {"SchemaVersion": 2})
        secret_files = {blob_id: scan.secret_files} \
            if scan.secret_files else {}
        return ArtifactReference(
            name=self._name(), type=self.ARTIFACT_TYPE,
            id=blob_id, blob_ids=[blob_id], secret_files=secret_files)

    @staticmethod
    def _content_id(bi: T.BlobInfo) -> str:
        return "sha256:" + hashlib.sha256(
            json.dumps(bi.to_json(), sort_keys=True).encode()).hexdigest()


class FilesystemArtifact(_SingleBlobArtifact):
    """A directory tree as one synthetic blob
    (pkg/fanal/artifact/local/fs.go:114)."""

    def __init__(self, root: str, cache, parallel: int = 1,
                 file_checksum: bool = False, skip_files: tuple = (),
                 skip_dirs: tuple = (), **kw):
        super().__init__(root, cache, **kw)
        self.root = root
        self.parallel = parallel
        self.file_checksum = file_checksum
        self.skip_files = skip_files
        self.skip_dir_globs = skip_dirs

    def _walk(self):
        return walk_fs(self.root, self.group,
                       collect_secrets="secret" in self.scanners,
                       secret_config_path=self.secret_config_path,
                       parallel=self.parallel,
                       file_checksum=self.file_checksum,
                       skip_files=self.skip_files,
                       skip_dir_globs=self.skip_dir_globs)

    def _name(self) -> str:
        return os.path.abspath(self.root).rstrip("/")


class VMArtifact(_SingleBlobArtifact):
    """Raw disk image / EBS snapshot as one synthetic blob (reference
    pkg/fanal/artifact/vm/vm.go): partition walk + read-only ext4
    through the same analyzer pipeline as the filesystem artifact."""

    ARTIFACT_TYPE = T.ArtifactType.VM

    def _walk(self):
        from .vm import open_device, walk_vm
        dev = open_device(self.target)
        try:
            return walk_vm(dev, self.group,
                           collect_secrets="secret" in self.scanners,
                           secret_config_path=self.secret_config_path)
        finally:
            dev.close()


class RegistryArtifact(_ImageInspectMixin):
    """Registry-pulled image, layers STREAMED straight from blob
    responses into the analyzer walk (reference
    pkg/fanal/artifact/image/image.go:241-330) — no intermediate
    tarball, no double disk I/O on registry sweeps."""

    def __init__(self, image: str, cache,
                 group: Optional[AnalyzerGroup] = None,
                 scanners: tuple = ("vuln",), secret_scanner=None,
                 secret_config_path: str = DEFAULT_SECRET_CONFIG,
                 platform: str = "linux/amd64", client=None,
                 skip_files: tuple = (), skip_dirs: tuple = (),
                 ingest=None):
        from ..oci import default_client, parse_ref
        self.image = image
        self.ref = parse_ref(image)
        self.client = client or default_client()
        self.platform = platform or "linux/amd64"
        self.cache = cache
        self.group = group or AnalyzerGroup()
        self.scanners = scanners
        self.secret_scanner = secret_scanner
        self.secret_config_path = secret_config_path
        self.skip_files = tuple(skip_files)
        self.skip_dir_globs = tuple(skip_dirs)
        self.ingest = ingest
        if "secret" in scanners and secret_scanner is None:
            from ..secret import SecretScanner
            self.secret_scanner = SecretScanner()
        self._manifest = None

    def manifest(self) -> dict:
        if self._manifest is None:
            self._manifest = self.client.manifest(self.ref,
                                                  self.platform)
        return self._manifest

    def image_digest(self) -> str:
        return self.manifest()["config"]["digest"]

    def inspect(self) -> ArtifactReference:
        import contextlib

        man = self.manifest()
        config = json.loads(self.client.blob(
            self.ref, man["config"]["digest"]))
        diff_ids = config.get("rootfs", {}).get("diff_ids", [])
        layers = man.get("layers", [])
        created_by = self._created_by(config, diff_ids)
        image_id = man["config"]["digest"]
        artifact_id, blob_ids = self._image_keys(image_id, diff_ids)
        missing_artifact, missing = self._missing_blobs(
            artifact_id, blob_ids)
        layer_digests = [ld["digest"] for ld in layers]

        @contextlib.contextmanager
        def open_layer(i):
            layer = layers[i]
            mode = "r|gz" if layer.get("mediaType", "").endswith(
                ("+gzip", ".gzip")) else "r|*"
            with self.client.blob_stream(self.ref,
                                         layer["digest"]) as stream:
                with tarfile.open(fileobj=stream, mode=mode) as ltf:
                    yield ltf
                # digest check AFTER the walk, BEFORE caching: a
                # corrupted/tampered blob must never populate the cache
                stream.verify()

        @contextlib.contextmanager
        def stream_open(i):
            # fanald: each call is its own registry connection, so
            # concurrent layer walkers stream independently. verify()
            # drains the remainder in bounded chunks AFTER a clean
            # walk; a digest mismatch is the one failure the pipeline
            # must NOT degrade around (tampered bytes never cache),
            # so it is wrapped as IngestIntegrityError and re-raised
            # by _walk_missing_pipelined as the original OCIError.
            from .pipeline import (IngestIntegrityError, bounded_drain,
                                   layer_tar_stream)
            with self.client.blob_stream(self.ref,
                                         layers[i]["digest"]) as stream:
                with layer_tar_stream(stream) as ls:
                    yield ls
                if not ls.fully_spooled and not bounded_drain(stream,
                                                              ls):
                    # mid-stream budget/parse stop with a tail too
                    # big/slow to hash within the layer's own budgets:
                    # draining it anyway would wedge the walker past
                    # the watchdog and trip the SHARED walk breaker —
                    # one hostile layer degrading every tenant. The
                    # layer is already a partial, which caches only
                    # under its salted id, never canonically, so
                    # nothing unverified becomes authoritative.
                    return
                try:
                    stream.verify()
                except Exception as e:
                    raise IngestIntegrityError(str(e)) from e

        secret_files = self._walk_missing_layers(
            diff_ids, blob_ids, created_by, missing, open_layer,
            layer_digests=layer_digests, stream_open=stream_open)

        metadata = T.Metadata(
            image_id=image_id,
            diff_ids=diff_ids,
            repo_tags=[self.image],
            image_config=config,
        )
        if missing_artifact:
            self._put_artifact_info(artifact_id, config)
        return ArtifactReference(
            name=self.image, type=T.ArtifactType.CONTAINER_IMAGE,
            id=artifact_id, blob_ids=blob_ids, image_metadata=metadata,
            secret_files=secret_files)
