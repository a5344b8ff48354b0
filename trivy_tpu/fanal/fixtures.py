"""Deterministic docker-save archive builders.

One implementation of the tar/gzip/docker-save layout shared by every
in-repo producer of synthetic images — graftstorm's ingest-drill
artifacts and tests/helpers.py make_image — so a change to the layout
(layer path naming, config history shape) cannot leave one builder
emitting archives the fanal artifact code no longer accepts. Zeroed
tar/gzip mtimes keep the bytes reproducible."""

from __future__ import annotations

import gzip
import hashlib
import io
import json
import tarfile


def tar_bytes(files: dict) -> bytes:
    """Deterministic plain tar of {path: bytes}."""
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w") as tf:
        for name in files:
            ti = tarfile.TarInfo(name)
            ti.size = len(files[name])
            tf.addfile(ti, io.BytesIO(files[name]))
    return buf.getvalue()


def gz_bytes(data: bytes, level: int = 9) -> bytes:
    buf = io.BytesIO()
    with gzip.GzipFile(fileobj=buf, mode="wb", mtime=0,
                       compresslevel=level) as gz:
        gz.write(data)
    return buf.getvalue()


def sha256_hex(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()


def write_docker_archive(path: str, layer_blobs: list[bytes],
                         diff_ids: list[str],
                         repo_tag: str = "fixture/img:1",
                         repo_tags=None, created_by=None,
                         config_sort_keys: bool = True) -> None:
    """Write a docker-save tarball from pre-built layer blobs (which
    may be gzipped, truncated, or otherwise hostile — `diff_ids` are
    recorded verbatim, the archive layout stays well-formed).

    `repo_tags`/`created_by`/`config_sort_keys` exist for
    tests/helpers.make_image, which delegates here so the whole repo
    has ONE copy of the docker-save layout (config_sort_keys=False
    preserves the insertion-order config bytes the test suite's
    image/config ids were minted from)."""
    if repo_tags is None:
        repo_tags = (repo_tag,)
    if created_by is None:
        created_by = [f"fixture-layer-{i}"
                      for i in range(len(diff_ids))]
    config = {
        "architecture": "amd64", "os": "linux",
        "rootfs": {"type": "layers", "diff_ids": diff_ids},
        "history": [{"created_by": created_by[i]}
                    for i in range(len(diff_ids))],
    }
    config_bytes = json.dumps(config,
                              sort_keys=config_sort_keys).encode()
    config_name = sha256_hex(config_bytes) + ".json"
    manifest = [{
        "Config": config_name,
        "RepoTags": list(repo_tags),
        "Layers": [f"layer{i}/layer.tar"
                   for i in range(len(layer_blobs))],
    }]
    with tarfile.open(path, "w") as tf:
        for name, data in [("manifest.json",
                            json.dumps(manifest).encode()),
                           (config_name, config_bytes)] + \
                [(f"layer{i}/layer.tar", b)
                 for i, b in enumerate(layer_blobs)]:
            ti = tarfile.TarInfo(name)
            ti.size = len(data)
            tf.addfile(ti, io.BytesIO(data))
