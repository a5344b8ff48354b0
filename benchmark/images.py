"""Docker-save image archives, written from an image's contents.

The layout is copied from `trivy_tpu/fanal/fixtures.write_docker_archive`
and `tests/helpers.make_image`, by way of `chip_smoke.build_archives`
(PR 21): a manifest, a config naming each layer's diff id, and one
uncompressed tar per layer.
"""

from __future__ import annotations

import hashlib
import io
import json
import tarfile

from .families import alpine

# the names tests have always imported: the Alpine family's
OS_RELEASE = alpine.OS_RELEASE
apk_installed = alpine.apk_installed


def _tar(files) -> bytes:
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w") as tf:
        for path, content in files:
            ti = tarfile.TarInfo(path)
            ti.size = len(content)
            tf.addfile(ti, io.BytesIO(content))
    return buf.getvalue()


def write_image(path: str, layers, tag: str) -> None:
    """layers: [[(path in image, bytes)]] → a docker-save tarball."""
    blobs = [_tar(files) for files in layers]
    diff_ids = ["sha256:" + hashlib.sha256(b).hexdigest() for b in blobs]
    config = json.dumps({
        "architecture": "amd64", "os": "linux",
        "rootfs": {"type": "layers", "diff_ids": diff_ids},
        "history": [{"created_by": f"layer-{i}"}
                    for i in range(len(blobs))]}).encode()
    config_name = hashlib.sha256(config).hexdigest() + ".json"
    manifest = json.dumps([{
        "Config": config_name, "RepoTags": [tag],
        "Layers": [f"layer{i}/layer.tar" for i in range(len(blobs))]}])
    _write_tar(path, [("manifest.json", manifest.encode()),
                      (config_name, config)]
               + [(f"layer{i}/layer.tar", b) for i, b in enumerate(blobs)])


def _write_tar(path: str, members) -> None:
    with tarfile.open(path, "w") as tf:
        for name, data in members:
            ti = tarfile.TarInfo(name)
            ti.size = len(data)
            tf.addfile(ti, io.BytesIO(data))


def image_layers(spec, family=alpine) -> list:
    """An image's layers: the OS layer (the family's `os-release` and
    package database), then its text layer if any."""
    layers = [[("etc/os-release", family.OS_RELEASE),
               *family.package_files(spec.pkgs)]]
    if spec.text_files:
        layers.append(list(spec.text_files))
    return layers
