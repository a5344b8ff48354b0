"""Test helpers: build synthetic docker-save image tarballs in memory,
a strict Prometheus text-exposition parser (the tier-1 gate that keeps
/metrics scrapeable), and an in-process fake Redis (the shared cache
backend the fleet tests and bench drive without a real server)."""

import hashlib
import io
import json
import socket
import sqlite3
import struct
import tarfile
import tempfile
import threading


# ---- in-process fake Redis (RESP2) -----------------------------------

class FakeRedis:
    """Tiny RESP2 server: SET/GET/EXISTS/DEL/RENAME/SCAN/AUTH/SELECT.
    The reference tests use testcontainers; this fake speaks enough
    protocol for RedisCache (integration/client_server_test.go
    setupRedis) and doubles as the shared fleet backend in
    tests/test_fleet.py."""

    def __init__(self, password=""):
        self.data = {}
        self.password = password
        self.sock = socket.socket()
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(16)
        self.port = self.sock.getsockname()[1]
        self.thread = threading.Thread(target=self._serve, daemon=True)
        self.thread.start()

    def _serve(self):
        while True:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            threading.Thread(target=self._handle, args=(conn,),
                             daemon=True).start()

    def _handle(self, conn):
        buf = b""
        authed = not self.password
        while True:
            try:
                chunk = conn.recv(65536)
            except OSError:
                return
            if not chunk:
                return
            buf += chunk
            while True:
                cmd, buf2 = self._parse(buf)
                if cmd is None:
                    break
                buf = buf2
                reply, authed = self._dispatch(cmd, authed)
                try:
                    conn.sendall(reply)
                except OSError:
                    return

    @staticmethod
    def _parse(buf):
        if not buf.startswith(b"*"):
            return None, buf
        try:
            head, rest = buf.split(b"\r\n", 1)
            n = int(head[1:])
            args = []
            for _ in range(n):
                if not rest.startswith(b"$"):
                    return None, buf
                lhead, rest2 = rest.split(b"\r\n", 1)
                ln = int(lhead[1:])
                if len(rest2) < ln + 2:
                    return None, buf
                args.append(rest2[:ln])
                rest = rest2[ln + 2:]
            return args, rest
        except (ValueError, IndexError):
            return None, buf

    def _dispatch(self, args, authed):
        cmd = args[0].decode().upper()
        if cmd == "AUTH":
            if args[1].decode() == self.password:
                return b"+OK\r\n", True
            return b"-ERR invalid password\r\n", authed
        if not authed:
            return b"-NOAUTH Authentication required.\r\n", authed
        if cmd == "SELECT":
            return b"+OK\r\n", authed
        if cmd == "SET":
            self.data[args[1]] = args[2]
            return b"+OK\r\n", authed
        if cmd == "GET":
            v = self.data.get(args[1])
            if v is None:
                return b"$-1\r\n", authed
            return b"$%d\r\n%s\r\n" % (len(v), v), authed
        if cmd == "EXISTS":
            return b":%d\r\n" % (1 if args[1] in self.data else 0), \
                authed
        if cmd == "DEL":
            n = 1 if self.data.pop(args[1], None) is not None else 0
            return b":%d\r\n" % n, authed
        if cmd == "RENAME":
            v = self.data.pop(args[1], None)
            if v is None:
                return b"-ERR no such key\r\n", authed
            self.data[args[2]] = v
            return b"+OK\r\n", authed
        if cmd == "SCAN":
            import fnmatch
            pat = b"*"
            for i, a in enumerate(args):
                if a.upper() == b"MATCH":
                    pat = args[i + 1]
            keys = [k for k in self.data
                    if fnmatch.fnmatch(k.decode(), pat.decode())]
            out = b"*2\r\n$1\r\n0\r\n*%d\r\n" % len(keys)
            for k in keys:
                out += b"$%d\r\n%s\r\n" % (len(k), k)
            return out, authed
        return b"-ERR unknown command\r\n", authed

    def close(self):
        self.sock.close()


# ---- strict Prometheus text exposition format 0.0.4 parser ----------
#
# Moved to trivy_tpu/obs/exposition.py in PR 8 so graftstorm's
# metrics_wellformed invariant and the test suite enforce ONE
# definition of "strict"; re-exported here for every existing caller.

from trivy_tpu.obs.exposition import parse_exposition  # noqa: F401,E402


def make_layer(files: dict[str, bytes]) -> bytes:
    """files: path → content; a path ending in '/' creates a directory."""
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w") as tf:
        for path, content in files.items():
            if path.endswith("/"):
                ti = tarfile.TarInfo(path.rstrip("/"))
                ti.type = tarfile.DIRTYPE
                tf.addfile(ti)
                continue
            ti = tarfile.TarInfo(path)
            ti.size = len(content)
            tf.addfile(ti, io.BytesIO(content))
    return buf.getvalue()


def make_image(path: str, layers: list[dict[str, bytes]],
               repo_tags=("test/image:latest",),
               created_by=None) -> list[str]:
    """Write a docker-save tarball; returns layer diff_ids.

    The layout itself lives in fanal.fixtures.write_docker_archive —
    one implementation for the whole repo (config_sort_keys=False
    keeps the insertion-order config bytes this helper has always
    produced, so image/config ids in existing tests are unchanged)."""
    from trivy_tpu.fanal.fixtures import write_docker_archive

    layer_blobs = [make_layer(files) for files in layers]
    diff_ids = ["sha256:" + hashlib.sha256(b).hexdigest()
                for b in layer_blobs]
    write_docker_archive(
        path, layer_blobs, diff_ids, repo_tags=repo_tags,
        created_by=(list(created_by) if created_by else
                    [f"layer-{i}" for i in range(len(layers))]),
        config_sort_keys=False)
    return diff_ids


ALPINE_OS_RELEASE = b"""\
NAME="Alpine Linux"
ID=alpine
VERSION_ID=3.17.3
PRETTY_NAME="Alpine Linux v3.17"
"""

APK_INSTALLED = b"""\
C:Q1pSXsQcqlY5clcXDHVqZBBIfPzg4=
P:musl
V:1.2.3-r4
A:x86_64
T:the musl c library (libc) implementation
o:musl
m:Timo Teras <timo.teras@iki.fi>
L:MIT

C:Q1poBWwSMyhbfAgVmGAgSqd1bYKTA=
P:libcrypto3
V:3.0.7-r0
A:x86_64
o:openssl
m:Ariadne Conill <ariadne@dereferenced.org>
L:Apache-2.0
D:so:libc.musl-x86_64.so.1

C:Q1QKYkcqhL4XqhVFQnyFyyFyQ5EJo=
P:libssl3
V:3.0.7-r0
A:x86_64
o:openssl
L:Apache-2.0

C:Q1apkZXhAbeCZgOlWTACfe9eCM8Co=
P:zlib
V:1.2.13-r0
A:x86_64
o:zlib
L:Zlib
"""

FLASK_METADATA = b"""\
Metadata-Version: 2.1
Name: Flask
Version: 2.2.2
Summary: A simple framework for building complex web applications.
License: BSD-3-Clause

Flask body text.
"""


# ---- rpm database builders (shared by test_rpm and the golden-image
# gate): hand-constructed rpm header blobs, the inverse of the
# header-image parser in fanal/analyzers/rpm.py ----

def _rpm_tags():
    from trivy_tpu.fanal.analyzers import rpm as rpm_mod
    return rpm_mod


def build_header(tags: dict) -> bytes:
    """tags: {tag: (type, value)} → rpm header image."""
    entries = []
    store = b""
    for tag, (typ, value) in sorted(tags.items()):
        if typ == 6:  # string
            off = len(store)
            store += value.encode() + b"\x00"
            cnt = 1
        elif typ == 4:  # int32
            while len(store) % 4:
                store += b"\x00"
            off = len(store)
            store += struct.pack(">i", value)
            cnt = 1
        else:
            raise NotImplementedError(typ)
        entries.append(struct.pack(">iiii", tag, typ, off, cnt))
    blob = struct.pack(">ii", len(entries), len(store))
    return blob + b"".join(entries) + store


def build_rpmdb(pkgs: list[dict]) -> bytes:
    with tempfile.NamedTemporaryFile(suffix=".sqlite") as f:
        conn = sqlite3.connect(f.name)
        conn.execute("CREATE TABLE Packages (hnum INTEGER PRIMARY KEY, "
                     "blob BLOB NOT NULL)")
        for i, p in enumerate(pkgs):
            tags = {
                _rpm_tags().TAG_NAME: (6, p["name"]),
                _rpm_tags().TAG_VERSION: (6, p["version"]),
                _rpm_tags().TAG_RELEASE: (6, p["release"]),
                _rpm_tags().TAG_ARCH: (6, p.get("arch", "x86_64")),
            }
            if "epoch" in p:
                tags[_rpm_tags().TAG_EPOCH] = (4, p["epoch"])
            if "sourcerpm" in p:
                tags[_rpm_tags().TAG_SOURCERPM] = (6, p["sourcerpm"])
            if "license" in p:
                tags[_rpm_tags().TAG_LICENSE] = (6, p["license"])
            conn.execute("INSERT INTO Packages VALUES (?, ?)",
                         (i + 1, build_header(tags)))
        conn.commit()
        conn.close()
        f.seek(0)
        return open(f.name, "rb").read()


