"""Prove the served scan path runs on the chip: `python chip_smoke.py`.

One process, the only one that touches JAX. It refuses to run unless
JAX's device is a TPU; there is no CPU branch.

Phases (one chip, the default):

  known_answer  a standalone `image` scan through `trivy_tpu.cli.main`
                of the synthetic alpine archive plus one file carrying
                an AWS key: exactly the fixture DB's 5 vulnerabilities
                and the planted secret.
  served        a seeded trivy-db-shaped advisory table (~205k interval
                rows) behind the Twirp server, built with the options
                `server` builds (`cli.server_options`), detectd warmup
                on. ARCHIVES image archives go through RemoteCache /
                RemoteScanner over HTTP, CONCURRENCY at a time; every
                finding is compared with the host oracle.

`--chips 4` runs only the mesh phase: the same table and traffic
through a server on a dp2 × db2 mesh (`--mesh-devices 4
--mesh-db-shards 2`), compared with single-device BatchDetector results
and the host oracle, plus the mesh-sharded secret prefilter against
the host; each device must hold only its db shard of the table.

Any failed check raises, and the process exits non-zero. The last line
of stdout is the JSON result the driver reads.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

REPO = os.path.dirname(os.path.abspath(__file__))
SEED = 7
ARCHIVES = 16
CONCURRENCY = 4
PKGS_PER_IMAGE = 80
SKEW_IMAGE_FRAC = 0.3
# above the secret engine's 2 MiB small-batch floor, so each layer's
# prefilter runs on the device
SECRET_FILE_BYTES = 3 << 20
FIXTURE_VULNS = 5


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def require_tpu():
    """→ jax.devices() when JAX's default device is a TPU; otherwise
    exits non-zero saying why. bench.py makes the same check."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"no TPU: JAX's default device is {devices[0].platform!r} "
            f"({devices[0].device_kind}); this program measures the "
            f"chip only and has no CPU branch")
    return devices


@contextlib.contextmanager
def phase(name: str):
    t0 = time.perf_counter()
    yield
    print(f"# phase {name}: {time.perf_counter() - t0:.3f} s wall",
          flush=True)


def secret_file(rng, key: str, n_bytes: int = SECRET_FILE_BYTES) -> bytes:
    """Text of about n_bytes with one AWS access key on a line of its
    own, at a seeded position."""
    lines = [b"line %07d: the quick brown fox jumps over the lazy dog\n" % i
             for i in range(n_bytes // 56)]
    at = int(rng.integers(1, len(lines) - 1))
    lines.insert(at, f"aws_access_key_id = {key}\n".encode())
    return b"".join(lines)


def aws_key(rng) -> str:
    alphabet = "ABCDEFGHIJKLMNOPQRSTUVWXYZ234567"
    return "AKIA" + "".join(alphabet[int(i)]
                            for i in rng.integers(0, 32, 16))


def counters() -> dict:
    from trivy_tpu.metrics import METRICS
    out = {"fallback_joins":
           int(METRICS.get("trivy_tpu_fallback_joins_total"))}
    for p in ("pallas", "jnp", "host"):
        out[p] = int(METRICS.get("trivy_tpu_secret_prefilter_path_total",
                                 path=p))
    return out


# ---- known answer --------------------------------------------------------

def known_answer(tmp: str, rng) -> None:
    sys.path.insert(0, os.path.join(REPO, "tests"))
    from helpers import ALPINE_OS_RELEASE, APK_INSTALLED, make_image

    from trivy_tpu import cli
    key = aws_key(rng)
    img = os.path.join(tmp, "alpine.tar")
    make_image(img, [{"etc/os-release": ALPINE_OS_RELEASE,
                      "lib/apk/db/installed": APK_INSTALLED},
                     {"app/settings.txt": secret_file(rng, key)}])
    out = os.path.join(tmp, "report.json")
    rc = cli.main(["image", "--input", img,
                   "--db", os.path.join(REPO, "tests/fixtures/db/*.yaml"),
                   "--scanners", "vuln,secret",
                   "--cache-dir", os.path.join(tmp, "cache"),
                   "--format", "json", "--output", out])
    check(rc == 0, f"image scan exited {rc}")
    with open(out) as f:
        results = json.load(f).get("Results") or []
    vulns = [v for r in results for v in r.get("Vulnerabilities") or []]
    secrets = [s for r in results for s in r.get("Secrets") or []]
    print(f"# known_answer: {len(vulns)} vulnerabilities, "
          f"{len(secrets)} secrets "
          f"(want {FIXTURE_VULNS} and 1 aws-access-key-id)", flush=True)
    check(len(vulns) == FIXTURE_VULNS,
          f"known answer: {len(vulns)} vulnerabilities, "
          f"want {FIXTURE_VULNS}")
    check([s.get("RuleID") for s in secrets] == ["aws-access-key-id"],
          f"known answer: secrets {secrets!r}")


# ---- served --------------------------------------------------------------

def build_archives(tmp: str, rng, installed_pool, n: int):
    """→ [(path, [(name, version)], (secret path, secret bytes))]."""
    sys.path.insert(0, os.path.join(REPO, "tests"))
    from helpers import make_image

    from trivy_tpu.db.synth import N_PKG_NAMES, SKEW_PKG
    os_release = (b'NAME="Alpine Linux"\nID=alpine\n'
                  b'VERSION_ID=3.19.1\n')
    out = []
    for i in range(n):
        names = [f"pkg{k:05d}" for k in
                 rng.choice(N_PKG_NAMES, PKGS_PER_IMAGE, replace=False)]
        if rng.random() < SKEW_IMAGE_FRAC:
            names[-1] = SKEW_PKG
        pkgs = [(nm, installed_pool[int(v)]) for nm, v in
                zip(names, rng.integers(0, len(installed_pool),
                                        len(names)))]
        installed = "".join(
            f"P:{nm}\nV:{ver}\nA:x86_64\no:{nm}\nL:MIT\n\n"
            for nm, ver in pkgs).encode()
        secret = (f"srv/app{i}/settings.txt",
                  secret_file(rng, aws_key(rng)))
        path = os.path.join(tmp, f"img{i}.tar")
        make_image(path, [{"etc/os-release": os_release,
                           "lib/apk/db/installed": installed},
                          {secret[0]: secret[1]}],
                   repo_tags=(f"smoke/img{i}:1",))
        out.append((path, pkgs, secret))
    return out


def scan_remote(url: str, path: str):
    """One client: inspect the archive into the server's cache, then
    Scan. → (vulnerability triples, secret tuples)."""
    from trivy_tpu import types as T
    from trivy_tpu.fanal.artifact import ImageArchiveArtifact
    from trivy_tpu.server.client import RemoteCache, RemoteScanner
    ref = ImageArchiveArtifact(path, RemoteCache(url),
                               scanners=("vuln", "secret")).inspect()
    results, _os = RemoteScanner(url).scan(
        ref.name, ref.id, ref.blob_ids,
        T.ScanOptions(scanners=("vuln", "secret")))
    vulns = sorted((v.pkg_name, v.installed_version, v.vulnerability_id)
                   for r in results for v in r.vulnerabilities)
    secrets = sorted((r.target.lstrip("/"), f.rule_id, f.start_line,
                      f.match)
                     for r in results for f in r.secrets)
    return vulns, secrets


def host_oracle(table, archives):
    """Per archive: the hostjoin vulnerability triples and the host
    secret scanner's findings for the same inputs."""
    import numpy as np

    from trivy_tpu.db.synth import SOURCE
    from trivy_tpu.detect.engine import BatchDetector, PkgQuery
    from trivy_tpu.resilience.hostjoin import host_pair_join
    from trivy_tpu.secret.engine import SecretScanner
    det = BatchDetector(table)     # host prep and assembly only
    host = SecretScanner(use_device=False)
    out = []
    try:
        for _path, pkgs, (spath, sbytes) in archives:
            prep = det._prepare([PkgQuery(source=SOURCE,
                                          ecosystem="alpine", name=nm,
                                          version=ver)
                                 for nm, ver in pkgs])
            bits = np.zeros(prep.pair_row.shape[0], np.int8)
            n = prep.n_pairs
            t = table
            bits[:n] = host_pair_join(
                t.lo_tok, t.hi_tok, t.flags, det.ver_snapshot(),
                prep.pair_row[:n], prep.pair_ver[:n], np.ones(n, bool))
            vulns = sorted((h.query.name, h.query.version, h.vuln_id)
                           for h in det._assemble(prep, bits))
            secrets = sorted((s.file_path, f.rule_id, f.start_line,
                              f.match)
                             for s in host.scan_files([(spath, sbytes)])
                             for f in s.findings)
            out.append((vulns, secrets))
    finally:
        det.close()
    return out


@contextlib.contextmanager
def running_server(table, cache_dir: str, *extra: str):
    """The scan server as `trivy_tpu.cli server` configures it, on an
    ephemeral localhost port. → (url, ServerState)."""
    from trivy_tpu import cli
    from trivy_tpu.server.listen import serve_background
    args = cli.build_parser().parse_args(
        ["server", "--listen", "127.0.0.1:0", "--cache-dir", cache_dir,
         "--detect-warmup", *extra])
    host, _, port = args.listen.rpartition(":")
    httpd, state = serve_background(host, int(port), table,
                                    args.cache_dir,
                                    **cli.server_options(args))
    try:
        yield f"http://127.0.0.1:{httpd.server_address[1]}", state
    finally:
        httpd.shutdown()
        httpd.server_close()
        state.close()


def healthz(url: str) -> dict:
    import urllib.request
    with urllib.request.urlopen(url + "/healthz", timeout=30) as r:
        return json.loads(r.read())


def push(url: str, archives) -> list:
    with ThreadPoolExecutor(CONCURRENCY) as pool:
        return list(pool.map(lambda a: scan_remote(url, a[0]), archives))


def compare(got: list, want: list, what: str) -> tuple[int, int]:
    n_v = n_s = 0
    for i, ((gv, gs), (wv, ws)) in enumerate(zip(got, want)):
        check(gv == wv, f"{what}: archive {i} vulnerabilities differ "
              f"({len(gv)} vs {len(wv)}; first extra "
              f"{sorted(set(gv) ^ set(wv))[:3]})")
        check(gs == ws, f"{what}: archive {i} secrets differ "
              f"({gs!r} vs {ws!r})")
        n_v += len(gv)
        n_s += len(gs)
    check(len(got) == len(want), f"{what}: {len(got)} results")
    return n_v, n_s


def served(tmp: str, rng, table, installed_pool) -> None:
    from trivy_tpu.resilience import GUARD
    archives = build_archives(tmp, rng, installed_pool, ARCHIVES)
    with running_server(table, os.path.join(tmp, "srv-cache")) as (url,
                                                                   _st):
        t0 = time.perf_counter()
        got = push(url, archives)
        print(f"# served: {len(archives)} archives, {CONCURRENCY} at a "
              f"time, {time.perf_counter() - t0:.3f} s wall", flush=True)
        health = healthz(url)
    platform = health["device"]["platform"]
    breaker = GUARD.status()["breaker"]["state"]
    c = counters()
    print(f"# counters: fallback_joins={c['fallback_joins']} prefilter "
          f"pallas={c['pallas']} jnp={c['jnp']} host={c['host']}; "
          f"breaker {breaker}; /healthz device.platform={platform}",
          flush=True)
    n_v, n_s = compare(got, host_oracle(table, archives), "served")
    print(f"# served: {n_v} vulnerabilities and {n_s} secrets equal to "
          f"the host oracle", flush=True)
    check(c["fallback_joins"] == 0,
          f"{c['fallback_joins']} joins fell back to the host")
    check(c["pallas"] >= 1 and c["jnp"] == 0,
          f"prefilter paths {c}: want pallas >= 1 and jnp = 0")
    check(breaker == "closed", f"breaker {breaker}")
    check(platform == "tpu", f"/healthz device.platform {platform!r}")


# ---- four chips ----------------------------------------------------------

def mesh_phase(tmp: str, rng, table, installed_pool) -> None:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from trivy_tpu.db.synth import SOURCE
    from trivy_tpu.detect.engine import BatchDetector, PkgQuery
    from trivy_tpu.parallel.mesh import MeshDetector
    from trivy_tpu.resilience import GUARD
    from trivy_tpu.secret.engine import SecretScanner
    check(len(jax.devices()) == 4, f"{len(jax.devices())} devices")
    archives = build_archives(tmp, rng, installed_pool, ARCHIVES)
    with running_server(table, os.path.join(tmp, "mesh-cache"),
                        "--mesh-devices", "4",
                        "--mesh-db-shards", "2") as (url, state):
        det = state._scanner.detector
        check(isinstance(det, MeshDetector),
              f"server detector is {type(det).__name__}")
        mesh = det.mesh
        print(f"# mesh: {dict(mesh.shape)} over devices "
              f"{[d.id for d in mesh.devices.flat]}", flush=True)
        # each device holds exactly its db shard of the table
        for name in ("lo_tok", "hi_tok", "flags"):
            arr = getattr(det._st_dev, name)
            check(arr.sharding.is_equivalent_to(
                NamedSharding(mesh, P("db")), arr.ndim),
                f"{name} sharding {arr.sharding}")
            per_shard = arr.nbytes // arr.shape[0]
            for shard in arr.addressable_shards:
                check(shard.data.nbytes == per_shard
                      and shard.data.shape[0] == 1,
                      f"{name} on device {shard.device.id}: "
                      f"{shard.data.nbytes} bytes, want {per_shard}")
            print(f"# {name}: {arr.shape} {arr.dtype}, "
                  f"{per_shard} bytes on each device", flush=True)
        t0 = time.perf_counter()
        got = push(url, archives)
        print(f"# mesh served: {len(archives)} archives, "
              f"{time.perf_counter() - t0:.3f} s wall", flush=True)
        # the mesh-sharded secret prefilter against the host, on the
        # same secret files
        files = [sbytes for _p, _k, (_sp, sbytes) in archives]
        meshed = SecretScanner(mesh=mesh)
        got_masks, path = meshed._keyword_masks_device(files)
        want_masks = meshed._keyword_masks_host(files)
        check(got_masks == want_masks and any(got_masks),
              "mesh-sharded prefilter != host keyword masks")
        print(f"# mesh prefilter ({path}): {len(files)} files equal "
              f"to the host", flush=True)
    want = host_oracle(table, archives)
    n_v, n_s = compare(got, want, "mesh")
    single = BatchDetector(table)
    try:
        hits = single.detect_many([
            [PkgQuery(source=SOURCE, ecosystem="alpine", name=nm,
                      version=ver) for nm, ver in pkgs]
            for _p, pkgs, _s in archives])
    finally:
        single.close()
    for i, (h, (wv, _ws)) in enumerate(zip(hits, want)):
        sv = sorted((x.query.name, x.query.version, x.vuln_id)
                    for x in h)
        check(sv == wv, f"single-device archive {i} differs from host")
    c = counters()
    breaker = GUARD.status()["breaker"]["state"]
    print(f"# mesh: {n_v} vulnerabilities and {n_s} secrets equal to "
          f"single-device and host; fallback_joins="
          f"{c['fallback_joins']} breaker {breaker}", flush=True)
    check(c["fallback_joins"] == 0,
          f"{c['fallback_joins']} joins fell back to the host")
    check(breaker == "closed", f"breaker {breaker}")


# ---- main ----------------------------------------------------------------

def print_compiles() -> None:
    from trivy_tpu.obs.perf import LEDGER
    rows = [r for r in LEDGER.shape_table() if r["compiles"]]
    for r in rows:
        print(f"# compile site={r['site']} rows={r['t_pad']} "
              f"h_cap={r['h_cap']}: {r['compiles']} x, "
              f"{r['compile_ms']} ms", flush=True)
    print(f"# compile total: {sum(r['compile_ms'] for r in rows):.3f} "
          f"ms over {sum(r['compiles'] for r in rows)} compiles",
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the dp2 x db2 mesh phase")
    args = ap.parse_args(argv)
    devices = require_tpu()
    sys.path.insert(0, REPO)
    import numpy as np

    from trivy_tpu import compile_cache, native
    from trivy_tpu.db.synth import skewed_table
    cache_dir = compile_cache.enable()
    d = devices[0]
    print(f"# device: {d.device_kind} ({d.platform}) x {len(devices)}",
          flush=True)
    print(f"# compile cache: {cache_dir}", flush=True)
    print(f"# native helper loaded: {native.available()}", flush=True)
    rng = np.random.default_rng(SEED)
    with tempfile.TemporaryDirectory() as tmp:
        with phase("table"):
            table, installed_pool = skewed_table(rng)
        print(f"# table: {len(table)} interval rows x "
              f"{table.lo_tok.shape[1]} tokens, "
              f"{table.device_nbytes()} bytes of columns on the device",
              flush=True)
        if args.chips == 4:
            with phase("mesh"):
                mesh_phase(tmp, rng, table, installed_pool)
        else:
            with phase("known_answer"):
                known_answer(tmp, rng)
            with phase("served"):
                served(tmp, rng, table, installed_pool)
    print_compiles()
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
