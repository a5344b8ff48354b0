"""Benchmark: batched CVE-scan throughput (images/sec) on the device.

Workload models the north-star registry sweep (BASELINE.md config 3/4)
with the real trivy-db's *skew*: a synthetic advisory table (~205k
interval rows, Zipf-distributed bucket sizes, plus one `linux`-style
source package carrying 4,000 advisory rows) and a stream of image SBOMs
(~80 installed packages each, ~30% of images including the skewed
package). Measured path = the full detect stack: vectorized host prep
(memoized version encode, batch hash, searchsorted bucket lookup, CSR
pair expansion) → device pair_join → host hit assembly/verification —
i.e. the part of the pipeline the reference spends in pkg/detector loops.

Three measured points on identical inputs:
  python_loop — the reference's per-package/per-advisory loop shape
                re-implemented in Python (NOT the Go reference binary,
                which cannot run in this image; see BASELINE.md) on a
                subsample, extrapolated.
  numpy_cpu   — the same CSR prep + the interval predicate evaluated
                with vectorized numpy on host (the best CPU version of
                this design).
  device      — the pair_join on the accelerator, pipelined batches.

`vs_baseline` = device ÷ python_loop. The honest Go-reference
comparison remains unmeasured (BASELINE.md); numpy_cpu bounds a
vectorized CPU design.

One process: it first checks that JAX's device is a TPU (the same
check as chip_smoke.py) and exits non-zero without printing a result
when it is not. There is no CPU fallback; host baselines carry host
names (`python_loop_*`, `numpy_cpu_*`, `secret*_host*`). Run it on the
chip through the chip tool.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

from trivy_tpu.db.synth import (N_PKG_NAMES, SKEW_PKG,  # noqa: E402
                                SOURCE, skewed_table, synth_versions)

N_IMAGES = 2048
PKGS_PER_IMAGE = 80
BASELINE_IMAGES = 256  # large enough to preserve the Zipf-skew density
BATCH_IMAGES = 512   # sweet spot on-chip: dispatch latency dominates
                     # below this, assemble cache pressure above it
SKEW_IMAGE_FRAC = 0.3


def build_workload():
    import numpy as np
    from trivy_tpu.detect.engine import BatchDetector, PkgQuery

    rng = np.random.default_rng(7)
    table, installed_pool = skewed_table(rng)
    detector = BatchDetector(table)
    images = []
    for _ in range(N_IMAGES):
        qs = []
        names = rng.integers(0, N_PKG_NAMES, PKGS_PER_IMAGE)
        vers = rng.integers(0, len(installed_pool), PKGS_PER_IMAGE)
        for n, v in zip(names, vers):
            qs.append(PkgQuery(source=SOURCE, ecosystem="alpine",
                               name=f"pkg{n:05d}",
                               version=installed_pool[int(v)]))
        if rng.random() < SKEW_IMAGE_FRAC:
            qs[-1] = PkgQuery(source=SOURCE, ecosystem="alpine",
                              name=SKEW_PKG,
                              version=installed_pool[int(vers[-1])])
        images.append(qs)
    return table, detector, images


def batches_of(images, batch_images=BATCH_IMAGES):
    return [
        [q for img in images[i:i + batch_images] for q in img]
        for i in range(0, len(images), batch_images)
    ]


def run_device(detector, images):
    return sum(len(h) for h in detector.detect_many(batches_of(images)))


def split_timings(detector, images):
    """Non-overlapped single-batch pass → (host_prep_s, device_s,
    assemble_s, assemble_compact_s, n_pairs, transfer_bytes).

    Both assemble numbers keep the legacy timing boundary — they
    INCLUDE the device→host fetch (assemble_ms has always been
    device_get + host nonzero + assembly, and the fetch is exactly
    what compaction shrinks, so excluding it would overstate nothing
    but compare nothing): assemble_s is the dense path (full padded
    bit vector fetched, host nonzero), assemble_compact_s the compact
    path (O(hits) triple fetched, index lookups). transfer_bytes is
    the actual device→host bytes this dispatch moved per path, read
    back from the transfer counter so the overflow fallback is
    visible."""
    import jax
    from trivy_tpu.detect.engine import _PendingCompact
    from trivy_tpu.metrics import METRICS
    from trivy_tpu.resilience.hostjoin import CompactBits
    qs = batches_of(images)[0]
    t0 = time.perf_counter()
    prep = detector._prepare(qs)
    t1 = time.perf_counter()
    out = detector._dispatch(prep)
    jax.block_until_ready(out)
    t2 = time.perf_counter()
    b_compact = METRICS.get("trivy_tpu_detect_transfer_bytes_total",
                            path="compact")
    b_dense = METRICS.get("trivy_tpu_detect_transfer_bytes_total",
                          path="dense")
    bits = detector._fetch_bits(out)
    transfer = {
        "compact": METRICS.get("trivy_tpu_detect_transfer_bytes_total",
                               path="compact") - b_compact,
        "dense": METRICS.get("trivy_tpu_detect_transfer_bytes_total",
                             path="dense") - b_dense,
    }
    if isinstance(bits, CompactBits):
        detector._assemble(prep, bits)
        asm_compact_s = time.perf_counter() - t2
        # dense baseline over the same dispatch: fetch the dense bits
        # retained on device (a real transfer, not a host rebuild) so
        # the two numbers share one boundary
        t3 = time.perf_counter()
        dense_bits = (jax.device_get(out.dense)
                      if isinstance(out, _PendingCompact)
                      else bits.dense())
        detector._assemble(prep, dense_bits)
        asm_s = time.perf_counter() - t3
    else:
        asm_compact_s = None
        detector._assemble(prep, bits)
        asm_s = time.perf_counter() - t2
    return (t1 - t0, t2 - t1, asm_s, asm_compact_s, prep.n_pairs,
            transfer)


def run_numpy_cpu(table, detector, images):
    """Same CSR prep; predicate evaluated with vectorized numpy."""
    import numpy as np
    from trivy_tpu.ops import join as J

    def np_bits(prep):
        rows = prep.pair_row[:prep.n_pairs].astype(np.int64)
        flags = table.flags[rows]
        lo = table.lo_tok[rows]
        hi = table.hi_tok[rows]
        inst = detector._ver_mat[prep.pair_ver[:prep.n_pairs]]

        def lex_less(a, b):
            neq = a != b
            seen = np.cumsum(neq, axis=-1)
            first = neq & (seen == 1)
            return np.any(first & (a < b), axis=-1)

        def lex_eq(a, b):
            return np.all(a == b, axis=-1)

        has_lo = (flags & J.HAS_LO) != 0
        lo_incl = (flags & J.LO_INCL) != 0
        has_hi = (flags & J.HAS_HI) != 0
        hi_incl = (flags & J.HI_INCL) != 0
        ok_lo = (~has_lo) | lex_less(lo, inst) | (lo_incl & lex_eq(lo, inst))
        ok_hi = (~has_hi) | lex_less(inst, hi) | (hi_incl & lex_eq(inst, hi))
        sat = ok_lo & ok_hi
        inex = (flags & J.INEXACT) != 0
        bits = np.zeros(prep.pair_row.shape[0], np.int8)
        bits[:prep.n_pairs] = sat.astype(np.int8) | (inex.astype(np.int8) << 1)
        return bits

    hits = 0
    for qs in batches_of(images):
        prep = detector._prepare(qs)
        if prep is None or prep.n_pairs == 0:
            continue
        hits += len(detector._assemble(prep, np_bits(prep)))
    return hits


def run_python_loop(table, images):
    """Reference-shaped loop: per package, bucket lookup + per-advisory
    exact version compare (alpine.go:86-117 semantics)."""
    from trivy_tpu import version as V
    buckets: dict = {}
    for g in table.groups:
        buckets.setdefault((g.source, g.pkg_name), []).append(g)
    hits = 0
    for img in images:
        for q in img:
            for g in buckets.get((q.source, q.name), []):
                for positive, iv in g.rows:
                    ok = True
                    if iv.lo is not None:
                        c = V.compare(q.ecosystem, iv.lo, q.version)
                        ok &= c < 0 or (iv.lo_incl and c == 0)
                    if ok and iv.hi is not None:
                        c = V.compare(q.ecosystem, q.version, iv.hi)
                        ok &= c < 0 or (iv.hi_incl and c == 0)
                    if ok and positive:
                        hits += 1
                        break
    return hits


SECRET_FILES = 64
SECRET_FILE_BYTES = 1 << 20
SECRET_LAYERS = 8   # coalesced-ingest shape: files grouped per layer


def _secret_corpus(n_files=SECRET_FILES, file_bytes=SECRET_FILE_BYTES):
    """n_files files: half of each file is a shared base (container
    layers repeat blocks across images — the chunk dedup must see SOME
    redundancy, but not a degenerate all-duplicates corpus that would
    reduce the device metric to hashing speed), half is per-file
    unique; a few files carry real-looking keys."""
    import numpy as np
    rng = np.random.default_rng(3)
    corpus = []
    half = file_bytes // 2
    base = rng.integers(32, 127, size=half, dtype=np.uint8).tobytes()
    for i in range(n_files):
        uniq = rng.integers(32, 127, size=half, dtype=np.uint8) \
            .tobytes()
        body = bytearray(base + uniq)
        if i % 8 == 0:
            body[5000:5004] = b"AKIA"
            body[5004:5020] = b"IOSFODNN7EXAMPLE"
        corpus.append((f"f{i}.txt", bytes(body)))
    return corpus


def bench_secrets_device(n_files=SECRET_FILES,
                         file_bytes=SECRET_FILE_BYTES):
    """Secrets engine v2 scenario: coalesced-ingest device throughput
    plus the per-phase split, one warm pass.

    The corpus is grouped into SECRET_LAYERS batches and scanned
    through `scan_files_many` — the exact entry fanald's pipelined
    layer walk uses, so the measured launch IS the coalesced path
    (many layers, one device prefilter). Returns a dict:

      secret_mbps_device       keyword-gate MB/s (pack + dispatch +
                               exact-bitmask decode; the device
                               counterpart of `bench_secrets_host`'s
                               bytes.find loop, scanner.go:363-371)
      secret_scan_mbps_device  full scan_files_many MB/s (gate + the
                               regex-only host confirm stage)
      secret_phase_ms          {pack, dedup_dispatch_decode, confirm}
                               — the gate's host packing cost vs the
                               rest of the gate (content-dedup blake2b
                               hashing is HOST work and lives in this
                               bucket with the device dispatch+decode
                               — the split is pack vs gate-remainder,
                               not host vs device) vs the regex tail
      secret_prefilter_path    which engine served the gate
                               ("pallas" | "jnp" | "host")
    """
    from trivy_tpu.metrics import METRICS
    from trivy_tpu.ops import ac
    from trivy_tpu.secret.engine import SecretScanner
    corpus = _secret_corpus(n_files, file_bytes)
    prof0 = _graftprof_snapshot()
    contents = [c for _, c in corpus]
    per_layer = max(1, len(corpus) // SECRET_LAYERS)
    layers = [corpus[i:i + per_layer]
              for i in range(0, len(corpus), per_layer)]
    # small_batch_bytes=0: this scenario MEASURES the device path (the
    # host path has its own bench) — without it a scaled-down corpus
    # sitting at the production 2 MiB floor would silently flip the
    # whole measurement to bytes.find on any size drift
    scanner = SecretScanner(small_batch_bytes=0)
    total_mb = sum(len(c) for _, c in corpus) / 1e6
    bank = scanner._bank
    # warmup compiles every chunk-batch shape the timed run will use
    scanner.scan_files_many(layers)
    t0 = time.perf_counter()
    ac.pack_chunks(contents, 16384, bank.max_kw_len - 1)
    pack_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    scanner._keyword_masks(contents)
    gate_s = time.perf_counter() - t0
    path_counts = {
        p: METRICS.get("trivy_tpu_secret_prefilter_path_total", path=p)
        for p in ("pallas", "jnp", "host")}
    t0 = time.perf_counter()
    scanner.scan_files_many(layers)
    scan_s = time.perf_counter() - t0
    path_after = {
        p: METRICS.get("trivy_tpu_secret_prefilter_path_total", path=p)
        for p in ("pallas", "jnp", "host")}
    served = next((p for p in ("pallas", "jnp", "host")
                   if path_after[p] > path_counts[p]), "host")
    return {
        "secret_mbps_device": round(total_mb / gate_s, 1),
        "secret_scan_mbps_device": round(total_mb / scan_s, 1),
        "secret_phase_ms": {
            "pack": round(pack_s * 1e3, 1),
            "dedup_dispatch_decode": round(
                max(gate_s - pack_s, 0.0) * 1e3, 1),
            "confirm": round(max(scan_s - gate_s, 0.0) * 1e3, 1),
        },
        "secret_prefilter_path": served,
        "secret_corpus_mb": round(total_mb, 1),
        # the dispatch ledger's aggregate over this scenario's own
        # launches (waste ratio, compile count/ms, bytes moved) —
        # perfcheck-consumable device attribution per round
        "graftprof": _graftprof_delta(prof0),
    }


SERVER_IMAGES = 1000
SERVER_CLIENTS = 16
ARCHIVE_IMAGES = 64
ARCHIVE_LAYERS_PAD = 4       # gzipped pad layers per image
ARCHIVE_PAD_BYTES = 4 << 20  # decompressed pad per layer


def bench_archive_e2e(table):
    """HEADLINE scenario (ROADMAP item 1): wall-clock through the FULL
    archive path — docker-save tar → layer walk → analyzers → cache →
    applier → detect → report JSON — on realistic multi-layer gzipped
    OS images (distinct alpine package sets; pad layers give each
    image the fat-layer decompression profile real images have).

    Two timed passes over the same fixture set: the fanald pipeline
    (concurrent budgeted layer walkers, the default) vs the serial
    parity-oracle walker (`--ingest-serial`), plus hit-count parity
    between them, walker-pool occupancy from the instrumented pass,
    and the per-phase breakdown PR 7 baselined."""
    import io
    import tempfile

    import numpy as np
    from trivy_tpu import types as Ty
    from trivy_tpu.fanal.artifact import ImageArchiveArtifact
    from trivy_tpu.fanal.cache import MemoryCache
    from trivy_tpu.fanal.fixtures import (gz_bytes, sha256_hex,
                                          tar_bytes,
                                          write_docker_archive)
    from trivy_tpu.fanal.pipeline import IngestOptions
    from trivy_tpu.report import build_report, to_json
    from trivy_tpu.scanner import LocalScanner

    rng = np.random.default_rng(13)
    installed_pool = synth_versions(rng, major_lo=4, major_hi=9)
    prof0 = _graftprof_snapshot()

    def installed_db(i):
        names = rng.integers(0, N_PKG_NAMES, 40)
        vers = rng.integers(0, len(installed_pool), 40)
        blocks = []
        for n, v in zip(names, vers):
            blocks.append(f"P:pkg{n:05d}\nV:{installed_pool[int(v)]}\n"
                          f"A:x86_64\no:pkg{n:05d}\nL:MIT\n")
        return ("\n".join(blocks) + "\n").encode()

    os_release = (b'NAME="Alpine Linux"\nID=alpine\n'
                  b'VERSION_ID=3.19.1\n')

    def write_image(path, layer_tars):
        write_docker_archive(
            path, [gz_bytes(t, level=6) for t in layer_tars],
            ["sha256:" + sha256_hex(t) for t in layer_tars],
            repo_tag="bench/img:1")

    def scan_one(path, ingest=None):
        cache = MemoryCache()
        art = ImageArchiveArtifact(path, cache, scanners=("vuln",),
                                   ingest=ingest)
        ref = art.inspect()
        scanner = LocalScanner(cache, table)
        try:
            results, os_info = scanner.scan(
                ref.name, ref.id, ref.blob_ids,
                Ty.ScanOptions(scanners=("vuln",)))
        finally:
            # one scanner per image: without close() the engine's
            # idle executor threads accumulate across the whole run
            scanner.close()
        rep = build_report(ref.name, "container_image", results,
                           os_info, metadata=Ty.Metadata())
        out = io.StringIO()
        out.write(to_json(rep))
        return sum(len(r.vulnerabilities) for r in results)

    pipeline_opts = IngestOptions()
    serial_opts = IngestOptions(enabled=False)
    # pad layers are shared across images and COMPRESSIBLE (real layer
    # content — docs, configs, locale data — compresses ~5-10×): the
    # walk cost is then gzip inflate, which the pipeline streams
    # straight off the archive (no buffer-then-decompress copy) and
    # overlaps across layer walkers, zlib releasing the GIL; only the
    # apk layer differs per image
    line = (b"Name: pkg-%05d  Version: 1.2.%d  License: MIT  "
            b"Description: benchmark filler line for layer padding "
            b"sum=%s\n")
    # the deterministic digest suffix keeps each line unique so the
    # pad really compresses ~6x (pure repeated text deflates 35x,
    # which understates per-byte inflate cost)
    import hashlib as _hl
    pad_raw = b"".join(
        line % (k, k % 10, _hl.sha256(b"pad%d" % k).hexdigest()[:16]
                .encode())
        for k in range(ARCHIVE_PAD_BYTES // (len(line) + 14) + 1)
    )[:ARCHIVE_PAD_BYTES]
    pad_tars = [tar_bytes({f"usr/share/doc/pad{k}.txt": pad_raw})
                for k in range(ARCHIVE_LAYERS_PAD)]
    os_tar = tar_bytes({"etc/os-release": os_release})

    with tempfile.TemporaryDirectory() as td:
        paths = []
        for i in range(ARCHIVE_IMAGES):
            p = os.path.join(td, f"img{i}.tar")
            write_image(p, [os_tar,
                            tar_bytes({"lib/apk/db/installed":
                                       installed_db(i)})] + pad_tars)
            paths.append(p)
        # warm EVERY image once: per-image package sets can land in
        # different bucket-ladder shapes, and whichever timed pass
        # runs first would otherwise eat those compiles — the
        # pipeline-vs-serial ratio must compare walks, not jit order
        for p in paths:
            scan_one(p, pipeline_opts)
        t0 = time.perf_counter()
        hits = sum(scan_one(p, pipeline_opts) for p in paths[1:])
        dt = time.perf_counter() - t0
        t1 = time.perf_counter()
        hits_serial = sum(scan_one(p, serial_opts)
                          for p in paths[1:])
        dt_serial = time.perf_counter() - t1
        # graftwatch attribution pass (UNTIMED — recording arms the
        # detect engine's fence): a subset re-scan under the collector
        # yields the walker/analyzer/applier split plus walker-pool
        # occupancy (layer-walk busy time / walkers × wall)
        from trivy_tpu.obs import COLLECTOR
        attr_paths = paths[:16]
        COLLECTOR.enable()
        ta = time.perf_counter()
        try:
            for p in attr_paths:
                scan_one(p, pipeline_opts)
            phase = COLLECTOR.phase_totals()
        finally:
            COLLECTOR.disable()
        attr_wall_ms = (time.perf_counter() - ta) * 1e3

    def ms(name):
        return phase.get(name, {}).get("total_ms", 0.0)

    breakdown = {
        # pipeline mode: layer-walk spans run on walker threads and
        # analyzer dispatches on the analyzer pool — the two overlap,
        # so they are reported side by side (not netted like the
        # pre-fanald serial breakdown)
        "walker_ms": round(ms("fanal.layer_walk"), 3),
        "analyzer_ms": round(ms("fanal.analyze"), 3),
        "applier_ms": round(ms("fanal.apply_layers"), 3),
        "cache_check_ms": round(ms("fanal.cache_check"), 3),
        "detect_ms": round(ms("scan.detect"), 3),
        "assemble_results_ms": round(ms("scan.assemble_results"), 3),
        "images": len(attr_paths),
        "pipelined": True,
    }
    ips = (ARCHIVE_IMAGES - 1) / dt
    ips_serial = (ARCHIVE_IMAGES - 1) / dt_serial
    return {
        "graftprof": _graftprof_delta(prof0),
        "images_per_sec_archive_e2e": round(ips, 2),
        "images_per_sec_archive_serial": round(ips_serial, 2),
        "archive_pipeline_speedup": round(ips / max(ips_serial, 1e-9),
                                          2),
        "archive_hits_parity": bool(hits == hits_serial),
        "walker_pool_occupancy": round(
            ms("fanal.layer_walk") /
            max(pipeline_opts.n_walkers() * attr_wall_ms, 1e-9), 3),
        "walkers": pipeline_opts.n_walkers(),
        "archive_layers": 2 + ARCHIVE_LAYERS_PAD,
        "archive_phase_ms": breakdown,
    }


def bench_server(table, clients=SERVER_CLIENTS, images=SERVER_IMAGES,
                 detect_opts=None, warm=32, tenant_of=None):
    """BASELINE config-3 shape: images/s through the FULL server path —
    HTTP PutBlob + Scan per image (RPC codec, cache, applier, detect,
    assembly) against an in-process scan server, `clients` concurrent
    clients the way a registry sweep drives the reference's
    client/server mode (reference pkg/rpc + server.ScanServer).
    `detect_opts` (SchedOptions) configures detectd — None keeps the
    server default (coalescing on). `tenant_of` (image index → tenant
    id) stamps X-Trivy-Tenant per request so graftcost scenarios can
    measure per-tenant attribution through the real HTTP path."""
    import tempfile
    import urllib.request
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np
    from trivy_tpu.server.listen import serve_background

    rng = np.random.default_rng(9)
    installed_pool = synth_versions(rng, major_lo=4, major_hi=9)
    blobs = []
    for i in range(images):
        names = rng.integers(0, N_PKG_NAMES, PKGS_PER_IMAGE)
        pkgs = [{"Name": f"pkg{n:05d}",
                 "Version": installed_pool[int(v)],
                 "SrcName": f"pkg{n:05d}",
                 "SrcVersion": installed_pool[int(v)]}
                for n, v in zip(names, rng.integers(
                    0, len(installed_pool), PKGS_PER_IMAGE))]
        blobs.append({
            "SchemaVersion": 2, "DiffID": f"sha256:{i:064x}",
            "OS": {"Family": "alpine", "Name": "3.19.1"},
            "PackageInfos": [{"FilePath": "lib/apk/db/installed",
                              "Packages": pkgs}],
        })

    with tempfile.TemporaryDirectory() as cache_dir:
        httpd, _state = serve_background("127.0.0.1", 0, table,
                                         cache_dir,
                                         detect_opts=detect_opts)
        port = httpd.server_address[1]
        base = f"http://127.0.0.1:{port}"

        def post(route, doc, tenant=""):
            req = urllib.request.Request(
                base + route, data=json.dumps(doc).encode(),
                headers={"Content-Type": "application/json",
                         **({"X-Trivy-Tenant": tenant}
                            if tenant else {})},
                method="POST")
            with urllib.request.urlopen(req, timeout=300) as r:
                return json.loads(r.read())

        def scan_one(i):
            tenant = tenant_of(i) if tenant_of else ""
            diff = blobs[i]["DiffID"]
            post("/twirp/trivy.cache.v1.Cache/PutBlob",
                 {"diff_id": diff, "blob_info": blobs[i]}, tenant)
            out = post("/twirp/trivy.scanner.v1.Scanner/Scan",
                       {"target": f"img{i}", "artifact_id": diff,
                        "blob_ids": [diff],
                        "options": {"scanners": ["vuln"]}}, tenant)
            return sum(len(r.get("Vulnerabilities") or [])
                       for r in out.get("results", []))

        try:
            # serial warmup first: per-request shapes land in a few
            # ladder pair buckets, and 16 clients racing the first
            # compiles of each bucket stalls the whole pool
            for i in range(warm):
                scan_one(i)
            with ThreadPoolExecutor(clients) as pool:
                t0 = time.perf_counter()
                hits = sum(pool.map(scan_one, range(warm, images)))
                dt = time.perf_counter() - t0
        finally:
            httpd.shutdown()
            httpd.server_close()
            _state.close()
    return (images - warm) / dt, hits


SERVER_CONC_IMAGES = 320
SERVER_CONC_CLIENTS = (1, 4, 16)


def _occupancy_snapshot():
    from trivy_tpu.metrics import METRICS
    _row, total, count = METRICS.hist_get(
        "trivy_tpu_batch_occupancy_ratio")
    return total, count


def _graftprof_snapshot():
    from trivy_tpu.obs.perf import LEDGER
    return LEDGER.aggregate()


def _tenant_device_ms_snapshot():
    from trivy_tpu.obs import cost as _cost
    return {t: row["device_ms"]
            for t, row in _cost.TENANTS.table().items()}


def _tenant_device_ms_shares(before):
    """graftcost tail block: each tenant's share of the device ms
    attributed during one scenario window (None when the window
    attributed nothing — e.g. a pure-host backend)."""
    after = _tenant_device_ms_snapshot()
    delta = {t: after.get(t, 0.0) - before.get(t, 0.0) for t in after}
    delta = {t: d for t, d in delta.items() if d > 1e-9}
    total = sum(delta.values())
    if total <= 0:
        return None
    return {t: round(d / total, 4) for t, d in sorted(delta.items())}


def _graftprof_delta(before):
    """graftprof ledger aggregate covering ONE scenario: the counter
    deltas since `before` (= _graftprof_snapshot() at scenario start),
    with the waste ratio recomputed over just this window's rows —
    the per-scenario block perfcheck diffs across bench rounds."""
    after = _graftprof_snapshot()

    def diff(a, b):
        out = {}
        for k, v in a.items():
            if isinstance(v, dict):
                out[k] = diff(v, b.get(k) if isinstance(b.get(k), dict)
                              else {})
            elif isinstance(v, (int, float)) \
                    and not isinstance(v, bool):
                ov = b.get(k)
                out[k] = round(v - ov, 3) \
                    if isinstance(ov, (int, float)) else v
        return out

    d = diff(after, before)
    real = d.get("real_rows") or 0
    padded = d.get("padded_rows") or 0
    d["padding_waste_ratio"] = round(1.0 - real / padded, 4) \
        if padded else None
    # shape-set size is a level, not a counter — report the current one
    d["distinct_shapes"] = after.get("distinct_shapes")
    return d


def bench_server_concurrency(table):
    """detectd acceptance scenario: the server path swept over
    c ∈ {1, 4, 16} concurrent clients with the coalescing scheduler on,
    plus the c=16 point with per-request dispatch (scheduler disabled —
    the pre-detectd path), each with the mean per-dispatch occupancy
    over the point's own dispatches. `coalesce_speedup_c16` is the
    headline: images/s at c=16 coalesced ÷ uncoalesced.

    Coalesced points run with --detect-warmup semantics (the bucket
    ladder pre-compiled at server boot): merged dispatches land on
    rungs the per-request serial warmup never visits, and paying
    those XLA compiles mid-measurement charges a boot cost to the
    steady state (measured 2x distortion on a cold first point)."""
    from trivy_tpu.detect.sched import SchedOptions

    coalesced = SchedOptions(warmup=True, warmup_max_pairs=1 << 15)

    def point(clients, detect_opts, tenant_of=None):
        from trivy_tpu.metrics import METRICS
        s0, n0 = _occupancy_snapshot()
        b0 = METRICS.get("trivy_tpu_detect_batches_total")
        ips, hits = bench_server(table, clients=clients,
                                 images=SERVER_CONC_IMAGES,
                                 detect_opts=detect_opts, warm=16,
                                 tenant_of=tenant_of)
        s1, n1 = _occupancy_snapshot()
        b1 = METRICS.get("trivy_tpu_detect_batches_total")
        occ = (s1 - s0) / (n1 - n0) if n1 > n0 else None
        return {"ips": round(ips, 1), "hits": hits,
                "occ": round(occ, 4) if occ is not None else None,
                # device dispatches per image: the coalescing effect
                # itself, independent of how host-bound the backend is
                "dpi": round((b1 - b0) / SERVER_CONC_IMAGES, 3)}

    out = {}
    hits_ref = None
    for c in SERVER_CONC_CLIENTS:
        # the widest point runs with a 3-tenant round-robin so the
        # tail reports graftcost's per-tenant device-ms split through
        # the real coalescing path (the header costs nothing to the
        # other points' comparability)
        tenant_of = (lambda i: f"bench-t{i % 3}") \
            if c == max(SERVER_CONC_CLIENTS) else None
        shares0 = _tenant_device_ms_snapshot() if tenant_of else None
        p = point(c, coalesced, tenant_of)
        if tenant_of:
            out["tenant_device_ms_share"] = \
                _tenant_device_ms_shares(shares0)
        out[f"c{c}"] = p["ips"]
        out[f"c{c}_mean_occupancy"] = p["occ"]
        out[f"c{c}_dispatches_per_image"] = p["dpi"]
        hits_ref = p["hits"] if hits_ref is None else hits_ref
        if p["hits"] != hits_ref:
            out["parity_ok"] = False
    pu = point(16, SchedOptions(enabled=False))
    out["c16_uncoalesced"] = pu["ips"]
    out["c16_uncoalesced_mean_occupancy"] = pu["occ"]
    out["c16_uncoalesced_dispatches_per_image"] = pu["dpi"]
    out.setdefault("parity_ok", pu["hits"] == hits_ref)
    if pu["ips"]:
        out["coalesce_speedup_c16"] = round(out["c16"] / pu["ips"], 2)
    # graftcost overhead A/B: back-to-back c=16 coalesced points with
    # attribution off then on — what the ledger + apportionment
    # machinery itself costs the serving path. Adjacent runs, not a
    # compare against the sweep's earlier c16 point: by here every
    # compile/cache warming has happened, so the pair differs only by
    # the attribution switch (and the residual later-is-warmer drift
    # favors the ON arm, which UNDERSTATES overhead — the stable side
    # to err on for a hard gate). perfcheck gates this on an absolute
    # cap (cost_overhead_pct < 2), not relative drift.
    # Two alternating off/on pairs: linear drift (caches, allocator,
    # CPU thermal) hits both arms equally and cancels in the means.
    from trivy_tpu.obs import cost as _cost
    off_ips, on_ips = [], []
    for _ in range(2):
        _cost.set_attribution_enabled(False)
        try:
            off_ips.append(point(16, coalesced)["ips"])
        finally:
            _cost.set_attribution_enabled(True)
        on_ips.append(point(16, coalesced)["ips"])
    off_mean = sum(off_ips) / len(off_ips)
    on_mean = sum(on_ips) / len(on_ips)
    if off_mean and on_mean:
        out["cost_overhead_pct"] = round(
            max(0.0, (1.0 - on_mean / off_mean) * 100.0), 2)
    return out


DEGRADED_IMAGES = 192   # subset: the python-side host join bounds this


def bench_degraded_mode(table, images):
    """graftguard scenario: (a) host-fallback join throughput with the
    breaker forced open vs the device path on the same subset, and
    (b) p99 per-image detect latency under flaky(0.05) dispatch faults
    (each flake costs one breaker round-trip plus a host recompute —
    the tail a production SLO would feel). Hit parity across all three
    passes is recorded: degraded mode must never change findings."""
    from trivy_tpu.detect.engine import BatchDetector
    from trivy_tpu.resilience import FAILPOINTS, GUARD

    sub = images[:DEGRADED_IMAGES]
    det = BatchDetector(table)
    try:
        run_device(det, sub)   # warm compiles out of the timed pass
        t0 = time.perf_counter()
        hits_dev = run_device(det, sub)
        dev_s = time.perf_counter() - t0

        # force degraded mode and HOLD it: with the default 5 s reset
        # window a half-open probe would flip the pass back onto the
        # healthy device mid-measurement and overstate host throughput
        GUARD.configure(reset_timeout_s=3600.0)
        GUARD.breaker.trip()
        t0 = time.perf_counter()
        hits_host = run_device(det, sub)
        host_s = time.perf_counter() - t0
        GUARD.breaker.reset()

        # seeded 5% dispatch flakes; short reset window so the breaker
        # exercises open→half-open→closed repeatedly during the sweep
        GUARD.configure(reset_timeout_s=0.05)
        FAILPOINTS.set("detect.dispatch", "flaky", 0.05, seed=9)
        lats = []
        hits_flaky = 0
        for img in sub:
            t1 = time.perf_counter()
            hits_flaky += sum(len(h) for h in det.detect_many([img]))
            lats.append(time.perf_counter() - t1)
        lats.sort()
        p99 = lats[min(len(lats) - 1, int(len(lats) * 0.99))]
        return {
            "device_ips": round(len(sub) / dev_s, 2),
            "host_fallback_ips": round(len(sub) / host_s, 2),
            "fallback_slowdown": round(host_s / dev_s, 2),
            "flaky05_p99_ms": round(p99 * 1e3, 2),
            "flaky05_mean_ms": round(
                sum(lats) / len(lats) * 1e3, 2),
            "parity_ok": bool(hits_host == hits_dev
                              and hits_flaky == hits_dev),
        }
    finally:
        # an exception mid-scenario must not leave global fault
        # injection armed (or the breaker held open) for every
        # subsequent bench in this process
        FAILPOINTS.configure("")
        GUARD.breaker.reset()
        GUARD.configure(reset_timeout_s=5.0)
        det.close()


MESH_DEGRADED_IMAGES = 192   # subset: mesh joins gather synchronously


def bench_mesh_degraded(table, images):
    """meshguard scenario: detect throughput on the full N-device mesh
    vs the shrunk N-1 mesh (one fault domain lost and re-meshed, the
    steady state after a shrink rebuild) — the cost of losing one chip
    should be ~1/N of throughput, not the cliff down to the host
    fallback. Hit parity across both meshes and the single-chip path
    is recorded: a shrunk mesh must never change findings."""
    import jax

    from trivy_tpu.detect.engine import BatchDetector
    from trivy_tpu.parallel.mesh import MeshDetector, mesh_from_devices

    devs = jax.devices()
    if len(devs) < 2:
        return None   # nothing to shrink on a single-device backend
    n = min(len(devs), 4)
    db_pref = 2 if n % 2 == 0 else 1
    sub = images[:MESH_DEGRADED_IMAGES]

    single = BatchDetector(table)
    try:
        hits_ref = run_device(single, sub)
    finally:
        single.close()

    def point(k):
        det = MeshDetector(table, mesh_from_devices(devs[:k], db_pref))
        try:
            run_device(det, sub)   # warm the partition compiles
            t0 = time.perf_counter()
            hits = run_device(det, sub)
            return len(sub) / (time.perf_counter() - t0), hits
        finally:
            det.close()

    full_ips, full_hits = point(n)
    deg_ips, deg_hits = point(n - 1)
    return {
        "devices": n,
        "full_ips": round(full_ips, 2),
        "degraded_ips": round(deg_ips, 2),
        "degraded_slowdown": round(full_ips / deg_ips, 3)
        if deg_ips else None,
        "parity_ok": bool(full_hits == hits_ref
                          and deg_hits == hits_ref),
    }


TABLE_SWEEP_POINTS = (("small", 2000), ("mid", 8000), ("big", 32000))
TABLE_SWEEP_IMAGES = 48
TABLE_SWEEP_PKGS = 40
SWEEP_TRAFFIC_REQS = 32   # paced narrow-band requests per prefetch mode


def _sweep_prefetch_traffic(table, bounds, budget_mb, inst_pool):
    """graftfeed admission-aware prefetch under paced random traffic
    on the BIG streamed point. Requests alternate WIDE (queries
    spread over the whole table — a slow all-slice walk) and NARROW
    (queries in one random ~2-slice hash band): the narrow request is
    submitted a fraction into the wide one's round, so it sits queued
    (pending) while that round walks — exactly the window detectd's
    between-rounds peek reads — and with prefetch on, its band's
    slices are warm when its own round starts. The stream ledger's
    cold slice waits then compare the two modes over byte-identical
    traffic and pacing: `prefetch_cold_waits` < `noprefetch_cold_waits`
    is the mechanism working."""
    import numpy as np

    from trivy_tpu.detect.engine import PkgQuery
    from trivy_tpu.detect.sched import DispatchScheduler, SchedOptions
    from trivy_tpu.obs.perf import LEDGER
    from trivy_tpu.parallel.stream import (StreamingDetector,
                                           StreamOptions)

    n_rows = len(table)
    n_slices = int(bounds.size - 1)
    r = np.random.default_rng(31)
    # the table is HASH-sorted, so sweepNNNNNN names scatter over the
    # row space — recover each name's row through the same hash order
    # _prepare uses, then group names by the slice their bucket lands
    # in, so a "narrow" request really touches one slice
    from trivy_tpu.native import fnv1a64_batch
    names = [f"sweep{i:06d}" for i in range(n_rows)]
    hv = np.asarray(fnv1a64_batch(
        [SOURCE.encode() + b"\x00" + n.encode() for n in names]),
        np.uint64)
    rows = np.searchsorted(table.hash_u64, hv, side="left")
    slice_of = np.clip(np.searchsorted(bounds, rows, "right") - 1,
                       0, n_slices - 1)

    def queries(name_idx):
        vs = r.integers(0, len(inst_pool), len(name_idx))
        return [PkgQuery(source=SOURCE, ecosystem="alpine",
                         name=names[int(k)],
                         version=inst_pool[int(v)])
                for k, v in zip(name_idx, vs)]

    wide, narrow = [], []
    for _ in range(SWEEP_TRAFFIC_REQS // 2):
        wide.append(queries(
            r.integers(0, n_rows, 4 * TABLE_SWEEP_PKGS)))
        pool = np.nonzero(slice_of == int(r.integers(0, n_slices)))[0]
        narrow.append(queries(r.choice(pool, TABLE_SWEEP_PKGS)))

    def run(prefetch_on):
        # resident=6 so the admission peek's warmups coexist with the
        # walk's own tail prefetch instead of evicting it (bounds stay
        # the big point's plan — resident here only sizes the cache,
        # not the slice count)
        opts = StreamOptions(device_budget_mb=budget_mb, resident=6)
        det = StreamingDetector(table, opts, bounds=bounds)
        sched = DispatchScheduler(
            det, SchedOptions(coalesce_wait_ms=0.0,
                              prefetch=prefetch_on))
        try:
            # stagger off the measured wide-round time: the narrow
            # request must land DURING the wide one's round, because
            # detectd peeks only the requests queued behind the round
            # it just dispatched. Warm EVERY request once first (each
            # pair-count rung compiles its own program) — a compile-
            # inflated measurement would overshoot the walk and the
            # narrow request would always arrive too late
            for qs in wide + narrow:
                sched.detect_many([qs])
            t0 = time.perf_counter()
            sched.detect_many([wide[0]])
            stagger_s = (time.perf_counter() - t0) * 0.25
            up0 = dict(LEDGER.shard_upload_stats().get("stream", {}))
            for w_qs, n_qs in zip(wide, narrow):
                f1 = sched.submit([w_qs])
                time.sleep(stagger_s)
                f2 = sched.submit([n_qs])
                f1.result()
                f2.result()   # drain: pair boundaries stay clean
            up1 = LEDGER.shard_upload_stats().get("stream", {})
        finally:
            sched.close()
            det.close()
        return up1.get("cold_waits", 0) - up0.get("cold_waits", 0)

    return {"prefetch_cold_waits": run(True),
            "noprefetch_cold_waits": run(False)}


def bench_table_sweep():
    """graftstream scenario (ROADMAP item 4): scan ips vs table_rows
    sweeping past the per-device budget cliff. The budget is pinned so
    the SMALL table fits resident while mid/big exceed it (4× and 16×
    the small footprint) — exactly the larger-than-HBM regime the
    shard-streaming detector exists for. Per point: resident-path ips,
    streamed-path ips (double-buffered slice walk, slice count from
    the budget), hit parity between the two (bit-identity is the hard
    contract), and the shard_upload ledger's stall/bytes for the
    streamed pass — upload_stall after the first pass ≈ 0 is the
    overlap working. Flat keys so perfcheck diffs each leaf across
    rounds."""
    import numpy as np

    from trivy_tpu.db.table import RawAdvisory, build_table
    from trivy_tpu.detect.engine import BatchDetector, PkgQuery
    from trivy_tpu.obs.perf import LEDGER
    from trivy_tpu.parallel.stream import (StreamingDetector,
                                           StreamOptions, plan_slices)

    rng = np.random.default_rng(29)
    fixed_pool = synth_versions(rng, n=500, major_lo=0, major_hi=6)
    inst_pool = synth_versions(rng, n=500, major_lo=4, major_hi=9)

    def synth_table(n_rows):
        raw = [RawAdvisory(
            source=SOURCE, ecosystem="alpine",
            pkg_name=f"sweep{i:06d}",
            vuln_id=f"CVE-2025-{i:06d}",
            fixed_version=fixed_pool[i % len(fixed_pool)])
            for i in range(n_rows)]
        return build_table(raw)

    def workload(n_rows, seed):
        r = np.random.default_rng(seed)
        return [[PkgQuery(source=SOURCE, ecosystem="alpine",
                          name=f"sweep{int(k):06d}",
                          version=inst_pool[int(v)])
                 for k, v in zip(
                     r.integers(0, n_rows, TABLE_SWEEP_PKGS),
                     r.integers(0, len(inst_pool),
                                TABLE_SWEEP_PKGS))]
                for _ in range(TABLE_SWEEP_IMAGES)]

    out = {}
    budget_mb = None
    for label, n_rows in TABLE_SWEEP_POINTS:
        table = synth_table(n_rows)
        if budget_mb is None:
            # resident slice pair ≤ budget ⇒ the small table stays
            # resident (dev ≤ budget/2); mid/big cross the cliff
            budget_mb = table.device_nbytes() * 2.2 / (1 << 20)
            out["budget_mb"] = round(budget_mb, 3)
        batches = workload(n_rows, 1000 + n_rows)
        out[f"{label}_rows"] = len(table)

        resident = BatchDetector(table)
        try:
            resident.detect_many(batches)          # warm compiles
            t0 = time.perf_counter()
            hits_res = sum(len(h) for h in
                           resident.detect_many(batches))
            res_s = time.perf_counter() - t0
        finally:
            resident.close()
        out[f"{label}_resident_ips"] = round(
            TABLE_SWEEP_IMAGES / res_s, 2)

        opts = StreamOptions(device_budget_mb=budget_mb)
        bounds = plan_slices(table, opts)
        if bounds is None:
            # below the cliff: the streamed config runs resident
            out[f"{label}_slices"] = 0
            continue
        streamed = StreamingDetector(table, opts, bounds=bounds)
        out[f"{label}_slices"] = streamed.n_slices
        try:
            streamed.detect_many(batches)          # warm + first pass
            up0 = dict(LEDGER.shard_upload_stats().get("stream", {}))
            t0 = time.perf_counter()
            hits_str = sum(len(h) for h in
                           streamed.detect_many(batches))
            str_s = time.perf_counter() - t0
            up1 = LEDGER.shard_upload_stats().get("stream", {})
        finally:
            streamed.close()
        out[f"{label}_streamed_ips"] = round(
            TABLE_SWEEP_IMAGES / str_s, 2)
        out[f"{label}_stream_slowdown"] = round(
            out[f"{label}_resident_ips"]
            / out[f"{label}_streamed_ips"], 3) \
            if out[f"{label}_streamed_ips"] else None
        out[f"{label}_parity_ok"] = bool(hits_res == hits_str)
        out[f"{label}_upload_stall_ms"] = round(
            up1.get("stall_ms", 0.0) - up0.get("stall_ms", 0.0), 2)
        out[f"{label}_upload_mb"] = round(
            (up1.get("bytes", 0) - up0.get("bytes", 0)) / (1 << 20),
            2)
        out[f"{label}_cold_waits"] = \
            up1.get("cold_waits", 0) - up0.get("cold_waits", 0)
        if label == "big":
            # graftfeed: cold-wait reduction from the admission-aware
            # slice prefetch under paced random traffic
            out.update(_sweep_prefetch_traffic(table, bounds,
                                               budget_mb, inst_pool))
    return out


FLEET_REPLICAS = 2
FLEET_IMAGES = 192
FLEET_CLIENTS = 8
FLEET_WARM = 16
FLEET_KILL_AT = 64   # image index whose worker shoots replica 0


def bench_server_fleet(table):
    """graftfleet scenario: N in-process server replicas sharing one
    (fake) redis cache backend behind the scan router. Three results:

      * aggregate images/s through the router at 1 replica vs N
        (`scaling` = ipsN / ips1);
      * the kill drill — replica 0 shot mid-load at c=8 must yield
        ZERO failed requests with per-image results bit-identical to
        the unfaulted run (ring failover + the per-replica breaker);
      * readmission — the killed replica restarted on its port is
        readmitted by the /healthz probe loop.
    """
    import hashlib
    import urllib.request
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    sys.path.insert(0, os.path.join(REPO, "tests"))
    from helpers import FakeRedis

    from trivy_tpu.fleet import (ReplicaOptions, RouterOptions,
                                 serve_router_background)
    from trivy_tpu.metrics import METRICS
    from trivy_tpu.resilience import RetryPolicy
    from trivy_tpu.server.listen import serve_background

    rng = np.random.default_rng(11)
    installed_pool = synth_versions(rng, major_lo=4, major_hi=9)
    blobs = []
    for i in range(FLEET_IMAGES):
        names = rng.integers(0, N_PKG_NAMES, PKGS_PER_IMAGE)
        pkgs = [{"Name": f"pkg{n:05d}",
                 "Version": installed_pool[int(v)],
                 "SrcName": f"pkg{n:05d}",
                 "SrcVersion": installed_pool[int(v)]}
                for n, v in zip(names, rng.integers(
                    0, len(installed_pool), PKGS_PER_IMAGE))]
        blobs.append({
            "SchemaVersion": 2, "DiffID": f"sha256:{i:064x}",
            "OS": {"Family": "alpine", "Name": "3.19.1"},
            "PackageInfos": [{"FilePath": "lib/apk/db/installed",
                              "Packages": pkgs}],
        })

    def post(base, route, doc, tenant=""):
        req = urllib.request.Request(
            base + route, data=json.dumps(doc).encode(),
            headers={"Content-Type": "application/json",
                     **({"X-Trivy-Tenant": tenant} if tenant else {})},
            method="POST")
        with urllib.request.urlopen(req, timeout=300) as r:
            return r.read()

    def run_point(n_replicas, kill=False):
        fake = FakeRedis()
        cache_url = f"redis://127.0.0.1:{fake.port}"
        replicas = []   # [url, httpd, state, port]
        for _ in range(n_replicas):
            httpd, state = serve_background(
                "127.0.0.1", 0, table, cache_dir="",
                cache_backend=cache_url)
            port = httpd.server_address[1]
            replicas.append([f"http://127.0.0.1:{port}", httpd,
                             state, port])
        router, rstate = serve_router_background(
            "127.0.0.1", 0, [r[0] for r in replicas],
            RouterOptions(
                retry=RetryPolicy(attempts=3, base_delay_s=0.05,
                                  max_delay_s=0.5, budget_s=10.0),
                replica=ReplicaOptions(fail_threshold=2,
                                       reset_timeout_ms=500.0,
                                       probe_interval_ms=100.0)))
        base = f"http://127.0.0.1:{router.server_address[1]}"
        digests: dict[int, str] = {}
        failed: list = []
        f0 = METRICS.get("trivy_tpu_fleet_failovers_total")

        def scan_one(i):
            if kill and i == FLEET_KILL_AT:
                url, httpd, state, _port = replicas[0]
                httpd.shutdown()
                httpd.server_close()
                state.close()
            try:
                # 3-tenant round-robin: the router relays the header
                # per hop, so the tail's per-tenant device-ms shares
                # cover the full fleet path (failover included)
                tenant = f"bench-t{i % 3}"
                diff = blobs[i]["DiffID"]
                post(base, "/twirp/trivy.cache.v1.Cache/PutBlob",
                     {"diff_id": diff, "blob_info": blobs[i]}, tenant)
                raw = post(base,
                           "/twirp/trivy.scanner.v1.Scanner/Scan",
                           {"target": f"img{i}", "artifact_id": diff,
                            "blob_ids": [diff],
                            "options": {"scanners": ["vuln"]}},
                           tenant)
                # canonical digest: bit-identity is compared per image
                # across the faulted and unfaulted runs
                digests[i] = hashlib.sha256(json.dumps(
                    json.loads(raw), sort_keys=True).encode()) \
                    .hexdigest()
            except Exception as e:  # noqa: BLE001 — counted
                failed.append((i, f"{type(e).__name__}: {e}"))

        readmitted = None
        try:
            for i in range(FLEET_WARM):   # serial compile warmup
                scan_one(i)
            with ThreadPoolExecutor(FLEET_CLIENTS) as pool:
                t0 = time.perf_counter()
                list(pool.map(scan_one,
                              range(FLEET_WARM, FLEET_IMAGES)))
                dt = time.perf_counter() - t0
            if kill:
                # restart the victim on its port: the /healthz probe
                # loop must readmit it (its ring arcs snap back)
                url, _httpd, _state, port = replicas[0]
                httpd2, state2 = serve_background(
                    "127.0.0.1", port, table, cache_dir="",
                    cache_backend=cache_url)
                replicas[0][1], replicas[0][2] = httpd2, state2
                deadline = time.time() + 10.0
                while time.time() < deadline and \
                        url in rstate.supervisor.lost():
                    time.sleep(0.1)
                readmitted = url not in rstate.supervisor.lost()
        finally:
            router.shutdown()
            router.server_close()
            rstate.close()
            for _url, httpd, state, _port in replicas:
                try:
                    httpd.shutdown()
                    httpd.server_close()
                    state.close()
                except Exception:  # noqa: BLE001 — already killed
                    pass
            fake.close()
        ips = (FLEET_IMAGES - FLEET_WARM) / dt
        failovers = METRICS.get("trivy_tpu_fleet_failovers_total") - f0
        return {"ips": ips, "digests": digests, "failed": failed,
                "failovers": int(failovers), "readmitted": readmitted}

    prof0 = _graftprof_snapshot()
    shares0 = _tenant_device_ms_snapshot()
    one = run_point(1)
    many = run_point(FLEET_REPLICAS)
    drill = run_point(FLEET_REPLICAS, kill=True)
    baseline = many["digests"]
    identical = (not drill["failed"] and not many["failed"]
                 and all(drill["digests"].get(i) == baseline.get(i)
                         for i in range(FLEET_IMAGES)))
    return {
        "graftprof": _graftprof_delta(prof0),
        "tenant_device_ms_share": _tenant_device_ms_shares(shares0),
        "replicas": FLEET_REPLICAS,
        "ips_1_replica": round(one["ips"], 1),
        f"ips_{FLEET_REPLICAS}_replicas": round(many["ips"], 1),
        "scaling": round(many["ips"] / one["ips"], 2)
        if one["ips"] else None,
        "kill_drill": {
            "failed_requests": len(drill["failed"]),
            "bit_identical": bool(identical),
            "failovers": drill["failovers"],
            "readmitted": drill["readmitted"],
        },
    }


DEDUP_IMAGES = 24       # images sharing ONE fat base layer
DEDUP_THIN_PKGS = 8     # per-image thin-layer pip packages
DEDUP_CLIENTS = 8
DEDUP_WARM = 1          # image 0 scans first → base memo entry exists


def _dedup_tables():
    """Self-contained advisory pair for the rolling-swap drill: same
    package namespace, different seeded bounds → different content
    digests AND different results."""
    import numpy as np
    from trivy_tpu.db.table import RawAdvisory, build_table

    def one(seed):
        rng = np.random.default_rng(seed)
        raw, details = [], {}
        for i in range(64):
            vid = f"CVE-2026-B{i:03d}"
            raw.append(RawAdvisory(
                source="alpine 3.19", ecosystem="alpine",
                pkg_name=f"base-pkg-{i}", vuln_id=vid,
                fixed_version=f"{1 + int(rng.integers(0, 4))}."
                              f"{int(rng.integers(0, 10))}.0-r0"))
            details[vid] = {"Title": f"dedup {vid}", "Severity": "HIGH"}
        for i in range(32):
            vid = f"CVE-2026-T{i:03d}"
            lim = f"{1 + int(rng.integers(0, 4))}.{int(rng.integers(0, 10))}.0"
            raw.append(RawAdvisory(
                source="pip::Python", ecosystem="pip",
                pkg_name=f"pip-lib-{i}", vuln_id=vid,
                vulnerable_ranges=f"<{lim}", patched_versions=lim))
            details[vid] = {"Title": f"dedup {vid}", "Severity": "LOW"}
        return build_table(raw, details)

    return one(21), one(22)


def _dedup_dispatch_stage(table):
    """graftfeed stage of the overlap scenario, measured at the
    dispatch layer: the same 24 per-image query batches (64 shared
    base packages + a per-image thin pip tail) submitted as ONE
    detectd request, so the merge sweep sees the duplication
    graftmemo's blob-level memo cannot (mixed units inside one
    dispatch window). Keys:

      * `dispatch_unique_pair_ratio` — unique ÷ real pairs of the
        merged dispatch (the tentpole claim is ≤ 0.5 on this
        workload; unclassified for perfcheck — reported, never gated);
      * `dedup_digest_match` — per-image hit digests bit-identical
        dedup-on vs dedup-off (the correctness contract);
      * `dedup_on_ips` / `dedup_off_ips` — the same pass timed both
        ways;
      * `query_upload_stall_ms` — staged-upload stall over the timed
        pass from the `query_upload` ledger rows: steady state ≈ 0
        means the H2D transfer rode the previous dispatch's compute.
    """
    import hashlib

    from trivy_tpu.detect import feed as _feed
    from trivy_tpu.detect.engine import BatchDetector, PkgQuery
    from trivy_tpu.detect.sched import DispatchScheduler, SchedOptions
    from trivy_tpu.obs.perf import LEDGER

    batches = []
    for i in range(DEDUP_IMAGES):
        qs = [PkgQuery(source="alpine 3.19", ecosystem="alpine",
                       name=f"base-pkg-{k}",
                       version=f"{1 + k % 3}.2.0-r0")
              for k in range(64)]
        qs += [PkgQuery(source="pip::Python", ecosystem="pip",
                        name=f"pip-lib-{(i * 3 + j) % 32}",
                        version=f"{1 + j % 3}.{i % 10}.0")
               for j in range(DEDUP_THIN_PKGS)]
        batches.append(qs)

    def digests(hits_lists):
        return [hashlib.sha256(repr(hits).encode()).hexdigest()
                for hits in hits_lists]

    det = BatchDetector(table)
    try:
        preps = [p for p in (det._prepare(qs) for qs in batches)
                 if p is not None and p.n_pairs]
        total = sum(p.n_pairs for p in preps)
        plan = _feed.plan_from_preps(preps)
        unique = plan.unique_total if plan is not None else total

        def run(dedup_on):
            sched = DispatchScheduler(det,
                                      SchedOptions(dedup=dedup_on))
            try:
                sched.detect_many(batches)   # warm compiles + staging
                up0 = dict(LEDGER.shard_upload_stats()
                           .get("query_upload", {}))
                t0 = time.perf_counter()
                digs = digests(sched.detect_many(batches))
                dt = time.perf_counter() - t0
                up1 = LEDGER.shard_upload_stats() \
                    .get("query_upload", {})
            finally:
                sched.close()
            stall = (up1.get("stall_ms", 0.0)
                     - up0.get("stall_ms", 0.0))
            return digs, DEDUP_IMAGES / dt, stall

        d_on, on_ips, stall_ms = run(True)
        d_off, off_ips, _ = run(False)
    finally:
        det.close()
    return {
        "dispatch_unique_pair_ratio": round(unique / total, 3)
        if total else None,
        "dedup_digest_match": bool(d_on == d_off),
        "dedup_on_ips": round(on_ips, 1),
        "dedup_off_ips": round(off_ips, 1),
        "query_upload_stall_ms": round(stall_ms, 2),
    }


def bench_fleet_dedup():
    """graftmemo scenario: N replicas sharing one layer cache AND one
    detection-result memo behind the router, scanning DEDUP_IMAGES
    images built on ONE common fat base layer (plus a per-image thin
    pip layer). Reports:

      * aggregate ips at 1 vs N replicas (`scaling`) with the
        realistic base-layer overlap;
      * memo economics — hit rate over the timed pass, and the base
        layer's (stores, hits): the tentpole claim is stores == 1
        (detected once fleet-wide) with hits ≈ every later scan;
      * the rolling DB swap — mid-load every replica hot-swaps to a
        different advisory table (kicking redetectd); p99 across the
        swap window, zero failures, and every response's
        X-Trivy-DB-Version consistent with one of the two tables.
    """
    import hashlib
    import urllib.request
    from concurrent.futures import ThreadPoolExecutor

    from trivy_tpu.fanal.cache import MemoryCache
    from trivy_tpu.fleet import (MemoryMemo, ReplicaOptions,
                                 RouterOptions,
                                 serve_router_background)
    from trivy_tpu.metrics import METRICS
    from trivy_tpu.resilience import RetryPolicy
    from trivy_tpu.server.listen import serve_background

    table, table2 = _dedup_tables()
    base_blob = {
        "SchemaVersion": 2, "DiffID": f"sha256:{0xba5e:064x}",
        "OS": {"Family": "alpine", "Name": "3.19.1"},
        "PackageInfos": [{"FilePath": "lib/apk/db/installed",
                          "Packages": [
                              {"Name": f"base-pkg-{i}",
                               "Version": f"{1 + i % 3}.2.0-r0",
                               "SrcName": f"base-pkg-{i}",
                               "SrcVersion": f"{1 + i % 3}.2.0-r0"}
                              for i in range(64)]}],
    }
    thin_blobs = []
    for i in range(DEDUP_IMAGES):
        thin_blobs.append({
            "SchemaVersion": 2, "DiffID": f"sha256:{0x7f1a0000 + i:064x}",
            "Applications": [{
                "Type": "pip", "FilePath": f"app{i}/requirements.txt",
                "Packages": [
                    {"Name": f"pip-lib-{(i * 3 + j) % 32}",
                     "Version": f"{1 + j % 3}.{i % 10}.0"}
                    for j in range(DEDUP_THIN_PKGS)]}],
        })

    def post(base, route, doc):
        req = urllib.request.Request(
            base + route, data=json.dumps(doc).encode(),
            headers={"Content-Type": "application/json"},
            method="POST")
        with urllib.request.urlopen(req, timeout=300) as r:
            return r.status, dict(r.headers), r.read()

    def run_point(n_replicas, rolling_swap=False):
        shared_cache, shared_memo = MemoryCache(), MemoryMemo()
        replicas = []
        for _ in range(n_replicas):
            httpd, state = serve_background(
                "127.0.0.1", 0, table, cache_dir="",
                cache_backend=shared_cache, memo_backend=shared_memo)
            replicas.append((httpd, state))
        router, rstate = serve_router_background(
            "127.0.0.1", 0,
            [f"http://127.0.0.1:{h.server_address[1]}"
             for h, _ in replicas],
            RouterOptions(
                retry=RetryPolicy(attempts=3, base_delay_s=0.05,
                                  max_delay_s=0.5, budget_s=10.0),
                replica=ReplicaOptions(fail_threshold=2,
                                       reset_timeout_ms=500.0,
                                       probe_interval_ms=100.0)))
        base = f"http://127.0.0.1:{router.server_address[1]}"
        failed, lat_ms, versions = [], [], set()

        def scan_one(i):
            t0 = time.perf_counter()
            try:
                art = f"dedup-img-{i}"
                for blob in (base_blob, thin_blobs[i]):
                    post(base, "/twirp/trivy.cache.v1.Cache/PutBlob",
                         {"diff_id": blob["DiffID"],
                          "blob_info": blob})
                code, headers, raw = post(
                    base, "/twirp/trivy.scanner.v1.Scanner/Scan",
                    {"target": art, "artifact_id": art,
                     "blob_ids": [base_blob["DiffID"],
                                  thin_blobs[i]["DiffID"]],
                     "options": {"scanners": ["vuln"]}})
                versions.add(headers.get("X-Trivy-DB-Version") or "")
                return hashlib.sha256(raw).hexdigest()
            except Exception as e:  # noqa: BLE001 — counted
                failed.append((i, f"{type(e).__name__}: {e}"))
                return None
            finally:
                lat_ms.append((time.perf_counter() - t0) * 1e3)

        try:
            for i in range(DEDUP_WARM):
                scan_one(i)
            lat_ms.clear()
            failed.clear()   # a warm-pass failure is not the timed
            # window's failure (it does leave the fleet cold, which
            # the hit-rate/store numbers then show honestly)
            # snapshot AFTER the warm pass: its lookups are misses by
            # design (it exists to seed the base entry) and must not
            # deflate the timed pass's reported hit rate
            h0 = METRICS.get("trivy_tpu_memo_hits_total",
                             backend="memory")
            m0 = METRICS.get("trivy_tpu_memo_misses_total",
                             backend="memory")
            swapper = None
            if rolling_swap:
                def roll():
                    time.sleep(0.05)
                    for _httpd, state in replicas:
                        state.swap_table(table2)
                        time.sleep(0.02)
                import threading
                swapper = threading.Thread(target=roll,
                                           name="dedup-roll")
                swapper.start()
            with ThreadPoolExecutor(DEDUP_CLIENTS) as pool:
                t0 = time.perf_counter()
                list(pool.map(scan_one,
                              range(DEDUP_WARM, DEDUP_IMAGES)))
                dt = time.perf_counter() - t0
            if swapper is not None:
                swapper.join()
            hits = METRICS.get("trivy_tpu_memo_hits_total",
                               backend="memory") - h0
            misses = METRICS.get("trivy_tpu_memo_misses_total",
                                 backend="memory") - m0
            base_stats = shared_memo.key_stats(
                base_blob["DiffID"], table.content_digest())
        finally:
            router.shutdown()
            router.server_close()
            rstate.close()
            for httpd, state in replicas:
                httpd.shutdown()
                httpd.server_close()
                state.close()
        lats = sorted(lat_ms)
        p99 = lats[min(len(lats) - 1, int(len(lats) * 0.99))] \
            if lats else 0.0
        return {
            "ips": (DEDUP_IMAGES - DEDUP_WARM) / dt,
            "failed": failed,
            "memo_hit_rate": round(hits / (hits + misses), 3)
            if hits + misses else None,
            "base_layer": base_stats,
            "p99_ms": round(p99, 1),
            "versions_seen": len(versions - {""}),
        }

    one = run_point(1)
    many = run_point(FLEET_REPLICAS)
    swap = run_point(FLEET_REPLICAS, rolling_swap=True)
    out = {
        "replicas": FLEET_REPLICAS,
        "images": DEDUP_IMAGES,
        "ips_1_replica": round(one["ips"], 1),
        f"ips_{FLEET_REPLICAS}_replicas": round(many["ips"], 1),
        "scaling": round(many["ips"] / one["ips"], 2)
        if one["ips"] else None,
        "memo_hit_rate": many["memo_hit_rate"],
        "base_layer_stores": many["base_layer"]["stores"],
        "base_layer_hits": many["base_layer"]["hits"],
        "rolling_swap": {
            "p99_ms": swap["p99_ms"],
            "failed_requests": len(swap["failed"]),
            "versions_seen": swap["versions_seen"],
        },
    }
    # graftfeed: the same overlap workload at the dispatch layer
    out.update(_dedup_dispatch_stage(table))
    return out


def bench_secrets_host(n_files=SECRET_FILES,
                       file_bytes=SECRET_FILE_BYTES):
    """Host bytes.find gate over the same corpus/keywords (MB/s), and
    the full host-only scan_files pipeline for the same corpus."""
    from trivy_tpu.secret.engine import SecretScanner
    from trivy_tpu.secret.rules import BUILTIN_RULES
    corpus = _secret_corpus(n_files, file_bytes)
    total_mb = sum(len(c) for _, c in corpus) / 1e6
    keywords = sorted({kw.lower().encode() for r in BUILTIN_RULES
                       for kw in r.keywords})
    t1 = time.perf_counter()
    for _, content in corpus:
        low = content.lower()
        for kw in keywords:
            low.find(kw)
    host_s = time.perf_counter() - t1
    scanner = SecretScanner(use_device=False)
    t1 = time.perf_counter()
    scanner.scan_files(corpus)
    scan_s = time.perf_counter() - t1
    return total_mb / host_s, total_mb / scan_s


# ---- device half ---------------------------------------------------------

def _scenarios(table, images, payload):
    """(name, thunk) for every scenario after the headline detect
    pass, in run order; the sbom thunk reads the archive result the
    loop has put in `payload` by then."""
    return [
        ("server", lambda: bench_server(table)),
        ("server_concurrency", lambda: bench_server_concurrency(table)),
        ("degraded_mode", lambda: bench_degraded_mode(table, images)),
        ("mesh_degraded", lambda: bench_mesh_degraded(table, images)),
        ("table_sweep", bench_table_sweep),
        ("server_fleet", lambda: bench_server_fleet(table)),
        ("fleet_dedup", bench_fleet_dedup),
        ("chaos_storm", bench_chaos_storm),
        ("tenant_qos", bench_tenant_qos),
        ("archive_e2e", lambda: bench_archive_e2e(table)),
        ("sbom_ingest", lambda: bench_sbom_ingest(
            table, (payload.get("archive_e2e") or {}).get(
                "images_per_sec_archive_e2e"))),
        ("lib_version", bench_lib_version),
    ]


def run_device_half(table, detector, images) -> tuple[dict, list]:
    """Every device measurement, in this process. → (payload, errors):
    a scenario that raises is recorded under errors, never as a
    number."""
    # warmup/compile over the FULL image set: batches land in different
    # pow2 pair-capacity buckets, and each distinct bucket is its own
    # XLA compilation — a serve-many deployment compiles each once, so
    # the timed pass measures the warm path, not the compiler
    run_device(detector, images)
    # the table's ~1M advisory/interval objects are immutable from here
    # on; freeze them out of the collector so gen2 passes triggered by
    # per-batch Hit allocation don't stall a timed batch (~400ms each)
    import gc
    gc.collect()
    gc.freeze()

    t1 = time.time()
    dev_hits = run_device(detector, images)
    dev_s = time.time() - t1

    host_s, device_s, asm_s, asm_compact_s, n_pairs, transfer = \
        split_timings(detector, images)
    # per-phase graftscope breakdown from an untimed subset pass:
    # recording arms the detect engine's device fence, which serializes
    # the dispatch/transfer overlap — never record during the TIMED
    # pass above, only here where sub_hits (a parity check) is the goal
    from trivy_tpu.obs import COLLECTOR
    COLLECTOR.enable()
    sub_hits = run_device(detector, images[:BASELINE_IMAGES])
    phase_ms = COLLECTOR.phase_totals()
    COLLECTOR.disable()
    secrets = bench_secrets_device()
    payload = {
        "images_per_sec": N_IMAGES / dev_s,
        "dev_hits": dev_hits,
        "sub_hits": sub_hits,
        "host_prep_ms": host_s * 1e3,
        "device_ms": device_s * 1e3,
        "assemble_ms": asm_s * 1e3,
        "assemble_ms_compact": None if asm_compact_s is None
        else asm_compact_s * 1e3,
        "transfer_bytes_per_dispatch": transfer,
        "n_pairs": int(n_pairs),
        "phase_ms": phase_ms,
        "secrets": secrets,
        "secret_mbps_device": secrets["secret_mbps_device"],
        "scan_s": dev_s,
    }
    errors = []
    for name, thunk in _scenarios(table, images, payload):
        try:
            payload[name] = thunk()
        except Exception as e:  # recorded; main exits non-zero
            errors.append(f"{name}: {type(e).__name__}: {e}"[:300])
    server = payload.pop("server", None)
    if server is not None:
        payload["images_per_sec_server"], payload["server_hits"] = server
    # chip-in-the-loop dispatch-ledger aggregate — the graftprof
    # block the round's baselines (and perfcheck diffs) read
    payload["graftprof"] = _graftprof_snapshot()
    return payload, errors


def bench_chaos_storm():
    """graftstorm scenario: one standard seeded multi-fault schedule
    (dispatch hang + device-get flakes + a DB hot swap overlapping at
    c=8) against a single-server topology, reporting p99 latency and
    shed rate UNDER compound chaos plus whether every invariant probe
    (no lost requests, oracle bit-identity, breaker liveness, thread
    hygiene, strict /metrics) held. Uses the storm engine's own small
    table — the scenario measures the resilience stack, not the join."""
    from trivy_tpu.resilience.storm import (Schedule, StormEvent,
                                            StormOptions, run_storm,
                                            storm_table)
    schedule = Schedule(seed=2026, topology="single",
                        horizon_ms=1200.0, events=[
                            StormEvent(at_ms=100.0,
                                       site="detect.dispatch",
                                       mode="hang", arg=150.0,
                                       dur_ms=500.0),
                            StormEvent(at_ms=250.0,
                                       site="detect.device_get",
                                       mode="flaky", arg=0.3, seed=5,
                                       dur_ms=600.0),
                            StormEvent(at_ms=400.0,
                                       kind="swap_table"),
                        ])
    opts = StormOptions(requests=32, concurrency=8,
                        admit_max_active=8, admit_max_queue=8)
    t0 = time.perf_counter()
    report = run_storm(schedule, opts, table=storm_table())
    n = max(len(report.outcomes), 1)
    return {
        "invariants_ok": report.ok,
        "violations": sorted(report.violations),
        "p99_ms": round(report.p99_ms(), 2),
        "shed_rate": round(report.sheds() / n, 3),
        "requests": len(report.outcomes),
        "wall_s": round(time.perf_counter() - t0, 2),
    }


def bench_tenant_qos():
    """graftfair scenario: the adversarial-tenant drill as a bench
    tail — one flooding tenant (20 simultaneous requests) against
    trickling victims at c=8, per-tenant quotas armed. Reports the
    victim p99 ratio vs a flood-free run of the same skeleton (the
    isolation headline: must stay near 1.0, hard-bounded at 3.0 by
    the storm invariant), the victim shed count (must stay 0 — quota
    pressure lands on the flooder only), and the flood's own shed
    rate + whether every overflow shed was a well-formed 429 with a
    finite Retry-After. Storm engine's own table: this measures the
    QoS stack, not the join."""
    from trivy_tpu.resilience.storm import (Schedule, StormEvent,
                                            StormOptions, run_storm,
                                            storm_table)
    table = storm_table()
    opts = StormOptions(requests=16, concurrency=8, tenants=2,
                        admit_tenant_max_active=4,
                        admit_tenant_max_queue=2)
    t0 = time.perf_counter()
    solo = run_storm(Schedule(seed=909, topology="single",
                              horizon_ms=900.0, events=[]),
                     opts, table=table)
    flooded = run_storm(
        Schedule(seed=909, topology="single", horizon_ms=900.0,
                 events=[StormEvent(at_ms=80.0,
                                    kind="adversarial_tenant",
                                    arg=20.0)]),
        opts, table=table)
    flood = flooded.flood_outcomes
    flood_sheds = [o for o in flood if o.status == "shed"]
    solo_p99 = max(solo.p99_ms(), 1e-3)
    return {
        "invariants_ok": flooded.ok and solo.ok,
        "violations": sorted(flooded.violations),
        "victim_p99_ms": round(flooded.p99_ms(), 2),
        "victim_p99_ratio": round(flooded.p99_ms() / solo_p99, 2),
        "victim_sheds": flooded.sheds(),
        "flood_requests": len(flood),
        "flood_shed_rate": round(len(flood_sheds)
                                 / max(1, len(flood)), 3),
        "flood_429_well_formed": all(
            o.code == 429 and o.well_formed for o in flood_sheds),
        "wall_s": round(time.perf_counter() - t0, 2),
    }


SBOM_DOCS = 32
SBOM_PKGS_PER_DOC = 60
SBOM_DUP_SCANS = 16
SBOM_CONCURRENCY = 8


def bench_sbom_ingest(table, archive_ips=None):
    """graftbom scenario: SBOM documents as pure-detect workloads.
    The document IS the inventory, so a ScanSBOM request skips the
    whole fanal walk — the scenario measures docs/s through the RPC
    (decode + detect + report), p99 at c=8, and the memo economics
    the content-addressed blob identity buys: N duplicate documents
    against a memo-wired server must store once and hit N-1 times.
    `archive_ips` (the archive-e2e headline, when that scenario ran)
    anchors the pure-detect-vs-archive ratio in the same tail."""
    import base64
    import threading
    import urllib.request

    import numpy as np
    from trivy_tpu.metrics import METRICS
    from trivy_tpu.server.listen import serve_background

    rng = np.random.default_rng(29)
    pool = synth_versions(rng, major_lo=4, major_hi=9)

    def doc_bytes(i):
        names = rng.integers(0, N_PKG_NAMES, SBOM_PKGS_PER_DOC)
        vers = rng.integers(0, len(pool), SBOM_PKGS_PER_DOC)
        comps = []
        for n, v in zip(names, vers):
            name, ver = f"pkg{int(n):05d}", pool[int(v)]
            purl = f"pkg:apk/alpine/{name}@{ver}?distro=3.19.1"
            comps.append({
                "type": "library", "bom-ref": purl,
                "name": name, "version": ver, "purl": purl,
                "properties": [
                    {"name": "aquasecurity:trivy:PkgType",
                     "value": "alpine"},
                    {"name": "aquasecurity:trivy:SrcName",
                     "value": name},
                    {"name": "aquasecurity:trivy:SrcVersion",
                     "value": ver},
                ]})
        return json.dumps({
            "bomFormat": "CycloneDX", "specVersion": "1.5",
            "serialNumber": f"urn:uuid:bench-sbom-{i}", "version": 1,
            "metadata": {"component": {
                "type": "operating-system", "name": "alpine",
                "version": "3.19.1",
                "properties": [{"name": "aquasecurity:trivy:Type",
                                "value": "alpine"}]}},
            "components": comps,
        }, sort_keys=True).encode()

    docs = [doc_bytes(i) for i in range(SBOM_DOCS)]

    def scan(url, raw, timeout=120):
        body = json.dumps({
            "target": "bench-sbom", "artifact_id": "",
            "kind": "cyclonedx",
            "document": base64.b64encode(raw).decode(),
            "options": {"scanners": ["vuln"]}}).encode()
        req = urllib.request.Request(
            url + "/twirp/trivy.scanner.v1.Scanner/ScanSBOM",
            data=body, headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read())

    # phase 1 — throughput + tail latency, memo OFF: every scan pays
    # the full decode + detect path (the pure-detect number, not the
    # memo's)
    httpd, state = serve_background("127.0.0.1", 0, table,
                                    cache_dir="",
                                    cache_backend="memory")
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        hits = 0
        for d in docs:   # warm: every pair-capacity bucket compiles
            scan(url, d)
        t0 = time.perf_counter()
        for d in docs:
            r = scan(url, d)
            hits += sum(len(res.get("Vulnerabilities") or [])
                        for res in r.get("results") or [])
        dt = time.perf_counter() - t0
        docs_per_sec = SBOM_DOCS / dt

        lat_ms: list = []
        lat_lock = threading.Lock()

        def worker(ids):
            for i in ids:
                t = time.perf_counter()
                scan(url, docs[i % SBOM_DOCS])
                ms = (time.perf_counter() - t) * 1e3
                with lat_lock:
                    lat_ms.append(ms)

        threads = [threading.Thread(
            target=worker,
            args=(range(k, SBOM_DOCS * 2, SBOM_CONCURRENCY),))
            for k in range(SBOM_CONCURRENCY)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        lat_ms.sort()
        p99 = lat_ms[min(len(lat_ms) - 1, int(len(lat_ms) * 0.99))]
    finally:
        httpd.shutdown()
        httpd.server_close()
        state.close()

    # phase 2 — duplicate-document economics, memo ON: the blob is
    # keyed by document digest, so the N-1 re-scans never re-detect
    httpd, state = serve_background("127.0.0.1", 0, table,
                                    cache_dir="",
                                    cache_backend="memory",
                                    memo_backend="memory")
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        # memo counters are labeled by backend — read the family sum
        # ("did ANY labeled series move"), like the fleet skew probes
        h0 = METRICS.family_sum("trivy_tpu_memo_hits_total")
        s0 = METRICS.family_sum("trivy_tpu_memo_stores_total")
        for _ in range(SBOM_DUP_SCANS):
            scan(url, docs[0])
        memo_hits = METRICS.family_sum("trivy_tpu_memo_hits_total") - h0
        memo_stores = (METRICS.family_sum("trivy_tpu_memo_stores_total")
                       - s0)
    finally:
        httpd.shutdown()
        httpd.server_close()
        state.close()

    out = {
        "sbom_docs_per_sec": round(docs_per_sec, 2),
        "sbom_p99_ms": round(p99, 2),
        "sbom_hits": hits,
        "sbom_memo_hit_rate": round(memo_hits / SBOM_DUP_SCANS, 3),
        "sbom_memo_stores": memo_stores,
        "docs": SBOM_DOCS,
        "concurrency": SBOM_CONCURRENCY,
    }
    if archive_ips:
        # how much the walk-free ingress buys over the archive path
        # on comparable inventories (docs/s ÷ images/s)
        out["sbom_vs_archive_e2e"] = round(
            docs_per_sec / archive_ips, 2)
    return out


LIB_CORPUS_LIBS = 400
LIB_VERSIONS_PER_LIB = 12
LIB_OBSERVATIONS = 4096
LIB_REPEATS = 5


def bench_lib_version():
    """graftbom second half: batched library-version confirmation.
    A fingerprint corpus flattens through LibraryIndex into the
    TABLE_SCHEMA arrays, observations dispatch through the UNCHANGED
    BatchDetector path, and the NumPy mirror must agree hit-for-hit
    on a subset (parity recorded, not fatal)."""
    import numpy as np
    from trivy_tpu.detect.engine import BatchDetector
    from trivy_tpu.detect.libscan import (LibraryFingerprint,
                                          LibraryIndex,
                                          LibraryObservation)

    rng = np.random.default_rng(31)
    fps = []
    for li in range(LIB_CORPUS_LIBS):
        for vi in range(LIB_VERSIONS_PER_LIB):
            fps.append(LibraryFingerprint(
                corpus="bench-corpus", library=f"lib{li:04d}",
                version=f"{vi % 4}.{vi}.{int(rng.integers(0, 10))}",
                token=f"tok-{li:04d}-{vi}"))
    t0 = time.perf_counter()
    index = LibraryIndex.build(fps)
    build_s = time.perf_counter() - t0

    obs = []
    for k in range(LIB_OBSERVATIONS):
        f = fps[int(rng.integers(0, len(fps)))]
        lying = rng.random() < 0.3
        # half the lying versions are valid-but-wrong, half do not
        # even tokenize (both must confirm nothing — the latter via
        # the unparseable-skip both paths share)
        ver = f.version if not lying \
            else ("9.9.9" if k % 2 else f"{f.version}.junk")
        obs.append(LibraryObservation(
            corpus=f.corpus, token=f.token, declared_version=ver,
            ref=k))
    detector = BatchDetector(index.table)
    try:
        confirmed = index.detect(detector, obs)   # warm/compile
        t1 = time.perf_counter()
        for _ in range(LIB_REPEATS):
            confirmed = index.detect(detector, obs)
        dt = time.perf_counter() - t1
        sub = obs[:256]
        parity = index.oracle(sub) == index.detect(detector, sub)
    finally:
        detector.close()
    return {
        "lib_fingerprints_per_sec": round(
            LIB_OBSERVATIONS * LIB_REPEATS / dt, 1),
        "lib_index_build_ms": round(build_s * 1e3, 1),
        "lib_corpus_rows": len(fps),
        "lib_confirmed": len(confirmed),
        "lib_oracle_parity": bool(parity),
    }


def main():
    from chip_smoke import require_tpu
    from trivy_tpu import compile_cache
    devices = require_tpu()
    compile_cache.enable()
    result = {
        "metric": "images_per_sec_cve_scan",
        "unit": "images/s",
        "baseline": "python_loop_reimpl",
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind,
                   "count": len(devices)},
    }
    t0 = time.time()
    table, detector, images = build_workload()
    result["build_s"] = time.time() - t0
    result["table_rows"] = len(table)

    # host baselines on identical inputs
    t2 = time.time()
    np_hits = run_numpy_cpu(table, detector, images)
    result["numpy_cpu_images_per_sec"] = N_IMAGES / (time.time() - t2)
    t3 = time.time()
    base_hits = run_python_loop(table, images[:BASELINE_IMAGES])
    base_ips = BASELINE_IMAGES / (time.time() - t3)
    result["python_loop_images_per_sec"] = base_ips
    host_gate_mbs, host_scan_mbs = bench_secrets_host()
    result["secret_mbps_host"] = host_gate_mbs
    result["secrets_scan_host_mb_s"] = host_scan_mbs

    dev, errors = run_device_half(table, detector, images)
    result.update(dev)
    result["value"] = dev["images_per_sec"]
    result["vs_baseline"] = dev["images_per_sec"] / base_ips
    result["secret_device_speedup"] = \
        dev["secret_mbps_device"] / host_gate_mbs
    arch = dev.get("archive_e2e")
    if arch:
        result["images_per_sec_archive_e2e"] = \
            arch["images_per_sec_archive_e2e"]
    if dev.get("sbom_ingest"):
        result["sbom_docs_per_sec"] = \
            dev["sbom_ingest"]["sbom_docs_per_sec"]
    if dev.get("lib_version"):
        result["lib_fingerprints_per_sec"] = \
            dev["lib_version"]["lib_fingerprints_per_sec"]
    result["parity_ok"] = bool(dev["dev_hits"] == np_hits
                               and dev["sub_hits"] == base_hits)
    if errors:
        result["errors"] = errors
    print(json.dumps(result))
    return 0 if result["parity_ok"] and not errors else 1


if __name__ == "__main__":
    if "--server-concurrency" in sys.argv:
        # standalone detectd sweep on the current backend
        _table, _det, _imgs = build_workload()
        print(json.dumps(
            {"server_concurrency": bench_server_concurrency(_table)}))
    else:
        sys.exit(main())
