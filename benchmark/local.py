"""A standalone cell: `trivy image --input <archive>` as one long-lived
process scanning several images at a time.

Each scan is the path `cli image` takes: `ImageArchiveArtifact.inspect()`
into a fresh cache, then `LocalScanner.scan` against the table, which
this process holds on the chip. The images come from a pool that is
cycled; the fresh cache per scan means no work carries over.
"""

from __future__ import annotations

import copy
import os
import tempfile
import threading
import time

from . import images, spec, synth, traffic
from .window import Scan


def _scan(scanner, path: str, scanners: tuple):
    from trivy_tpu import types as T
    from trivy_tpu.fanal.artifact import ImageArchiveArtifact
    from trivy_tpu.fanal.cache import MemoryCache
    cache = MemoryCache()
    ref = ImageArchiveArtifact(path, cache, scanners=scanners).inspect()
    sc = copy.copy(scanner)         # the same detector, this scan's cache
    sc.cache = cache
    results, _os = sc.scan(ref.name, ref.id, ref.blob_ids,
                           T.ScanOptions(scanners=scanners))
    vulns = [(v.pkg_name, v.installed_version, v.vulnerability_id)
             for r in results for v in r.vulnerabilities]
    secrets = [(r.target.lstrip("/"), f.rule_id, f.start_line, f.match)
               for r in results for f in r.secrets]
    return vulns, secrets


def _bytes_scanned() -> float:
    from trivy_tpu.metrics import METRICS
    return METRICS.family_sum("trivy_tpu_secret_scan_bytes_total")


def _loop(scanner, paths, pool, scanners, workers, t0, t_end, first):
    """`workers` closed-loop scans from t0 to t_end over the pool,
    from request index `first` → (threads to join, records)."""
    counter, take = iter(range(first, 1 << 62)), threading.Lock()
    records, lock = [], threading.Lock()

    def loop():
        while time.monotonic() < t_end:
            with take:
                i = next(counter)
            start = time.monotonic()
            k = i % len(paths)
            try:
                vulns, secrets = _scan(scanner, paths[k], scanners)
                ok, err = True, ""
            except Exception as e:             # counted as failed
                vulns, secrets, ok, err = [], [], False, repr(e)
            with lock:
                records.append(Scan(i, pool[k], start, start,
                                    time.monotonic(), ok, err, vulns,
                                    secrets))

    ts = [threading.Thread(target=loop) for _ in range(workers)]
    for t in ts:
        t.start()
    return ts, records


def run(root: str, cell: dict, seed: int, seconds: float, window):
    from trivy_tpu.fanal.cache import MemoryCache
    from trivy_tpu.scanner import LocalScanner
    conf, traf = cell["config_doc"], cell["traffic_doc"]
    scanners = tuple(conf["scanners"])
    workers = traf["clients"]
    family = spec.family(root, cell["family"])
    adv = synth.advisories(seed, family=family, **conf["table"])
    table = synth.program_table(adv)
    pool = traffic.image_specs(adv, traf["image"], seed, 0, traf["pool"],
                               "p")
    scanner = LocalScanner(MemoryCache(), table)
    with tempfile.TemporaryDirectory(prefix="bench-") as tmp:
        paths = []
        for k, img in enumerate(pool):
            paths.append(os.path.join(tmp, f"p{k}.tar"))
            images.write_image(paths[-1], images.image_layers(img, family),
                               img.tag)
        try:
            def warm_round(_r):
                t0 = time.monotonic()
                ts, _ = _loop(scanner, paths, pool, scanners, workers, t0,
                              t0 + traf["warmup_seconds"], 0)
                for t in ts:
                    t.join()

            window.warm_up(traf, warm_round)
            window.open()
            t0 = time.monotonic() + 0.05
            t_end = t0 + seconds
            n1 = _bytes_scanned()
            ts, records = _loop(scanner, paths, pool, scanners, workers,
                                t0, t_end, 0)
            cpu0 = time.process_time()
            window.hold(t0, t_end)
            window.host_cpu((time.process_time() - cpu0) / seconds, 0.0)
            window.close(t0, t_end)
            for t in ts:
                t.join(timeout=90)
            # every file of every scan was scanned for secrets: nothing
            # carried over from an earlier scan of the same image
            scanned = _bytes_scanned() - n1
            want = sum(len(data) for r in records
                       for layer in images.image_layers(r.spec, family)
                       for _path, data in layer)
            window.note(f"# secret bytes scanned in the window: "
                        f"{scanned:.0f}, the {len(records)} scans' files "
                        f"hold {want}: equal {scanned == want}")
            window.read_memory()
        finally:
            scanner.close()
    return adv, records
