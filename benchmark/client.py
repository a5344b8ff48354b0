"""One client of a served cell: `python3 -m benchmark.client`.

A child process of `run.py` with JAX_PLATFORMS=cpu: it never touches the
chip. It runs Trivy's client half (`RemoteCache` and `RemoteScanner`,
as `chip_smoke.scan_remote`) on archives of its own, built before the
window. It talks to its parent in JSON lines: a job on stdin, then

  -> {"ready": n}            archives built
  <- {"url": ...}
  <- {"phase": "warm", "round": r, "t0": t, "seconds": s}
  -> {"warmed": n}           the cell's loop over the warm-up archives
  <- {"phase": "window", "t0": t, "seconds": s}
  -> one {"i", "due", "start", "end", "ok", "err", "vulns"} per scan of
     the window, then {"done": true, "ran_out", "cpu_s", "wall_s"}

`t0` is on the monotonic clock, which all processes share. The loop is
closed: a client sends its next scan when its last one returns. Every
scan a phase started is finished and reported, also where it ends after
the phase closed.
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import time
import traceback


def _say(obj) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def _hear() -> dict:
    line = sys.stdin.readline()
    if not line:
        raise SystemExit("parent closed the pipe")
    return json.loads(line)


def scan(url: str, path: str, scanners: tuple) -> list:
    """One scan as `trivy image --server` makes it → vulnerability
    triples."""
    from trivy_tpu import types as T
    from trivy_tpu.fanal.artifact import ImageArchiveArtifact
    from trivy_tpu.server.client import RemoteCache, RemoteScanner
    ref = ImageArchiveArtifact(path, RemoteCache(url),
                               scanners=scanners).inspect()
    results, _os = RemoteScanner(url).scan(
        ref.name, ref.id, ref.blob_ids, T.ScanOptions(scanners=scanners))
    return [(v.pkg_name, v.installed_version, v.vulnerability_id)
            for r in results for v in r.vulnerabilities]


def client_specs(adv, cell: dict, seed: int, client: int):
    """→ (warm-up specs, [(request index, spec)]) of one client. The
    parent calls this too, to know what each request scanned."""
    from . import traffic
    image = cell["image"]
    warm = traffic.image_specs(adv, image, seed, 1000 + client,
                               cell["warmup_scans"], f"w{client}-")
    n = traffic.archives_per_client(cell, cell["seconds"])
    specs = traffic.image_specs(adv, image, seed, client, n, f"c{client}-")
    return warm, [(client * n + k, s) for k, s in enumerate(specs)]


def run_loop(url, items, scanners, t0, seconds):
    """The closed loop from t0 for `seconds`: one scan after another
    over `items` [(index, path)] in order. → (records, whether the
    items ran out before the window closed)."""
    out = []
    time.sleep(max(t0 - time.monotonic(), 0))
    it = iter(items)
    while time.monotonic() < t0 + seconds:
        nxt = next(it, None)
        if nxt is None:
            return out, True
        start = time.monotonic()
        rec = {"i": nxt[0], "due": start, "start": start, "ok": True,
               "err": "", "vulns": []}
        try:
            rec["vulns"] = scan(url, nxt[1], scanners)
        except Exception as e:             # reported, counted as failed
            rec["ok"], rec["err"] = False, f"{type(e).__name__}: {e}"
        rec["end"] = time.monotonic()
        out.append(rec)
    return out, False


def main() -> int:
    job = _hear()
    sys.path.insert(0, job["root"])
    from benchmark import images, spec, synth
    cell, seed, client = job["cell"], job["seed"], job["client"]
    scanners = tuple(job["scanners"])
    family = spec.family(job["root"], job["family"])
    adv = synth.advisories(seed, family=family, **job["table"])
    warm, mine = client_specs(adv, cell, seed, client)
    os.makedirs(job["dir"], exist_ok=True)

    def build(img):
        path = os.path.join(job["dir"], img.tag.split("/")[1]
                            .replace(":", "_") + ".tar")
        images.write_image(path, images.image_layers(img, family), img.tag)
        return path

    warm_items = [(-1, build(s)) for s in warm]
    items = [(i, build(s)) for i, s in mine]
    _say({"ready": len(items)})
    url = _hear()["url"]
    while True:
        msg = _hear()
        if msg["phase"] == "warm":
            recs, _ = run_loop(url, itertools.cycle(warm_items), scanners,
                               msg["t0"], msg["seconds"])
            _say({"warmed": len(recs),
                  "failed": sum(not r["ok"] for r in recs)})
            continue
        cpu0 = time.process_time()
        recs, ran_out = run_loop(url, items, scanners, msg["t0"],
                                 msg["seconds"])
        for rec in recs:
            _say(rec)
        # this process's CPU seconds over its window, all threads
        _say({"done": True, "ran_out": ran_out,
              "cpu_s": time.process_time() - cpu0,
              "wall_s": time.monotonic() - msg["t0"]})
        return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception:
        traceback.print_exc()
        sys.exit(1)
