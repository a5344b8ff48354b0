"""OS package families as harness files.

Goldens taken before the family seam existed: the Alpine table, the
archives, the reference's findings at both precisions and the join's
least bytes must read exactly as they did. Then the promise that a new
family is files only: a stand-in family, with a source-to-binary
mapping and unfixed advisories, is taken by adding one family module,
one configuration and one traffic file to a copy of `benchmark/`; made
a shipped cell of such a copy, it passes every check that goes over the
shipped cells."""

from __future__ import annotations

import hashlib
import io
import json
import os
import shutil
import tarfile
from types import SimpleNamespace

import pytest

from benchmark import images, reference, spec, synth, traffic
from benchmark.spec import Bench
from family_checks import check_shipped_families
from test_benchmark_harness import check_cells

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEEDS = [3, 2**31 + 5]
TRAFFIC = {"sweep16": "cs-alpine.sweep",
           "secret-sweep5": "image-alpine-secret.sweep"}


def _sha(data) -> str:
    if not isinstance(data, bytes):
        data = json.dumps(data).encode()
    return hashlib.sha256(data).hexdigest()


# ---- goldens of the parent ---------------------------------------------------

# taken with the harness as it was before `benchmark/families/` existed
GOLDEN = {
    3: {
        "rows": "3bd0b311bb5c83eca7b4275ea142262c1658f33ab5bf9103eb565bc43c4743fa",
        "n_rows": 204795,
        "pools": "d1b38596728c1b904f1cfd641edd4995f936516da8537feadfd4459fd069e6c8",
        "archives.sweep16": "3a088541a6d9506290e6080f41126f452d789a189d4dfb0a22d441fef59a0f80",
        "archives.secret-sweep5": "89dfea2d9ed2ae342dc6bb7f1d106cdc8e71c187caca92584f4fb26c9ea5c9af",
        "findings.full": "9ed90b8261e49e8429ec3dd35b67e209bdf34bb35e1da194515a1fcbba72fa4c",
        "findings.minor": "f014af2249de418cbc440925c9b0cada59c6cbb2015610ec207be35fc6ddfc5f",
        "least_bytes": 1517006,
    },
    2**31 + 5: {
        "rows": "6b6ab8d312974a4882ecb159723281783cb49823b7bcb9d3ee458d7fc3cc7703",
        "n_rows": 204795,
        "pools": "d4da6a44c028cc06a792e2aa096a24b6d0b9799298254370fbe8b6044b291c7b",
        "archives.sweep16": "d8115f38545a0cb7430abd9247e690aa3dd07f23b8761fee744d395687647674",
        "archives.secret-sweep5": "a80298f105feb38c88f306889282a007ebdd8eee31742264c049eb01785d7ff7",
        "findings.full": "836bef03b14d61dbb68d9d6875822f99afeec52d85349c0af04658d1bb20839f",
        "findings.minor": "65acd9c4180e6f152de0c991665bc5635e6b46ec8f5ffc46d3f34a2daf4cd871",
        "least_bytes": 1392536,
    },
}


@pytest.fixture(scope="module")
def readings(tmp_path_factory):
    """Per seed, what each golden pins, computed from the names that
    tests have always imported."""
    bench = Bench(ROOT)
    join = bench.kernel("join")
    out = {}
    for seed in SEEDS:
        adv = synth.advisories(seed)
        got = {
            "rows": _sha("".join(
                f"{name}\t{vid}\t{fv}\n"
                for name, (ids, fixed) in adv.by_pkg.items()
                for vid, fv in zip(ids, fixed)).encode()),
            "n_rows": adv.n_rows,
            "pools": _sha([adv.fixed_pool, adv.installed_pool]),
        }
        specs = []
        for traf, cell in TRAFFIC.items():
            image = bench.cell(cell)["traffic_doc"]["image"]
            mine = traffic.image_specs(adv, image, seed, 0, 4, "g")
            tmp = tmp_path_factory.mktemp(f"img{seed}{traf}")
            archives = []
            for i, spec in enumerate(mine):
                path = str(tmp / f"{i}.tar")
                images.write_image(path, images.image_layers(spec),
                                   spec.tag)
                archives.append(_sha(open(path, "rb").read()))
            got[f"archives.{traf}"] = _sha(archives)
            specs += mine
        for precision in ("full", "minor"):
            ref = reference.VulnReference(adv, precision=precision)
            got[f"findings.{precision}"] = _sha(
                [ref.findings(s.pkgs) for s in specs])
        got["least_bytes"] = join.least_bytes(SimpleNamespace(
            adv=adv, scans=[SimpleNamespace(spec=s) for s in specs]))
        out[seed] = got
    return out


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("what", [
    "rows", "n_rows", "pools", "archives.sweep16", "archives.secret-sweep5",
    "findings.full", "findings.minor", "least_bytes"])
def test_alpine_reads_as_the_parent_did(readings, seed, what):
    assert readings[seed][what] == GOLDEN[seed][what]


# ---- a new family is files only ---------------------------------------------

# integer versions behind an epoch, two binaries per source, a quarter of
# the advisories unfixed
STANDIN = '''"""A stand-in OS package family."""

SOURCE = "standin 1"
ECOSYSTEM = "standin"
SKEW_PKG = "standin-kernel"
OS_RELEASE = b"ID=standin\\nVERSION_ID=1\\n"


def pools(rng):
    def draw(lo, hi):
        return [f"{e}:{n}" for e, n in zip(rng.integers(0, 3, 300),
                                           rng.integers(lo, hi, 300))]
    return draw(0, 60), draw(30, 90)


def fixed(rng, pool, n):
    idx = rng.integers(0, len(pool), n)
    unfixed = rng.random(n) < 0.25
    return ["" if u else pool[int(i)] for i, u in zip(idx, unfixed)]


def _key(v, precision):
    epoch, _, n = v.partition(":")
    return (int(epoch),) if precision == "minor" else (int(epoch), int(n))


def cmp(a, b, precision="full"):
    x, y = _key(a, precision), _key(b, precision)
    return (x > y) - (x < y)


def binaries(name, version):
    return [(name, version), ("lib" + name, version + "+b1")]


def package_files(pkgs):
    return [("var/lib/standin/installed", "".join(
        f"{b} {bv} {name}\\n" for name, ver in pkgs
        for b, bv in binaries(name, ver)).encode())]
'''
STANDIN_CONFIG = {
    "name": "cs-standin", "entry": "server", "family": "standin",
    "server_flags": [], "scanners": ["vuln"],
    "table": {"n_names": 300, "skew_rows": 20}}
STANDIN_TRAFFIC = {"clients": 2, "warmup_scans": 1, "max_scans_per_s": 10,
                   "image": {"pkgs": [15, 40]}}
ADDED = ["families/standin.py", "configs/cs-standin.json",
         "traffic/standin.json"]


def _files(root) -> dict:
    out = {}
    for d, dirs, names in os.walk(root):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for n in names:
            path = os.path.join(d, n)
            out[os.path.relpath(path, root)] = open(path, "rb").read()
    return out


@pytest.fixture(scope="module")
def standin(tmp_path_factory):
    """A copy of the benchmark with the stand-in family, a configuration
    naming it and a traffic file added, and nothing else changed →
    (root, cell, family, advisories, image specs)."""
    root = tmp_path_factory.mktemp("bench")
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    doc = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    doc["configs"].append({"name": "cs-standin", "source": "x",
                           "file": "benchmark/configs/cs-standin.json",
                           "reduced": [], "why": "x"})
    doc["workloads"].append({"name": "cs-standin.small", "config":
                             "cs-standin", "traffic": "standin",
                             "chips": 1, "why": "x"})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    for rel, text in zip(ADDED, [STANDIN, json.dumps(STANDIN_CONFIG),
                                 json.dumps(STANDIN_TRAFFIC)]):
        (root / "benchmark" / rel).write_text(text)
    cell = Bench(str(root)).cell("cs-standin.small")
    fam = spec.family(str(root), cell["family"])
    adv = synth.advisories(2**31 + 17, family=fam,
                           **cell["config_doc"]["table"])
    specs = traffic.image_specs(adv, cell["traffic_doc"]["image"],
                                2**31 + 17, 0, 12, "s")
    return SimpleNamespace(root=root, cell=cell, family=fam, adv=adv,
                           specs=specs)


def test_a_new_family_needs_no_edit_of_an_existing_file(standin):
    assert standin.cell["family"] == "standin"
    assert standin.cell["traffic_doc"] == STANDIN_TRAFFIC
    old = _files(os.path.join(ROOT, "benchmark"))
    new = _files(standin.root / "benchmark")
    assert sorted(set(new) - set(old)) == sorted(
        os.path.join(*p.split("/")) for p in ADDED)
    assert all(new[p] == data for p, data in old.items())
    # a shipped cell whose configuration names no family reads the
    # default, alpine; a family a shipped configuration names loads
    # from its own file and keeps the contract
    assert check_shipped_families(ROOT)


def test_a_shipped_cell_of_another_family_passes_every_cell_check(
        tmp_path):
    """A copy of the benchmark in which the stand-in family is a shipped
    cell: its family file and configuration, its entry in
    `BENCHMARK.json`, and its name added to the served metrics'
    `workloads`. Every check that goes over the shipped cells passes."""
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    doc = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    doc["configs"].append({"name": "cs-standin", "source": "x",
                           "file": "benchmark/configs/cs-standin.json",
                           "reduced": [], "why": "x"})
    doc["workloads"].append({"name": "cs-standin.sweep", "config":
                             "cs-standin", "traffic": "sweep16",
                             "chips": 1, "why": "x"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "cs-alpine.sweep" in m.get("workloads", []):
            m["workloads"].append("cs-standin.sweep")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    for rel, text in zip(ADDED[:2], [STANDIN, json.dumps(STANDIN_CONFIG)]):
        (tmp_path / "benchmark" / rel).write_text(text)
    root = str(tmp_path)
    assert check_shipped_families(root) == \
        check_shipped_families(ROOT) | {"standin"}
    check_cells(root)
    bench = Bench(root)
    assert bench.cell("cs-standin.sweep")["family"] == "standin"
    for kind in ("end_to_end", "per_layer"):
        assert bench.metrics("cs-standin.sweep", kind) == \
            bench.metrics("cs-alpine.sweep", kind)


def test_the_new_family_builds_its_table(standin):
    adv, fam = standin.adv, standin.family
    fixed = [f for _ids, fs in adv.by_pkg.values() for f in fs]
    assert adv.n_rows == len(fixed) > 300
    assert list(adv.by_pkg)[-1] == fam.SKEW_PKG
    assert 0.15 < fixed.count("") / len(fixed) < 0.35
    assert all(f in adv.fixed_pool for f in fixed if f)
    raw = adv.raw()
    assert len(raw) == adv.n_rows
    assert {(r.source, r.ecosystem) for r in raw} == {("standin 1",
                                                       "standin")}


def test_the_new_family_writes_its_package_database(standin, tmp_path):
    s = standin.specs[0]
    path = str(tmp_path / "s.tar")
    images.write_image(path, images.image_layers(s, standin.family), s.tag)
    with tarfile.open(path) as tf:
        manifest = json.load(tf.extractfile("manifest.json"))
        layer = tf.extractfile(manifest[0]["Layers"][0]).read()
    with tarfile.open(fileobj=io.BytesIO(layer)) as tf:
        files = {m.name: tf.extractfile(m).read() for m in tf}
    assert files["etc/os-release"] == b"ID=standin\nVERSION_ID=1\n"
    rows = files["var/lib/standin/installed"].decode().splitlines()
    assert len(rows) == 2 * len(s.pkgs)
    assert f"lib{s.pkgs[0][0]} {s.pkgs[0][1]}+b1 {s.pkgs[0][0]}" in rows
    assert "lib/apk/db/installed" not in files


def test_the_reference_reports_unfixed_rows_for_every_binary(standin):
    adv, fam = standin.adv, standin.family
    ref = reference.VulnReference(adv)
    n_unfixed = 0
    for s in standin.specs:
        want = []
        for name, ver in s.pkgs:
            ids, fixed = adv.by_pkg[name]
            hit = [vid for vid, f in zip(ids, fixed)
                   if f == "" or fam.cmp(ver, f) < 0]
            n_unfixed += sum(f == "" for f in fixed)
            want += [(b, bv, vid) for b, bv in fam.binaries(name, ver)
                     for vid in hit]
        got = ref.findings(s.pkgs)
        assert got == sorted(want)
        # an unfixed row is reported for both binaries of its source
        for name, ver in s.pkgs:
            ids, fixed = adv.by_pkg[name]
            for vid, f in zip(ids, fixed):
                if f == "":
                    assert (name, ver, vid) in got
                    assert ("lib" + name, ver + "+b1", vid) in got
    assert n_unfixed > 20
    # the control cuts by the family's own order: epochs only
    low = reference.VulnReference(adv, precision="minor")
    assert sum(reference.mismatches(low.findings(s.pkgs),
                                     ref.findings(s.pkgs))
               for s in standin.specs) > 0
