"""The checks that every OS package family file is held to.

A family (`benchmark/families/<family>.py`) is the yardstick's whole
knowledge of an OS: its version order, its packages, its control cut.
These checks hold it to the program on the CPU at a small size, and to
the promise that its control can fail. They take a family module, or
the small case `case` builds from one, so that the shipped families and
the drills in `test_family_contract.py` run the same code.

The contract a family exports:

  SOURCE, ECOSYSTEM, SKEW_PKG   str
  OS_RELEASE                    bytes
  pools, fixed, cmp, binaries, package_files   as `families/alpine.py`
                                documents them
  EDGE_VERSIONS                 optional: versions whose order is easy
                                to get wrong (epochs, tildes, revisions,
                                suffixes). The order check compares
                                every pair of them, besides the pairs
                                drawn from the pools.
"""

from __future__ import annotations

import json
import os
from collections import Counter
from types import SimpleNamespace

from benchmark import images, reference, spec, synth, traffic
from benchmark.spec import Bench

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONTRACT = ("SOURCE", "ECOSYSTEM", "SKEW_PKG", "OS_RELEASE", "pools",
            "fixed", "cmp", "binaries", "package_files")
SEED = 2**31 + 30
N_PAIRS = 4000
N_TRIPLES = 1000
N_IMAGES = 12
TABLE = {"n_names": 400, "skew_rows": 60}


def families() -> list:
    """The names of the family files under `benchmark/families/`."""
    return sorted(f[:-3] for f in os.listdir(
        os.path.join(ROOT, "benchmark", "families"))
        if f.endswith(".py") and not f.startswith("_"))


def sweep_image() -> dict:
    """`sweep16`'s image parameters: 20-140 source packages."""
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "sweep16.json")) as f:
        return json.load(f)["image"]


# ---- the contract -----------------------------------------------------------

def check_contract(fam) -> None:
    missing = [k for k in CONTRACT if not hasattr(fam, k)]
    assert not missing, f"{fam.__name__} lacks {missing}"
    for k in ("SOURCE", "ECOSYSTEM", "SKEW_PKG"):
        assert isinstance(getattr(fam, k), str) and getattr(fam, k), k
    assert isinstance(fam.OS_RELEASE, bytes) and fam.OS_RELEASE
    edges = getattr(fam, "EDGE_VERSIONS", [])
    assert all(isinstance(v, str) and v for v in edges)
    rng = synth.rng_for(SEED, 0)
    fixed_pool, installed_pool = fam.pools(rng)
    assert fixed_pool and installed_pool
    assert all(isinstance(v, str) and v
               for v in fixed_pool + installed_pool)
    fixed = fam.fixed(rng, fixed_pool, 50)
    assert len(fixed) == 50 and all(f == "" or f in fixed_pool
                                    for f in fixed)
    ver = installed_pool[0]
    bins = fam.binaries("pkg00001", ver)
    assert bins and len({b for b, _ in bins}) == len(bins)
    assert all(isinstance(b, str) and isinstance(bv, str)
               for b, bv in bins)
    files = fam.package_files([("pkg00001", ver)])
    assert files and all(isinstance(p, str) and isinstance(d, bytes)
                         for p, d in files)


def check_shipped_families(root: str) -> set:
    """Every shipped cell's family: the default (`alpine`) where its
    configuration names none, and whatever it names loads through
    `spec.family` from its own file and keeps the contract. → the
    names."""
    bench = Bench(root)
    names = set()
    for w in bench.doc["workloads"]:
        cell = bench.cell(w["name"])
        if "family" not in cell["config_doc"]:
            assert cell["family"] == "alpine", w["name"]
        names.add(cell["family"])
    for name in names:
        assert os.path.isfile(os.path.join(root, "benchmark", "families",
                                           name + ".py")), name
        check_contract(spec.family(root, name))
    return names


# ---- the order --------------------------------------------------------------

def _sign(x: int) -> int:
    return (x > 0) - (x < 0)


def check_order(fam) -> int:
    """The family's plain order against the program's
    `version.compare`, on seeded pairs from both pools and
    `EDGE_VERSIONS` and on every pair of the latter; then a total
    preorder at both precisions on seeded triples, as the reference's
    ranking needs. → the pairs compared."""
    from trivy_tpu import version
    rng = synth.rng_for(SEED, 2)
    fixed_pool, installed_pool = fam.pools(rng)
    edges = list(getattr(fam, "EDGE_VERSIONS", []))
    pool = fixed_pool + installed_pool + edges
    pairs = [(pool[i], pool[j])
             for i, j in rng.integers(0, len(pool), (N_PAIRS, 2))]
    pairs += [(a, b) for a in edges for b in edges]
    bad = [(a, b, fam.cmp(a, b), version.compare(fam.ECOSYSTEM, a, b))
           for a, b in pairs
           if _sign(fam.cmp(a, b))
           != _sign(version.compare(fam.ECOSYSTEM, a, b))]
    assert not bad, (f"{len(bad)} of {len(pairs)} pairs ordered apart "
                     f"from the program (a, b, family, program): "
                     f"{bad[:5]}")
    for precision in ("full", "minor"):
        def cmp(a, b):
            return _sign(fam.cmp(a, b, precision))
        for i, j, k in rng.integers(0, len(pool), (N_TRIPLES, 3)):
            a, b, c = pool[i], pool[j], pool[k]
            assert cmp(a, b) == -cmp(b, a), (precision, a, b)
            if cmp(a, b) <= 0 and cmp(b, c) <= 0:
                assert cmp(a, c) <= 0, (precision, a, b, c)
                if cmp(a, b) == cmp(b, c) == 0:
                    assert cmp(a, c) == 0, (precision, a, b, c)
    return len(pairs)


# ---- a small table and its images -------------------------------------------

def case(fam) -> SimpleNamespace:
    """A small table of `fam` and 12 images of `sweep16`'s package
    range → (family, adv, specs)."""
    adv = synth.advisories(SEED, family=fam, **TABLE)
    specs = traffic.image_specs(adv, sweep_image(), SEED, 0, N_IMAGES, "f")
    return SimpleNamespace(family=fam, adv=adv, specs=specs)


def check_ingest(c, tmp: str) -> int:
    """Each image written with the family's files and scanned by the
    path `benchmark/local.py` `_scan` takes (archive inspection, OS
    detection, the package database parse, source-to-binary mapping,
    the join, the reported binary versions), vuln scanner only: its
    findings equal the reference's. → the findings compared."""
    from trivy_tpu.fanal.cache import MemoryCache
    from trivy_tpu.scanner import LocalScanner

    from benchmark import local
    ref = reference.VulnReference(c.adv)
    scanner = LocalScanner(MemoryCache(), synth.program_table(c.adv))
    n = 0
    try:
        for i, s in enumerate(c.specs):
            path = os.path.join(tmp, f"{i}.tar")
            images.write_image(path, images.image_layers(s, c.family),
                               s.tag)
            vulns, _secrets = local._scan(scanner, path, ("vuln",))
            got, want = sorted(vulns), ref.findings(s.pkgs)
            assert got == want, (
                f"image {i}: {reference.mismatches(got, want)} findings "
                f"apart; program only {sorted(set(got) - set(want))[:3]}, "
                f"reference only {sorted(set(want) - set(got))[:3]}")
            n += len(want)
    finally:
        scanner.close()
    assert n > 100, n
    return n


def check_unfixed(c) -> int:
    """Where the family draws unfixed advisories (fixed version ""),
    each one of an installed source is reported once for each binary
    the source stands for. → the unfixed advisories met."""
    fam, ref = c.family, reference.VulnReference(c.adv)
    n = 0
    for s in c.specs:
        got = Counter(ref.findings(s.pkgs))
        for name, ver in s.pkgs:
            ids, fixed = c.adv.by_pkg[name]
            for vid, f in zip(ids, fixed):
                if f == "":
                    n += 1
                    for b, bv in fam.binaries(name, ver):
                        assert got[(b, bv, vid)] == 1, (b, bv, vid)
    drawn = any(f == "" for _ids, fixed in c.adv.by_pkg.values()
                for f in fixed)
    assert n > 0 or not drawn
    return n


def check_control(c) -> int:
    """The control, the reference at the family's `minor` cut, differs
    from the full reference on the images: `control.py` can read it
    as not correct. → the findings apart."""
    full = reference.VulnReference(c.adv)
    low = reference.VulnReference(c.adv, precision="minor")
    bad = sum(reference.mismatches(low.findings(s.pkgs),
                                   full.findings(s.pkgs))
              for s in c.specs)
    assert bad > 0, "the control reads 0 findings apart"
    return bad
