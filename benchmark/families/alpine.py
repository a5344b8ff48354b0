"""Alpine 3.19: apk packages, apk-tools' version order.

An OS package family is one module `benchmark/families/<family>.py`,
named by a configuration's `"family"` key (no key: this one). It holds
everything the harness knows of the family, and imports nothing of the
program:

  SOURCE, ECOSYSTEM   the advisory source and ecosystem of its rows
  SKEW_PKG            the source package of the table's skewed bucket,
                      which no container image installs
  OS_RELEASE          the bytes of an image's `etc/os-release`
  pools(rng)          → (fixed pool, installed pool) of versions
  fixed(rng, pool, n) → n advisories' fixed versions; "" is unfixed:
                      vulnerable at every installed version
  cmp(a, b, precision)  -1, 0 or 1 by the family's plain version order;
                      precision "minor" is the control's cut
  binaries(name, version)  → the installed (binary name, binary
                      version) pairs that one (source name, source
                      version) of an image stands for
  package_files(pkgs) → [(path in image, bytes)]: the package database
                      for an image's [(source name, source version)]

Advisories are keyed by source package; traffic draws (source name,
source version) keys from the table.
"""

from __future__ import annotations

import re

SOURCE = "alpine 3.19"
ECOSYSTEM = "alpine"
SKEW_PKG = "linux-lts"
OS_RELEASE = b'NAME="Alpine Linux"\nID=alpine\nVERSION_ID=3.19.1\n'
POOL_VERSIONS = 2000


def versions(rng, n=POOL_VERSIONS, major_lo=0, major_hi=9) -> list:
    """n versions in apk grammar, majors in [major_lo, major_hi]."""
    out = []
    for _ in range(n):
        v = (f"{rng.integers(major_lo, major_hi + 1)}."
             f"{rng.integers(0, 31)}.{rng.integers(0, 31)}")
        r = rng.random()
        if r < 0.15:
            v += f"_p{rng.integers(1, 10)}"
        elif r < 0.3:
            v += ["_rc1", "_git20230101", "a"][int(rng.integers(0, 3))]
        v += f"-r{rng.integers(0, 21)}"
        out.append(v)
    return out


def pools(rng):
    """Fixed versions skew low and installed ones high, so an
    80-package image hits ~30 advisories, the density of real images."""
    return (versions(rng, major_lo=0, major_hi=6),
            versions(rng, major_lo=4, major_hi=9))


def fixed(rng, pool: list, n: int) -> list:
    """Every Alpine advisory has a fixed version."""
    return [pool[int(x)] for x in rng.integers(0, len(pool), n)]


_VERSION = re.compile(
    r"^(\d+(?:\.\d+)*)([a-z]?)((?:_(?:alpha|beta|pre|rc|cvs|svn|git|hg|p)"
    r"\d*)*)(?:-r(\d+))?$")
# apk-tools' suffix order; a version without a suffix sits between rc
# and cvs
_SUFFIX = ["alpha", "beta", "pre", "rc", "", "cvs", "svn", "git", "hg", "p"]
_NO_SUFFIX = _SUFFIX.index("")


def _parse(v: str):
    m = _VERSION.match(v)
    if m is None:
        raise ValueError(f"not an apk version: {v!r}")
    parts = m.group(1).split(".")
    suffixes = [(_SUFFIX.index(re.match(r"[a-z]+", s).group(0)),
                 int(re.sub(r"[a-z]+", "", s) or 0))
                for s in m.group(3).split("_")[1:]]
    return parts, m.group(2), suffixes, int(m.group(4) or 0)


def _cmp_part(a: str, b: str, first: bool) -> int:
    if not first and ((len(a) > 1 and a[0] == "0")
                      or (len(b) > 1 and b[0] == "0")):
        # a leading zero makes both decimal fractions
        x, y = a.rstrip("0"), b.rstrip("0")
    else:
        x, y = int(a), int(b)
    return (x > y) - (x < y)


def cmp(a: str, b: str, precision: str = "full") -> int:
    """-1, 0 or 1 as apk-tools orders versions `a` and `b`;
    `precision="minor"` compares major.minor only, the cut a two-token
    version key would make."""
    pa, la, sa, ra = _parse(a)
    pb, lb, sb, rb = _parse(b)
    if precision == "minor":
        pa, pb = pa[:2], pb[:2]
    for i in range(max(len(pa), len(pb))):
        if i == len(pa) or i == len(pb):
            return -1 if i == len(pa) else 1
        c = _cmp_part(pa[i], pb[i], i == 0)
        if c:
            return c
    if precision == "minor":
        return 0
    if la != lb:
        return -1 if la < lb else 1
    for i in range(max(len(sa), len(sb))):
        x = sa[i] if i < len(sa) else (_NO_SUFFIX, 0)
        y = sb[i] if i < len(sb) else (_NO_SUFFIX, 0)
        if x != y:
            return -1 if x < y else 1
    return (ra > rb) - (ra < rb)


def binaries(name: str, version: str) -> list:
    """One apk package per drawn key: its origin is itself."""
    return [(name, version)]


def apk_installed(pkgs) -> bytes:
    """The apk database (`lib/apk/db/installed`) for [(name, version)]."""
    return "".join(f"P:{nm}\nV:{ver}\nA:x86_64\no:{nm}\nL:MIT\n\n"
                   for nm, ver in pkgs).encode()


def package_files(pkgs) -> list:
    return [("lib/apk/db/installed", apk_installed(pkgs))]
