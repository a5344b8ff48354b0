"""Every OS package family file keeps the checks that decide `correct`.

Parametrised over each `benchmark/families/*.py` found at collection
time, so a family that a later change adds is held to them with no edit
here: the contract (`family_checks.CONTRACT`, and the optional
`EDGE_VERSIONS`, versions whose order is easy to get wrong), its order
against the program's, its reference against the program through
ingest, its unfixed advisories, and a control that can fail.

Then drills, that the checks are not shaped by Alpine: a family in
dpkg grammar, written here as text into a temporary directory (never
into `benchmark/families/`), passes every check; each of three broken
copies of it fails the check it targets."""

from __future__ import annotations

import pytest

import family_checks as fc
from benchmark import spec

CHECKS = {
    "contract": lambda c, tmp: fc.check_contract(c.family),
    "order": lambda c, tmp: fc.check_order(c.family),
    "ingest": lambda c, tmp: fc.check_ingest(c, str(tmp)),
    "unfixed": lambda c, tmp: fc.check_unfixed(c),
    "control": lambda c, tmp: fc.check_control(c),
}


@pytest.fixture(scope="module", params=fc.families())
def shipped(request):
    """Each family file's small case, built once."""
    return fc.case(spec.family(fc.ROOT, request.param))


@pytest.mark.parametrize("check", list(CHECKS))
def test_every_family_keeps_the_checks(shipped, tmp_path, check):
    CHECKS[check](shipped, tmp_path)


# ---- drills -----------------------------------------------------------------

# Debian 12's grammar: epochs, tildes, +debNuM/~debNuM revisions, some
# versions the program's keys cannot hold exactly (so pairs take its host
# re-check), a
# quarter of the advisories unfixed, one to three binaries per source,
# some with binNMU (+b1) versions or versioned apart from their source,
# `etc/debian_version`. Both pools draw from
# one list of upstream versions, so a fix and an installed version often
# differ by their revision alone, and the control (revision dropped) errs.
DPKG = '''"""A dpkg-grammar fixture family."""

import zlib

SOURCE = "debian 12"
ECOSYSTEM = "debian"
SKEW_PKG = "linux"
OS_RELEASE = (b'PRETTY_NAME="Debian GNU/Linux 12 (bookworm)"\\n'
              b'ID=debian\\nVERSION_ID="12"\\n')
EDGE_VERSIONS = [
    "1.0", "1.0-0", "1.0-1", "1.0-2", "1.0-10", "1.0-1.1", "1.0-1+b1",
    "1.0-1~deb12u1", "1.0-1+deb12u1", "1.0-1+deb12u2", "1.0-1+deb12u10",
    "1.0~rc1-1", "1.0~~-1", "1.0~rc1", "1.0+dfsg-1", "1.0a-1", "1.0.1-1",
    "1.00-1", "0:1.0-1", "1:0.9-1", "2:0.1-1", "10.0-1", "1.0-a", "1.0-1a",
    "1.0+git20230415123456-1", "1.0+git20230415123457-1",
    "1.0+really0.9+git2023.abc-1", "1.0+really0.9+git2023.abd-1",
]


def _upstream(rng):
    v = f"{rng.integers(0, 100)}.{rng.integers(0, 100)}.{rng.integers(0, 100)}"
    r = rng.random()
    if r < 0.1:
        v += f"~rc{rng.integers(1, 4)}"
    elif r < 0.2:
        v += "+dfsg"
    elif r < 0.25:   # a number past the program's numeric cap
        v += f"+git{rng.integers(20230101000000, 20240101000000)}"
    elif r < 0.3:    # more parts than the program's version key holds
        v += f"+really{rng.integers(0, 9)}.{rng.integers(0, 9)}+git2023.abc"
    return (f"{rng.integers(1, 3)}:" if rng.random() < 0.1 else "") + v


def _revision(rng):
    v = f"-{rng.integers(1, 6)}"
    r = rng.random()
    if r < 0.3:
        v += f"+deb12u{rng.integers(1, 12)}"
    elif r < 0.4:
        v += f"~deb12u{rng.integers(1, 12)}"
    return v


def pools(rng):
    ups = [_upstream(rng) for _ in range(120)]
    return _draw(rng, ups), _draw(rng, ups)


def _draw(rng, ups):
    return [ups[i] + _revision(rng) for i in rng.integers(0, len(ups), 300)]


def fixed(rng, pool, n):
    idx = rng.integers(0, len(pool), n)
    unfixed = rng.random(n) < 0.25
    return ["" if u else pool[int(i)] for i, u in zip(idx, unfixed)]


def _order(c):
    return -1 if c == "~" else ord(c) if c.isalpha() else ord(c) + 256


def _key(s):
    """Debian Policy 5.6.12: alternating non-digit and digit parts; a
    non-digit part by its characters' order then its end (0), a digit
    part by its number."""
    out, i = [], 0
    while i < len(s):
        j = i
        while j < len(s) and not s[j].isdigit():
            j += 1
        k = j
        while k < len(s) and s[k].isdigit():
            k += 1
        out.append((tuple(_order(c) for c in s[i:j]) + (0,),
                    int(s[j:k] or 0)))
        i = k
    return out


def _cmp_parts(a, b):
    x, y = _key(a), _key(b)
    pad = ((0,), 0)
    for p, q in zip(x + [pad] * (len(y) - len(x)),
                    y + [pad] * (len(x) - len(y))):
        if p != q:
            return -1 if p < q else 1
    return 0


def _split(v):
    epoch, _, rest = v.partition(":") if ":" in v else ("0", "", v)
    upstream, _, revision = rest.rpartition("-") if "-" in rest else (
        rest, "", "")
    return int(epoch), upstream, revision


def cmp(a, b, precision="full"):
    """precision "minor" drops the Debian revision."""
    ea, ua, ra = _split(a)
    eb, ub, rb = _split(b)
    if ea != eb:
        return -1 if ea < eb else 1
    c = _cmp_parts(ua, ub)
    if c or precision == "minor":
        return c
    return _cmp_parts(ra, rb)


def binaries(name, version):
    k = zlib.crc32(name.encode()) % 6
    out = [(name, version)]
    if k >= 2:
        out.append(("lib" + name + "1", version + "+b1" if k % 2 else version))
    if k >= 4:
        # versioned apart from its source, as gcc-defaults' cpp is
        out.append((name + "-data", "4:" + version.rpartition(":")[2]))
    return out


def package_files(pkgs):
    stanzas = []
    for name, ver in pkgs:
        for b, bv in binaries(name, ver):
            src = ("" if b == name and bv == ver else
                   f"Source: {name}\\n" if bv == ver else
                   f"Source: {name} ({ver})\\n")
            stanzas.append(f"Package: {b}\\nStatus: install ok installed\\n"
                           f"{src}Version: {bv}\\nArchitecture: amd64\\n")
    return [("etc/debian_version", b"12.5\\n"),
            ("var/lib/dpkg/status", "\\n".join(stanzas).encode())]
'''

# variant → (text replaced, its replacement, the check it must fail,
# and how that check says so)
BROKEN = {
    "tilde-sorts-like-hyphen": (
        'return -1 if c == "~" else',
        'return ord("-") + 256 if c == "~" else', "order",
        "ordered apart from the program"),
    "independent-pools": (
        "return _draw(rng, ups), _draw(rng, ups)",
        "return _fresh(rng), _fresh(rng)\n\n\n"
        "def _fresh(rng):\n"
        "    return [_upstream(rng) + _revision(rng) for _ in range(300)]",
        "control", "the control reads 0"),
    "binnmu-version-dropped": (
        'f"{src}Version: {bv}', 'f"{src}Version: {ver}', "ingest",
        "findings apart"),
}


def _fixture_case(tmp_path_factory, name: str, text: str):
    """The family `text` written as `benchmark/families/<name>.py` of a
    temporary root, loaded as the harness loads a family, and its small
    case."""
    root = tmp_path_factory.mktemp("fam")
    (root / "benchmark" / "families").mkdir(parents=True)
    (root / "benchmark" / "families" / f"{name}.py").write_text(text)
    return fc.case(spec.family(str(root), name))


@pytest.fixture(scope="module")
def dpkg(tmp_path_factory):
    return _fixture_case(tmp_path_factory, "dpkgfix", DPKG)


@pytest.mark.parametrize("check", list(CHECKS))
def test_the_dpkg_fixture_passes_every_check(dpkg, tmp_path, check):
    got = CHECKS[check](dpkg, tmp_path)
    if check in ("ingest", "unfixed", "control"):
        assert got > 0


@pytest.mark.parametrize("variant", list(BROKEN))
def test_a_broken_dpkg_fixture_fails_the_check_it_targets(
        tmp_path_factory, tmp_path, variant):
    old, new, check, says = BROKEN[variant]
    assert DPKG.count(old) == 1
    c = _fixture_case(tmp_path_factory, "dpkgbroken",
                      DPKG.replace(old, new))
    with pytest.raises(AssertionError, match=says):
        CHECKS[check](c, tmp_path)
