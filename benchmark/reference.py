"""The plain reference: what a scan of each image must report.

Imports nothing of the program and takes nothing it made. It works from
the advisories of `synth.py` and the image contents of `images.py`:

- vulnerabilities: for each (source name, source version) an image
  installs, every advisory of that source whose fixed version sorts
  above the installed one, or that is unfixed, reported once for each
  binary package the source stands for. The version order and the
  source-to-binary mapping are the OS package family's
  (`families/<family>.py`), written out there, not the program's
  tokens or join;
- secrets: every AWS access key id in a text file, by the rule's own
  regular expression, with its line and its censored line.

`precision="minor"` is the control: the family's cut of its order
(Alpine: major.minor only, the cut a two-token version key would make).
It must come out as not correct.
"""

from __future__ import annotations

import functools
import re

import numpy as np

from .families import alpine

# the name tests have always imported: the Alpine family's order
apk_cmp = alpine.cmp


def version_ranks(versions, precision: str = "full",
                  cmp=apk_cmp) -> dict:
    """→ {version: rank}, equal ranks for versions that compare equal
    by `cmp`."""
    order = sorted(set(versions), key=functools.cmp_to_key(
        lambda a, b: cmp(a, b, precision)))
    ranks, r = {}, 0
    for i, v in enumerate(order):
        if i and cmp(order[i - 1], v, precision):
            r += 1
        ranks[v] = r
    return ranks


class VulnReference:
    """Vulnerability findings of any image over one table of
    advisories (a `synth.Advisories`), by its family's version order
    and source-to-binary mapping."""

    def __init__(self, adv, precision: str = "full"):
        self._family = adv.family
        ranks = version_ranks(adv.fixed_pool + adv.installed_pool,
                              precision, adv.family.cmp)
        self._rank = ranks
        # an unfixed advisory ("") ranks above every installed version
        unfixed = len(ranks)
        self._by_pkg = {name: (ids, np.array([ranks[f] if f else unfixed
                                              for f in fixed]))
                        for name, (ids, fixed) in adv.by_pkg.items()}

    def findings(self, pkgs) -> list:
        """pkgs: [(source name, source version)] → sorted [(binary
        name, binary version, vulnerability id)] for every binary that
        each source key stands for."""
        out = []
        for name, ver in pkgs:
            ids, fixed = self._by_pkg.get(name, ((), np.zeros(0)))
            hit = [ids[j] for j in np.flatnonzero(self._rank[ver] < fixed)]
            for b_name, b_ver in self._family.binaries(name, ver):
                out += [(b_name, b_ver, vid) for vid in hit]
        return sorted(out)


_AWS_KEY = re.compile(
    r"""["']?(?P<secret>(A3T[A-Z0-9]|AKIA|AGPA|AIDA|AROA|AIPA|ANPA|ANVA"""
    r"""|ASIA)[A-Z0-9]{16})["']?[.,]?(\s+|$)""")
AWS_RULE = "aws-access-key-id"


def secret_findings(files) -> list:
    """files: [(path, bytes)] → sorted [(path, rule, line, censored
    line)] for every AWS access key id."""
    out = []
    for path, data in files:
        text = data.decode("utf-8", errors="surrogateescape")
        for m in _AWS_KEY.finditer(text):
            s, e = m.span("secret")
            line_start = text.rfind("\n", 0, s) + 1
            line_end = text.find("\n", s)
            line_end = len(text) if line_end < 0 else line_end
            if line_end - line_start > 100:
                line_start, line_end = max(s - 30, 0), min(e + 20,
                                                           len(text))
            line = text[line_start:s] + "*" * (e - s) + text[e:line_end]
            out.append((path, AWS_RULE, text.count("\n", 0, s) + 1, line))
    return sorted(out)


def mismatches(got: list, want: list) -> int:
    """Findings in one list and not the other, counted with
    multiplicity."""
    from collections import Counter
    g, w = Counter(map(tuple, got)), Counter(map(tuple, want))
    return sum(((g - w) + (w - g)).values())
