"""Seeded advisory tables shaped like the real trivy-db.

30,000 package names with Zipf(1.7) bucket sizes clipped to [1, 64],
plus one `linux`-style source package carrying 4,000 advisory rows:
about 205k interval rows. Fixed versions skew low and the installed
pool skews high, so an 80-package image hits ~30 CVEs, the density of
real images. chip_smoke.py builds its tables here.
"""

from __future__ import annotations

import numpy as np

N_PKG_NAMES = 30_000
SOURCE = "alpine 3.19"
SKEW_PKG = "linux-lts"
SKEW_ROWS = 4000


def synth_versions(rng, n=2000, major_lo=0, major_hi=9) -> list[str]:
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


def skewed_table(rng):
    """→ (AdvisoryTable, installed-version pool), drawing from `rng`."""
    from .table import RawAdvisory, build_table
    fixed_pool = synth_versions(rng, major_lo=0, major_hi=6)
    installed_pool = synth_versions(rng, major_lo=4, major_hi=9)
    bucket = np.clip(rng.zipf(1.7, N_PKG_NAMES), 1, 64)
    raw = []
    for i in range(N_PKG_NAMES):
        for j in range(int(bucket[i])):
            raw.append(RawAdvisory(
                source=SOURCE, ecosystem="alpine", pkg_name=f"pkg{i:05d}",
                vuln_id=f"CVE-2024-{i % 10000:04d}-{j}",
                fixed_version=fixed_pool[int(rng.integers(
                    0, len(fixed_pool)))]))
    # the skewed bucket: mostly-patched old advisories (low fix versions)
    for j in range(SKEW_ROWS):
        raw.append(RawAdvisory(
            source=SOURCE, ecosystem="alpine", pkg_name=SKEW_PKG,
            vuln_id=f"CVE-2019-{j:05d}",
            fixed_version=fixed_pool[int(rng.integers(0, len(fixed_pool)))]))
    return build_table(raw), installed_pool
