"""The advisory table of a deployment, made from the run's seed.

Copied from `trivy_tpu/db/synth.py` (PR 21) so that no program PR can
move the yardstick: 30,000 source package names with Zipf(1.7) bucket
sizes clipped to [1, 64], plus one skewed source package (Alpine's
`linux-lts`) carrying 4,000 advisory rows: 204,795 interval rows. The
versions, the fixed version of each row and the source and ecosystem
are the OS package family's (`families/<family>.py`).

One change from the copy: the multiset of bucket sizes is the one seed
7 drew in PR 21, for every run seed. The seed deals those sizes out to
package names and draws every version, so each seed has the same table
shape (and so the same compiled programs) and the same amount of work.
"""

from __future__ import annotations

import functools

import numpy as np

from .families import alpine

N_PKG_NAMES = 30_000
SKEW_ROWS = 4000
SHAPE_SEED = 7
# the names tests have always imported: the Alpine family's
SOURCE, ECOSYSTEM, SKEW_PKG = alpine.SOURCE, alpine.ECOSYSTEM, alpine.SKEW_PKG
synth_versions = alpine.versions


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one stream of a run. `seed` is any integer,
    negative or past 64 bits included."""
    return np.random.default_rng([int(seed) % (1 << 63), *stream])


@functools.lru_cache(maxsize=1)
def bucket_sizes() -> np.ndarray:
    """The advisory count of each of the 30,000 packages, as the PR 21
    smoke drew them from seed 7 (same draw order): two apk version
    pools, then the Zipf draw. It sets the table's shape for every
    family."""
    rng = np.random.default_rng(SHAPE_SEED)
    alpine.pools(rng)
    return np.clip(rng.zipf(1.7, N_PKG_NAMES), 1, 64)


class Advisories:
    """The deployment's advisories as plain data: for each source
    package name, its vulnerability ids and fixed versions (vulnerable
    iff the installed version sorts below the fixed one, or the fixed
    version is "", unfixed), and the family (`families/<family>.py`)
    whose versions and packages they are."""

    def __init__(self, by_pkg: dict, fixed_pool: list, installed_pool: list,
                 family=alpine):
        self.by_pkg = by_pkg                # name -> (ids, fixed versions)
        self.fixed_pool = fixed_pool
        self.installed_pool = installed_pool
        self.family = family

    @property
    def n_rows(self) -> int:
        return sum(len(ids) for ids, _ in self.by_pkg.values())

    def raw(self):
        """→ the program's RawAdvisory list, in table order."""
        from trivy_tpu.db.table import RawAdvisory
        fam = self.family
        return [RawAdvisory(source=fam.SOURCE, ecosystem=fam.ECOSYSTEM,
                            pkg_name=name, vuln_id=vid, fixed_version=fv)
                for name, (ids, fixed) in self.by_pkg.items()
                for vid, fv in zip(ids, fixed)]


def advisories(seed: int, n_names: int = N_PKG_NAMES,
               skew_rows: int = SKEW_ROWS, family=alpine) -> Advisories:
    """The table of `family` from `seed`. Smaller `n_names`/`skew_rows`
    are for the CPU tests only."""
    rng = rng_for(seed, 0)
    fixed_pool, installed_pool = family.pools(rng)
    sizes = rng.permutation(bucket_sizes())[:n_names]
    fixed = family.fixed(rng, fixed_pool, int(sizes.sum()))
    by_pkg = {}
    k = 0
    for i in range(n_names):
        n = int(sizes[i])
        by_pkg[f"pkg{i:05d}"] = (
            [f"CVE-2024-{i % 10000:04d}-{j}" for j in range(n)],
            fixed[k:k + n])
        k += n
    # the skewed bucket: mostly-patched old advisories (low fix versions)
    by_pkg[family.SKEW_PKG] = (
        [f"CVE-2019-{j:05d}" for j in range(skew_rows)],
        family.fixed(rng, fixed_pool, skew_rows))
    return Advisories(by_pkg, fixed_pool, installed_pool, family)


def program_table(adv: Advisories):
    """The system under test's table, built by the program's loader
    from the same advisories."""
    from trivy_tpu.db.table import build_table
    return build_table(adv.raw())
