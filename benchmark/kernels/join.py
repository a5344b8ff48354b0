"""Least bytes of the advisory join (`ops/join.py` csr_pair_join*) over
the scans a window completed: what any implementation has to move.

- each distinct advisory row the window's packages select, once:
  lower and upper bound tokens and the flags word, 2 x 40 x 4 + 4 =
  324 bytes;
- each distinct installed version that meets a candidate row, once:
  40 tokens, 160 bytes;
- one result bit per candidate pair.

Counted from the traffic and the advisories, never from the program's
padded shapes, so no change to padding, dedup or row reuse can raise
it. An image's keys are (source name, source version), as the program
joins them: the binaries of one source share its rows and its version.
"""

KEY_WIDTH = 40
ROW_BYTES = 2 * KEY_WIDTH * 4 + 4
VERSION_BYTES = KEY_WIDTH * 4


def least_bytes(ctx) -> int:
    by_pkg = ctx.adv.by_pkg
    names, versions, pairs = set(), set(), 0
    for s in ctx.scans:
        for name, ver in s.spec.pkgs:
            n = len(by_pkg[name][0]) if name in by_pkg else 0
            if n:
                names.add(name)
                versions.add(ver)
                pairs += n
    rows = sum(len(by_pkg[n][0]) for n in names)
    return rows * ROW_BYTES + len(versions) * VERSION_BYTES + -(-pairs // 8)
