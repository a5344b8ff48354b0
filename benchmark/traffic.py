"""The one traffic generator: every mix is a data file it reads.

A traffic file (`benchmark/traffic/<traffic>.json`) gives the
parameters below; nothing here knows a cell by name. Every loop is
closed: each client or worker sends its next scan when its last one
returns.

  clients        client processes (server entry) or scan workers
                 (image entry)
  max_scans_per_s  server entry: the highest rate the window's archives
                 cover. Each client gets max_scans_per_s x seconds /
                 clients distinct archives; a client that runs out
                 makes the run not correct (check `archives_ran_out`)
  warmup_scans   archives each client keeps for warming up; warm-up
                 runs the cell's own loop in rounds of `warmup_seconds`
                 (cycling these archives) until `warmup_quiet_rounds`
                 rounds in a row compile nothing (a cell's first run in
                 a checkout, which compiles: `warmup_cold_quiet_rounds`)
  pool           image entry: images cycled through, each scan on a
                 fresh cache
  image          pkgs [lo, hi]: source packages per image, spread evenly
                 over the range; text_files, text_file_bytes: a second
                 layer of seeded text with one AWS key planted per image

Every seed gets the same multiset of image sizes, in another order, so
seeds change which work is done and not how much.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as np

from . import synth

# no word here is a keyword of a builtin secret rule, so only the
# planted key can match
_WORDS = np.array([w.encode() for w in (
    "the quick brown fox jumps over lazy dog river stone cloud maple "
    "harbor lantern meadow copper violet summer winter autumn garden "
    "window pencil rocket marble silver candle forest island valley "
    "thunder pebble orchid canyon willow falcon ember glacier prairie "
    "compass anchor beacon cobalt dune fern grove hollow ivory jasper "
    "juniper lagoon lotus mosaic nectar opal quartz ripple saffron "
    "timber tundra umber velvet wren yarrow zephyr amber birch cedar "
    "delta").split()], dtype=object)


@dataclass
class ImageSpec:
    tag: str
    pkgs: list                       # [(name, installed version)]
    text_files: list = field(default_factory=list)   # [(path, bytes)]

    @property
    def text_bytes(self) -> int:
        return sum(len(b) for _, b in self.text_files)


def archives_per_client(traf: dict, seconds: float) -> int:
    """Distinct archives each client of a served cell has for a window
    of `seconds`: enough for `max_scans_per_s` over the whole window."""
    return math.ceil(traf["max_scans_per_s"] * seconds / traf["clients"])


def spread(rng, lo: int, hi: int, n: int) -> np.ndarray:
    """n integers spread evenly over [lo, hi], in seeded order."""
    return rng.permutation(lo + ((np.arange(n) + 0.5) * (hi - lo + 1)
                                 / n).astype(int))


def aws_key(rng) -> str:
    alphabet = "ABCDEFGHIJKLMNOPQRSTUVWXYZ234567"
    return "AKIA" + "".join(alphabet[int(i)]
                            for i in rng.integers(0, 32, 16))


def text_file(rng, n_bytes: int) -> bytes:
    """Seeded lines of words, exactly n_bytes long."""
    words = _WORDS[rng.integers(0, len(_WORDS), n_bytes // 5)]
    lines = [b" ".join(words[i:i + 10]) for i in range(0, len(words), 10)]
    return (b"\n".join(lines))[:n_bytes - 1] + b"\n"


def plant(data: bytes, rng, line: bytes) -> bytes:
    """Replace one whole line of `data` (not the first or last) by
    `line`."""
    lines = data.split(b"\n")
    lines[int(rng.integers(1, len(lines) - 2))] = line
    return b"\n".join(lines)


def image_specs(adv, image: dict, seed: int, stream: int, n: int,
                prefix: str) -> list:
    """n image specs of one stream of a run."""
    rng = synth.rng_for(seed, 1, stream)
    n_names = len(adv.by_pkg) - 1     # the kernel's bucket is not drawn
    sizes = spread(rng, image["pkgs"][0], image["pkgs"][1], n)
    pool = adv.installed_pool
    out = []
    for i in range(n):
        names = [f"pkg{k:05d}" for k in
                 rng.choice(n_names, int(sizes[i]), replace=False)]
        pkgs = [(nm, pool[int(v)]) for nm, v in
                zip(names, rng.integers(0, len(pool), len(names)))]
        out.append(ImageSpec(f"bench/{prefix}{i}:1", pkgs,
                             _text_layer(rng, image)))
    return out


def _text_layer(rng, image: dict) -> list:
    n_files = image.get("text_files", 0)
    if not n_files:
        return []
    files = [[f"srv/app/doc{j:03d}.txt", text_file(rng, image[
        "text_file_bytes"])] for j in range(n_files)]
    j = int(rng.integers(0, n_files))
    files[j][1] = plant(files[j][1], rng,
                        f"aws_access_key_id = {aws_key(rng)}".encode())
    return [tuple(f) for f in files]
