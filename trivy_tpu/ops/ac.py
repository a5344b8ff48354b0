"""Secrets engine v2: exact multi-pattern keyword matching on device.

The reference gates each of its 86 secret rules on a bytes.Contains
keyword check before running the rule regex
(pkg/fanal/secret/scanner.go:363-371) — that prefilter is the bulk of the
scan cost over a filesystem. Keywords are fixed strings, so no DFA is
needed. Engine v1 tested only each keyword's packed 4-byte PREFIX on
device (a superset filter) and re-confirmed every candidate with a host
substring pass; v2 verifies FULL keywords on device with a bit-parallel
shift-or (bitap) formulation, so the device output is the EXACT
per-chunk keyword bitmask and the host stage shrinks to "run the regex
for gated rules" — nothing is re-scanned.

The shift-or recurrence per pattern j is S ← ((S << 1) | 1) & B[c]; a
match fires when bit m_j-1 of S sets. Two transforms make it
TPU-shaped:

  * radix-2^32 alphabet: instead of a per-byte B[c] table gather (a
    256-way gather per position — hostile to the VPU), each byte
    position p carries the packed little-endian word of its next 4
    bytes (w4[p], three shift-ors), and a pattern's state advances 4
    bytes per word compare: `(w4[p + 4w] ^ word_w) & mask_w == 0`.
    Keywords shorter than 4(w+1) bytes mask the tail of word w;
    words fully past the keyword have mask 0 (always true).
  * position parallelism: because the state width (max keyword length,
    25 for the builtin bank) never exceeds one chunk, the recurrence
    unrolls completely — pattern j matches ENDING AT p iff every one of
    its ceil(m_j/4) word compares holds starting at p-m_j+1 — so all
    positions evaluate simultaneously instead of marching one byte at a
    time. Pattern states live on the 128-lane axis (one lane per
    keyword, ≤128 like the v1 bank); the multi-WORD state extends the
    v1 single-prefix-word layout to `state_words` planes.

Files are packed into fixed [B, L] uint8 chunk tensors with an overlap
of max keyword length - 1, so every occurrence lies wholly inside some
row and the per-row verdicts are exact for the whole file. The jnp
`shiftor_scan` here is the CPU and mesh path; on TPU backends the
Pallas kernel in ops/shiftor_pallas.py does the same compares out of
VMEM in a single HBM pass. The host engine (`bytes.find` per keyword)
remains the graftguard fallback and the parity oracle — device ≡ host
finding-for-finding is gated in tier-1.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp

_LOWER = np.arange(256, dtype=np.uint8)
_LOWER[65:91] += 32  # A-Z → a-z


def lower_bytes(data: bytes) -> np.ndarray:
    return _LOWER[np.frombuffer(data, dtype=np.uint8)]


@dataclass
class LiteralBank:
    """Keyword literals (matched lowercased) + packed word planes.

    The multi-word arrays are the full shift-or state — word w of
    keyword k covers its bytes 4w..4w+3 (v1 carried only word 0, the
    4-byte prefix, and was therefore a superset filter)."""
    kw_bytes: list          # [Nk] lowercased keyword bytes (host path)
    kw_words: np.ndarray    # uint32[W, Nk] packed 4-byte words
    kw_masks: np.ndarray    # uint32[W, Nk] per-word byte masks
    n_keywords: int
    max_kw_len: int

    @property
    def words(self) -> int:
        """Output bitmask words: 32 keyword bits per int32."""
        return max(1, (self.n_keywords + 31) // 32)

    @property
    def state_words(self) -> int:
        """Shift-or state words per keyword: ceil(max_kw_len / 4)."""
        return self.kw_words.shape[0]


def build_literal_bank(keywords: list[bytes]) -> LiteralBank:
    kws = [bytes(_LOWER[np.frombuffer(k, np.uint8)]) for k in keywords]
    n = len(kws)
    max_len = max((len(k) for k in kws), default=1)
    n_state = max(1, (max_len + 3) // 4)
    words = np.zeros((n_state, n), dtype=np.uint32)
    masks = np.zeros((n_state, n), dtype=np.uint32)
    for i, k in enumerate(kws):
        for w in range(n_state):
            p = k[4 * w:4 * w + 4]
            if not p:
                break  # word fully past the keyword: mask 0 = always true
            words[w, i] = int.from_bytes(p.ljust(4, b"\0"), "little")
            masks[w, i] = (1 << (8 * len(p))) - 1 if len(p) < 4 \
                else 0xFFFFFFFF
    return LiteralBank(kw_bytes=kws, kw_words=words, kw_masks=masks,
                       n_keywords=n, max_kw_len=max_len)


@functools.partial(jax.jit, static_argnames=("n_words",))
def shiftor_scan(kw_words, kw_masks, chunks, *, n_words: int):
    """chunks: uint8[B, L] (lowercased) → int32[B, W] EXACT keyword
    bitmask — bit k set iff keyword k occurs somewhere in the chunk.

    Flattened lax.scan over (keyword, state word) pairs: the carry
    holds the per-position running AND of word compares (`match`,
    reset at each keyword's word 0) and the accumulated output
    bitmask. The shifted word plane for state word w is a
    dynamic_slice of the single padded w4 plane at byte offset 4w —
    dynamic on purpose: a static slice per word would be hoisted out
    of the scan as W materialized [B, L] planes (state_words × the
    input in live memory); the in-loop slice keeps the working set at
    two [B, L] planes regardless of keyword length."""
    b, length = chunks.shape
    n_state, n_kw = kw_words.shape
    c = chunks.astype(jnp.uint32)
    pad = jnp.pad(c, ((0, 0), (0, 4)))
    w4 = (pad[:, :length]
          | (pad[:, 1:length + 1] << 8)
          | (pad[:, 2:length + 2] << 16)
          | (pad[:, 3:length + 3] << 24))                  # [B, L]
    # zero-pad so the shifted slice at offset 4w exists for every w;
    # keywords never contain NULs the mask keeps, so padding cannot
    # create a false positive
    w4p = jnp.pad(w4, ((0, 0), (0, 4 * n_state)))

    steps = n_kw * n_state
    ki = jnp.repeat(jnp.arange(n_kw, dtype=jnp.int32), n_state)
    wi = jnp.tile(jnp.arange(n_state, dtype=jnp.int32), n_kw)
    xs = (kw_words.T.reshape(-1), kw_masks.T.reshape(-1), ki, wi)

    def step(carry, x):
        match, acc = carry
        word, mask, k, w = x
        plane = jax.lax.dynamic_slice(w4p, (0, 4 * w), (b, length))
        eq = ((plane ^ word) & mask) == 0                  # [B, L]
        match = jnp.where(w == 0, eq, match & eq)
        hit = jnp.any(match, axis=-1)                      # [B]
        bit = jnp.where(
            jnp.arange(n_words, dtype=jnp.int32) == k // 32,
            jnp.int32(1) << (k % 32), jnp.int32(0))        # [W]
        # fold the keyword's verdict in only after its LAST word
        take = (w == n_state - 1) & hit[:, None]
        acc = acc | jnp.where(take, bit[None, :], 0)
        return (match, acc), None

    init = (jnp.zeros((b, length), dtype=bool),
            jnp.zeros((b, n_words), dtype=jnp.int32))
    (_, acc), _ = jax.lax.scan(step, init, xs, length=steps)
    return acc


def chunk_counts(lengths, chunk_len: int, overlap: int) -> np.ndarray:
    """Rows each file of `lengths` bytes packs into: one for a non-empty
    file of up to chunk_len bytes, then one per stride (chunk_len -
    overlap, at least 1) until a row reaches the file's end. Row r
    starts at r·stride, so any overlap + 1 consecutive bytes lie wholly
    inside some row — with a clamped stride (overlap ≥ chunk_len) too."""
    n = np.asarray(lengths, np.int64)
    stride = max(1, chunk_len - overlap)
    more = np.maximum(0, -(-(n - chunk_len) // stride))
    return np.where(n > 0, 1 + more, 0)


def pack_batch(files: list[bytes], chunk_len: int, overlap: int,
               capacity=None, fingerprint: bool = False
               ) -> tuple[np.ndarray, np.ndarray, np.ndarray | None]:
    """Lowercase and chunk every file, row after row, into one buffer
    in one pass → (rows uint8[capacity(n), chunk_len], owner int64[n]:
    row → file index, fp uint64[n] or None), n the rows the files make
    (chunk_counts). Row r of a file holds its bytes [r·stride, r·stride
    + chunk_len), zero past the file's end; rows from n on are zero
    (capacity: n → buffer rows, default n). The native packer
    (trivy_tpu.native) fills the buffer when the library builds, numpy
    otherwise, identically; `fingerprint` (chunk_len a multiple of 8)
    adds each row's row_fingerprints value, from the native packer
    while the row is still in cache."""
    from .. import native
    counts = chunk_counts([len(f) for f in files], chunk_len, overlap)
    n = int(counts.sum())
    rows = np.zeros((n if capacity is None else capacity(n), chunk_len),
                    np.uint8)
    fp = np.empty(n, np.uint64) if fingerprint else None
    if not native.lower_pack_batch(files, counts, chunk_len, overlap,
                                   rows, fp):
        _pack_py(files, counts, chunk_len, overlap, rows)
        if fingerprint:
            fp = row_fingerprints(rows[:n])
    return rows, np.repeat(np.arange(len(files), dtype=np.int64),
                           counts), fp


def pack_chunks(files: list[bytes], chunk_len: int,
                overlap: int) -> tuple[np.ndarray, np.ndarray]:
    """Pack lowercased file bytes into [B, chunk_len] with per-chunk file
    index map [B] (pack_batch without padding or fingerprints)."""
    rows, owner, _ = pack_batch(files, chunk_len, overlap)
    return rows, owner


# row fingerprint: Σ_j mix((w_j + j·G) · C) mod 2^64 over a row's
# little-endian 64-bit words, mix(x) = x ^ (x >> 31) — the same sum
# native/trivy_native.cpp lower_pack_batch computes
_FP_G = np.uint64(0x9E3779B97F4A7C15)
_FP_C = np.uint64(0xBF58476D1CE4E5B9)


def row_fingerprints(rows: np.ndarray) -> np.ndarray:
    """uint8[n, L] (L a multiple of 8, rows contiguous) → uint64[n]: a
    64-bit fingerprint per row. Equal rows give equal fingerprints;
    unequal rows almost never do, and callers compare the bytes of rows
    that share one."""
    words = np.ascontiguousarray(rows).view(np.uint64)
    x = words + np.arange(words.shape[1], dtype=np.uint64) * _FP_G
    x *= _FP_C
    x ^= x >> np.uint64(31)
    return x.sum(axis=1, dtype=np.uint64)


def _pack_py(files: list[bytes], counts: np.ndarray, chunk_len: int,
             overlap: int, out: np.ndarray) -> None:
    """numpy twin of the native packer: rows of `files` into `out`."""
    stride = max(1, chunk_len - overlap)
    row = 0
    for data, n_rows in zip(files, counts.tolist()):
        src = np.frombuffer(data, np.uint8)
        for r in range(n_rows):
            piece = src[r * stride:r * stride + chunk_len]
            out[row, :len(piece)] = _LOWER[piece]
            out[row, len(piece):] = 0
            row += 1


def _pack_one_py(data: bytes, chunk_len: int, overlap: int) -> np.ndarray:
    counts = chunk_counts([len(data)], chunk_len, overlap)
    out = np.zeros((int(counts[0]), chunk_len), np.uint8)
    _pack_py([data], counts, chunk_len, overlap, out)
    return out
