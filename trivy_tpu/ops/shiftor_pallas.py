"""Pallas TPU kernel for the exact shift-or secret keyword engine.

The jnp path (`ops.ac.shiftor_scan`) re-reads the packed word plane
from HBM once per (keyword, state word) pair — a `lax.scan` over ~93
keywords × state_words ≈ 650 full HBM passes over a [B, 16384] uint32
plane. This kernel is the TPU-first form of the same exact match: each
[128, 128] tile of chunk positions is DMA'd into VMEM once per state
word and every keyword's FULL multi-word state is checked there, so
HBM traffic is `state_words` reads of the input plus a tiny hit-row
write. Every word of every keyword is verified — the output bitmask
is exact and the host stage runs regexes only.

Layout: positions fill the whole tile (sublanes × lanes, no
transpose), keywords are a `fori_loop` whose word/mask operands are
SMEM scalars broadcast by the VPU. Plane w is the base word plane
shifted 4w bytes (precomputed by XLA inside the same jit), so a
match's later words read past the tile without lane-unaligned slicing
in the kernel. Per keyword the kernel ANDs the masked-XOR equalities
over the state words, reduces "any position" to one value and writes
it into that keyword's output lane.

The keyword loop keeps one tile-sized temporary live at a time. The
previous form unrolled 128 position columns × state_words statically
and needed ~54 MiB of scoped VMEM for the builtin bank, which v5e's
16 MiB limit refuses; this one compiles in well under a second.

Output: int32[B, W] packed keyword bitmask, identical layout to
`ac.shiftor_scan` — the host decode stage is shared.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

K_LANES = 128  # keyword bank padded to one full lane register


def _kernel(kww_ref, kwm_ref, y_ref, out_ref):
    n_state = y_ref.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, K_LANES), 1)

    def keyword(k, hits):
        m = None
        for w in range(n_state):
            eq = ((y_ref[0, w] ^ kww_ref[w, k]) & kwm_ref[w, k]) == 0
            m = eq if m is None else (m & eq)
        hit = jnp.max(m.astype(jnp.int32), axis=0, keepdims=True)
        hit = jnp.max(hit, axis=1, keepdims=True)          # [1, 1]
        return jnp.where(lane == k, hit, hits)

    out_ref[0] = jax.lax.fori_loop(0, kww_ref.shape[1], keyword,
                                   jnp.zeros((1, K_LANES), jnp.int32))


@functools.partial(jax.jit,
                   static_argnames=("n_words", "interpret"))
def shiftor(kw_words, kw_masks, kw_bits, chunks, *, n_words: int,
            interpret: bool = False):
    """chunks: uint8[B, L] (lowercased, L % 16384 == 0) →
    int32[B, n_words] EXACT keyword bitmask (bit k set iff keyword k
    occurs in the chunk). kw_* come from `pack_bank`."""
    b, length = chunks.shape
    n_state = kw_words.shape[0]
    # keywords past 32 * n_words fall outside the returned words, so
    # the loop never visits those padding lanes
    n_kw = min(K_LANES, 32 * n_words)
    c = chunks.astype(jnp.uint32)
    pad = jnp.pad(c, ((0, 0), (0, 4)))
    w4 = (pad[:, :length]
          | (pad[:, 1:length + 1] << 8)
          | (pad[:, 2:length + 2] << 16)
          | (pad[:, 3:length + 3] << 24)).astype(jnp.int32)
    # state-word planes: plane w is w4 shifted 4w bytes left (row-
    # locally — chunk rows are independent), so the kernel's word-w
    # compare at position p reads w4[p + 4w]. Zero tail padding cannot
    # false-positive: no keyword word has a NUL under its mask.
    w4p = jnp.pad(w4, ((0, 0), (0, 4 * n_state)))
    planes = jnp.stack([w4p[:, 4 * w:4 * w + length]
                        for w in range(n_state)], axis=1)  # [B, W, L]
    n_tiles = length // (K_LANES * K_LANES)
    y = planes.reshape(b, n_state, length // K_LANES, K_LANES)
    hits = pl.pallas_call(
        _kernel,
        grid=(b, n_tiles),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, n_state, K_LANES, K_LANES),
                         lambda i, t: (i, 0, t, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, K_LANES),
                               lambda i, t: (i * n_tiles + t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * n_tiles, 1, K_LANES),
                                       jnp.int32),
        interpret=interpret,
        name="shiftor",
    )(kw_words[:, :n_kw], kw_masks[:, :n_kw], y)
    # a chunk row spans L/16384 tiles; OR them back together.
    # Pack bits: entries are 0/1, so bit-weighted group sums equal
    # bitwise OR within each 32-keyword word.
    row_hits = jnp.max(hits.reshape(b, n_tiles, K_LANES), axis=1)
    bits = row_hits * kw_bits                            # [B, 128]
    words = jnp.sum(bits.reshape(b, K_LANES // 32, 32), axis=2)
    return words[:, :n_words]


def pack_bank(bank) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """LiteralBank → kernel-ready ([W, 128] int32 word/mask planes,
    [1, 128] int32 bit values). Padding entries carry word=-1/mask=-1
    (an all-0xFF word CAN occur in binary data, but their bit value is
    0 so a spurious hit never sets a bit)."""
    n = bank.n_keywords
    if n > K_LANES:
        raise ValueError(f"keyword bank > {K_LANES} needs multi-tile "
                         f"lanes: {n}")
    n_state = bank.state_words
    kww = np.full((n_state, K_LANES), -1, dtype=np.int32)
    kwm = np.full((n_state, K_LANES), -1, dtype=np.int32)
    bit = np.zeros(K_LANES, dtype=np.int32)
    kww[:, :n] = bank.kw_words.view(np.int32)
    kwm[:, :n] = bank.kw_masks.view(np.int32)
    bit[:n] = (np.uint32(1) << (np.arange(n, dtype=np.uint32) % 32)) \
        .view(np.int32)
    return kww, kwm, bit.reshape(1, -1)
