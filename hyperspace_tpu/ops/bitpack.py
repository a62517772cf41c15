"""Bit-packed i32 transport for oversubscribed residency.

A resident predicate column costs one full int32 lane per row today even
when its value domain needs far fewer bits — a 7-value shipmode column
is 3 bits of information in a 32-bit slot. At SF100 that waste is the
difference between a table fitting the HBM budget and the engine falling
off the device fast path entirely (ROADMAP R5). This module supplies the two compounding codecs
of the residency tier ladder (docs/15-streaming-residency.md):

* **plain pack** — values re-based to their minimum (frame of reference)
  and packed ``ceil(log2(span))`` bits each into int32 words,
  straddle-free: ``vpw`` values per word (the largest POWER OF TWO with
  ``vpw * bits <= 32`` — a power of two so any block/window/tile grain,
  all powers of two themselves, slices on word boundaries), so device
  unpack is one gather + shift + mask with no cross-word reassembly.
  Effective bits per value = ``32 / vpw``; packing is only adopted when
  ``vpw >= 2`` (a guaranteed >= 2x capacity win): exactly ``bits <= 16``.
* **frame-of-reference delta (FoR)** — for GLOBALLY SORTED streams (the
  join regions' pre-sorted right codes, PR 5): one raw int32 reference
  per ``block`` values plus packed in-block offsets, sized to the worst
  block's span. Decode is ``ref[i // block] + unpack(i)`` — no prefix
  scan, so it fuses into ``searchsorted`` dispatches unchanged.

Both decoders are pure jnp tracers: they run INSIDE the jitted mask /
join executables, so decompression never round-trips to host and the
D2H protocol (count vectors, match ranges) is untouched. The bit-budget
rule lives in ONE helper (``pack_spec`` / ``for_spec``), the same
discipline as ops.build._pack_plan for the radix sort composite —
callers never re-derive widths.

Packed words travel and live as int32 (the tile convention of every
resident plane); shifts and masks run on a uint32 bitcast so arithmetic
right-shift of a sign-bit-carrying word can never smear ones into a
neighbor's lanes.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

# Packing is adopted only at >= 2x savings: at bits > 16 a word holds one
# value and the "pack" would be a copy with extra decode work.
MAX_PACK_BITS = 16


def _vpw(bits: int) -> int:
    """Largest power of two with vpw * bits <= 32 — the one word-width
    rule (module docstring: powers of two keep every power-of-two grain
    word-aligned)."""
    v = 1
    while v * 2 * bits <= 32:
        v *= 2
    return v


@dataclass(frozen=True)
class PackSpec:
    """The static shape of one packed plane — the part of a codec that
    keys compiled executables (words/refs are operands, this is
    structure). ``block == 0`` means plain pack (single frame ``ref0``);
    ``block > 0`` means FoR-delta with one reference per block."""

    bits: int
    vpw: int  # values per 32-bit word (straddle-free)
    n: int  # logical values
    ref0: int = 0  # plain pack frame of reference
    block: int = 0  # FoR rows per reference (0 = plain)

    @property
    def n_words(self) -> int:
        return -(-self.n // self.vpw)

    @property
    def packed_nbytes(self) -> int:
        refs = 4 * (-(-self.n // self.block)) if self.block else 0
        return 4 * self.n_words + refs


def pack_spec(lo: int, hi: int, n: int) -> Optional[PackSpec]:
    """The plain-pack spec for ``n`` values spanning [lo, hi], or None
    when packing cannot win (span too wide for <= MAX_PACK_BITS, or
    nothing to pack). THE one copy of the bit-budget rule for plain
    planes — build and decode both read widths from here."""
    if n <= 0:
        return None
    span = hi - lo
    if span < 0:
        return None
    bits = max(int(span).bit_length(), 1)
    if bits > MAX_PACK_BITS:
        return None
    return PackSpec(bits=bits, vpw=_vpw(bits), n=n, ref0=int(lo))


def for_spec(sorted_vals: np.ndarray, block: int = 128) -> Optional[PackSpec]:
    """The FoR-delta spec for a SORTED int stream, sized to the worst
    block's span, or None when in-block spans exceed MAX_PACK_BITS (the
    stream is too sparse for the codec to win). Caller guarantees
    sortedness — it is what bounds every in-block offset by
    ``vals[block_end] - vals[block_start]``."""
    n = int(len(sorted_vals))
    if n == 0:
        return None
    v = np.asarray(sorted_vals, dtype=np.int64)
    refs = v[::block]
    spans = np.maximum.reduceat(v, np.arange(0, n, block)) - refs
    bits = max(int(spans.max()).bit_length(), 1)
    if bits > MAX_PACK_BITS:
        return None
    return PackSpec(bits=bits, vpw=_vpw(bits), n=n, block=int(block))


def pack_plain(values: np.ndarray, spec: PackSpec) -> np.ndarray:
    """Host-side plain pack: int array -> int32 words under ``spec``.
    Values must lie in [ref0, ref0 + 2^bits); the caller derived the
    spec from the same data, so violations are programming errors."""
    v = np.asarray(values, dtype=np.int64) - spec.ref0
    return _pack_offsets(v, spec)


def pack_for(sorted_vals: np.ndarray, spec: PackSpec) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side FoR-delta pack of a sorted stream: (words, refs), both
    int32. ``refs[i]`` is the raw first value of block i; offsets are
    packed plain under the spec's width."""
    v = np.asarray(sorted_vals, dtype=np.int64)
    refs64 = v[:: spec.block]
    offsets = v - np.repeat(refs64, spec.block)[: len(v)]
    return _pack_offsets(offsets, spec), refs64.astype(np.int32)


def _pack_offsets(off: np.ndarray, spec: PackSpec) -> np.ndarray:
    """Non-negative int64 offsets (< 2^bits each) -> packed int32 words,
    straddle-free: word w holds values [w*vpw, (w+1)*vpw), value j at
    bit position (j % vpw) * bits. Accumulates in uint32 so the top
    value's shift cannot overflow a signed lane."""
    n_pad = spec.n_words * spec.vpw
    padded = np.zeros(n_pad, dtype=np.uint32)
    padded[: len(off)] = off.astype(np.uint32)
    lanes = padded.reshape(spec.n_words, spec.vpw)
    words = np.zeros(spec.n_words, dtype=np.uint32)
    for j in range(spec.vpw):
        words |= lanes[:, j] << np.uint32(j * spec.bits)
    return words.view(np.int32)


# ---------------------------------------------------------------------------
# device decoders — pure jnp tracers, fused into the consuming executable
# ---------------------------------------------------------------------------


def unpack_plain_jnp(words, spec: PackSpec):
    """Traced decode of a plain-packed plane: flat int32 words (length
    >= n_words — tile padding tolerated) -> (n,) int32 values. One
    gather + shift + mask; runs inside the caller's jit."""
    import jax.numpy as jnp
    from jax import lax

    idx = lax.iota(jnp.int32, spec.n)
    w = words.reshape(-1)[idx // spec.vpw]
    u = lax.bitcast_convert_type(w, jnp.uint32)
    shift = (idx % spec.vpw).astype(jnp.uint32) * jnp.uint32(spec.bits)
    mask = jnp.uint32((1 << spec.bits) - 1)
    off = (u >> shift) & mask
    return lax.bitcast_convert_type(off, jnp.int32) + jnp.int32(spec.ref0)


def unpack_for_jnp(words, refs, spec: PackSpec):
    """Traced decode of a FoR-delta plane: (words, per-block refs) ->
    (n,) int32 sorted values. ``ref[i // block] + offset`` — no prefix
    scan, so searchsorted consumers fuse it with zero extra passes."""
    import jax.numpy as jnp
    from jax import lax

    idx = lax.iota(jnp.int32, spec.n)
    w = words.reshape(-1)[idx // spec.vpw]
    u = lax.bitcast_convert_type(w, jnp.uint32)
    shift = (idx % spec.vpw).astype(jnp.uint32) * jnp.uint32(spec.bits)
    mask = jnp.uint32((1 << spec.bits) - 1)
    off = lax.bitcast_convert_type((u >> shift) & mask, jnp.int32)
    return refs.reshape(-1)[idx // spec.block] + off


def unpack_plain_host(words: np.ndarray, spec: PackSpec) -> np.ndarray:
    """Numpy twin of unpack_plain_jnp — the streaming tier's host planes
    decode through HERE when a window must be re-evaluated host-side
    (device loss mid-window), so both engines share one codec."""
    idx = np.arange(spec.n)
    u = words.reshape(-1)[: spec.n_words].view(np.uint32)[idx // spec.vpw]
    shift = ((idx % spec.vpw) * spec.bits).astype(np.uint32)
    off = (u >> shift) & np.uint32((1 << spec.bits) - 1)
    return off.view(np.int32) + np.int32(spec.ref0)
