"""Fused multi-array device gather (dense lane packing).

Every fusion-breaking HLO op (gather, sort pass, cumsum, scan) is its
own pass over HBM with its own launch floor, with bandwidth mattering
only for wide matrices. So a 26-array payload gather runs as ONE
``(cap, K)`` int64-matrix gather plus fusible elementwise pack/unpack
instead of 26 gathers (one gather beats twenty-six; the ratio is not
measured on the directly attached chip) — and the matrix should be as
NARROW as possible: bools pack 64 to a lane,
int8s 8, int16s 4, int32/float32s 2. This module is that pack/unpack;
float64s ride a separate f64 matrix (64-bit float bitcasts don't lower
on this TPU stack).

The reference hits the same per-call economics at a different layer:
its JNI crossings batch into one cudf Table op per batch
(GpuColumnVector.java handle arrays); here the batching is per-HLO.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

_U64 = jnp.uint64


def _bit_width(dt) -> int:
    if dt == jnp.bool_:
        return 1
    return jnp.dtype(dt).itemsize * 8


def _as_u64_bits(a: jax.Array) -> jax.Array:
    """Value -> its raw bits in a u64 (zero-extended), elementwise."""
    dt = a.dtype
    if dt == jnp.bool_:
        return a.astype(_U64)
    if dt == jnp.float32:
        return jax.lax.bitcast_convert_type(a, jnp.int32) \
            .astype(jnp.int64).view(_U64) & _U64(0xFFFFFFFF)
    if dt == jnp.uint64:
        return a
    if dt in (jnp.int64,):
        return a.view(_U64)
    # smaller ints (signed or not): zero-extend the raw two's-complement
    w = _bit_width(dt)
    mask = _U64((1 << w) - 1)
    return a.astype(jnp.int64).view(_U64) & mask


def _from_u64_bits(bits: jax.Array, dt, w: int) -> jax.Array:
    if dt == jnp.bool_:
        return bits != _U64(0)
    if dt == jnp.float32:
        return jax.lax.bitcast_convert_type(
            bits.view(jnp.int64).astype(jnp.int32), jnp.float32)
    if dt == jnp.uint64:
        return bits
    v = bits.view(jnp.int64)
    if w < 64 and jnp.issubdtype(jnp.dtype(dt), jnp.signedinteger):
        v = (v << jnp.int64(64 - w)) >> jnp.int64(64 - w)  # sign-extend
    return v.astype(dt)


class _LaneAlloc:
    """First-fit slot allocator over u64 lanes."""

    def __init__(self):
        self.lanes: List[List[jax.Array]] = []  # per-lane shifted parts
        self.free: List[int] = []               # bits free per lane

    def add(self, bits: jax.Array, w: int) -> Tuple[int, int]:
        for li in range(len(self.lanes)):
            if self.free[li] >= w:
                off = 64 - self.free[li]
                self.lanes[li].append(bits << _U64(off) if off else bits)
                self.free[li] -= w
                return li, off
        self.lanes.append([bits])
        self.free.append(64 - w)
        return len(self.lanes) - 1, 0

    def materialize(self) -> List[jax.Array]:
        out = []
        for parts in self.lanes:
            lane = parts[0]
            for p in parts[1:]:
                lane = lane | p
            out.append(lane.view(jnp.int64))
        return out


def chars_to_u64_words(chars: jax.Array) -> List[jax.Array]:
    """uint8[cap, w] (w % 8 == 0) -> w/8 big-endian u64 words. Shared
    with ops/groupby.pack_string_words: big-endian word order == byte
    lexicographic order, which the sort kernels rely on."""
    cap, w = chars.shape
    c64 = chars.astype(_U64)
    words = []
    for k in range(w // 8):
        word = jnp.zeros(cap, dtype=_U64)
        for j in range(8):
            word = word | (c64[:, 8 * k + j] << _U64(56 - 8 * j))
        words.append(word)
    return words


_chars_to_words = chars_to_u64_words


def _words_to_chars(words: List[jax.Array], w: int) -> jax.Array:
    cols = []
    for word in words:
        for j in range(8):
            cols.append(((word >> _U64(56 - 8 * j))
                         & _U64(0xFF)).astype(jnp.uint8))
    return jnp.stack(cols[:w], axis=1)


def fused_take(arrays: Sequence[jax.Array], idx: jax.Array
               ) -> List[jax.Array]:
    """``[a[idx] for a in arrays]`` as at most two real gathers: one over
    a densely-packed int64 lane matrix, one over an f64 matrix. 2D uint8
    char matrices (width % 8 == 0) pack as u64 words; any other shape
    falls back to its own gather. Duplicate array objects pack once."""
    alloc = _LaneAlloc()
    flanes: List[jax.Array] = []
    plan: List[Tuple] = []
    out: List[Optional[jax.Array]] = [None] * len(arrays)
    seen: dict = {}
    for i, a in enumerate(arrays):
        dup = seen.get(id(a))
        if dup is not None:
            plan.append(("dup", i, dup))
            continue
        seen[id(a)] = i
        if a.ndim == 1 and a.dtype == jnp.float64:
            plan.append(("f", i, len(flanes)))
            flanes.append(a)
        elif a.ndim == 1:
            w = _bit_width(a.dtype)
            li, off = alloc.add(_as_u64_bits(a), w)
            plan.append(("i", i, li, off, w, a.dtype))
        elif (a.ndim == 2 and a.dtype == jnp.uint8
              and a.shape[1] % 8 == 0 and a.shape[1] > 0):
            slots = [alloc.add(wd, 64) for wd in _chars_to_words(a)]
            plan.append(("c", i, [s[0] for s in slots], a.shape[1]))
        else:
            out[i] = jnp.take(a, idx, axis=0)
    ilanes = alloc.materialize()
    if len(ilanes) == 1:
        ig = [jnp.take(ilanes[0], idx)]
    elif ilanes:
        imat = jnp.stack(ilanes, axis=1)
        g = jnp.take(imat, idx, axis=0)
        ig = [g[:, k] for k in range(len(ilanes))]
    else:
        ig = []
    if len(flanes) == 1:
        fg = [jnp.take(flanes[0], idx)]
    elif flanes:
        fmat = jnp.stack(flanes, axis=1)
        gf = jnp.take(fmat, idx, axis=0)
        fg = [gf[:, k] for k in range(len(flanes))]
    else:
        fg = []
    for ent in plan:
        if ent[0] == "f":
            _k, i, li = ent
            out[i] = fg[li]
        elif ent[0] == "i":
            _k, i, li, off, w, dt = ent
            bits = ig[li].view(_U64)
            if off:
                bits = bits >> _U64(off)
            if w < 64:
                bits = bits & _U64((1 << w) - 1)
            out[i] = _from_u64_bits(bits, dt, w)
        elif ent[0] == "c":
            _k, i, lis, w = ent
            out[i] = _words_to_chars([ig[li].view(_U64) for li in lis], w)
    for ent in plan:
        if ent[0] == "dup":
            out[ent[1]] = out[ent[2]]
    return out  # type: ignore[return-value]
