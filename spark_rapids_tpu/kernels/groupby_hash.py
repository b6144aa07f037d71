"""Single-pass hash-table group-by kernel (Pallas).

Replaces ``ops/groupby.py``'s lexsort + segmented-scan pipeline for the
PARTIAL aggregation update when every slot is in the SUM/COUNT/MIN/MAX
family over fixed-width data: one open-addressed insert/combine pass
over the batch instead of a multi-word radix sort plus scans — the
direct twin of the cuDF hash aggregation the reference leans on
(SURVEY.md §2.4), shaped for this engine's static-capacity batches.

Bit-identity with the oracle is by construction, not by luck:

- every accumulator lane is **int64** (counts, integer/decimal sums in
  the exact 32-bit-part encoding of ``seg_sums_batched``, min/max over
  order-preserving integer ranks), so accumulation order cannot change
  a single bit — float sums are *not* eligible (their segmented-scan
  order is part of the oracle's contract);
- group KEY columns are gathered from the original batch by each
  group's first-occurrence row index, never reconstructed from hashes;
- partial-mode group ORDER is not part of the engine contract (the
  merge/final stage re-groups), so the kernel emitting groups in
  table-slot order instead of hash-sorted order is invisible
  downstream — q1/q3 stay bit-identical end to end.

The table lives in the program's value space (``slots`` entries, power
of two); a batch with more distinct groups than the table holds raises
the ``overflow`` flag and the exec re-runs it on the oracle
(``kernelFallbacks.groupbyHash``) — the remaining blocks short-circuit
the moment overflow is known.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

import numpy as np

from spark_rapids_tpu.sql import expressions as E
from spark_rapids_tpu.sql import types as T

_I64_MAX = np.int64(2**63 - 1)
_I64_MIN = np.int64(-(2**63))
_ROW_BIG = np.int32(2**31 - 1)

# aggregation primitives the kernel implements, by lane op
_SUM_PRIMS = {E.PRIM_COUNT, E.PRIM_SUM, E.PRIM_SUM_NONNULL}
_EXTREME_PRIMS = {E.PRIM_MIN, E.PRIM_MAX}

# key/value scalar types whose equality words / min-max ranks are a
# fixed number of integer lanes (floats stay on the oracle: their
# NaN-word encodings are float-typed and their sums are order-bound)
_WORD_KEY_TYPES = (T.BooleanType, T.ByteType, T.ShortType,
                   T.IntegerType, T.LongType, T.DateType,
                   T.TimestampType, T.StringType, T.DecimalType)
_EXTREME_TYPES = (T.BooleanType, T.ByteType, T.ShortType,
                  T.IntegerType, T.LongType, T.DateType,
                  T.TimestampType)


def _key_type_ok(dt: T.DataType) -> bool:
    return isinstance(dt, _WORD_KEY_TYPES)


def _extreme_type_ok(dt: T.DataType) -> bool:
    if isinstance(dt, _EXTREME_TYPES):
        return True
    return isinstance(dt, T.DecimalType) and dt.precision <= 18


def agg_kernel_eligible(mode: str,
                        grouping: Sequence[E.AttributeReference],
                        slot_srcs: Sequence[E.Expression],
                        prims: Sequence[Tuple[str, T.DataType]]) -> bool:
    """Static shape check (no tracing): can the whole aggregation
    program run through the hash-table kernel? All-or-nothing — a
    single ineligible slot keeps the entire program on the oracle, so
    one program never mixes the two pipelines."""
    from spark_rapids_tpu.columnar.device import storage_jnp_dtype
    if mode != "partial" or not grouping:
        return False
    for g in grouping:
        if not _key_type_ok(g.data_type):
            return False
    for src, (prim, out_type) in zip(slot_srcs, prims):
        if prim == E.PRIM_COUNT:
            continue
        if prim in (E.PRIM_SUM, E.PRIM_SUM_NONNULL):
            if T.is_limb_decimal(out_type):
                continue
            if jnp.issubdtype(storage_jnp_dtype(out_type),
                              jnp.floating):
                return False
            continue
        if prim in _EXTREME_PRIMS:
            if not _extreme_type_ok(out_type):
                return False
            continue
        return False
    return True


def pack_words_i64(words: Sequence[jax.Array]) -> jax.Array:
    """Equality words (bool / uintN / intN, as grouping_subkeys emits
    them) -> one ``(cap, K)`` int64 bit-image matrix. Equality on the
    bit images is exactly equality on the words."""
    from spark_rapids_tpu.ops.lanes import _as_u64_bits
    cols = [_as_u64_bits(w).view(jnp.int64) for w in words]
    return jnp.stack(cols, axis=1)


# ---------------------------------------------------------------------------
# lane planning: (col, prim, out_type) entries -> int64 lanes + decode
# ---------------------------------------------------------------------------

def plan_lanes(entries, active: jax.Array):
    """Encode every aggregation slot into int64 lanes, mirroring
    ``seg_sums_batched``'s exact encodings (32-bit decimal parts with a
    wraparound high limb) plus rank-encoded min/max lanes. Returns
    ``(add_lanes, min_lanes, max_lanes, decode)`` where ``decode``
    rebuilds the slot's device columns from the accumulated tables."""
    from spark_rapids_tpu.columnar.device import (DeviceColumn as DC,
                                                  DeviceDecimal128Column,
                                                  storage_jnp_dtype)
    from spark_rapids_tpu.ops import int128 as I
    add_lanes: List[jax.Array] = []
    min_lanes: List[jax.Array] = []
    max_lanes: List[jax.Array] = []
    specs: List[Tuple] = []
    lane_of: dict = {}
    m32 = jnp.uint64(0xFFFFFFFF)
    z64 = jnp.int64(0)

    def _add(arr, tag, a) -> int:
        key = (id(arr), tag)
        li = lane_of.get(key)
        if li is None:
            li = len(add_lanes)
            add_lanes.append(a)
            lane_of[key] = li
        return li

    for col, prim, out_type in entries:
        valid = col.validity & active
        if prim == E.PRIM_COUNT:
            specs.append(("count",
                          _add(col.validity, "valid",
                               valid.astype(jnp.int64))))
            continue
        if prim in _EXTREME_PRIMS:
            is_min = prim == E.PRIM_MIN
            dt = col.data.dtype
            enc = col.data.astype(jnp.int64)
            sent = jnp.int64(_I64_MAX if is_min else _I64_MIN)
            lane = jnp.where(valid, enc, sent)
            has = _add(col.validity, "valid", valid.astype(jnp.int64))
            if is_min:
                specs.append(("min", len(min_lanes), has, out_type, dt))
                min_lanes.append(lane)
            else:
                specs.append(("max", len(max_lanes), has, out_type, dt))
                max_lanes.append(lane)
            continue
        nwe = prim == E.PRIM_SUM  # null_when_empty
        has_lane = _add(col.validity, "valid",
                        valid.astype(jnp.int64)) if nwe else None
        if T.is_limb_decimal(out_type):
            if isinstance(col, DeviceDecimal128Column):
                hi, lo = col.hi, col.lo
            else:
                hi, lo = I.from_i64(jnp, col.data.astype(jnp.int64))
            hi = jnp.where(valid, hi, z64)
            lo = jnp.where(valid, lo, z64)
            ulo = lo.view(jnp.uint64)
            l0 = _add(col, "dec0", (ulo & m32).astype(jnp.int64))
            l1 = _add(col, "dec1",
                      (ulo >> jnp.uint64(32)).astype(jnp.int64))
            lh = _add(col, "dechi", hi)  # wraparound == mod-2^128 high
            specs.append(("dec", (l0, l1, lh), has_lane, out_type))
        else:
            specs.append(("int",
                          _add(col, "ival",
                               jnp.where(valid,
                                         col.data.astype(jnp.int64),
                                         z64)),
                          has_lane, out_type))

    def decode(add_out, min_out, max_out, used):
        from spark_rapids_tpu.columnar.device import storage_jnp_dtype
        outs = []
        for spec in specs:
            if spec[0] == "count":
                run = add_out[:, spec[1]]
                outs.append(DC(T.LongT, jnp.where(used, run, z64), used))
                continue
            if spec[0] in ("min", "max"):
                _k, li, has, out_type, dt = spec
                lane = (min_out if spec[0] == "min" else max_out)[:, li]
                validity = used & (add_out[:, has] > 0)
                data = jnp.where(validity, lane, z64).astype(dt)
                outs.append(DC(out_type, data, validity))
                continue
            kind, lane, has_lane, out_type = spec
            validity = used
            if has_lane is not None:
                validity = validity & (add_out[:, has_lane] > 0)
            if kind == "dec":
                l0, l1, lh = lane
                s0, s1 = add_out[:, l0], add_out[:, l1]
                shi = add_out[:, lh]
                rhi, rlo = I.from_i64(jnp, s0)
                h1, lo1 = I.mul_i64(jnp, s1, jnp.full_like(s1, 1 << 32))
                rhi, rlo = I.add(jnp, rhi, rlo, h1, lo1)
                rhi = rhi + shi
                ok = I.fits_precision(jnp, rhi, rlo, out_type.precision)
                validity = validity & ok
                rhi = jnp.where(validity, rhi, z64)
                rlo = jnp.where(validity, rlo, z64)
                outs.append(DeviceDecimal128Column(out_type, rhi, rlo,
                                                   validity))
            else:
                run = add_out[:, lane]
                acc = storage_jnp_dtype(out_type)
                outs.append(DC(out_type,
                               jnp.where(validity, run.astype(acc),
                                         jnp.zeros((), acc)), validity))
        return outs

    return add_lanes, min_lanes, max_lanes, decode


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------

def _block_rows(cap: int) -> int:
    """Largest power-of-two block <= 4096 that divides the capacity
    (batch capacities are {1,1.25,1.5,1.75} x 2^k buckets, so this is
    at least cap/7 and usually 4096)."""
    return min(4096, cap & -cap)


# probe-loop bound per block: a row unresolved after this many steps
# (pathological clustering or a full table) overflows to the oracle
_MAX_PROBES = 64


def insert_step(kw, rows, slot, done, tbl_kw, tbl_used, tbl_row,
                T_: int, K: int):
    """ONE lockstep open-addressing insert iteration — the
    concurrency-critical core shared by this kernel's group-by loop
    and the join build loop (kernels/join_probe.py): probe the current
    slot, claim empties with deterministic min-row-id winners (losers
    land on the dead row ``T_``), then RE-match so a row whose key was
    claimed by another row this very step resolves here instead of
    inserting a duplicate group at the next free slot. Returns
    ``(hit, tbl_kw, tbl_used, tbl_row)``; callers advance ``slot`` for
    ``~(done | hit)`` rows."""
    tk = jnp.take(tbl_kw, slot, axis=0)
    used = jnp.take(tbl_used, slot)
    match = used
    for w in range(K):
        match = match & (tk[:, w] == kw[:, w])
    want = (~done) & (~used)
    claim = jnp.full((T_ + 1,), _ROW_BIG, jnp.int32).at[
        jnp.where(want, slot, T_)].min(rows)
    won = want & (jnp.take(claim, slot) == rows)
    idx = jnp.where(won, slot, T_)
    tbl_kw = tbl_kw.at[idx].set(kw)
    tbl_row = tbl_row.at[idx].set(rows)
    tbl_used = tbl_used.at[idx].set(True)
    tk2 = jnp.take(tbl_kw, slot, axis=0)
    match2 = jnp.take(tbl_used, slot)
    for w in range(K):
        match2 = match2 & (tk2[:, w] == kw[:, w])
    hit = (~done) & (match | won | match2)
    return hit, tbl_kw, tbl_used, tbl_row


def _probe_rows(kw, h, valid, rows, tbl_kw, tbl_used, tbl_row,
                T_: int, K: int):
    """The bounded insert/probe loop over one row block — shared
    verbatim by the whole-array kernel and the tiled kernel so their
    table state transitions are structurally identical (bit-identity
    between the two is by construction, like ``insert_step``).
    Returns ``(done, fslot, tbl_kw, tbl_used, tbl_row)``."""
    slot0 = (h & (T_ - 1)).astype(jnp.int32)

    def probe_cond(st):
        _s, done, _f, _tk, _tu, _tr, it = st
        return jnp.any(~done) & (it < _MAX_PROBES)

    def probe_body(st):
        slot, done, fslot, tbl_kw, tbl_used, tbl_row, it = st
        hit, tbl_kw, tbl_used, tbl_row = insert_step(
            kw, rows, slot, done, tbl_kw, tbl_used, tbl_row, T_, K)
        fslot = jnp.where(hit, slot, fslot)
        done = done | hit
        slot = jnp.where(done, slot, (slot + 1) & (T_ - 1))
        return slot, done, fslot, tbl_kw, tbl_used, tbl_row, it + 1

    (_slot, done, fslot, tbl_kw, tbl_used, tbl_row,
     _it) = jax.lax.while_loop(
         probe_cond, probe_body,
         (slot0, ~valid, jnp.zeros_like(slot0),
          tbl_kw, tbl_used, tbl_row, jnp.int32(0)))
    return done, fslot, tbl_kw, tbl_used, tbl_row


def _build_kernel(cap: int, K: int, n_add: int, n_min: int, n_max: int,
                  slots: int, interpret: bool) -> Callable:
    """The pallas_call wrapper: (kw, h, valid, add?, min?, max?) ->
    (tbl_row, used, add_out?, min_out?, max_out?, overflow). Traced
    into the caller's jitted program (built only inside JitCache
    builders — the compile-discipline lint holds for kernels too)."""
    from jax.experimental import pallas as pl
    RB = _block_rows(cap)
    T_ = slots

    def kern(*refs):
        kw_ref, h_ref, valid_ref = refs[:3]
        off_in = 3
        add_ref = mnr = mxr = None
        if n_add:
            add_ref = refs[off_in]
            off_in += 1
        if n_min:
            mnr = refs[off_in]
            off_in += 1
        if n_max:
            mxr = refs[off_in]
            off_in += 1
        outs = refs[off_in:]
        row_ref, used_ref = outs[:2]
        off_out = 2
        add_out_ref = mno = mxo = None
        if n_add:
            add_out_ref = outs[off_out]
            off_out += 1
        if n_min:
            mno = outs[off_out]
            off_out += 1
        if n_max:
            mxo = outs[off_out]
            off_out += 1
        ovf_ref = outs[off_out]

        def block(b, carry):
            (tbl_kw, tbl_used, tbl_row, tbl_add, tbl_min, tbl_max,
             ovf) = carry
            off = b * RB
            kw = kw_ref[pl.ds(off, RB), :]
            h = h_ref[pl.ds(off, RB)]
            valid = valid_ref[pl.ds(off, RB)]
            rows = off + jax.lax.broadcasted_iota(
                jnp.int32, (RB, 1), 0)[:, 0]
            done, fslot, tbl_kw, tbl_used, tbl_row = _probe_rows(
                kw, h, valid, rows, tbl_kw, tbl_used, tbl_row, T_, K)
            ovf = ovf | jnp.any(valid & ~done)
            contrib = valid & done
            idx = jnp.where(contrib, fslot, T_)
            if n_add:
                tbl_add = tbl_add.at[idx].add(
                    add_ref[pl.ds(off, RB), :])
            if n_min:
                tbl_min = tbl_min.at[idx].min(
                    mnr[pl.ds(off, RB), :])
            if n_max:
                tbl_max = tbl_max.at[idx].max(
                    mxr[pl.ds(off, RB), :])
            return (tbl_kw, tbl_used, tbl_row, tbl_add, tbl_min,
                    tbl_max, ovf)

        def body(b, carry):
            # an overflowed batch re-runs whole on the oracle: skip the
            # remaining blocks instead of thrashing the full table
            return jax.lax.cond(carry[6], lambda c: c,
                                lambda c: block(b, c), carry)

        init = (jnp.zeros((T_ + 1, K), jnp.int64),
                jnp.zeros((T_ + 1,), jnp.bool_),
                jnp.zeros((T_ + 1,), jnp.int32),
                jnp.zeros((T_ + 1, n_add), jnp.int64),
                jnp.full((T_ + 1, n_min), _I64_MAX, jnp.int64),
                jnp.full((T_ + 1, n_max), _I64_MIN, jnp.int64),
                jnp.zeros((), jnp.bool_))
        (tbl_kw, tbl_used, tbl_row, tbl_add, tbl_min, tbl_max,
         ovf) = jax.lax.fori_loop(0, cap // RB, body, init)
        row_ref[...] = tbl_row[:T_]
        used_ref[...] = tbl_used[:T_]
        if n_add:
            add_out_ref[...] = tbl_add[:T_]
        if n_min:
            mno[...] = tbl_min[:T_]
        if n_max:
            mxo[...] = tbl_max[:T_]
        ovf_ref[...] = ovf.reshape(1)

    out_shape = [jax.ShapeDtypeStruct((T_,), jnp.int32),
                 jax.ShapeDtypeStruct((T_,), jnp.bool_)]
    if n_add:
        out_shape.append(jax.ShapeDtypeStruct((T_, n_add), jnp.int64))
    if n_min:
        out_shape.append(jax.ShapeDtypeStruct((T_, n_min), jnp.int64))
    if n_max:
        out_shape.append(jax.ShapeDtypeStruct((T_, n_max), jnp.int64))
    out_shape.append(jax.ShapeDtypeStruct((1,), jnp.bool_))
    return pl.pallas_call(kern, out_shape=tuple(out_shape),
                          interpret=interpret)


def _sanitize_tiling(cap: int, n_add: int, block_rows: int,
                     lane_groups: int) -> Tuple[int, int, int]:
    """Clamp tuning parameters to shapes the tiled kernel can lower:
    block rows a power of two dividing the capacity, lane groups that
    actually split the accumulator matrix, the add width padded to a
    lane-group multiple. Returns ``(RB, LG, n_add_padded)``."""
    rb = int(block_rows) if block_rows else _block_rows(cap)
    rb = max(1, rb)
    rb = 1 << (rb.bit_length() - 1)
    rb = min(rb, cap & -cap)
    lg = max(1, int(lane_groups))
    if n_add == 0 or lg > n_add:
        lg = 1
    return rb, lg, ((n_add + lg - 1) // lg) * lg


def _build_kernel_tiled(cap: int, K: int, n_add: int, n_min: int,
                        n_max: int, slots: int, interpret: bool,
                        block_rows: int = 0,
                        lane_groups: int = 1) -> Callable:
    """The native-tuned variant of ``_build_kernel``: same table state
    machine (``_probe_rows`` / ``insert_step``), but the batch streams
    through a ``(lane_groups, cap // RB)`` grid of VMEM-sized blocks
    instead of one whole-array body. The grid's BlockSpec pipeline
    double-buffers the key/accumulator tile DMAs behind the probe
    compute, the tables persist in VMEM scratch across the sequential
    block steps, and the lane-group dimension is ``parallel`` so
    megacore splits the accumulator columns across cores (each group
    re-probes — the table build is cheap next to the DMA volume).
    Output signature matches ``_build_kernel`` exactly."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    RB, LG, n_add_p = _sanitize_tiling(cap, n_add, block_rows,
                                       lane_groups)
    GA = n_add_p // LG if n_add_p else 0
    nb = cap // RB
    T_ = slots
    n_in = 3 + (1 if n_add else 0) + (1 if n_min else 0) \
        + (1 if n_max else 0)
    n_out = 3 + (1 if n_add else 0) + (1 if n_min else 0) \
        + (1 if n_max else 0)

    def kern(*refs):
        ins = refs[:n_in]
        outs = refs[n_in:n_in + n_out]
        scr = refs[n_in + n_out:]
        kw_ref, h_ref, valid_ref = ins[:3]
        ii = 3
        add_ref = mnr = mxr = None
        if n_add:
            add_ref = ins[ii]
            ii += 1
        if n_min:
            mnr = ins[ii]
            ii += 1
        if n_max:
            mxr = ins[ii]
            ii += 1
        row_ref, used_ref = outs[:2]
        oo = 2
        add_out_ref = mno = mxo = None
        if n_add:
            add_out_ref = outs[oo]
            oo += 1
        if n_min:
            mno = outs[oo]
            oo += 1
        if n_max:
            mxo = outs[oo]
            oo += 1
        ovf_ref = outs[oo]
        si = 0
        s_kw, s_used, s_row = scr[:3]
        si = 3
        s_add = s_min = s_max = None
        if n_add:
            s_add = scr[si]
            si += 1
        if n_min:
            s_min = scr[si]
            si += 1
        if n_max:
            s_max = scr[si]
            si += 1
        s_ovf = scr[si]

        b = pl.program_id(1)

        @pl.when(b == 0)
        def _init():
            s_kw[...] = jnp.zeros((T_ + 1, K), jnp.int64)
            s_used[...] = jnp.zeros((T_ + 1,), jnp.bool_)
            s_row[...] = jnp.zeros((T_ + 1,), jnp.int32)
            if n_add:
                s_add[...] = jnp.zeros((T_ + 1, GA), jnp.int64)
            if n_min:
                s_min[...] = jnp.full((T_ + 1, n_min), _I64_MAX,
                                      jnp.int64)
            if n_max:
                s_max[...] = jnp.full((T_ + 1, n_max), _I64_MIN,
                                      jnp.int64)
            s_ovf[...] = jnp.zeros((1,), jnp.bool_)

        kw = kw_ref[...]
        h = h_ref[...]
        valid = valid_ref[...]
        rows = b * RB + jax.lax.broadcasted_iota(
            jnp.int32, (RB, 1), 0)[:, 0]

        def run(carry):
            (tbl_kw, tbl_used, tbl_row, tbl_add, tbl_min, tbl_max,
             ovf) = carry
            done, fslot, tbl_kw, tbl_used, tbl_row = _probe_rows(
                kw, h, valid, rows, tbl_kw, tbl_used, tbl_row, T_, K)
            ovf = ovf | jnp.any(valid & ~done)
            contrib = valid & done
            idx = jnp.where(contrib, fslot, T_)
            if n_add:
                tbl_add = tbl_add.at[idx].add(add_ref[...])
            if n_min:
                tbl_min = tbl_min.at[idx].min(mnr[...])
            if n_max:
                tbl_max = tbl_max.at[idx].max(mxr[...])
            return (tbl_kw, tbl_used, tbl_row, tbl_add, tbl_min,
                    tbl_max, ovf)

        carry = (s_kw[...], s_used[...], s_row[...],
                 s_add[...] if n_add
                 else jnp.zeros((T_ + 1, 0), jnp.int64),
                 s_min[...] if n_min
                 else jnp.zeros((T_ + 1, 0), jnp.int64),
                 s_max[...] if n_max
                 else jnp.zeros((T_ + 1, 0), jnp.int64),
                 s_ovf[0])
        # an overflowed batch re-runs whole on the oracle: skip the
        # remaining blocks instead of thrashing the full table
        carry = jax.lax.cond(carry[6], lambda c: c, run, carry)
        s_kw[...] = carry[0]
        s_used[...] = carry[1]
        s_row[...] = carry[2]
        if n_add:
            s_add[...] = carry[3]
        if n_min:
            s_min[...] = carry[4]
        if n_max:
            s_max[...] = carry[5]
        s_ovf[...] = carry[6].reshape(1)

        # every output block is indexed by the parallel lane-group
        # dimension, so concurrent cores never write the same HBM
        # block; the caller reads group 0's copy of the replicated
        # outputs and concatenates the split accumulator columns
        @pl.when(b == nb - 1)
        def _emit():
            row_ref[0, :] = s_row[...][:T_]
            used_ref[0, :] = s_used[...][:T_]
            if n_add:
                add_out_ref[...] = s_add[...][:T_]
            if n_min:
                mno[0] = s_min[...][:T_]
            if n_max:
                mxo[0] = s_max[...][:T_]
            ovf_ref[0, :] = s_ovf[...]

    in_specs = [pl.BlockSpec((RB, K), lambda g, b: (b, 0)),
                pl.BlockSpec((RB,), lambda g, b: (b,)),
                pl.BlockSpec((RB,), lambda g, b: (b,))]
    out_specs = [pl.BlockSpec((1, T_), lambda g, b: (g, 0)),
                 pl.BlockSpec((1, T_), lambda g, b: (g, 0))]
    out_shape = [jax.ShapeDtypeStruct((LG, T_), jnp.int32),
                 jax.ShapeDtypeStruct((LG, T_), jnp.bool_)]
    scratch = [pltpu.VMEM((T_ + 1, K), jnp.int64),
               pltpu.VMEM((T_ + 1,), jnp.bool_),
               pltpu.VMEM((T_ + 1,), jnp.int32)]
    if n_add:
        in_specs.append(pl.BlockSpec((RB, GA), lambda g, b: (b, g)))
        out_specs.append(pl.BlockSpec((T_, GA), lambda g, b: (0, g)))
        out_shape.append(jax.ShapeDtypeStruct((T_, n_add_p), jnp.int64))
        scratch.append(pltpu.VMEM((T_ + 1, GA), jnp.int64))
    if n_min:
        in_specs.append(pl.BlockSpec((RB, n_min), lambda g, b: (b, 0)))
        out_specs.append(pl.BlockSpec((1, T_, n_min),
                                      lambda g, b: (g, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((LG, T_, n_min),
                                              jnp.int64))
        scratch.append(pltpu.VMEM((T_ + 1, n_min), jnp.int64))
    if n_max:
        in_specs.append(pl.BlockSpec((RB, n_max), lambda g, b: (b, 0)))
        out_specs.append(pl.BlockSpec((1, T_, n_max),
                                      lambda g, b: (g, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((LG, T_, n_max),
                                              jnp.int64))
        scratch.append(pltpu.VMEM((T_ + 1, n_max), jnp.int64))
    out_specs.append(pl.BlockSpec((1, 1), lambda g, b: (g, 0)))
    out_shape.append(jax.ShapeDtypeStruct((LG, 1), jnp.bool_))
    scratch.append(pltpu.VMEM((1,), jnp.bool_))
    call = pl.pallas_call(kern, grid=(LG, nb), in_specs=in_specs,
                          out_specs=out_specs,
                          out_shape=tuple(out_shape),
                          scratch_shapes=scratch, interpret=interpret,
                          compiler_params=pltpu.CompilerParams(
                              dimension_semantics=("parallel",
                                                   "arbitrary")))

    def wrapper(kw, h, valid, *lanes):
        args = [kw, h, valid]
        li = 0
        if n_add:
            add = lanes[li]
            li += 1
            if n_add_p != n_add:
                cap_ = add.shape[0]
                add = jnp.concatenate(
                    [add, jnp.zeros((cap_, n_add_p - n_add),
                                    jnp.int64)], axis=1)
            args.append(add)
        if n_min:
            args.append(lanes[li])
            li += 1
        if n_max:
            args.append(lanes[li])
            li += 1
        res = list(call(*args))
        outs = [res[0][0], res[1][0]]
        oi = 2
        if n_add:
            outs.append(res[oi][:, :n_add])
            oi += 1
        if n_min:
            outs.append(res[oi][0])
            oi += 1
        if n_max:
            outs.append(res[oi][0])
            oi += 1
        outs.append(res[oi][0])
        return tuple(outs)

    return wrapper


def hash_groupby(key_cols, entries, active: jax.Array, slots: int,
                 has_nans: Optional[bool] = None,
                 params: Optional[dict] = None):
    """Traced single-pass group-by: ``(key_out, buffers, used, cnt,
    overflow)``, all capacity ``slots``. ``entries`` are ``(col, prim,
    out_type)`` like ``seg_sums_batched``; callers pre-check
    ``agg_kernel_eligible``. Output groups sit in table-slot order
    (compacted by the caller); the key columns are gathered from the
    batch by first-occurrence row, so values round-trip untouched.

    ``params`` carries the autotuner's per-bucket tuning (blockRows /
    laneGroups); native lowering always takes the tiled pipelined
    builder, interpret mode keeps the legacy whole-array kernel (the
    tier-1 bit-identity baseline) unless params ask for tiling."""
    from spark_rapids_tpu import kernels as KR
    from spark_rapids_tpu.columnar.device import take_columns
    from spark_rapids_tpu.ops import groupby as G
    cap = active.shape[0]
    subkeys: List[jax.Array] = []
    for c in key_cols:
        subkeys.extend(G.grouping_subkeys(c, has_nans))
    kw = pack_words_i64(subkeys)
    h = G.hash_subkey_words(subkeys).view(jnp.int64)
    add_lanes, min_lanes, max_lanes, decode = plan_lanes(entries, active)
    p = dict(params or {})
    interp = KR.interpret()
    rb = int(p.get("blockRows", 0))
    lg = int(p.get("laneGroups", 0))
    tiled = (not interp) or rb > 0 or lg > 1 or bool(p.get("tiled"))
    if tiled:
        call = _build_kernel_tiled(cap, kw.shape[1], len(add_lanes),
                                   len(min_lanes), len(max_lanes),
                                   slots, interp, block_rows=rb,
                                   lane_groups=lg or 1)
    else:
        call = _build_kernel(cap, kw.shape[1], len(add_lanes),
                             len(min_lanes), len(max_lanes), slots,
                             interp)
    args = [kw, h, active]
    for lanes in (add_lanes, min_lanes, max_lanes):
        if lanes:
            args.append(lanes[0][:, None] if len(lanes) == 1
                        else jnp.stack(lanes, axis=1))
    outs = list(call(*args))
    tbl_row, used = outs[0], outs[1]
    oi = 2
    add_out = min_out = max_out = None
    if add_lanes:
        add_out = outs[oi]
        oi += 1
    if min_lanes:
        min_out = outs[oi]
        oi += 1
    if max_lanes:
        max_out = outs[oi]
        oi += 1
    overflow = outs[oi][0]
    key_out = take_columns(key_cols,
                           jnp.clip(tbl_row, 0, cap - 1).astype(
                               jnp.int32),
                           valid_at=used)
    buffers = decode(add_out, min_out, max_out, used)
    cnt = jnp.sum(used)
    return key_out, buffers, used, cnt, overflow


def autotune_probe(params: dict) -> bool:
    """Oracle validation of one tiled-kernel tuning candidate on a
    synthetic batch: build the tiled kernel with the candidate's
    blockRows/laneGroups/slotsMult, run it over random int64 keys with
    nulls, and compare every per-group sum/min/max against a pure
    numpy group-by. The autotuner times only candidates that pass —
    a tuning table can never make the kernel wrong."""
    cap, K, n_add, n_min, n_max = 512, 1, 3, 1, 1
    slots = 128 * max(1, int(params.get("slotsMult", 1)))
    rng = np.random.RandomState(5)
    keys = rng.randint(0, 50, size=cap).astype(np.int64)
    valid = rng.rand(cap) < 0.9
    add = rng.randint(-1000, 1000, size=(cap, n_add)).astype(np.int64)
    mn = rng.randint(-1000, 1000, size=(cap, n_min)).astype(np.int64)
    mx = rng.randint(-1000, 1000, size=(cap, n_max)).astype(np.int64)
    from spark_rapids_tpu import kernels as KR
    fn = _build_kernel_tiled(cap, K, n_add, n_min, n_max, slots,
                             KR.interpret(),
                             block_rows=int(params.get("blockRows", 0)),
                             lane_groups=int(params.get("laneGroups",
                                                        1)))
    row, used, add_out, min_out, max_out, ovf = fn(
        jnp.asarray(keys)[:, None], jnp.asarray(keys),
        jnp.asarray(valid),
        jnp.asarray(add), jnp.asarray(mn), jnp.asarray(mx))
    if bool(ovf[0]):
        return False
    want: dict = {}
    for i in range(cap):
        if not valid[i]:
            continue
        e = want.setdefault(int(keys[i]),
                            [np.zeros(n_add, np.int64),
                             _I64_MAX, _I64_MIN])
        e[0] = e[0] + add[i]
        e[1] = min(e[1], mn[i, 0])
        e[2] = max(e[2], mx[i, 0])
    used_np = np.asarray(used)
    row_np = np.asarray(row)
    got_keys = []
    for s in range(slots):
        if not used_np[s]:
            continue
        k = int(keys[row_np[s]])
        got_keys.append(k)
        e = want.get(k)
        if e is None:
            return False
        if not (np.array_equal(np.asarray(add_out)[s], e[0])
                and int(np.asarray(min_out)[s, 0]) == e[1]
                and int(np.asarray(max_out)[s, 0]) == e[2]):
            return False
    return sorted(got_keys) == sorted(want.keys())
