"""Device equi-join kernel: count-then-gather with static shapes.

The reference joins on device through cudf hash joins + chunked gather
maps (GpuHashJoin.scala:377, JoinGatherer.scala:55). A hash table is the
wrong shape for XLA, so this kernel re-designs the same contract around
the sort/segment machinery the groupby and sort kernels already use:

1. **Key-id assignment**: concatenate the (evaluated) join-key columns of
   both sides into one combined key set and run ``build_segments`` over
   it — every row gets a dense key id, and two rows (either side) share
   an id iff their keys are Spark-equal (NaN==NaN, -0.0==0.0, null
   excluded from matching entirely by masking it out of ``active``).
2. **Count phase** (one jitted program per structure): per-key right
   counts via ``segment_sum``, per-left-row match counts, exclusive
   offsets, the right side's key-grouped ordering, and the outer-join
   extras — everything capacity-shaped. Two scalars (total pairs, extra
   rows) sync to host to pick the output capacity bucket.
3. **Gather phase** (one jitted program per (structure, out-capacity)):
   output slot ``s`` finds its left row by ``searchsorted`` over the
   offsets, its k-th match through the right ordering, and gathers both
   sides with null rows for the outer sides — the gather-map idea, built
   in one fused XLA program instead of cudf calls.

Semi/anti joins never expand: they are pure mask updates on the left
batch (m > 0 / m == 0), the cheapest possible form on this design. With
a residual condition (a decorrelated ``EXISTS``: ``srt_join_cond_mask``)
the count phase's extents name each left row's candidates, the
condition is evaluated rank by rank over the left row and its j-th
candidate, gathered through the right ordering, and OR-reduced onto the
left row: ``max_m x cap_l`` gathered elements, no output bucket.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.device import (AnyDeviceColumn, DeviceBatch,
                                              DeviceColumn,
                                              DeviceDecimal128Column,
                                              DeviceStringColumn,
                                              bucket_capacity, make_column,
                                              take_columns)
from spark_rapids_tpu.ops import exprs as X
from spark_rapids_tpu.ops import groupby as G
from spark_rapids_tpu.ops.rle import run_index
from spark_rapids_tpu.sql import expressions as E
from spark_rapids_tpu.sql import types as T

import numpy as np

# bounded LRUs (jit_cache.py): long sessions planning many distinct
# join shapes must not pin unbounded XLA executables
from spark_rapids_tpu.jit_cache import JitCache, named_jit, program_name

_COUNT_CACHE = JitCache("joinCount")
_GATHER_CACHE = JitCache("joinGather")
_MASK_CACHE = JitCache("joinMask")
_COND_MASK_CACHE = JitCache("joinCondMask")

# tpu-lint: disable=jit-direct(single fixed 3-scalar stack program — one executable, bounded by construction)
_stack3 = named_jit("srt_join_stack3",
                    lambda a, b, c: jnp.stack([a, b, c]))

# join types that expand to (left, right) pairs
PAIR_JOINS = ("inner", "cross", "left", "leftouter", "right", "rightouter",
              "full", "fullouter")
MASK_JOINS = ("leftsemi", "leftanti")


def _concat_key_columns(kl: Sequence[AnyDeviceColumn],
                        kr: Sequence[AnyDeviceColumn]
                        ) -> List[AnyDeviceColumn]:
    """Stack left over right key columns (left rows first)."""
    out: List[AnyDeviceColumn] = []
    for a, b in zip(kl, kr):
        if isinstance(a, DeviceStringColumn):
            cc = max(a.char_cap, b.char_cap)
            ac, bc = a.chars, b.chars
            if a.char_cap < cc:
                ac = jnp.pad(ac, ((0, 0), (0, cc - a.char_cap)))
            if b.char_cap < cc:
                bc = jnp.pad(bc, ((0, 0), (0, cc - b.char_cap)))
            out.append(DeviceStringColumn(
                a.dtype, jnp.concatenate([ac, bc]),
                jnp.concatenate([a.lengths, b.lengths]),
                jnp.concatenate([a.validity, b.validity])))
        elif isinstance(a, DeviceDecimal128Column):
            out.append(DeviceDecimal128Column(
                a.dtype, jnp.concatenate([a.hi, b.hi]),
                jnp.concatenate([a.lo, b.lo]),
                jnp.concatenate([a.validity, b.validity])))
        else:
            out.append(DeviceColumn(
                a.dtype, jnp.concatenate([a.data, b.data]),
                jnp.concatenate([a.validity, b.validity])))
    return out


def _key_words(keys: Sequence, null_safe: Sequence[bool]) -> List[jax.Array]:
    """Comparison words for evaluated key columns; null-safe keys get a
    validity word so null groups with null. ONE implementation shared by
    _key_plan and the FK-uniqueness probe — they must agree on key
    equality or the probe's certificate lies to the fast path."""
    words: List[jax.Array] = []
    for c, nsf in zip(keys, null_safe):
        if nsf:
            words.append(c.validity)
        words.extend(G.value_words(c))
    return words


def _group_extents(words: List[jax.Array], valid: jax.Array, cap: int):
    """Sort rows by key words (invalid rows sink) and return
    (active_s, order, start, end): per-sorted-position group extents.
    Shared by _key_plan and build_key_max_multiplicity."""
    from spark_rapids_tpu.columnar.device import sort_with_payload
    with jax.named_scope("join_plan/sort"):
        sorted_all, order, _p = sort_with_payload([~valid] + words, [])
    with jax.named_scope("join_plan/extents"):
        active_s = ~sorted_all[0]
        boundary, is_end = G._boundaries_from_words(sorted_all[1:],
                                                    active_s, cap)
        pos = jnp.arange(cap, dtype=jnp.int32)
        start = jax.lax.cummax(jnp.where(boundary, pos, -1))
        end = jnp.flip(jax.lax.cummin(
            jnp.flip(jnp.where(is_end, pos, cap))))
    return active_s, order, start, end


def _key_plan(lkeys: Sequence[E.Expression], rkeys: Sequence[E.Expression],
              ctx_l: X.Ctx, ctx_r: X.Ctx, active_l, active_r,
              null_safe: Sequence[bool] = ()):
    """Shared by both phases: evaluate keys, segment the combined key
    set, and derive per-row match counts/offsets with prefix sums over
    the sorted layout — NO scatter-based segment ops (XLA scatters
    serialize on TPU), and op-count-lean: the key sort skips validity
    words (invalid-key rows are masked out of the sort's active set
    entirely), the two prefix sums ride one 2-lane cumsum, and all
    back-to-original-row gathers ride one fused lane gather."""
    ns = list(null_safe) or [False] * len(lkeys)
    with jax.named_scope("join_plan/keys"):
        kl = [X.dev_eval(e, ctx_l) for e in lkeys]
        kr = [X.dev_eval(e, ctx_r) for e in rkeys]
        valid_l = active_l
        for c, nsf in zip(kl, ns):
            if not nsf:  # <=> keys keep null rows in the match set
                valid_l = valid_l & c.validity
        valid_r = active_r
        for c, nsf in zip(kr, ns):
            if not nsf:
                valid_r = valid_r & c.validity
        cap_l = active_l.shape[0]
        cap_r = active_r.shape[0]
        cap_c = cap_l + cap_r
        combined = _concat_key_columns(kl, kr)
        valid_c = jnp.concatenate([valid_l, valid_r])
        words = _key_words(combined, ns)
    # scopes join_plan/sort and /extents
    active_s, order, start, end = _group_extents(words, valid_c, cap_c)
    with jax.named_scope("join_plan/extents"):
        pos_c = jnp.arange(cap_c, dtype=jnp.int32)
        is_left_s = order < cap_l
        left_valid_s = is_left_s & active_s
        right_valid_s = (~is_left_s) & active_s
        # both prefix sums in ONE 2-lane cumsum
        pref = jnp.cumsum(jnp.stack(
            [left_valid_s.astype(jnp.int64),
             right_valid_s.astype(jnp.int64)], axis=1), axis=0)
        before = jnp.where((start > 0)[:, None],
                           jnp.take(pref, jnp.maximum(start - 1, 0), axis=0),
                           jnp.int64(0))
        at_end = jnp.take(pref, jnp.clip(end, 0, cap_c - 1), axis=0)
        cnt_l_s = at_end[:, 0] - before[:, 0]
        cnt_r_s = at_end[:, 1] - before[:, 1]
        base_r_s = before[:, 1]
    with jax.named_scope("join_plan/inverse"):
        # original combined row -> sorted pos (one stable sort pass), then
        # ONE fused gather brings every per-sorted-row stat back to
        # original row order
        _o, inv = jax.lax.sort((order, pos_c), num_keys=1, is_stable=True)
        from spark_rapids_tpu.ops.lanes import fused_take
        g = fused_take([cnt_r_s, base_r_s, cnt_l_s], inv)
        m = jnp.where(valid_l, g[0][:cap_l], jnp.int64(0))
        base = jnp.where(valid_l, g[1][:cap_l], jnp.int64(0))
        cnt_l_at_r = jnp.where(valid_r, g[2][cap_l:], jnp.int64(0))
    with jax.named_scope("join_plan/right_order"):
        # order_r[j] = original right index of the j-th valid right row in
        # key-sorted order (base/cnt index into this)
        rkey_sorted = jnp.where(right_valid_s, pos_c, jnp.int32(cap_c))
        _k2, ord2 = jax.lax.sort((rkey_sorted, pos_c), num_keys=1,
                                 is_stable=True)
        order_r = jnp.clip(jnp.take(order, ord2[:cap_r]) - cap_l, 0,
                           cap_r - 1)
    return kl, kr, valid_l, valid_r, m, base, order_r, cnt_l_at_r


def _build_count_fn(lkeys: Tuple[E.Expression, ...],
                    rkeys: Tuple[E.Expression, ...],
                    join_type: str,
                    null_safe: Tuple[bool, ...] = ()) -> Callable:
    left_outer = join_type in ("left", "leftouter", "full", "fullouter")
    right_outer = join_type in ("right", "rightouter", "full", "fullouter")

    def fn(cols_l, active_l, lits_l, cols_r, active_r, lits_r):
        cap_l = active_l.shape[0]
        cap_r = active_r.shape[0]
        ctx_l = X.Ctx(cols_l, cap_l, lkeys, lits_l)
        ctx_r = X.Ctx(cols_r, cap_r, rkeys, lits_r)
        (_kl, _kr, _valid_l, valid_r, m, base, order_r, cnt_l_at_r
         ) = _key_plan(lkeys, rkeys, ctx_l, ctx_r, active_l,
                      active_r, null_safe)
        if left_outer:
            m_eff = jnp.where(active_l, jnp.maximum(m, 1), 0)
        else:
            m_eff = m
        m_eff = m_eff.astype(jnp.int64)
        offsets = jnp.cumsum(m_eff) - m_eff  # exclusive
        total_pairs = jnp.sum(m_eff)
        max_m = jnp.max(m)
        # matched-right mask: consumed by the right/full-outer extras
        # here, and accumulated across stream chunks by the exec's
        # chunked outer path (JoinGatherer.scala:55 role)
        matched_r = valid_r & (cnt_l_at_r > 0)
        if right_outer:
            extra_r = active_r & ~matched_r
            n_extra = jnp.sum(extra_r.astype(jnp.int64))
            pos = jnp.arange(cap_r, dtype=jnp.int32)
            extra_order = jnp.argsort(
                jnp.where(extra_r, pos, jnp.int32(cap_r)), stable=True)
        else:
            n_extra = jnp.int64(0)
            extra_order = jnp.zeros(cap_r, dtype=jnp.int32)
        return (total_pairs, n_extra, max_m, m, offsets, base, order_r,
                extra_order, matched_r)
    return named_jit("srt_join_probe", fn)


def _build_fast_gather_fn(join_type: str) -> Callable:
    """max_m <= 1 path (FK/star-schema joins: every stream row matches at
    most one build row). The output keeps the LEFT batch's capacity and
    row order: left columns pass through untouched, the matched right row
    arrives by ONE fused gather, and inner joins just shrink the active
    mask. No searchsorted expansion, no output-capacity bucket, no
    per-total recompile."""
    inner = join_type in ("inner", "cross")

    def fn(cols_l, cols_r, active_l, m, base, order_r):
        cap_r = order_r.shape[0]
        has = m > 0
        ri = jnp.take(order_r,
                      jnp.clip(base, 0, cap_r - 1).astype(jnp.int32))
        out_r = take_columns(cols_r, jnp.where(has, ri, 0), valid_at=has)
        active = (active_l & has) if inner else active_l
        return out_r, active, jnp.sum(active.astype(jnp.int64))
    return named_jit(program_name("join_gather_fast", join_type), fn)


def _build_gather_fn(out_cap: int, join_type: str) -> Callable:
    right_outer = join_type in ("right", "rightouter", "full", "fullouter")
    outer = right_outer or join_type in ("left", "leftouter")

    def fn(cols_l, cols_r, total_pairs, n_extra, m, offsets, base,
           order_r):
        cap_l = m.shape[0]
        cap_r = order_r.shape[0]
        s = jnp.arange(out_cap, dtype=jnp.int64)
        if outer:
            # the stream row of output lane s, as below, by one scatter
            # and one prefix sum (the page decode's spelling, PR 27).
            # The search's loop gathers `offsets` out_cap lanes at a
            # time, eighteen times over; on the chip that gather's
            # seconds follow where the runtime put `offsets`, not what
            # is in it: 0.29-0.41 s of query 51's full outer join, in
            # two groups, query to query on the same data (PERF.md §6).
            # Inner joins keep the search until a perf_opt pairs them on
            # every cell (ROADMAP S17.1).
            li = run_index(offsets, out_cap)
        else:
            li = jnp.clip(
                jnp.searchsorted(offsets, s, side="right") - 1, 0,
                cap_l - 1).astype(jnp.int32)
        k = s - jnp.take(offsets, li)
        in_pairs = s < total_pairs
        has_match = jnp.take(m, li) > 0
        b = jnp.take(base, li)
        ri_matched = jnp.take(
            order_r,
            jnp.clip(b + k, 0, cap_r - 1).astype(jnp.int32))
        left_valid = in_pairs
        right_valid = in_pairs & has_match
        ri = jnp.where(right_valid, ri_matched, 0).astype(jnp.int32)
        active = in_pairs
        out_l = take_columns(cols_l, jnp.where(left_valid, li, 0),
                             valid_at=left_valid)
        return out_l, take_columns(cols_r, ri, valid_at=right_valid), \
            active, left_valid, right_valid

    def fn_right(cols_l, cols_r, total_pairs, n_extra, m, offsets, base,
                 order_r, extra_order):
        out_l, out_r0, active, lv, rv = fn(
            cols_l, cols_r, total_pairs, n_extra, m, offsets, base,
            order_r)
        cap_r = order_r.shape[0]
        s = jnp.arange(out_cap, dtype=jnp.int64)
        e = s - total_pairs
        is_extra = (s >= total_pairs) & (e < n_extra)
        ei = jnp.take(extra_order,
                      jnp.clip(e, 0, cap_r - 1).astype(jnp.int32))
        extra_cols = take_columns(cols_r, jnp.where(is_extra, ei, 0),
                                  valid_at=is_extra)
        # merge the pairs region with the extras region
        merged: List[AnyDeviceColumn] = []
        for a, b in zip(out_r0, extra_cols):
            if isinstance(a, DeviceStringColumn):
                merged.append(DeviceStringColumn(
                    a.dtype,
                    jnp.where(is_extra[:, None], b.chars, a.chars),
                    jnp.where(is_extra, b.lengths, a.lengths),
                    jnp.where(is_extra, b.validity, a.validity)))
            elif isinstance(a, DeviceDecimal128Column):
                merged.append(DeviceDecimal128Column(
                    a.dtype, jnp.where(is_extra, b.hi, a.hi),
                    jnp.where(is_extra, b.lo, a.lo),
                    jnp.where(is_extra, b.validity, a.validity)))
            else:
                merged.append(DeviceColumn(
                    a.dtype, jnp.where(is_extra, b.data, a.data),
                    jnp.where(is_extra, b.validity, a.validity)))
        active = active | is_extra
        return out_l, merged, active, lv, rv | is_extra

    return named_jit(program_name("join_gather", join_type),
                     fn_right if right_outer else fn)


def _build_mask_fn(lkeys: Tuple[E.Expression, ...],
                   rkeys: Tuple[E.Expression, ...],
                   join_type: str,
                   null_safe: Tuple[bool, ...] = ()) -> Callable:
    is_semi = join_type == "leftsemi"

    def fn(cols_l, active_l, lits_l, cols_r, active_r, lits_r):
        cap_l = active_l.shape[0]
        cap_r = active_r.shape[0]
        ctx_l = X.Ctx(cols_l, cap_l, lkeys, lits_l)
        ctx_r = X.Ctx(cols_r, cap_r, rkeys, lits_r)
        (_kl, _kr, _valid_l, _valid_r, m, _base, _order_r, _cnt_l_at_r
         ) = _key_plan(lkeys, rkeys, ctx_l, ctx_r, active_l,
                      active_r, null_safe)
        if is_semi:
            return active_l & (m > 0)
        return active_l & (m == 0)
    return named_jit("srt_join_mask", fn)


def _build_cond_mask_fn(cond: E.Expression, n_left: int,
                        used_r: Tuple[int, ...], n_right: int,
                        join_type: str) -> Callable:
    """Semi/anti mask under a residual ``cond`` (bound to the pair
    layout, left columns then right). Left row i's candidates are the
    build rows ``order_r[base[i] + j]``, ``j < m[i]`` (the count
    phase's extents): rank j of every row is gathered at once, the
    condition evaluated over the pair, and any-reduced onto the left
    row. A pair passes only where the condition is true, not null
    (Spark). ``used_r`` are the right columns the condition reads; the
    others are never gathered."""
    is_semi = join_type == "leftsemi"

    def fn(cols_l, cols_r_used, active_l, lits, m, base, order_r, max_m):
        cap_l = active_l.shape[0]
        cap_r = order_r.shape[0]

        def rank(j, found):
            with jax.named_scope("join_cond/gather"):
                has = j.astype(m.dtype) < m
                at = jnp.clip(base + j.astype(base.dtype), 0, cap_r - 1)
                ri = jnp.take(order_r, at.astype(jnp.int32))
                got = take_columns(cols_r_used, jnp.where(has, ri, 0),
                                   valid_at=has)
            with jax.named_scope("join_cond/eval"):
                pair: List = list(cols_l) + [None] * n_right
                for k, c in zip(used_r, got):
                    pair[n_left + k] = c
                p = X.dev_eval(cond, X.Ctx(pair, cap_l, (cond,), lits))
                passes = has & p.validity & X._as_bool(p)
            with jax.named_scope("join_cond/reduce"):
                return found | passes

        found = jax.lax.fori_loop(
            jnp.int32(0), max_m.astype(jnp.int32), rank,
            jnp.zeros(cap_l, dtype=jnp.bool_))
        return active_l & (found if is_semi else ~found)
    return named_jit("srt_join_cond_mask", fn)


def _align_string_caps(kl: Sequence[AnyDeviceColumn],
                       kr: Sequence[AnyDeviceColumn]):
    """Pad string key columns to a common char capacity so both sides
    emit the SAME equality-word layout (pack_string_words emits
    ceil(char_cap/8) words)."""
    out_l, out_r = list(kl), list(kr)
    for i, (a, b) in enumerate(zip(kl, kr)):
        if isinstance(a, DeviceStringColumn):
            cc = max(a.char_cap, b.char_cap)
            if a.char_cap < cc:
                out_l[i] = DeviceStringColumn(
                    a.dtype,
                    jnp.pad(a.chars, ((0, 0), (0, cc - a.char_cap))),
                    a.lengths, a.validity)
            if b.char_cap < cc:
                out_r[i] = DeviceStringColumn(
                    b.dtype,
                    jnp.pad(b.chars, ((0, 0), (0, cc - b.char_cap))),
                    b.lengths, b.validity)
    return out_l, out_r


_MULT_CACHE = JitCache("joinMult")


def build_key_max_multiplicity(right: DeviceBatch,
                               rkeys: List[E.Expression],
                               null_safe: Sequence[bool] = ()
                               ) -> Callable[[], int]:
    """Max number of build rows sharing one join key (0 when no valid
    keys), as a LAZY resolver: the program + async host copy dispatch
    now, the blocking read happens at the first call — overlapping the
    probe's flat fetch latency with the stream side's scan. Computed
    ONCE per broadcast build side; == 1 certifies every stream chunk
    for the FK fast path with NO per-chunk sizing sync — the reference
    reads the same property off its hash table build
    (GpuHashJoin.scala:377 buildSide distinct-count role)."""
    rk = tuple(rkeys)
    ns = tuple(null_safe) or (False,) * len(rk)
    salt = G.kernel_salt()
    key = (tuple(X.expr_key(e) for e in rk), ns, salt)
    def _build_mult():
        def _fn(cols_r, active_r, lits_r):
            cap_r = active_r.shape[0]
            ctx = X.Ctx(cols_r, cap_r, rk, lits_r)
            kr = [X.dev_eval(e, ctx) for e in rk]
            valid = active_r
            for c, nsf in zip(kr, ns):
                if not nsf:
                    valid = valid & c.validity
            active_s, _order, start, end = _group_extents(
                _key_words(kr, ns), valid, cap_r)
            length = jnp.where(active_s, end - start + 1, 0)
            return jnp.max(length)
        return named_jit("srt_join_build", _fn)
    fn, _ = _MULT_CACHE.get_or_build(key, _build_mult)
    with G.nan_scope(salt[0]):
        out = fn(right.columns, right.active, X.literal_values(list(rk)))
    from spark_rapids_tpu.columnar.device import _prefetch_host
    _prefetch_host([out])  # overlap the fetch with the stream-side scan
    def resolve() -> int:
        from spark_rapids_tpu import trace as TR
        with TR.device_sync("joinBuild"):
            return int(np.asarray(out))
    return resolve


_EXTRAS_CACHE = JitCache("joinExtras")
# tpu-lint: disable=jit-direct(single fixed boolean-OR program — one executable, bounded by construction)
_OR = named_jit("srt_join_or", lambda a, b: a | b)


def or_masks(a, b):
    """Accumulate matched-right masks across stream chunks (jitted —
    eager ops pay a per-op dispatch)."""
    return _OR(a, b)


def right_extras_batch(right: DeviceBatch, matched_any: jax.Array,
                       left_fields, out_schema: T.StructType
                       ) -> DeviceBatch:
    """Pair-layout batch of the UNMATCHED right rows (null left side) —
    the final emission of a chunked right/full outer join, after every
    stream chunk ORed its matched mask into ``matched_any``."""
    from spark_rapids_tpu.columnar.device import (flatten_batch,
                                                  rebuild_columns)
    flat, spec = flatten_batch(right)
    cap_r = right.capacity
    shapes = tuple((a.shape, str(a.dtype)) for a in flat)
    ldts = tuple(repr(f.data_type) for f in left_fields)
    key = (shapes, ldts)
    def _build_extras():
        ltypes = [f.data_type for f in left_fields]

        def build(matched, active_r, *rflat):
            keep = active_r & ~matched
            outs = []
            for a in rflat:
                if a.dtype == jnp.bool_ and a.ndim == 1:
                    outs.append(a & keep)
                elif a.ndim == 2:
                    outs.append(jnp.where(keep[:, None], a, 0))
                else:
                    outs.append(jnp.where(keep, a,
                                          jnp.zeros((), a.dtype)))
            lefts = []
            fv = jnp.zeros(cap_r, dtype=jnp.bool_)
            for dt in ltypes:
                if isinstance(dt, T.ArrayType):
                    raise X.DeviceUnsupported(
                        "array columns in outer join output")
                if T.is_limb_decimal(dt):
                    z = jnp.zeros(cap_r, dtype=jnp.int64)
                    lefts += [z, z, fv]
                elif isinstance(dt, (T.StringType, T.BinaryType)):
                    lefts += [jnp.zeros((cap_r, 8), dtype=jnp.uint8),
                              jnp.zeros(cap_r, dtype=jnp.int32), fv]
                else:
                    from spark_rapids_tpu.columnar.device import \
                        storage_jnp_dtype
                    lefts += [jnp.zeros(cap_r,
                                        dtype=storage_jnp_dtype(dt)), fv]
            return tuple(lefts), tuple(outs), keep
        return named_jit("srt_join_extras", build)
    fn, _ = _EXTRAS_CACHE.get_or_build(key, _build_extras)
    lefts, routs, keep = fn(matched_any, right.active, *flat)
    from spark_rapids_tpu.columnar.device import column_arity, make_column
    lcols = []
    off = 0
    for f in left_fields:
        k = column_arity(f.data_type)
        lcols.append(make_column(f.data_type, lefts[off:off + k]))
        off += k
    rcols = rebuild_columns(spec, routs)
    return DeviceBatch(out_schema, lcols + rcols, keep, None)


def device_join(left: DeviceBatch, right: DeviceBatch,
                lkeys: List[E.Expression], rkeys: List[E.Expression],
                join_type: str,
                out_schema: T.StructType,
                collect_matched_r: bool = False,
                null_safe: Sequence[bool] = (),
                fk_hint: bool = False,
                metrics=None,
                condition: Optional[E.Expression] = None):
    """Run the equi-join of two device batches; keys are pre-bound device
    expressions. Returns the joined batch (pair layout: left columns then
    right columns) or, for semi/anti, the masked left batch — under
    ``condition`` (semi/anti only; bound to the pair layout) a left row
    matches where some build row of its key also passes it. With
    ``collect_matched_r`` returns ``(batch, matched_r)`` where
    ``matched_r`` is the device bool mask of right rows that matched any
    left row — the exec's chunked right/full-outer path ORs these across
    stream chunks (JoinGatherer.scala:55 role)."""
    lk = tuple(lkeys)
    rk = tuple(rkeys)
    nst = tuple(null_safe) or (False,) * len(lk)
    salt = G.kernel_salt()  # snapshot: key AND trace use this value
    struct = (tuple(X.expr_key(e) for e in lk),
              tuple(X.expr_key(e) for e in rk), nst, salt)
    lits_l = X.literal_values(list(lk))
    lits_r = X.literal_values(list(rk))

    if join_type in MASK_JOINS and condition is None:
        key = (struct, join_type)
        fn, _ = _MASK_CACHE.get_or_build(
            key, lambda: _build_mask_fn(lk, rk, join_type, nst))
        with G.nan_scope(salt[0]):
            new_active = fn(left.columns, left.active, lits_l,
                            right.columns, right.active, lits_r)
        out = DeviceBatch(left.schema, left.columns, new_active, None)
        return (out, None) if collect_matched_r else out

    if join_type not in PAIR_JOINS + MASK_JOINS:
        raise X.DeviceUnsupported(f"join type {join_type}")

    ckey = (struct, join_type)
    count_fn, _ = _COUNT_CACHE.get_or_build(
        ckey, lambda: _build_count_fn(lk, rk, join_type, nst))
    from spark_rapids_tpu import trace as TR
    TR.first_dispatch(metrics, count_fn)
    with G.nan_scope(salt[0]):
        (total_pairs, n_extra, max_m, m, offsets, base, order_r,
         extra_order, matched_r) = count_fn(
             left.columns, left.active, lits_l,
             right.columns, right.active, lits_r)

    if join_type in MASK_JOINS:
        # the count phase's extents feed the mask program on the device
        # (max_m too: nothing sizes it); the host reads only the
        # candidate pairs, for the counter, once the mask is enqueued
        n_left = len(left.columns)
        used_r = tuple(sorted({b.ordinal - n_left for b in condition.collect(
            lambda e: isinstance(e, E.BoundReference))
            if b.ordinal >= n_left}))
        mask_fn, _ = _COND_MASK_CACHE.get_or_build(
            (X.expr_key(condition), n_left, used_r, join_type, salt),
            lambda: _build_cond_mask_fn(condition, n_left, used_r,
                                        len(right.columns), join_type))
        with G.nan_scope(salt[0]):
            new_active = mask_fn(
                left.columns, [right.columns[k] for k in used_r],
                left.active, X.literal_values([condition]), m, base,
                order_r, max_m)
        if metrics is not None:
            from spark_rapids_tpu import metrics as M
            with TR.device_sync("joinCondPairs", metrics):
                pairs = int(np.asarray(total_pairs))
            metrics.create(M.JOIN_CONDITION_PAIRS, M.ESSENTIAL).add(pairs)
        out = DeviceBatch(left.schema, left.columns, new_active, None)
        return (out, None) if collect_matched_r else out

    shapes = (tuple((a.shape, str(a.dtype))
                    for c in left.columns for a in c.arrays()),
              tuple((a.shape, str(a.dtype))
                    for c in right.columns for a in c.arrays()))
    def run_fast(num_rows: Optional[int]):
        # FK fast path (max_m <= 1: every stream row matches at most one
        # build row): output stays in the left batch's own layout — no
        # expansion program, no output-capacity bucket. The device count
        # rides along (prefetched) so downstream sizing reads resolve
        # without a fresh count program + flat roundtrip.
        fkey = (shapes, join_type, "fast")
        fast_fn, _ = _GATHER_CACHE.get_or_build(
            fkey, lambda: _build_fast_gather_fn(join_type))
        out_r, active, cnt = fast_fn(left.columns, right.columns,
                                     left.active, m, base, order_r)
        from spark_rapids_tpu.columnar.device import _prefetch_host
        _prefetch_host([cnt])
        out = DeviceBatch(out_schema, list(left.columns) + list(out_r),
                          active, num_rows, cnt)
        return (out, matched_r) if collect_matched_r else out

    if fk_hint and join_type in ("inner", "left", "leftouter"):
        # build-side keys certified unique: NO sizing sync at all — the
        # row count stays lazily unknown (resolved from the prefetched
        # device count only if someone asks)
        return run_fast(None)

    # ONE host sync for sizing: all scalars ride one stacked fetch
    # (each D2H read is a device sync)
    with TR.device_sync("joinSize", metrics):
        sc = np.asarray(_stack3(total_pairs, n_extra, max_m))
    total = int(sc[0]) + int(sc[1])
    out_cap = bucket_capacity(max(1, total))

    if int(sc[2]) <= 1 and join_type in ("inner", "left", "leftouter"):
        return run_fast(total)

    gkey = (shapes, out_cap, join_type, m.shape, order_r.shape)
    gather_fn, _ = _GATHER_CACHE.get_or_build(
        gkey, lambda: _build_gather_fn(out_cap, join_type))
    if join_type in ("right", "rightouter", "full", "fullouter"):
        out_l, out_r, active, _lv, _rv = gather_fn(
            left.columns, right.columns, total_pairs, n_extra, m, offsets,
            base, order_r, extra_order)
    else:
        out_l, out_r, active, _lv, _rv = gather_fn(
            left.columns, right.columns, total_pairs, n_extra, m, offsets,
            base, order_r)
    out = DeviceBatch(out_schema, list(out_l) + list(out_r), active, total)
    return (out, matched_r) if collect_matched_r else out
