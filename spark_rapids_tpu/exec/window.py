"""TpuWindowExec: device window functions (GpuWindowExec.scala:187 twin).

One fused jitted program per (expression structure, capacity bucket):
sort rows by (partition keys, order keys) with the existing subkey
encodings, derive partition/peer boundary flags, and compute every window
expression with segment ops + prefix scans — the batched-running-window
idea of the reference (GpuWindowExec's GpuRunningWindowExec path)
generalized to the whole supported frame set:

- ranking: row_number / rank / dense_rank / ntile from boundary flags
- offset: lag / lead as shifted gathers inside the partition
- aggregates sum/count/avg/min/max/first/last over
  - the whole partition (segment ops, broadcast back),
  - running frames (prefix scans; RANGE frames take the value at the
    last peer row — Spark's default frame),
  - bounded ROWS frames for sum/count/avg (prefix differences).

Running min/max is a segmented doubling scan over (total-order rank
words, winner position), and the winner's value is gathered, so values
round-trip bit-exactly.
Results are scattered back to ORIGINAL row order (the exec appends
columns without permuting its input, matching CpuWindowExec).

Decimal sources (PR 36): sum/min/max/count/first/last read a
``DecimalType`` column in its 64-bit (p <= 18) or two-limb form, in the
frames above. ``sum`` is Spark's ``decimal(min(38, p + 10), s)``: while
that fits 18 digits the accumulator is the int64 it is stored in, past
them the source is split into 32-bit digits, prefix-summed side by
side (and differenced, for a bounded frame) in int64, and the digit sums
are recombined with their carries once a lane (``_sum_limbs``) — exact, and
null where the true sum passes the result's precision (non-ANSI).
min/max compare a two-limb value by ``groupby.limb_words``, two more
rank words of the same scan, and gather both limbs at the winner.
``avg`` over a decimal (a division into ``decimal(p + 4, s + 4)``) and
every aggregate over a string are refused by name.

The program's steps carry ``jax.named_scope``s for ``tools trace``:
``window/sort`` (the multi-key sort), ``window/layout`` (boundaries,
``part_id``, partition and peer ends), ``window/sum`` (prefix sums and
counts), ``window/extreme`` (running / sparse-table min and max, and the
first / last non-null), ``window/unsort`` (back to input order).
"""

from __future__ import annotations

from typing import Callable, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_tpu import metrics as M
from spark_rapids_tpu import trace as TR
from spark_rapids_tpu.columnar.device import (AnyDeviceColumn, DeviceBatch,
                                              DeviceColumn,
                                              DeviceStringColumn,
                                              concat_device, make_column,
                                              storage_jnp_dtype)
from spark_rapids_tpu.conf import TpuConf
from spark_rapids_tpu.exec.base import (DevicePartitionThunk, TpuExec,
                                        device_channel)
from spark_rapids_tpu.ops import exprs as X
from spark_rapids_tpu.ops import groupby as G
from spark_rapids_tpu.ops import sort as S
from spark_rapids_tpu.sql import expressions as E
from spark_rapids_tpu.sql import physical as P
from spark_rapids_tpu.sql import types as T

from spark_rapids_tpu.jit_cache import JitCache, named_jit

_WINDOW_FN_CACHE = JitCache("window")



def is_device_window(window_exprs: List[E.Expression],
                     partition_spec: List[E.Expression],
                     order_spec: List[E.SortOrder],
                     conf: TpuConf) -> Optional[str]:
    """Tagging helper (GpuWindowExpression tagging rules)."""
    for e in partition_spec:
        dt = e.data_type
        if isinstance(dt, (T.ArrayType, T.MapType, T.StructType)):
            return f"window partition key type {dt} runs on CPU"
        r = X.is_device_expr(e, conf)
        if r:
            return r
        if X.contains_ansi_cast(e):
            return "ANSI casts in window partition keys run on CPU"
    for o in order_spec:
        dt = o.child.data_type
        if isinstance(dt, (T.ArrayType, T.MapType, T.StructType)):
            return f"window order key type {dt} runs on CPU"
        r = X.is_device_expr(o.child, conf)
        if r:
            return r
        if X.contains_ansi_cast(o.child):
            return "ANSI casts in window order keys run on CPU"
    for alias in window_exprs:
        wx = alias.child if isinstance(alias, E.Alias) else alias
        if not isinstance(wx, E.WindowExpression):
            return f"{type(wx).__name__} is not a window expression"
        func = wx.func
        frame = wx.frame
        if isinstance(func, (E.RowNumber, E.Rank, E.DenseRank, E.NTile)):
            continue
        if isinstance(func, E.Lag):  # covers Lead
            r = X.is_device_expr(func.input, conf)
            if r:
                return r
            if X.contains_ansi_cast(func.input):
                return "ANSI casts in lag/lead inputs run on CPU"
            if func.default is not None:
                r = X.is_device_expr(func.default, conf)
                if r:
                    return r
                if X.contains_ansi_cast(func.default):
                    return "ANSI casts in lag/lead defaults run on CPU"
                in_str = isinstance(func.input.data_type,
                                    (T.StringType, T.BinaryType))
                df_str = isinstance(func.default.data_type,
                                    (T.StringType, T.BinaryType))
                if in_str != df_str:
                    return ("lag/lead default type is incompatible with "
                            "the input type; runs on CPU")
            continue
        if isinstance(func, E.AggregateExpression):
            agg = func.func
            if func.is_distinct:
                return "DISTINCT window aggregates are not supported"
            if not isinstance(agg, (E.Sum, E.Count, E.Min, E.Max,
                                    E.Average, E.First, E.Last)):
                return (f"window aggregate {type(agg).__name__} has no "
                        "device implementation")
            if agg.children:
                from spark_rapids_tpu import device_caps as DC
                from spark_rapids_tpu.conf import ENABLE_FLOAT_AGG
                src = agg.children[0]
                if isinstance(src.data_type, (T.StringType, T.BinaryType)):
                    return (f"window aggregate over {src.data_type} "
                            "runs on CPU")
                if isinstance(src.data_type, T.DecimalType) \
                        and isinstance(agg, E.Average):
                    return (f"window average over {src.data_type} (a "
                            "decimal division) runs on CPU")
                float_ok = bool(conf.get(ENABLE_FLOAT_AGG))
                if isinstance(agg, (E.Sum, E.Average)) \
                        and T.is_floating(src.data_type) and not float_ok:
                    return ("device float window sum/average may differ "
                            "from CPU due to addition ordering "
                            "(spark.rapids.sql.variableFloatAgg.enabled"
                            "=false)")
                if isinstance(agg, E.Average) and not DC.float_div_exact()\
                        and not float_ok:
                    return ("device Average division is not bit-identical "
                            "to CPU on this backend; set spark.rapids.sql."
                            "variableFloatAgg.enabled=true to allow")
                r = X.is_device_expr(src, conf)
                if r:
                    return r
                if X.contains_ansi_cast(src):
                    return "ANSI casts in window aggregates run on CPU"
            bounded = not (frame.is_unbounded_whole or frame.is_running)
            if bounded and not isinstance(agg, (E.Sum, E.Count, E.Average,
                                                E.Min, E.Max)):
                return (f"bounded {frame.frame_type} frames are device-"
                        "supported for sum/count/avg/min/max only")
            if bounded and frame.frame_type == "range":
                if len(order_spec) != 1:
                    return ("value-bounded RANGE frames need exactly one "
                            "ORDER BY expression")
                odt = order_spec[0].child.data_type
                if not (T.is_integral(odt) or T.is_floating(odt)
                        or isinstance(odt, (T.DateType, T.TimestampType))):
                    return ("value-bounded RANGE frames need a numeric/"
                            "date/timestamp ORDER BY expression")
            continue
        return f"window function {type(func).__name__} is not supported"
    return None


# ---------------------------------------------------------------------------
# Kernel pieces (all operate in SORTED row space)
# ---------------------------------------------------------------------------

def _seg_running_extreme(part_id: jax.Array, words: List[jax.Array],
                         valid: jax.Array, is_min: bool
                         ) -> Tuple[jax.Array, jax.Array]:
    """Segmented running min/max over multi-word ranks (most-significant
    first; native dtypes — see groupby.rank_words). Returns (winner
    position per row, has-winner flag).

    A doubling scan: after step k a row holds the winner of the 2**k
    rows of its partition that end at it, and takes the better of its own
    and the one 2**k rows back; the loop ends once 2**k covers the
    longest partition. Every step is one elementwise pass, and the
    program is one loop body whatever the capacity (a
    ``lax.associative_scan`` over the same operands compiles to more
    than 100 MB of code at 786,432 lanes: docs/profiles/pr36)."""
    cap = part_id.shape[0]
    pos = jnp.arange(cap, dtype=jnp.int32)
    new_part = jnp.concatenate([jnp.ones(1, dtype=bool),
                                part_id[1:] != part_id[:-1]])
    start = jax.lax.cummax(jnp.where(new_part, pos, 0))
    longest = jnp.max(pos - start) + 1

    def step(state):
        # b: the row d rows back (``roll`` wraps; ``pos - d >= start``
        # keeps only a row of this partition)
        d, a_valid, a_pos, aw = state
        b_valid = jnp.roll(a_valid, d) & (pos - d >= start)
        b_pos = jnp.roll(a_pos, d)
        bw = [jnp.roll(w, d) for w in aw]
        better = jnp.zeros(cap, dtype=bool)   # the earlier one strictly
        eq = jnp.ones(cap, dtype=bool)
        for wa, wb in zip(aw, bw):
            c = (wb < wa) if is_min else (wb > wa)
            better = better | (eq & c)
            eq = eq & (wa == wb)
        take_b = b_valid & ((~a_valid) | better)
        return (d * 2, a_valid | b_valid, jnp.where(take_b, b_pos, a_pos),
                [jnp.where(take_b, wb, wa) for wa, wb in zip(aw, bw)])

    _, has, win, _ = jax.lax.while_loop(
        lambda state: state[0] < longest, step,
        (jnp.int32(1), valid, pos, list(words)))
    return win, has


def _rows(mask: jax.Array, x: jax.Array) -> jax.Array:
    """A per-row mask shaped to broadcast over ``x``'s columns."""
    return mask.reshape(mask.shape + (1,) * (x.ndim - 1))


def _prefix_in_part(x: jax.Array, start_of_row: jax.Array) -> jax.Array:
    """Inclusive prefix sum restarting at each partition boundary, down
    the rows of ``x`` (a vector, or a matrix of columns summed side by
    side). ``start_of_row[i]`` is the sorted position where row i's
    partition begins. Floats use a segmented scan (no cross-partition
    cancellation); ints use the cheaper global-cumsum difference."""
    if jnp.issubdtype(x.dtype, jnp.floating):
        return G.seg_running_sum(start_of_row, x)
    prefix = jnp.cumsum(x, axis=0)
    base = jnp.where(_rows(start_of_row > 0, x),
                     jnp.take(prefix, jnp.maximum(start_of_row - 1, 0),
                              axis=0),
                     jnp.zeros((), x.dtype))
    return prefix - base


class _SortedLayout:
    """Everything the per-function kernels need, in sorted row space."""

    def __init__(self, perm, active_s, part_id, peer_id, pos, start_of_row,
                 end_of_row, peer_last, new_peer, part_size):
        self.perm = perm              # sorted pos -> original row
        self.active_s = active_s
        self.part_id = part_id
        self.peer_id = peer_id
        self.pos = pos
        self.start_of_row = start_of_row  # partition start pos, per row
        self.end_of_row = end_of_row      # partition end pos (incl)
        self.peer_last = peer_last        # last pos of row's peer group
        self.new_peer = new_peer
        self.part_size = part_size        # rows in row's partition


def _layout(part_keys: List[AnyDeviceColumn],
            order_specs: List[E.SortOrder],
            order_keys: List[AnyDeviceColumn],
            active: jax.Array) -> _SortedLayout:
    cap = active.shape[0]
    part_subkeys: List[jax.Array] = []
    for c in part_keys:
        part_subkeys.extend(G.grouping_subkeys(c))
    order_subkeys: List[jax.Array] = []
    for c, o in zip(order_keys, order_specs):
        order_subkeys.extend(S.order_subkeys(c, o.ascending, o.nulls_first))
    # significance: active first, then partition keys, then order keys;
    # ONE multi-operand sort gives the sorted keys directly (payload
    # sort — no per-key gathers, which are HBM-bound on TPU)
    from spark_rapids_tpu.columnar.device import sort_with_payload
    all_keys = [~active] + part_subkeys + order_subkeys
    with jax.named_scope("window/sort"):
        sorted_keys, perm, _ = sort_with_payload(all_keys, [])
    with jax.named_scope("window/layout"):
        active_s = ~sorted_keys[0]
        part_sorted = sorted_keys[1:1 + len(part_subkeys)]
        order_sorted = sorted_keys[1 + len(part_subkeys):]
        pos = jnp.arange(cap, dtype=jnp.int32)

        def boundaries(keys) -> jax.Array:
            new = jnp.zeros(cap, dtype=bool).at[0].set(True)
            for ks in keys:
                d = ks[1:] != ks[:-1]
                new = new.at[1:].set(new[1:] | d)
            return new.at[1:].set(
                new[1:] | (active_s[1:] != active_s[:-1]))

        new_part = boundaries(part_sorted)
        new_peer = new_part | boundaries(list(part_sorted)
                                         + list(order_sorted))
        part_id = jnp.cumsum(new_part.astype(jnp.int32)) - 1
        peer_id = jnp.cumsum(new_peer.astype(jnp.int32)) - 1
        # boundary latches, not segment ops (XLA scatters serialize on TPU):
        # partition start = last boundary position at-or-before me (cummax),
        # ends = next boundary position at-or-after me (reverse cummin)
        start_of_row = jax.lax.cummax(jnp.where(new_part, pos, -1))
        part_last_flag = jnp.concatenate(
            [new_part[1:], jnp.ones(1, dtype=bool)])
        end_of_row = jnp.flip(jax.lax.cummin(
            jnp.flip(jnp.where(part_last_flag, pos, cap))))
        peer_last_flag = jnp.concatenate(
            [new_peer[1:], jnp.ones(1, dtype=bool)])
        peer_last = jnp.flip(jax.lax.cummin(
            jnp.flip(jnp.where(peer_last_flag, pos, cap))))
        part_size = end_of_row - start_of_row + 1
    return _SortedLayout(perm, active_s, part_id, peer_id, pos,
                         start_of_row, end_of_row, peer_last, new_peer,
                         part_size)


def _ranking(func, lay: _SortedLayout) -> Tuple[jax.Array, jax.Array]:
    """(data int32, validity) in sorted space."""
    if isinstance(func, E.RowNumber):
        return (lay.pos - lay.start_of_row + 1).astype(jnp.int32), \
            lay.active_s
    if isinstance(func, E.Rank):
        # peer-group start = last new_peer boundary at-or-before me
        first = jax.lax.cummax(jnp.where(lay.new_peer, lay.pos, -1))
        return (first - lay.start_of_row + 1).astype(jnp.int32), \
            lay.active_s
    if isinstance(func, E.DenseRank):
        prefix = jnp.cumsum(lay.new_peer.astype(jnp.int32))
        base = jnp.take(prefix, lay.start_of_row)
        return (prefix - base + 1).astype(jnp.int32), lay.active_s
    if isinstance(func, E.NTile):
        k = func.n
        m = lay.part_size
        p = lay.pos - lay.start_of_row
        base = m // k
        rem = m % k
        big = rem * (base + 1)
        tile = jnp.where(
            p < big,
            p // jnp.maximum(base + 1, 1),
            rem + (p - big) // jnp.maximum(base, 1))
        return (tile + 1).astype(jnp.int32), lay.active_s
    raise X.DeviceUnsupported(type(func).__name__)


def _offset_fn(func: E.Lag, val: AnyDeviceColumn, default_val,
               lay: _SortedLayout):
    """lag/lead as a shifted gather inside the partition."""
    cap = lay.pos.shape[0]
    off = func.offset if not isinstance(func, E.Lead) else -func.offset
    src = lay.pos - off
    ok = (src >= lay.start_of_row) & (src <= lay.end_of_row) & lay.active_s
    safe = jnp.clip(src, 0, cap - 1)
    src_orig = jnp.take(lay.perm, safe)  # gather from ORIGINAL rows
    if isinstance(val, DeviceStringColumn):
        chars = val.chars[src_orig]
        lengths = val.lengths[src_orig]
        validity = val.validity[src_orig] & ok
        if default_val is not None:
            dchars, dlengths, dvalid = default_val
            cc = max(chars.shape[1], dchars.shape[1])
            if chars.shape[1] < cc:
                chars = jnp.pad(chars, ((0, 0), (0, cc - chars.shape[1])))
            if dchars.shape[1] < cc:
                dchars = jnp.pad(dchars,
                                 ((0, 0), (0, cc - dchars.shape[1])))
            chars = jnp.where(ok[:, None], chars, dchars)
            lengths = jnp.where(ok, lengths, dlengths)
            validity = jnp.where(ok, validity, dvalid & lay.active_s)
        chars = jnp.where(validity[:, None], chars, 0)
        lengths = jnp.where(validity, lengths, 0)
        return (chars, lengths), validity
    from spark_rapids_tpu.columnar.device import DeviceDecimal128Column
    if isinstance(val, DeviceDecimal128Column):
        hi = val.hi[src_orig]
        lo = val.lo[src_orig]
        validity = val.validity[src_orig] & ok
        if default_val is not None:
            dhi, dlo, dvalid = default_val
            hi = jnp.where(ok, hi, dhi)
            lo = jnp.where(ok, lo, dlo)
            validity = jnp.where(ok, validity, dvalid & lay.active_s)
        z = jnp.zeros((), jnp.int64)
        return (jnp.where(validity, hi, z),
                jnp.where(validity, lo, z)), validity
    data = val.data[src_orig]
    validity = val.validity[src_orig] & ok
    if default_val is not None:
        dflt_data, dflt_valid = default_val
        data = jnp.where(ok, data, dflt_data)
        validity = jnp.where(ok, validity, dflt_valid & lay.active_s)
    data = jnp.where(validity, data, jnp.zeros((), data.dtype))
    return (data,), validity


def _to_orig(inv_perm: jax.Array, arr: jax.Array) -> jax.Array:
    """Map a sorted-space result back to original row order via the
    inverse permutation (a gather; scatters serialize on TPU)."""
    return jnp.take(arr, inv_perm, axis=0)


def _value_arrays(val: AnyDeviceColumn) -> Tuple[jax.Array, ...]:
    """A fixed-width column's value arrays: ``(data,)``, or both limbs
    of a DECIMAL128 column."""
    return val.arrays()[:-1]


def _winner_value(val: AnyDeviceColumn, lay: _SortedLayout,
                  win_pos: jax.Array, has: jax.Array
                  ) -> Tuple[Tuple[jax.Array, ...], jax.Array]:
    """Gather the value at sorted position ``win_pos`` (per sorted row)."""
    cap = lay.pos.shape[0]
    orig = jnp.take(lay.perm, jnp.clip(win_pos, 0, cap - 1))
    validity = has & lay.active_s
    return _gather_value(val, orig, validity), validity


def _gather_value(val: AnyDeviceColumn, orig: jax.Array,
                  validity: jax.Array) -> Tuple[jax.Array, ...]:
    """``val``'s arrays at the ORIGINAL rows ``orig``, zeroed where the
    result is null."""
    return tuple(
        jnp.where(validity, jnp.take(a, orig), jnp.zeros((), a.dtype))
        for a in _value_arrays(val))


def _sum_limbs(scan: Callable, val: AnyDeviceColumn, valid_s: jax.Array,
               lay: _SortedLayout, precision: int
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Framed sum of a decimal source into a two-limb accumulator:
    ``(hi, lo, fits)``. The source is split into 32-bit digits (two of
    an int64 source, four of a two-limb one; the top digit signed), the
    columns of one matrix that ``scan`` sums over the frame in int64 — a
    partition's sum of fewer than 2**31 rows of 32-bit digits stays
    under 2**63, and a bounded frame's difference of two prefixes is
    exact — and the digit sums are recombined with their carries once a
    lane. ``fits`` is false where the true sum does not fit 128 bits or
    ``precision``. (One prefix sum and one row gather for all digits: a
    gather costs by the element it addresses, and a digit a vector 3.5
    times as much on the chip, docs/profiles/pr36.)"""
    from spark_rapids_tpu.ops import int128 as I
    z = jnp.int64(0)
    m32 = jnp.int64(0xFFFFFFFF)
    sh = jnp.int64(32)
    limbs = [jnp.where(valid_s, jnp.take(a, lay.perm), z)
             for a in _value_arrays(val)]
    if len(limbs) == 2:
        hi, lo = limbs
        digits = [lo & m32, (lo >> sh) & m32, hi & m32, hi >> sh]
    else:
        digits = [limbs[0] & m32, limbs[0] >> sh]
    summed = scan(jnp.stack(digits, axis=1))
    sums = [summed[:, k] for k in range(len(digits))]
    # value = sum(sums[i] << 32 i): carry each digit sum's overflow of
    # 32 bits into the next (arithmetic shifts: the top one is signed)
    words = []
    carry = jnp.zeros_like(sums[0])
    for total in sums:
        t = total + carry
        words.append(t & m32)
        carry = t >> sh
    while len(words) < 4:  # sign-extend an int64 source's two digits
        words.append(carry & m32)
        carry = carry >> sh
    rlo = words[0] | (words[1] << sh)
    rhi = words[2] | (words[3] << sh)
    # bits 128 and up must repeat the sign bit
    fits = carry == (rhi >> jnp.int64(63))
    return rhi, rlo, fits & I.fits_precision(jnp, rhi, rlo, precision)


def _frame_bounds(lay: _SortedLayout, frame: E.WindowFrame, cap: int
                  ) -> Tuple[jax.Array, jax.Array]:
    """Per-row inclusive [lo, hi] sorted-position bounds of a BOUNDED
    frame. ROWS frames are position offsets; value-bounded RANGE frames
    resolve [ov+lower, ov+upper] with a vectorized binary search over
    the partition-sorted order values (GpuWindowExec's bounded-range
    resolution). Null-ordered rows frame their null peer block."""
    if frame.frame_type == "rows":
        lo = (lay.start_of_row if frame.lower is None
              else jnp.maximum(lay.pos + frame.lower, lay.start_of_row))
        hi = (lay.end_of_row if frame.upper is None
              else jnp.minimum(lay.pos + frame.upper, lay.end_of_row))
        return lo, hi
    ov_s, ook, asc, nulls_first = lay.order_val
    # sign-normalize so values ASCEND with sorted position; the offsets
    # apply unnegated in this space (see the CPU twin, window_exec.py).
    # Widen BEFORE negating: -int32.min overflows in int32
    if jnp.issubdtype(ov_s.dtype, jnp.floating):
        sgn = ov_s.astype(jnp.float64)
        off_cast = float
    else:
        sgn = ov_s.astype(jnp.int64)
        off_cast = int
    if not asc:
        sgn = -sgn

    def gallop(pred_at) -> jax.Array:
        """Last position p in [start-1, end] whose prefix predicate is
        still True (monotone True->False within the partition)."""
        idx = lay.start_of_row - 1
        k = cap.bit_length()
        for step in (1 << j for j in reversed(range(k + 1))):
            nxt = idx + step
            ok = (nxt <= lay.end_of_row) & pred_at(
                jnp.clip(nxt, 0, cap - 1))
            idx = jnp.where(ok, nxt, idx)
        return idx

    # null order values sort to one contiguous peer block; treat them
    # as -inf (nulls first) / +inf (nulls last) so the searches stay
    # monotone and never include them in a value frame. NaNs form their
    # OWN peer block (Spark total order: NaN greatest, all NaNs equal):
    # last under ASC (+inf-like), first under DESC (-inf-like) — NaN
    # comparisons being natively false handles ASC, DESC needs the
    # explicit before-range treatment.
    if jnp.issubdtype(sgn.dtype, jnp.floating):
        is_nan_v = jnp.isnan(sgn)
    else:
        is_nan_v = jnp.zeros(cap, dtype=bool)

    def lt(p, t):
        v = jnp.take(sgn, p)
        nl = ~jnp.take(ook, p)
        nn = jnp.take(is_nan_v, p)
        base = jnp.where(nn, jnp.bool_(not asc), v < t)
        return jnp.where(nl, jnp.bool_(nulls_first), base)

    def le(p, t):
        v = jnp.take(sgn, p)
        nl = ~jnp.take(ook, p)
        nn = jnp.take(is_nan_v, p)
        base = jnp.where(nn, jnp.bool_(not asc), v <= t)
        return jnp.where(nl, jnp.bool_(nulls_first), base)

    # the engine's bounded-range convention (CPU twin identical): value
    # frames of searchable rows span searchable positions only — the
    # leading block (nulls when nulls-first, NaNs under DESC) and
    # trailing block (nulls when nulls-last, NaNs under ASC) stay out
    def leading(p):
        nl = ~jnp.take(ook, p)
        nn = jnp.take(is_nan_v, p)
        return (nl & jnp.bool_(nulls_first)) | (nn & jnp.bool_(not asc))

    def keep(p):
        nl = ~jnp.take(ook, p)
        nn = jnp.take(is_nan_v, p)
        trailing = (nl & jnp.bool_(not nulls_first)) \
            | (nn & jnp.bool_(asc))
        return ~trailing

    if frame.lower is None:
        lo = gallop(leading) + 1
    else:
        t_lo = sgn + off_cast(frame.lower)
        lo = gallop(lambda p: lt(p, t_lo)) + 1
    if frame.upper is None:
        hi = gallop(keep)
    else:
        t_hi = sgn + off_cast(frame.upper)
        hi = gallop(lambda p: le(p, t_hi))
    # null rows AND valid-NaN rows frame their whole peer block instead
    # (each is its own contiguous peer group under Spark's total order)
    peer_first = jax.lax.cummax(jnp.where(lay.new_peer, lay.pos, -1))
    peer_framed = ~ook | is_nan_v
    lo = jnp.where(peer_framed, peer_first, lo)
    hi = jnp.where(peer_framed, lay.peer_last, hi)
    return lo, hi


def _agg_window(agg: E.AggregateFunction, frame: E.WindowFrame,
                val: Optional[AnyDeviceColumn], lay: _SortedLayout,
                out_type: T.DataType
                ) -> Tuple[Tuple[jax.Array, ...], jax.Array]:
    """(value arrays, validity) in sorted space for one windowed
    aggregate: one array, or both limbs of a DECIMAL128 result."""
    scope = ("window/sum" if isinstance(agg, (E.Count, E.Sum, E.Average))
             else "window/extreme")
    with jax.named_scope(scope):
        return _agg_window_body(agg, frame, val, lay, out_type)


def _agg_window_body(agg: E.AggregateFunction, frame: E.WindowFrame,
                     val: Optional[AnyDeviceColumn], lay: _SortedLayout,
                     out_type: T.DataType
                     ) -> Tuple[Tuple[jax.Array, ...], jax.Array]:
    from spark_rapids_tpu.columnar.device import DeviceDecimal128Column
    cap = lay.pos.shape[0]
    if val is not None:
        valid_s = jnp.take(val.validity, lay.perm) & lay.active_s
    else:  # Count(*) — every active row counts
        valid_s = lay.active_s
    ones = jnp.where(valid_s, jnp.int64(1), jnp.int64(0))

    def running(x):
        """Inclusive running value; RANGE frames read the peer-group end."""
        pp = _prefix_in_part(x, lay.start_of_row)
        if frame.frame_type == "range":
            return jnp.take(pp, lay.peer_last, axis=0)
        return pp

    def whole(x):
        # running total read at the partition's END row (scatter-free)
        pp = _prefix_in_part(x, lay.start_of_row)
        return jnp.take(pp, lay.end_of_row, axis=0)

    def bounded(x):
        pp = _prefix_in_part(x, lay.start_of_row)
        lo, hi = _frame_bounds(lay, frame, cap)
        hi_v = jnp.take(pp, jnp.clip(hi, 0, cap - 1), axis=0)
        lo_base = jnp.where(
            _rows(lo > lay.start_of_row, x),
            jnp.take(pp, jnp.clip(lo - 1, 0, cap - 1), axis=0),
            jnp.zeros((), x.dtype))
        return jnp.where(_rows(hi >= lo, x), hi_v - lo_base,
                         jnp.zeros((), x.dtype))

    if frame.is_unbounded_whole:
        scan = whole
    elif frame.is_running:
        scan = running
    else:
        scan = bounded

    if isinstance(agg, E.Count):
        return (scan(ones),), lay.active_s

    if isinstance(agg, E.Sum) and T.is_limb_decimal(out_type):
        rhi, rlo, fits = _sum_limbs(scan, val, valid_s, lay,
                                    out_type.precision)
        validity = (scan(ones) > 0) & lay.active_s & fits
        z = jnp.int64(0)
        return (jnp.where(validity, rhi, z),
                jnp.where(validity, rlo, z)), validity

    if isinstance(agg, (E.Sum, E.Average)):
        # a decimal sum stored in 64 bits (p + 10 <= 18) cannot pass its
        # precision: 10**p a row, under 2**31 < 10**10 rows
        acc_dt = (jnp.float64 if isinstance(agg, E.Average)
                  else storage_jnp_dtype(out_type))
        data_s = jnp.take(val.data, lay.perm)
        x = jnp.where(valid_s, data_s.astype(acc_dt),
                      jnp.zeros((), acc_dt))
        cnt = scan(ones)
        s = scan(x)
        validity = (cnt > 0) & lay.active_s
        if isinstance(agg, E.Average):
            d = s / jnp.maximum(cnt, 1).astype(jnp.float64)
        else:
            d = s
        return (jnp.where(validity, d, jnp.zeros((), d.dtype)),), validity

    if isinstance(agg, (E.Min, E.Max)):
        is_min = isinstance(agg, E.Min)
        sorted_arrs = [jnp.take(a, lay.perm) for a in _value_arrays(val)]
        if isinstance(val, DeviceDecimal128Column):
            words = G.limb_words(DeviceDecimal128Column(
                val.dtype, *sorted_arrs, valid_s))
        else:
            words = G.rank_words(DeviceColumn(val.dtype, *sorted_arrs,
                                              valid_s))
        bounded_frame = not (frame.is_unbounded_whole or frame.is_running)
        if bounded_frame:
            lo, hi = _frame_bounds(lay, frame, cap)
            win, has = _sparse_table_extreme(words, valid_s, lo, hi,
                                             cap, is_min)
            return _winner_value(val, lay, win, has)
        win, has = _seg_running_extreme(lay.part_id, words, valid_s,
                                        is_min)
        if frame.is_unbounded_whole:
            # the running winner at the partition END row is the
            # whole-partition winner — broadcast by gather
            win = jnp.take(win, lay.end_of_row)
            has = jnp.take(has, lay.end_of_row)
        elif frame.frame_type == "range":
            win = jnp.take(win, lay.peer_last)
            has = jnp.take(has, lay.peer_last)
        return _winner_value(val, lay, win, has)

    if isinstance(agg, (E.First, E.Last)):
        is_first = isinstance(agg, E.First)
        if not agg.ignore_nulls:
            if frame.is_unbounded_whole:
                tgt = lay.start_of_row if is_first else lay.end_of_row
            elif is_first:
                tgt = lay.start_of_row
            else:  # running last row = current row / last peer
                tgt = (lay.peer_last if frame.frame_type == "range"
                       else lay.pos)
            orig = jnp.take(lay.perm, tgt)
            v = jnp.take(val.validity, orig) & lay.active_s
            return _gather_value(val, orig, v), v
        # ignore_nulls: running min/max over the position of valid rows
        posrank = (lay.pos + 1).astype(jnp.uint64)
        win, has = _seg_running_extreme(lay.part_id, [posrank],
                                        valid_s, is_first)
        if frame.is_unbounded_whole:
            win = jnp.take(win, lay.end_of_row)
            has = jnp.take(has, lay.end_of_row)
        elif frame.frame_type == "range":
            win = jnp.take(win, lay.peer_last)
            has = jnp.take(has, lay.peer_last)
        return _winner_value(val, lay, win, has)

    raise X.DeviceUnsupported(type(agg).__name__)


def _sparse_table_extreme(words: List[jax.Array], valid: jax.Array,
                          lo: jax.Array, hi: jax.Array, cap: int,
                          is_min: bool) -> Tuple[jax.Array, jax.Array]:
    """Bounded-interval min/max: winner POSITION per row over the
    per-row inclusive interval [lo, hi] in sorted space, via a sparse
    table of winner positions (O(cap log cap) build, two gathers per
    query — the XLA shape of sliding-window RMQ; the reference's
    GpuWindowExec does the same bounded frames via cudf windowed
    reductions, GpuWindowExec.scala:283). Intervals never cross
    partition boundaries because callers clamp lo/hi to the row's
    partition. Returns (winner position, has-winner)."""
    pos = jnp.arange(cap, dtype=jnp.int32)
    sentinel = jnp.int32(cap)  # loses to every real candidate

    def better(p1: jax.Array, p2: jax.Array) -> jax.Array:
        """Pick the winning position (ties -> earlier position, which
        keeps results deterministic and matches the CPU fold)."""
        a_ok = p1 < sentinel
        b_ok = p2 < sentinel
        c1 = jnp.clip(p1, 0, cap - 1)
        c2 = jnp.clip(p2, 0, cap - 1)
        a_wins = jnp.zeros(p1.shape, dtype=bool)
        decided = jnp.zeros(p1.shape, dtype=bool)
        for w in words:
            w1 = jnp.take(w, c1)
            w2 = jnp.take(w, c2)
            gt = (w1 > w2) if not is_min else (w1 < w2)
            lt = (w1 < w2) if not is_min else (w1 > w2)
            a_wins = jnp.where(~decided & gt, True, a_wins)
            decided = decided | gt | lt
        a_wins = jnp.where(~decided, p1 <= p2, a_wins)  # tie: earlier
        a_wins = jnp.where(~b_ok, True, jnp.where(~a_ok, False, a_wins))
        return jnp.where(a_wins, p1, p2)

    level = jnp.where(valid, pos, sentinel)
    levels = [level]
    k = 1
    while (1 << k) <= cap:
        half = 1 << (k - 1)
        shifted = jnp.concatenate(
            [level[half:], jnp.full(half, sentinel, dtype=jnp.int32)])
        level = better(level, shifted)
        levels.append(level)
        k += 1
    tbl = jnp.stack(levels)  # (L, cap): winner over [i, i + 2^k)

    length = jnp.maximum(hi - lo + 1, 1)
    # floor(log2(len)): exact in f64 for every len <= cap
    kq = jnp.floor(jnp.log2(length.astype(jnp.float64))).astype(jnp.int32)
    c_lo = jnp.clip(lo, 0, cap - 1)
    c_hi = jnp.clip(hi - (1 << kq) + 1, 0, cap - 1)
    w1 = tbl[kq, c_lo]
    w2 = tbl[kq, c_hi]
    win = better(w1, w2)
    nonempty = hi >= lo
    has = nonempty & (win < sentinel)
    return jnp.where(has, win, jnp.int32(0)), has


# ---------------------------------------------------------------------------
# Program builder + exec
# ---------------------------------------------------------------------------

def _key_chunk_ids(keycols_per_batch: List[List], actives: List[jax.Array],
                   goal: int, n_chunks: int) -> List[jax.Array]:
    """Per-batch chunk ids that NEVER split a partition-key group: rows
    are ranked by key (one stable sort over the resident key columns,
    the global_range_pids discipline), each group's chunk is decided by
    the row count preceding its FIRST row, and ids map back through the
    inverse permutation. A single group larger than ``goal`` stays in
    one chunk (same contract as GpuKeyBatchingIterator)."""
    from spark_rapids_tpu.columnar.device import (DeviceStringColumn,
                                                  sort_with_payload)
    from spark_rapids_tpu.ops import sort as S
    n_keys = len(keycols_per_batch[0])
    for ki in range(n_keys):
        cols = [kc[ki] for kc in keycols_per_batch]
        if isinstance(cols[0], DeviceStringColumn):
            cc = max(c.char_cap for c in cols)
            for bi, c in enumerate(cols):
                if c.char_cap < cc:
                    keycols_per_batch[bi][ki] = DeviceStringColumn(
                        c.dtype,
                        jnp.pad(c.chars, ((0, 0), (0, cc - c.char_cap))),
                        c.lengths, c.validity)
    keysets = []
    for kc in keycols_per_batch:
        subkeys: List[jax.Array] = []
        for c in kc:
            subkeys.extend(S.order_subkeys(c, True, True))
        keysets.append(tuple(subkeys))
    combined = [jnp.concatenate([ks[i] for ks in keysets])
                for i in range(len(keysets[0]))]
    active = jnp.concatenate(actives)
    cap = active.shape[0]
    sorted_all, perm, _p = sort_with_payload([~active] + combined, [])
    active_s = ~sorted_all[0]
    sorted_keys = sorted_all[1:]
    pos = jnp.arange(cap, dtype=jnp.int32)
    differs = jnp.zeros(cap, dtype=bool)
    for k in sorted_keys:
        d = k[1:] != k[:-1]
        differs = differs.at[1:].set(differs[1:] | d)
    boundary = differs.at[0].set(True)
    group_start = jax.lax.cummax(jnp.where(boundary, pos, 0))
    chunk_sorted = jnp.minimum(group_start // jnp.int32(goal),
                               jnp.int32(n_chunks - 1)).astype(jnp.int32)
    chunk_sorted = jnp.where(active_s, chunk_sorted, jnp.int32(0))
    inv = jnp.argsort(perm)
    chunk_orig = jnp.take(chunk_sorted, inv)
    out: List[jax.Array] = []
    off = 0
    for a in actives:
        out.append(chunk_orig[off:off + a.shape[0]])
        off += a.shape[0]
    return out


def _build_window_fn(part_bound: Tuple[E.Expression, ...],
                     order_specs: Tuple[E.SortOrder, ...],
                     order_bound: Tuple[E.Expression, ...],
                     items: Tuple[Tuple, ...],
                     all_exprs: Tuple[E.Expression, ...]) -> Callable:
    """items: ("rank", func) | ("offset", func, src_i, default_i|None)
    | ("agg", agg_func, frame, src_i|None, out_type)."""

    def fn(cols, active, lit_vals):
        cap = active.shape[0]
        ctx = X.Ctx(cols, cap, all_exprs, lit_vals)
        part_cols = [X.dev_eval(e, ctx) for e in part_bound]
        order_cols = [X.dev_eval(e, ctx) for e in order_bound]
        lay = _layout(part_cols, list(order_specs), order_cols, active)
        needs_ov = any(
            it[0] == "agg" and it[2].frame_type == "range"
            and not (it[2].is_unbounded_whole or it[2].is_running)
            for it in items)
        if needs_ov:
            oc = order_cols[0]
            lay.order_val = (jnp.take(oc.data, lay.perm),
                             jnp.take(oc.validity, lay.perm)
                             & lay.active_s,
                             order_specs[0].ascending,
                             order_specs[0].nulls_first)
        with jax.named_scope("window/unsort"):
            inv = jnp.argsort(lay.perm)  # original row -> sorted pos

        def to_orig(arrs, v):
            with jax.named_scope("window/unsort"):
                return (tuple(_to_orig(inv, a) for a in arrs),
                        _to_orig(inv, v))
        outs = []
        for item in items:
            kind = item[0]
            if kind == "rank":
                d, v = _ranking(item[1], lay)
                outs.append(to_orig((d,), v))
            elif kind == "offset":
                _k, func, src_i, dflt_i = item
                val = X.dev_eval(all_exprs[src_i], ctx)
                dflt = None
                if dflt_i is not None:
                    from spark_rapids_tpu.columnar.device import \
                        DeviceDecimal128Column
                    dc = X.dev_eval(all_exprs[dflt_i], ctx)
                    if isinstance(dc, (DeviceStringColumn,
                                       DeviceDecimal128Column)):
                        dflt = dc.arrays()
                    else:
                        dflt = (dc.data, dc.validity)
                arrs, v = _offset_fn(func, val, dflt, lay)
                outs.append(to_orig(arrs, v))
            else:  # agg
                _k, agg, frame, src_i, out_type = item
                val = (X.dev_eval(all_exprs[src_i], ctx)
                       if src_i is not None else None)
                arrs, v = _agg_window(agg, frame, val, lay, out_type)
                outs.append(to_orig(arrs, v))
        return outs
    return named_jit("srt_window", fn)


class TpuWindowExec(TpuExec):
    def __init__(self, window_exprs: List[E.Expression],
                 partition_spec: List[E.Expression],
                 order_spec: List[E.SortOrder], child: TpuExec,
                 conf: TpuConf):
        super().__init__(conf)
        self.children = [child]
        self.window_exprs = window_exprs
        self.partition_spec = partition_spec
        self.order_spec = order_spec

    @property
    def child(self) -> TpuExec:
        return self.children[0]

    @property
    def output(self):
        return list(self.child.output) + [E.named_output(e)
                                          for e in self.window_exprs]

    def _plan_items(self):
        """Bind everything and build the static item descriptors."""
        child_out = self.child.output
        part_bound = tuple(E.bind_references(e, child_out)
                           for e in self.partition_spec)
        order_bound = tuple(E.bind_references(o.child, child_out)
                            for o in self.order_spec)
        extra: List[E.Expression] = []
        base = len(part_bound) + len(order_bound)

        def add(e: E.Expression) -> int:
            extra.append(E.bind_references(e, child_out))
            return base + len(extra) - 1

        items: List[Tuple] = []
        out_types: List[T.DataType] = []
        for alias in self.window_exprs:
            wx = alias.child
            func = wx.func
            if isinstance(func, (E.RowNumber, E.Rank, E.DenseRank,
                                 E.NTile)):
                items.append(("rank", func))
            elif isinstance(func, E.Lag):
                src_i = add(func.input)
                dflt_i = None
                if func.default is not None:
                    dflt = func.default
                    # full type equality, not class equality: a
                    # decimal(3,2) default against a decimal(25,2)
                    # input still needs the cast to the two-limb form
                    if dflt.data_type != func.input.data_type:
                        dflt = E.Cast(dflt, func.input.data_type)
                    dflt_i = add(dflt)
                items.append(("offset", func, src_i, dflt_i))
            else:
                agg = func.func
                src_i = add(agg.children[0]) if agg.children else None
                items.append(("agg", agg, wx.frame, src_i, wx.data_type))
            out_types.append(wx.data_type)
        all_exprs = part_bound + order_bound + tuple(extra)
        return part_bound, order_bound, items, all_exprs, out_types

    def _item_key(self, items) -> Tuple:
        out = []
        for it in items:
            if it[0] == "rank":
                out.append(("rank", type(it[1]).__name__,
                            getattr(it[1], "n", None)))
            elif it[0] == "offset":
                out.append(("offset", type(it[1]).__name__, it[1].offset,
                            it[2], it[3]))
            else:
                out.append(("agg", type(it[1]).__name__,
                            getattr(it[1], "ignore_nulls", None),
                            it[2].key(), it[3], repr(it[4])))
        return tuple(out)

    def _run_batch(self, batch: DeviceBatch) -> DeviceBatch:
        (part_bound, order_bound, items, all_exprs, out_types
         ) = self._plan_items()
        salt = G.kernel_salt()  # snapshot: key AND trace use this value
        key = (tuple(X.expr_key(e) for e in all_exprs),
               len(part_bound),
               tuple((o.ascending, o.nulls_first) for o in self.order_spec),
               self._item_key(items), salt)
        fn = _WINDOW_FN_CACHE.get(key)
        if fn is None:
            fn = _WINDOW_FN_CACHE.put(key, _build_window_fn(
                part_bound, tuple(self.order_spec), order_bound,
                tuple(items), all_exprs))
        lit_vals = X.literal_values(list(all_exprs))
        self.metrics.create(M.DISPATCH_COUNT, M.ESSENTIAL).add(1)
        TR.first_dispatch(self.metrics, fn)
        with G.nan_scope(salt[0]):
            outs = fn(batch.columns, batch.active, lit_vals)
        new_cols: List[AnyDeviceColumn] = list(batch.columns)
        for (arrs, validity), dt in zip(outs, out_types):
            new_cols.append(make_column(dt, tuple(arrs) + (validity,)))
        return DeviceBatch(self.schema, new_cols, batch.active,
                           batch._num_rows)

    def _decimal_aggs(self) -> int:
        """Window aggregates of this exec that read a decimal source."""
        n = 0
        for alias in self.window_exprs:
            func = alias.child.func
            if isinstance(func, E.AggregateExpression) \
                    and func.func.children and isinstance(
                        func.func.children[0].data_type, T.DecimalType):
                n += 1
        return n

    def _run_whole(self, handles: List) -> DeviceBatch:
        """One program over the concatenation of ``handles``' batches
        (released here): the host's part of it is ``windowTime``."""
        with self.metrics.timed(M.WINDOW_TIME):
            parts = [h.get() for h in handles]
            whole = parts[0] if len(parts) == 1 else concat_device(parts)
            for h in handles:
                h.close()
            return self._run_batch(whole)

    def device_partitions(self) -> List[DevicePartitionThunk]:
        goal = self.conf.batch_size_rows
        self.metrics.create(M.WINDOW_DECIMAL_AGG_COUNT, M.ESSENTIAL).add(
            self._decimal_aggs())

        def make(thunk: DevicePartitionThunk) -> DevicePartitionThunk:
            def run() -> Iterator[DeviceBatch]:
                from spark_rapids_tpu.exec.exchange import (
                    range_key_columns, realign_spilled_pids, split_by_pid)
                from spark_rapids_tpu.memory import get_device_store
                store = get_device_store(self.conf)
                part_bound = P.bind_list(self.partition_spec,
                                         self.child.output)
                part_orders = [E.SortOrder(e, ascending=True)
                               for e in self.partition_spec]
                handles, keycols, actives = [], [], []
                for b in thunk():
                    if b._num_rows == 0:
                        continue
                    if part_bound:
                        keycols.append(range_key_columns(
                            part_orders, part_bound, b))
                    actives.append(b.active)
                    handles.append(self.register_spillable(store, b))
                if not handles:
                    return
                # the one value this exec reads back: a batch's row
                # count, where its producer attached none
                total = sum(h.row_count(site="windowRows")
                            for h in handles)
                self.metrics.create(M.WINDOW_ROWS, M.ESSENTIAL).add(total)
                if total <= goal or len(handles) == 1 or not part_bound:
                    # small partition (or global window): one program
                    yield self._run_whole(handles)
                    return
                # KEY-BATCHING (GpuKeyBatchingIterator.scala:35 role):
                # chunk the stream so every partition-key GROUP lands
                # whole in exactly one chunk; chunks stay near the
                # batch-row goal and inputs are spillable handles, so
                # the partition never has to fit HBM at once
                n_chunks = max(1, (total + goal - 1) // goal)
                buckets: List[List] = [[] for _ in range(n_chunks)]
                with self.metrics.timed(M.WINDOW_TIME):
                    pids_per_batch = _key_chunk_ids(keycols, actives, goal,
                                                    n_chunks)
                    keycols.clear()
                    for h, pids, act in zip(handles, pids_per_batch,
                                            actives):
                        b, pids = realign_spilled_pids(h, pids, act)
                        parts = split_by_pid(b, pids, n_chunks)
                        h.close()
                        for pid, part in enumerate(parts):
                            if part is not None:
                                buckets[pid].append(
                                    self.register_spillable(store, part))
                for bucket in buckets:
                    if bucket:
                        yield self._run_whole(bucket)
            return run
        return [make(t) for t in device_channel(self.child)]

    def simple_string(self):
        return (f"TpuWindow {self.window_exprs} part={self.partition_spec} "
                f"order={self.order_spec}")
