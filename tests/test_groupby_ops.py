"""ops/groupby directly: the sort+segment composition every aggregate
program is built from (exec/agg.py ``_build_fn``), against a numpy
group-by over the same arrays — keys with nulls, inactive rows,
SUM/COUNT/MIN/MAX over longs and a decimal SUM that widens to int128.
Both segmentations: the exact multi-word sort (final/complete) and the
one-pass hash sort (partial/merge), whose fragments the test merges the
way the merge stage does."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.device import (DeviceColumn,
                                              flatten_columns,
                                              rebuild_columns)
from spark_rapids_tpu.exec.agg import apply_prim_device
from spark_rapids_tpu.ops import groupby as G
from spark_rapids_tpu.sql import expressions as E
from spark_rapids_tpu.sql import types as T

DEC = T.DecimalType(15, 2)
_PRIMS = [(E.PRIM_SUM, T.LongT), (E.PRIM_COUNT, T.LongT),
          (E.PRIM_MIN, T.LongT), (E.PRIM_MAX, T.LongT)]
_SUM_KINDS = {E.PRIM_COUNT: "count", E.PRIM_SUM: "sum"}


def _gb_direct(cap, keys, kvalid, vals, vvalid, active, hashed,
               dec_vals=None):
    """One int key, four aggregates of one long value (+ a decimal
    sum), segmented and reduced inside one jit; returns numpy views:
    the flat result arrays (key (data, validity), each entry (data,
    validity), the decimal (hi, lo, validity)) and the output mask."""
    use_dec = dec_vals is not None
    out_dec = T.DecimalType(25, 2)

    @jax.jit
    def run(kd, kv, vd, vv, act, dd):
        key_cols = [DeviceColumn(T.IntegerT, kd, kv)]
        vals_c = [DeviceColumn(T.LongT, vd, vv)]
        if use_dec:
            vals_c.append(DeviceColumn(DEC, dd, vv))
        flat, spec = flatten_columns(key_cols + vals_c)
        if hashed:
            seg = G.build_segments_hashed(
                key_cols, act, payload=flat,
                sorted_keys_from_payload=lambda ps:
                    rebuild_columns(spec, ps)[:1])
        else:
            seg = G.build_segments(key_cols, act, payload=flat)
        sorted_cols = rebuild_columns(spec, seg.payload)
        key_s, v_s = sorted_cols[0], sorted_cols[1]
        entries = [(v_s, _SUM_KINDS[p], dt) for p, dt in _PRIMS
                   if p in _SUM_KINDS]
        if use_dec:
            entries.append((sorted_cols[2], "sum", out_dec))
        sums = G.seg_sums_batched(seg, entries)
        bufs = [sums[0], sums[1],
                apply_prim_device(E.PRIM_MIN, seg, v_s, T.LongT),
                apply_prim_device(E.PRIM_MAX, seg, v_s, T.LongT)]
        bufs += sums[2:]
        out = [a for c in [key_s] + bufs for a in c.arrays()]
        return out, seg.out_active

    flat, used = run(
        jnp.asarray(keys, jnp.int32), jnp.asarray(kvalid),
        jnp.asarray(vals, jnp.int64), jnp.asarray(vvalid),
        jnp.asarray(active),
        jnp.asarray(dec_vals if use_dec else np.zeros(cap), jnp.int64))
    return [np.asarray(a) for a in flat], np.asarray(used)


def _gb_numpy_oracle(keys, kvalid, vals, vvalid, active, dec_vals=None):
    acc = {}
    for i in range(len(keys)):
        if not active[i]:
            continue
        k = (bool(kvalid[i]), int(keys[i]) if kvalid[i] else 0)
        e = acc.setdefault(k, {"sum": 0, "cnt": 0, "mn": None,
                               "mx": None, "dsum": 0, "dcnt": 0})
        if vvalid[i]:
            v = int(vals[i])
            e["sum"] += v
            e["cnt"] += 1
            e["mn"] = v if e["mn"] is None else min(e["mn"], v)
            e["mx"] = v if e["mx"] is None else max(e["mx"], v)
            if dec_vals is not None:
                e["dsum"] += int(dec_vals[i])
                e["dcnt"] += 1
    return acc


def _i128(hi, lo):
    return (int(hi) << 64) | (int(lo) & ((1 << 64) - 1))


def _merge(a, b, fn):
    return b if a is None else a if b is None else fn(a, b)


def _groups(flat, used, hashed, with_dec):
    """{key: aggregates} from the output rows. The exact sort gives one
    row a group; the hash sort may give a group several (a collision
    interleaves two keys' rows), which merge as the merge stage would."""
    kd, kv = flat[0], flat[1]
    got = {}
    for t in np.nonzero(used)[0]:
        k = (bool(kv[t]), int(kd[t]) if kv[t] else 0)
        row = {"sum": int(flat[2][t]) if flat[3][t] else None,
               "cnt": int(flat[4][t]),
               "mn": int(flat[6][t]) if flat[7][t] else None,
               "mx": int(flat[8][t]) if flat[9][t] else None}
        if with_dec:
            row["dsum"] = _i128(flat[10][t], flat[11][t]) \
                if flat[12][t] else None
        if k not in got:
            got[k] = row
            continue
        assert hashed, f"the exact sort emitted group {k} twice"
        g = got[k]
        g["cnt"] += row["cnt"]
        g["sum"] = _merge(g["sum"], row["sum"], lambda a, b: a + b)
        g["mn"] = _merge(g["mn"], row["mn"], min)
        g["mx"] = _merge(g["mx"], row["mx"], max)
        if with_dec:
            g["dsum"] = _merge(g["dsum"], row["dsum"],
                               lambda a, b: a + b)
    return got


@pytest.mark.parametrize("hashed", [False, True], ids=["exact", "hashed"])
@pytest.mark.parametrize("cap,ngroups,null_prob",
                         [(64, 5, 0.0), (256, 17, 0.3), (96, 9, 0.15)],
                         ids=["tiny", "nulls", "oddcap"])
def test_groupby_vs_numpy_oracle(cap, ngroups, null_prob, hashed):
    rng = np.random.default_rng(cap + ngroups)
    kvalid = rng.random(cap) >= null_prob
    # engine invariant: invalid slots hold zeros (mask_col et al.)
    keys = np.where(kvalid, rng.integers(-3, ngroups, cap), 0)
    vals = rng.integers(-10**6, 10**6, cap)
    vvalid = rng.random(cap) >= null_prob
    active = rng.random(cap) >= 0.1
    dec = rng.integers(-10**9, 10**9, cap)
    flat, used = _gb_direct(cap, keys, kvalid, vals, vvalid, active,
                            hashed, dec_vals=dec)
    exp = _gb_numpy_oracle(keys, kvalid, vals, vvalid, active,
                           dec_vals=dec)
    want = {k: {"sum": e["sum"] if e["cnt"] else None, "cnt": e["cnt"],
                "mn": e["mn"], "mx": e["mx"],
                "dsum": e["dsum"] if e["dcnt"] else None}
            for k, e in exp.items()}
    assert _groups(flat, used, hashed, with_dec=True) == want


@pytest.mark.parametrize("hashed", [False, True], ids=["exact", "hashed"])
def test_groupby_empty_and_single_row(hashed):
    cap = 64
    zeros = np.zeros(cap, dtype=np.int64)
    none_active = np.zeros(cap, dtype=bool)
    flat, used = _gb_direct(cap, zeros, zeros > -1, zeros, zeros > -1,
                            none_active, hashed)
    assert not used.any()
    one = none_active.copy()
    one[17] = True
    vals = zeros.copy()
    vals[17] = -42
    flat, used = _gb_direct(cap, zeros, zeros > -1, vals, zeros > -1,
                            one, hashed)
    assert used.sum() == 1
    t = int(np.argmax(used))
    assert int(flat[2][t]) == -42 and int(flat[4][t]) == 1


@pytest.mark.slow
def test_groupby_property_sweep():
    """Wider sweep: null pattern x capacity bucket x group cardinality
    against the numpy oracle (slow: dozens of compiles)."""
    for cap in (64, 96, 160, 512):
        for ngroups in (1, 3, 50):
            for null_prob in (0.0, 0.5, 0.95):
                rng = np.random.default_rng(cap * ngroups + 1)
                kvalid = rng.random(cap) >= null_prob
                keys = np.where(kvalid,
                                rng.integers(-2, ngroups, cap), 0)
                vals = rng.integers(-10**9, 10**9, cap)
                vvalid = rng.random(cap) >= null_prob
                active = rng.random(cap) >= 0.2
                flat, used = _gb_direct(cap, keys, kvalid, vals, vvalid,
                                        active, False)
                exp = _gb_numpy_oracle(keys, kvalid, vals, vvalid,
                                       active)
                assert used.sum() == len(exp), (cap, ngroups, null_prob)
