"""Fused Parquet decode kernel (Pallas).

PR 8's encoded scan path decodes a batch through a *chain* of logical
stages inside one XLA program — RLE/bit-unpack of the hybrid streams,
dictionary gather, definition-level validity expansion, the byte-array
offsets-from-lengths segmented cumsum plus char gather, DELTA
reconstruction, BSS reinterleave. XLA fuses what it can, but each
stage still materializes its intermediates in HBM between fusion
islands. This module collapses every device-decoded column of a batch
into ONE Pallas kernel per (layout, capacity bucket): all
intermediates live in the kernel's on-chip value space, and the only
HBM traffic is the raw page words in and the final columns out.

Bit-identity is structural, not tested-into: the kernel body executes
``columnar.transfer._encoded_decode_body`` — the *same function* the
stock XLA chain jits — over the device-decoded subset of the layout
(the murmur3 kernel's shared-arithmetic model). Host-decoded columns
pass through OUTSIDE the kernel untouched, exactly as the chain
passes them through. The chain remains the oracle and the per-call
fallback: any lowering/compile/dispatch failure poisons the (layout,
cap) key and the batch re-decodes on the chain
(``kernelFallbacks.decodeFused``).

The one tunable, ``charChunk``, bounds the string char-gather's live
index matrix by evaluating the gather over row chunks
(``ops/rle.py::gather_chars_chunked``) — row-independent, so chunking
cannot change a byte. The autotuner (``kernels/autotune.py``) sweeps
it per capacity bucket.
"""

from __future__ import annotations

from typing import Callable, List, Tuple

import jax
import jax.numpy as jnp


def _dev_extras_count(ent: Tuple) -> int:
    """How many extras arrays one ``("dev", ...)`` layout entry
    consumes (must mirror ``_encoded_decode_body``'s walk)."""
    (_tag, _kind, _np_dt, _elem_bytes, _char_cap, _npg, ndl, nvr, ndr,
     dict_shapes, _has_plain, has_delta, _has_bss, has_slen) = ent
    return (3 + (1 if has_delta else 0) + (5 if ndl else 0)
            + (5 if nvr else 0) + (5 if ndr else 0)
            + (1 if has_slen else 0) + len(dict_shapes))


def _dev_out_count(ent: Tuple) -> int:
    return 3 if ent[1] in ("str", "dec128") else 2


def split_layout(layout: Tuple):
    """Partition a decode layout into the device-decoded entries the
    kernel fuses and the host passthrough segments spliced around it.
    Returns ``(steps, dev_layout, dev_slices)``: ``steps`` is the
    output-assembly plan (``("host", extras_lo, extras_hi)`` or
    ``("dev", n_outputs)`` in layout order), ``dev_layout`` the
    dev-only layout tuple the kernel body runs over, ``dev_slices``
    the extras index ranges it consumes."""
    steps: List[Tuple] = []
    dev_layout: List[Tuple] = []
    dev_slices: List[Tuple[int, int]] = []
    cur = 0
    for ent in layout:
        if ent[0] == "host":
            steps.append(("host", cur, cur + ent[1]))
            cur += ent[1]
            continue
        k = _dev_extras_count(ent)
        dev_slices.append((cur, cur + k))
        cur += k
        dev_layout.append(ent)
        steps.append(("dev", _dev_out_count(ent)))
    return steps, tuple(dev_layout), dev_slices


def chain_programs(layout: Tuple) -> int:
    """Static logical decode-stage count of the stock XLA chain for
    one layout (what the fused kernel replaces with 1): the
    ``deviceDecodePrograms`` metric bills this per chain-decoded
    batch, so the bench's programs-per-batch attribution is exact."""
    from spark_rapids_tpu.io.device_decode import dev_entry_stages
    total = 0
    for ent in layout:
        if ent[0] != "dev":
            continue
        (_tag, _kind, _np_dt, _eb, _cc, _npg, ndl, _nvr, _ndr,
         dict_shapes, _has_plain, has_delta, has_bss, has_slen) = ent
        total += dev_entry_stages(ndl, len(dict_shapes), has_slen,
                                  has_delta, has_bss)
    return max(1, total)


def build_fused_decode(layout: Tuple, cap: int, *, interpret: bool,
                       char_chunk: int = 0) -> Callable:
    """One jitted fn with the chain program's exact signature —
    ``fn(words, n_dev, *extras) -> (active, outs)`` — whose
    device-decoded columns all come out of ONE ``pallas_call``. Built
    only inside ``_DECODE_CACHE`` builders (compile discipline)."""
    from jax.experimental import pallas as pl
    from spark_rapids_tpu.columnar.transfer import (
        _build_encoded_decode, _encoded_decode_body)
    steps, dev_layout, dev_slices = split_layout(layout)
    if not dev_layout:
        # nothing to fuse (all columns host-decoded): the chain IS the
        # program; callers still count the dispatch as fused=1 program
        return _build_encoded_decode(layout, cap)

    def body(words_v, n_v, *ins):
        return _encoded_decode_body(dev_layout, cap, words_v, n_v, ins,
                                    char_chunk=char_chunk)

    def fn(words, n_arr, *extras):
        dev_extras = []
        for lo, hi in dev_slices:
            dev_extras.extend(extras[lo:hi])
        n_in = 2 + len(dev_extras)
        n_vec = jnp.reshape(n_arr, (1,)).astype(jnp.int64)

        def flat_body(w, nv, *ins):
            active, outs = body(w, nv[0], *ins)
            return (active,) + tuple(outs)

        out_avals = jax.eval_shape(flat_body, words, n_vec, *dev_extras)

        def kern(*refs):
            ins = [r[...] for r in refs[:n_in]]
            res = flat_body(ins[0], ins[1], *ins[2:])
            for r, o in zip(refs[n_in:], res):
                r[...] = o

        call = pl.pallas_call(
            kern,
            out_shape=tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                            for a in out_avals),
            interpret=interpret)
        res = call(words, n_vec, *dev_extras)
        active = res[0]
        dev_outs = list(res[1:])
        outs: List[jax.Array] = []
        di = 0
        for step in steps:
            if step[0] == "host":
                outs.extend(extras[step[1]:step[2]])
            else:
                outs.extend(dev_outs[di:di + step[1]])
                di += step[1]
        return active, tuple(outs)

    from spark_rapids_tpu.jit_cache import named_jit
    return named_jit("srt_decode_fused", fn)
