"""TpuGenerateExec: device explode/posexplode (+outer)
(GpuGenerateExec.scala:440 twin over the segmented array columns).

The kernel is ONE jitted program per (shape-set, flags): per-row
effective counts (array length, or max(len, 1) under outer) prefix-sum
into output offsets; every output position finds its parent row with a
searchsorted over the cumulative counts (no scatters), gathers the
parent columns, and reads its element from the shared element pool via
start + ordinal. Output capacity is static: the element pool's capacity
(+ the row capacity under outer).
"""

from __future__ import annotations

from typing import Iterator, List

import jax
import jax.numpy as jnp

from spark_rapids_tpu import metrics as M
from spark_rapids_tpu.columnar.device import (DeviceArrayColumn,
                                              DeviceBatch, DeviceColumn,
                                              flatten_batch,
                                              rebuild_columns,
                                              take_columns)
from spark_rapids_tpu.conf import TpuConf
from spark_rapids_tpu.exec.base import (DevicePartitionThunk, TpuExec,
                                        device_channel)
from spark_rapids_tpu.ops import exprs as X
from spark_rapids_tpu.sql import expressions as E
from spark_rapids_tpu.sql import physical as P
from spark_rapids_tpu.sql import types as T

# bounded LRU like every other structural jit cache (jit_cache.py);
# the raw module dict it replaces grew one pinned XLA executable per
# distinct (shape-set, flags) forever
from spark_rapids_tpu.jit_cache import (JitCache, mirror_to_metrics,
                                        named_jit)

_GEN_CACHE = JitCache("generate")


def is_device_generate(gen: E.Expression, conf: TpuConf):
    """Tagging helper (None = supported)."""
    if not isinstance(gen, E.Explode):
        return (f"generator {type(gen).__name__} has no device "
                "implementation")
    child = gen.children[0]
    dt = child.data_type
    if not isinstance(dt, T.ArrayType):
        return "explode input must be an array"
    if isinstance(dt.element_type, (T.ArrayType, T.MapType, T.StructType)):
        return "nested-of-nested explode runs on CPU"
    from spark_rapids_tpu import typesig as TS
    r = TS.common_tpu.support(dt.element_type)
    if r:
        return f"array element: {r}"
    if not isinstance(child, E.AttributeReference):
        return "explode over computed arrays runs on CPU"
    return None


class TpuGenerateExec(TpuExec):
    def __init__(self, generator: E.Explode,
                 gen_output: List[E.AttributeReference], child: TpuExec,
                 conf: TpuConf):
        super().__init__(conf)
        self.children = [child]
        self.generator = generator
        self.gen_output = gen_output

    @property
    def child(self) -> TpuExec:
        return self.children[0]

    @property
    def output(self):
        return list(self.child.output) + list(self.gen_output)

    def device_partitions(self) -> List[DevicePartitionThunk]:
        gen = self.generator
        bound = E.bind_references(gen.children[0], self.child.output)
        assert isinstance(bound, E.BoundReference)
        ordinal = bound.ordinal
        position, outer = gen.position, gen.outer
        metrics = self.metrics

        def explode_one(b: DeviceBatch) -> DeviceBatch:
            flat, spec = flatten_batch(b)
            shapes = tuple((a.shape, str(a.dtype)) for a in flat)
            key = (shapes, tuple(repr(dt) for dt, _ in spec), ordinal,
                   position, outer)
            fn, was_miss = _GEN_CACHE.get_or_build(
                key, lambda: named_jit("srt_generate", self._build_fn(
                    spec, ordinal, position, outer)))
            mirror_to_metrics(_GEN_CACHE, metrics, was_miss)
            active_out, outs = fn(b.active, *flat)
            from spark_rapids_tpu.columnar.device import is_string_like
            out_spec = list(spec)
            if position:
                out_spec.append((T.IntegerT, 2))
            out_spec.append((gen.data_type,
                             3 if is_string_like(gen.data_type) else 2))
            cols = rebuild_columns(out_spec, outs)
            return DeviceBatch(self.schema, cols, active_out, None)

        def make(thunk: DevicePartitionThunk) -> DevicePartitionThunk:
            def run() -> Iterator[DeviceBatch]:
                for b in thunk():
                    with metrics.timed(M.OP_TIME):
                        out = explode_one(b)
                    metrics.create(M.NUM_OUTPUT_BATCHES,
                                   M.ESSENTIAL).add(1)
                    yield out
            return run
        return [make(t) for t in device_channel(self.child)]

    @staticmethod
    def _build_fn(spec, ordinal: int, position: bool, outer: bool):
        def fn(active, *flat):
            cols = rebuild_columns(spec, flat)
            arr = cols[ordinal]
            assert isinstance(arr, DeviceArrayColumn)
            cap = active.shape[0]
            pool_cap = arr.child.capacity
            real_len = jnp.where(arr.validity & active, arr.lengths, 0)
            eff = jnp.maximum(real_len, 1) if outer else real_len
            eff = jnp.where(active, eff, 0)
            cum = jnp.cumsum(eff)
            total = cum[-1]
            out_cap = pool_cap + (cap if outer else 0)
            pos_out = jnp.arange(out_cap, dtype=jnp.int32)
            parent = jnp.searchsorted(cum, pos_out, side="right"
                                      ).astype(jnp.int32)
            parent = jnp.clip(parent, 0, cap - 1)
            base = cum[parent] - eff[parent]
            elem = (pos_out - base).astype(jnp.int32)
            active_out = pos_out < total
            is_real = active_out & (elem < real_len[parent])
            par_cols = take_columns(cols, parent, valid_at=active_out)
            out_cols = list(par_cols)
            if position:
                pdata = jnp.where(is_real, elem, 0)
                out_cols.append(DeviceColumn(T.IntegerT, pdata, is_real))
            src = jnp.clip(arr.starts[parent] + elem, 0, pool_cap - 1)
            elem_col = take_columns([arr.child], src, valid_at=is_real)[0]
            out_cols.append(elem_col)
            flat_out = []
            for c in out_cols:
                flat_out.extend(c.arrays())
            return active_out, tuple(flat_out)
        return fn

    def simple_string(self):
        return f"TpuGenerate {self.generator!r}"
