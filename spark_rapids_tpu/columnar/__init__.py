"""Columnar data layer: the cuDF-equivalent (SURVEY.md section 2.4 implication).

- host.py: CPU columns (numpy data + validity) used by the fallback engine,
  file readers, and the comparison baseline — the analogue of
  RapidsHostColumnVector (sql-plugin GpuColumnVector.java neighborhood).
- device.py: HBM-resident columns as JAX arrays with bucketed static
  capacities — the analogue of GpuColumnVector over cudf device memory.
- ../ops/: XLA programs for the cuDF Table operations the reference
  calls through JNI (Table.concatenate, groupBy, join gather maps, sort,
  filter, contiguousSplit...; SURVEY.md L1).
"""
