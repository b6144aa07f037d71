"""Live telemetry for the serving tier (docs/observability.md "Live
telemetry").

PRs 5/6 built *post-hoc* observability: per-query trace files and
profile artifacts you opt into before running. A long-lived multi-tenant
QueryServer needs the opposite — telemetry that is on by default, cheap
enough to never turn off, and able to reconstruct what just happened
after the fact. Four coordinated pieces:

- **flight recorder** (ring.py): ``spark.rapids.sql.trace.mode=ring``
  keeps the last N spans/instants/counter samples per thread in a
  fixed-size lock-free ring behind the existing Tracer; ``dump_ring``
  writes the standard Chrome-trace JSON so ``tools trace`` /
  ``tools hotspots`` work unchanged on dumps;
- **trigger engine** (triggers.py): declarative slow-query / retry /
  HBM-watermark / queue-saturation triggers that emit rate-limited
  *slow-query bundles* (ring dump + profile artifact + server stats +
  the triggering condition) into ``spark.rapids.sql.telemetry.dir``;
- **metrics endpoint** (prometheus.py): the QueryServer's ``metrics``
  protocol verb and the ``tools serve --metrics-port`` HTTP twin export
  the process metric registries + server stats in Prometheus text
  format, fed by a registry-delta aggregator whose counters stay
  monotone across plan lifetimes; ``tools top`` renders a live
  per-tenant terminal view over the same stats;
- **query history** (history.py): the persistent, bounded JSONL store
  of one record per finished query — the cross-run memory behind
  server warm-start (watchdog p99 + quarantine streaks survive
  restarts), per-tenant SLO burn tracking (``srt_slo_*`` families +
  the ``sloBurn`` trigger), ``tools history`` trends, and the
  ``tools doctor`` auto-diagnosis (doctor.py) that names WHY a query
  was slow against its signature's historical baseline.
"""

from spark_rapids_tpu.telemetry.ring import RingTrace, dump_ring  # noqa: F401
from spark_rapids_tpu.telemetry import triggers  # noqa: F401
