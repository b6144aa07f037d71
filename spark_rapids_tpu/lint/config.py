"""tpu-lint configuration.

Defaults below describe the real repo (scopes, allowlisted donating
sites, critical locks). A ``tpu-lint.json`` at the repo root can merge
overrides for the file-based knobs (no runtime conf keys — lint config
is deliberately outside the spark.rapids.* registry)::

    {
      "check_docs": false,
      "retry_allowlist": {"pkg/mod.py::fn": "why this site is exempt"},
      "baseline": "tpu-lint-baseline.json"
    }

Every allowlist entry maps ``<repo-relative-path>::<qualname>`` to a
written reason, mirroring the suppression grammar's
reason-is-mandatory rule.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, Tuple

CONFIG_FILENAME = "tpu-lint.json"


@dataclasses.dataclass
class LintConfig:
    # directories (relative to the lint root) scanned for *.py
    scan_roots: Tuple[str, ...] = ("spark_rapids_tpu",)

    # -- retry-coverage ----------------------------------------------------
    # files whose allocation/dispatch sites must sit inside the PR-4
    # retry protocol (docs/robustness.md wrapped-site table)
    retry_scope: Tuple[str, ...] = (
        "spark_rapids_tpu/exec/",
        "spark_rapids_tpu/parallel/",
        "spark_rapids_tpu/columnar/transfer.py",
        "spark_rapids_tpu/columnar/device.py",
    )
    retry_wrappers: Tuple[str, ...] = (
        "with_retry", "with_split_retry", "io_with_retry")
    # device allocation / dispatch entry points the rule tracks
    alloc_entrypoints: Tuple[str, ...] = (
        "device_put", "finish_upload", "start_upload", "finish_started",
        "upload_batch", "stack_batches")
    # "<rel>::<qualname>" -> reason. These are the protocol's own
    # implementation layer: the wrapped-site table wraps their CALLERS,
    # so the raw calls inside them are the single sanctioned copies.
    retry_allowlist: Dict[str, str] = dataclasses.field(
        default_factory=lambda: {
            "spark_rapids_tpu/columnar/transfer.py::finish_upload":
                "upload protocol implementation — every invoking site "
                "wraps it in with_retry (docs/robustness.md "
                "wrapped-site table)",
            "spark_rapids_tpu/columnar/transfer.py::start_upload":
                "async upload-ahead half: the ring owner handles OOM by "
                "shrinking the ring, then retries via _finish "
                "(docs/scan.md)",
            "spark_rapids_tpu/columnar/transfer.py::upload_batch":
                "composition of the wrapped halves; call sites run it "
                "under with_retry/with_split_retry",
            "spark_rapids_tpu/parallel/ici.py::mesh_exchange":
                "runs under the exchange materializer's with_retry "
                "(exec/exchange.py mesh path, docs/robustness.md)",
        })

    # -- jit discipline ----------------------------------------------------
    jit_home: str = "spark_rapids_tpu/jit_cache.py"
    # the Pallas kernel registry package: pallas_call is sanctioned
    # here (its builders only run inside JitCache-routed programs)
    kernels_home: str = "spark_rapids_tpu/kernels"

    # -- concurrency -------------------------------------------------------
    concurrency_scope: Tuple[str, ...] = (
        "spark_rapids_tpu/memory.py",
        "spark_rapids_tpu/resource.py",
        "spark_rapids_tpu/jit_cache.py",
        "spark_rapids_tpu/serve/",
    )
    # holding one of these, a blocking call is a stall for every task /
    # query in the process (DeviceStore + scheduler/semaphore locks)
    critical_locks: Tuple[str, ...] = (
        "DeviceStore._lock", "TpuSemaphore._cv",
        "AdmissionController._cv", "JitCache._lock")

    # -- cancellation discipline -------------------------------------------
    # files whose blocking waits must be cancellable: bounded timeout
    # (re-checked in a loop) or a lifecycle-aware helper — a new wait
    # site in the serving tier must not silently become uncancellable
    # (docs/serving.md "Query lifecycle")
    cancel_scope: Tuple[str, ...] = (
        "spark_rapids_tpu/serve/",
        "spark_rapids_tpu/retry.py",
        "spark_rapids_tpu/jit_cache.py",
    )

    # -- data-flow tier (tpu-lint v2, docs/linting.md family 6) -----------
    # hot-path scopes where a hidden device->host sync stalls the
    # async dispatch pipeline (the prefetched-device-scalar discipline)
    hot_scope: Tuple[str, ...] = (
        "spark_rapids_tpu/exec/",
        "spark_rapids_tpu/ops/",
        "spark_rapids_tpu/parallel/",
        "spark_rapids_tpu/columnar/",
    )
    # "<rel>::<qualname>" -> reason: the SANCTIONED drain points —
    # every one is a deliberate, documented sync the pipeline is built
    # around (prefetched scalars resolve here, sizing handshakes, the
    # host half of serde), not an accidental stall
    sync_allowlist: Dict[str, str] = dataclasses.field(
        default_factory=lambda: {
            "spark_rapids_tpu/exec/exchange.py::split_by_pid":
                "the ONE documented counts sync per input batch "
                "(contiguousSplit): partition row counts are attached "
                "so downstream consumers never re-sync",
            "spark_rapids_tpu/ops/join.py::build_key_max_multiplicity":
                "prefetched multiplicity scalar resolved lazily at the "
                "probe's sizing decision — _prefetch_host overlaps the "
                "copy with the stream-side scan",
            "spark_rapids_tpu/ops/join.py::device_join":
                "the ONE sizing sync per probe: all three scalars ride "
                "one stacked fetch, and the FK fast path skips it "
                "entirely",
            "spark_rapids_tpu/parallel/ici.py::mesh_exchange":
                "the size-exchange handshake: a tiny [n_dev, n_dev] "
                "counts fetch sizes occupancy-proportional send blocks "
                "before the collective (VERDICT r3 weak #6)",
        })
    # registration entry points whose returned handle/token must reach
    # a close/release_*/finish_* call or escape to a tracked container
    # (plus `<store>.register`, matched by receiver)
    handle_sources: Tuple[str, ...] = (
        "register_spillable", "start_upload")
    # "<rel>::<qualname>" -> reason for trace-purity exemptions
    purity_allowlist: Dict[str, str] = dataclasses.field(
        default_factory=lambda: {})

    # -- drift -------------------------------------------------------------
    metrics_rel: str = "spark_rapids_tpu/metrics.py"
    trace_rel: str = "spark_rapids_tpu/trace.py"
    # the telemetry endpoint module whose SERVER_FAMILY_HELP table the
    # prom-family rule checks emissions against
    prometheus_rel: str = "spark_rapids_tpu/telemetry/prometheus.py"
    # the query-history module whose HISTORY_FIELD_CATALOG the
    # history-field rule checks record construction against
    history_rel: str = "spark_rapids_tpu/telemetry/history.py"
    # the feedback-control module whose ACTION_CATALOG the
    # tuning-action rule checks action construction against
    tuning_rel: str = "spark_rapids_tpu/telemetry/tuning.py"
    # generated docs compared against `tools docs` regeneration
    check_docs: bool = True

    # -- engine ------------------------------------------------------------
    baseline: str = "tpu-lint-baseline.json"
    # total lint wall budget in seconds: `tools lint` exits 2 when a
    # run exceeds it, so the data-flow tier can never quietly make the
    # tier-1 gate unaffordable (per-rule timings ride --json)
    time_budget_s: float = 60.0


def load_config(root: str) -> LintConfig:
    """Defaults, merged with an optional ``tpu-lint.json`` at root."""
    cfg = LintConfig()
    path = os.path.join(root, CONFIG_FILENAME)
    if not os.path.exists(path):
        return cfg
    with open(path, "r", encoding="utf-8") as f:
        data = json.load(f)
    for key in ("check_docs", "baseline", "jit_home", "kernels_home",
                "metrics_rel", "trace_rel", "prometheus_rel",
                "history_rel", "tuning_rel", "time_budget_s"):
        if key in data:
            setattr(cfg, key, data[key])
    for key in ("scan_roots", "retry_scope", "retry_wrappers",
                "alloc_entrypoints", "concurrency_scope",
                "critical_locks", "cancel_scope", "hot_scope",
                "handle_sources"):
        if key in data:
            setattr(cfg, key, tuple(data[key]))
    for key in ("retry_allowlist", "sync_allowlist",
                "purity_allowlist"):
        if key in data:
            merged = dict(getattr(cfg, key))
            merged.update(data[key])
            setattr(cfg, key, merged)
    return cfg
