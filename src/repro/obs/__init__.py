"""repro.obs — runtime observability: metrics registry + span tracing.

`repro.obs.metrics` is the process-local registry (counters, gauges,
histograms; deterministic snapshots; disabled-path no-op). `repro.obs.trace`
records spans as ``jax.profiler`` annotations and, when a recorder is
installed, as Chrome trace-event JSON for Perfetto. `repro.obs.instrument`
(imported explicitly — it reaches into `repro.dist`) bridges the existing
accounting paths into the registry. See docs/observability.md for the
metric catalog and span naming convention.

Only ``metrics`` and ``trace`` are imported eagerly: instrumented layers
(`repro.dist.halo`, `repro.serve.graph`, …) import ``repro.obs`` at module
load, so this package must stay leaf-level (no repro.dist / jax imports).
"""
from repro.obs import metrics, trace

__all__ = ["metrics", "trace"]
