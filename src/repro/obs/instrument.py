"""Bridges from existing accounting paths into the obs registry.

Nothing here invents a number: every gauge is fed from a value an existing
layer already computes — `repro.dist.halo.HaloPlan` wire properties,
`repro.core.dataflow.exchange_cost`, `plan_cache_stats`,
`repro.graph.structure.blocked_stats` / `PlanBlockedAdjacency.stats`, the
`repro.dist.delta.DeltaPlanner.apply` report. That makes the pinned
metrics-vs-accounting equality tests (`tests/test_obs_integration.py`)
meaningful: the snapshot must reproduce the accounting bit-for-bit.

Every recorder early-returns when metrics are disabled BEFORE touching the
source object (the zero-overhead contract of `repro.obs.metrics` extends
to these helpers — they sit on the halo/serve hot paths).

`repro.dist` / `jax` are imported lazily inside functions so that
``import repro.obs`` stays dependency-light and free of import cycles
(`repro.dist.halo` itself imports `repro.obs.metrics`).
"""
from __future__ import annotations

from repro.obs import metrics

__all__ = [
    "record_exchange",
    "observe_plan_cache",
    "record_blocked",
    "record_delta_report",
    "record_relocalize_report",
    "record_compact_report",
]


def record_exchange(plan, d_feat: int, payload: str | None = None) -> None:
    """Runtime twin of the analytic ``exchange`` accounting
    (`repro.launch.steps.exchange_accounting`): fold one halo exchange's
    wire model for ``plan`` at feature width ``d_feat`` into the registry.

    Gauges (bytes are per device per exchange, from
    `repro.core.dataflow.ExchangeCost`): ``halo.rows_per_device`` per tier,
    ``halo.wire_bytes_per_exchange``, ``halo.exposed_bytes_per_exchange``,
    ``halo.payload_bits``, ``halo.overlap_fraction``, ``halo.wire_fraction``,
    ``halo.compression_vs_fp32``, ``halo.boundary_rows_max_device``.
    Counter ``halo.exchanges`` counts recorded exchanges."""
    if not metrics.enabled():
        return
    from repro.core.dataflow import exchange_cost
    from repro.core.quant import payload_bits

    bits = payload_bits(payload)
    ov = plan.overlap_fraction()
    cost = exchange_cost(plan.halo_rows_per_device, d_feat, bits, ov)
    metrics.inc("halo.exchanges")
    metrics.set_gauge("halo.rows_per_device", plan.halo_rows_per_device,
                      (("tier", "total"),))
    metrics.set_gauge("halo.rows_per_device", plan.broadcast_rows_per_device,
                      (("tier", "broadcast"),))
    if plan.is_hierarchical:
        metrics.set_gauge("halo.rows_per_device", plan.inter_pod_rows_crossing,
                          (("tier", "inter_pod_crossing"),))
        metrics.set_gauge("halo.rows_per_device", plan.intra_pod_rows_per_device,
                          (("tier", "intra_pod"),))
    metrics.set_gauge("halo.payload_bits", bits)
    metrics.set_gauge("halo.overlap_fraction", ov)
    metrics.set_gauge("halo.wire_fraction", plan.wire_fraction())
    metrics.set_gauge("halo.wire_bytes_per_exchange", cost.wire_bytes)
    metrics.set_gauge("halo.exposed_bytes_per_exchange", cost.exposed_bytes)
    metrics.set_gauge("halo.compression_vs_fp32", cost.compression)
    bnd = plan.boundary_rows_per_device()
    metrics.set_gauge("halo.boundary_rows_max_device",
                      int(bnd.max()) if bnd.size else 0)


def observe_plan_cache() -> None:
    """Mirror `repro.dist.halo.plan_cache_stats` into ``plan_cache.*``
    gauges (hits, misses, evictions, size)."""
    if not metrics.enabled():
        return
    from repro.dist.halo import plan_cache_stats

    for key, v in plan_cache_stats().items():
        metrics.set_gauge(f"plan_cache.{key}", v)


def record_blocked(stats, scope: str = "plan") -> None:
    """Fold a blocked-adjacency accounting record into ``bsr.*`` gauges.

    ``stats`` is the dict from `repro.graph.structure.blocked_stats` /
    `repro.dist.halo.plan_blocked_shape`, or a materialized
    `repro.dist.halo.PlanBlockedAdjacency` (its ``stats()`` is used; its
    ``lens.sum()`` IS ``nnz_blocks``, the executed-tile count). ``scope``
    labels the series (e.g. ``plan``, ``interior``, ``boundary``,
    ``global``)."""
    if not metrics.enabled():
        return
    if not isinstance(stats, dict):
        stats = stats.stats()
    labels = (("scope", scope),)
    metrics.set_gauge("bsr.executed_tiles", stats["nnz_blocks"], labels)
    metrics.set_gauge("bsr.max_nnzb", stats["max_nnzb"], labels)
    metrics.set_gauge("bsr.padded_tile_fraction",
                      stats["padded_tile_fraction"], labels)
    if "dense_tiles" in stats:
        metrics.set_gauge("bsr.dense_tiles", stats["dense_tiles"], labels)


def record_delta_report(report: dict) -> None:
    """Fold a `repro.dist.delta.DeltaPlanner.apply` report into ``delta.*``
    series: edit/remap counters, dirty-device gauge, the structural flag,
    repair latency (``delta.apply_ms`` histogram, if timed), and the
    executed-tile locality-drift gauge (``delta.drift_ratio``, if the
    report measured drift)."""
    if not metrics.enabled():
        return
    metrics.inc("delta.applies")
    metrics.inc("delta.inserts", float(report.get("inserts", 0)))
    metrics.inc("delta.deletes", float(report.get("deletes", 0)))
    metrics.inc("delta.senders_remapped", float(report.get("senders_remapped", 0)))
    metrics.inc("delta.blocked_patched", float(report.get("blocked_patched", 0)))
    dirty = report.get("dirty_devices") or ()
    metrics.set_gauge("delta.dirty_devices", len(dirty))
    metrics.set_gauge("delta.structural", 1.0 if report.get("structural") else 0.0)
    if "apply_ms" in report:
        metrics.observe("delta.apply_ms", float(report["apply_ms"]))
    if report.get("drift") is not None:
        d = report["drift"]
        metrics.set_gauge("delta.drift_ratio", d["drift_ratio"])
        metrics.set_gauge("delta.executed_tiles_current", d["executed_tiles_current"])
        metrics.set_gauge("delta.executed_tiles_reordered", d["executed_tiles_reordered"])


def record_relocalize_report(report: dict) -> None:
    """Fold a `repro.dist.delta.DeltaPlanner.relocalize` report into
    ``delta.relocalize*`` series: a fire counter, the re-localization
    latency histogram, and the executed-tile counts the fresh order was
    installed against (before = the drifted layout it replaced)."""
    if not metrics.enabled():
        return
    metrics.inc("delta.relocalizes")
    if "relocalize_ms" in report:
        metrics.observe("delta.relocalize_ms", float(report["relocalize_ms"]))
    metrics.set_gauge("delta.relocalize_tiles_before",
                      report.get("executed_tiles_before", 0))
    metrics.set_gauge("delta.relocalize_tiles_after",
                      report.get("executed_tiles_after", 0))


def record_compact_report(report: dict) -> None:
    """Fold a `repro.dist.delta.DeltaPlanner.compact` report into
    ``delta.compact*`` series plus the ``delta.pad_occupancy`` gauge (live
    slots / padded slots across tiers and store — 1.0 after a rebuildful
    compact, by construction)."""
    if not metrics.enabled():
        return
    metrics.inc("delta.compacts")
    metrics.inc("delta.pad_bytes_reclaimed",
                float(max(report.get("bytes_reclaimed", 0), 0)))
    occ = report.get("pad_occupancy") or {}
    metrics.set_gauge("delta.pad_occupancy", float(occ.get("frac", 1.0)))
    if "compact_ms" in report:
        metrics.observe("delta.compact_ms", float(report["compact_ms"]))
