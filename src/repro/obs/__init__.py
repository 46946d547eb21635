"""``repro.obs`` — observability for the plan → sim → serve stack
(DESIGN.md §12).

The simulator, serving engine, and DSE sweep all reduce to aggregate
scalars; this package is the instrumentation that shows *where the
cycles go* and what serving actually delivers:

``timeline.py``     Chrome/Perfetto ``trace_event`` export: resource
                    tracks for any ``sim.Trace``, serve-step and
                    per-request lifecycle tracks for ``ServeSimResult``,
                    a ``kernels`` track for ``KernelRecorder`` records,
                    and the ``validate_timeline`` CI gate.
``metrics.py``      Counter/gauge/histogram registry with exact-quantile
                    summaries + ``RequestSpan`` lifecycle records
                    (queue→admit→first-token→finish) behind the
                    TTFT/TPOT/queue-delay p50/p95/p99 in
                    ``Engine.stats()`` and ``ServeSimResult.metrics``,
                    and the engine==sim ``assert_serve_parity`` check.
``attribution.py``  Per-resource / per-op-class stall and busy
                    breakdowns: critical-resource share, exposed vs
                    overlapped rewrite cycles, the §I 57% rewrite-stall
                    fraction for any trace, and the ``bottleneck`` field
                    on DSE ``SweepRow``s.
``critpath.py``     Causal critical-path analysis over the stamped event
                    DAG: the chain that bounds the makespan, per-resource
                    / per-op-class *critical* shares, exposed-rewrite
                    on-path cycles (§I, causally), slack histograms.
``spans.py``        Named host spans on the profiler's clock
                    (``TraceAnnotation``) and the counted host read
                    ``host_read`` that ``serve.Engine`` and its K/V pool
                    open around every layer boundary of the serving loop.
``whatif.py``       What-if projection: rescale event durations and
                    replay the DAG schedule — "R k× faster", "link
                    bandwidth k×", "ping-pong toggled" — plus the
                    per-resource ``headroom`` stamped on ``SweepRow``s.

``python -m repro.obs`` renders a text utilization/stall report from a
saved plan artifact (or an on-the-fly model simulation) and can dump the
Perfetto timeline alongside; ``benchmarks/run.py --perfetto DIR`` dumps
timelines from every sim/serve/dse section it runs.
"""
from repro.obs.attribution import (INTERCONNECT, AttributionReport,
                                   OpClassBreakdown, attribute,
                                   base_resource, bottleneck_of,
                                   format_report, op_class,
                                   rewrite_stall_by_op)
from repro.obs.critpath import (CritPathReport, compute_slack,
                                critical_path, format_critpath)
from repro.obs.metrics import (METRICS_SCHEMA_VERSION, Counter, Gauge,
                               Histogram, MetricsRegistry, RequestSpan,
                               SPAN_METRICS, assert_serve_parity,
                               percentile, spans_from_steps, summarize,
                               summarize_spans)
from repro.obs.timeline import (KIND_COLORS, RESOURCE_ORDER,
                                TIMELINE_SCHEMA_VERSION, kernel_events,
                                load_timeline, timeline_from_records,
                                timeline_from_serve, timeline_from_sharded,
                                timeline_from_sim, timeline_from_trace,
                                trace_events, validate_timeline,
                                write_timeline)
from repro.obs.whatif import (WhatIfProjection, format_whatif, headroom,
                              project, run_whatif, whatif_link_bandwidth,
                              whatif_ping_pong, whatif_resource)

__all__ = [
    "INTERCONNECT", "AttributionReport", "OpClassBreakdown", "attribute",
    "base_resource", "bottleneck_of",
    "format_report", "op_class", "rewrite_stall_by_op",
    "CritPathReport", "compute_slack", "critical_path", "format_critpath",
    "WhatIfProjection", "format_whatif", "headroom", "project",
    "run_whatif", "whatif_link_bandwidth", "whatif_ping_pong",
    "whatif_resource",
    "METRICS_SCHEMA_VERSION", "Counter", "Gauge", "Histogram",
    "MetricsRegistry", "RequestSpan", "SPAN_METRICS", "assert_serve_parity",
    "percentile", "spans_from_steps", "summarize", "summarize_spans",
    "KIND_COLORS", "RESOURCE_ORDER", "TIMELINE_SCHEMA_VERSION",
    "kernel_events", "load_timeline", "timeline_from_records",
    "timeline_from_serve", "timeline_from_sharded", "timeline_from_sim",
    "timeline_from_trace", "trace_events", "validate_timeline",
    "write_timeline",
]
