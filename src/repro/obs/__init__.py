"""repro.obs — end-to-end observability for the anytime serving path.

The paper's product is a *trade-off curve* (execution time vs. accuracy
loss, §IV); this subsystem makes both axes observable from the running
system instead of only from offline benchmarks.

Layers (trace -> metrics -> probes -> decision)
===============================================

    [ trace ]    repro.obs.trace — span trees with explicit host clocks
        |        (never read inside jit); each live span is also a
        |        profiler annotation ("host." + name), on the device
        |        trace's clock, and no span blocks.  One served batch yields
        |        one tree: batcher enqueue->admit waits, the deadline grant,
        |        the aggregate-cache lookup (hit/built/merged/restored),
        |        per-shard MapReduce map dispatch / shuffle metering / reduce
        |        with shuffle bytes, stage-2 refinement and the response
        |        loop.  Propagated by contextvar
        |        (use_tracer / current_tracer): the engine and store pick
        |        the tracer up without threading a parameter; the default
        |        NULL_TRACER makes every call a no-op.  Export: JSON-lines
        |        (schema pinned by validate_trace_jsonl) + tree dump.
        v
    [ metrics ]  repro.obs.metrics — typed registry of counters, gauges,
        |        fixed-bucket histograms, and bounded reservoirs (Vitter
        |        algorithm R: flat memory under sustained load, the fix for
        |        ServeMetrics' unbounded latency lists) with labeled series
        |        (servable kind, SLO class, cache source, kernel op/path).
        |        Export: snapshot() JSON (validate_snapshot pins the
        |        schema) + Prometheus text.  ServeMetrics is reimplemented
        |        on this registry; summary() stays API-compatible.
        v
    [ probes ]   repro.obs.probes — KernelProbe hooks the dispatch layer in
        |        kernels/ops.py: host-level op calls are timed around
        |        block_until_ready (measured p50 per kernel path + pow2-
        |        bucketed dominant-shape label, the BENCH_kernels.json
        |        measured-time channel), in-trace calls are skipped (clocks
        |        inside jit record trace time, not run time).  The
        |        accuracy-proxy channel (stage-1 vs refined divergence:
        |        top-k overlap for kNN, rating-MAE delta for CF) rides
        |        Servable.accuracy_proxy into ServeMetrics — the hook
        |        ROADMAP item 3's confidence intervals will fill.
        v
    [ decision ] the closed loop over the raw signals:
                 * repro.obs.timeseries — WindowedRollup: aligned
                   fixed-width windows over observations and registry
                   counter deltas (rates, per-window streaming quantiles,
                   "last 10s p99" next to lifetime reservoirs);
                 * repro.obs.slo — declarative Objectives (deadline-met
                   rate, windowed p99, accuracy-proxy floor) with
                   multi-window burn-rate alerting + hysteresis,
                   LoadSignal (the DeadlineController's windowed load
                   input) and StragglerWatch (per-shard latency skew);
                 * repro.obs.flight — FlightRecorder: tail-sampling ring
                   keeping full span trees only for SLO-missed /
                   escalated / slowest-decile batches;
                 * repro.obs.regression — the BENCH gate: declarative
                   MetricSpecs with noise tolerances compared by
                   benchmarks/compare.py, measured wall-clock speedups as
                   a non-gating watch channel.

Everything is off by default and cheap when off: a server without a tracer
runs against NULL_TRACER, the kernel wrappers cost one ``is None`` test
when no probe is installed, and a server without ``window_s`` builds no
rollup, monitor, or recorder.
"""
from repro.obs.flight import (
    FlightEntry, FlightRecorder, validate_flight_jsonl,
)
from repro.obs.metrics import (
    Counter, Gauge, Histogram, MetricsRegistry, Reservoir,
    default_registry, percentile, validate_snapshot,
)
from repro.obs.probes import (
    KernelProbe, dominant_shape_label, install_kernel_probe,
    uninstall_kernel_probe,
)
from repro.obs.regression import (
    DEFAULT_SPECS, Finding, MetricSpec, Report, WatchEntry, compare,
)
from repro.obs.slo import (
    AccuracyObjective, Alert, DeadlineObjective, LatencyObjective,
    LoadSignal, Objective, SLOMonitor, StragglerWatch, default_objectives,
)
from repro.obs.timeseries import WindowedRollup
from repro.obs.trace import (
    NULL_TRACER, NullTracer, Span, Tracer, current_tracer, use_tracer,
    validate_trace_jsonl,
)

__all__ = [
    "AccuracyObjective",
    "Alert",
    "Counter",
    "DEFAULT_SPECS",
    "DeadlineObjective",
    "Finding",
    "FlightEntry",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "KernelProbe",
    "LatencyObjective",
    "LoadSignal",
    "MetricSpec",
    "MetricsRegistry",
    "NULL_TRACER",
    "NullTracer",
    "Objective",
    "Report",
    "Reservoir",
    "SLOMonitor",
    "Span",
    "StragglerWatch",
    "Tracer",
    "WatchEntry",
    "WindowedRollup",
    "compare",
    "current_tracer",
    "default_objectives",
    "default_registry",
    "dominant_shape_label",
    "install_kernel_probe",
    "percentile",
    "uninstall_kernel_probe",
    "use_tracer",
    "validate_flight_jsonl",
    "validate_snapshot",
    "validate_trace_jsonl",
]
