"""repro.obs -- low-overhead observability for both engines and the fleet.

Per-task lifecycle events (arrived -> queued -> leased/claimed -> dispatched
-> per-input resolve -> exec start/end -> done/failed/requeued, plus pool and
pump transitions) recorded into a bounded ring buffer (`Recorder`), exported
as Chrome-trace JSON (`export.chrome_trace`) and diffed task-by-task between
a measured run and its simulator replay (`diff.diff_outcomes`).

Recording is off by default and free when off: every hot-path hook is a
``if recorder is not None`` guard.  See DESIGN.md section 10.  Timed phases
inside an attempt are spans (`repro.obs.spans`): one emit point that feeds the
recorder and, in the same call, the JAX profiler.

The live telemetry plane (DESIGN.md section 13) rides alongside: a
lock-cheap `MetricsRegistry` (counters / gauges / fixed-bucket histograms
with mergeable snapshots), the per-run `Telemetry` bundle with its JSONL
time series, the `ClusterView` merged from fleet ``{"t": "stats"}`` frames,
a `HealthMonitor` evaluating window rules over the stream, and the
`TelemetryServer` endpoint that tools/monitor.py attaches to.  Same
free-when-off contract: ``metrics is None`` unless the spec asks.

The package loads its modules on first use of a name (PEP 562), so a hot
path that imports `repro.obs.spans` loads ``spans`` and ``events`` and
nothing else: the engines import this package, and `diff` imports the
workloads layer, which imports the engines.
"""
import importlib

_WHERE = {
    "events": ("EVENT_SCHEMA_VERSION", "EVENT_KINDS", "LIFECYCLE_KINDS",
               "OUTCOME_FIELDS", "exec_index", "lifecycle_fingerprints",
               "outcome_record"),
    "recorder": ("Recorder", "load_events"),
    "export": ("chrome_trace",),
    "diff": ("diff_outcomes", "format_divergence", "sim_replay_outcomes",
             "sim_twin_spec"),
    "metrics": ("METRICS_SCHEMA_VERSION", "ClusterView", "MetricsRegistry",
                "Telemetry", "TelemetryServer", "fetch_telemetry",
                "merge_snapshots", "quantile", "read_metrics"),
    "health": ("HealthMonitor",),
}
_MODULE_OF = {name: mod for mod, names in _WHERE.items() for name in names}


def __getattr__(name: str):
    mod = _MODULE_OF.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    value = getattr(importlib.import_module(f".{mod}", __name__), name)
    globals()[name] = value
    return value


__all__ = sorted(_MODULE_OF)
