"""Unified telemetry: metrics registry, span tracing, derived gauges
(MFU / tokens/s / HBM / comm bytes), and JSONL + Prometheus exporters.

The observability layer the reference never had (its
``DistributedLogger`` was an empty stub and it had no timeline tracing,
SURVEY.md §5). Library hot paths (trainer fit loop, serving engine,
decode driver) are instrumented against the GLOBAL registry, which
starts disabled — un-observed runs pay one branch per metric site and
an inert profiler annotation per span. Turn it on
with ``telemetry.enable()`` (or by adding a ``TelemetryCallback`` /
constructing an engine with an enabled registry) and attach exporters:

    from pipegoose_tpu import telemetry

    telemetry.enable()
    jsonl = telemetry.JSONLExporter("run.jsonl",
                                    registry=telemetry.get_registry())
    ...train / serve...
    jsonl.export_snapshot()
    telemetry.PrometheusTextfileExporter("run.prom").write(
        telemetry.get_registry())

On top of the substrate sits the health/forensics layer: in-graph
health stats fused into the compiled train step (``health_stats``,
``make_hybrid_train_step(with_health=True)``), the anomaly
``FlightRecorder`` (ring buffer + structured triggers + atomic JSON
black-box dumps, feeding ``FailureDetector``/``AutoRecovery``), and
Perfetto/Chrome trace export (``ChromeTraceExporter``,
``pipeline_trace_events``, the ``pipeline.bubble_fraction`` gauge).
The MEASURED layer closes the loop: ``profile_step``/``StepProfile``
(telemetry/xprof.py) attribute a real step's device time to compute /
per-mesh-axis collectives / idle from XLA profiler traces, and
``PerfSentinel`` (telemetry/sentinel.py) watches runs against a
rolling baseline, firing ``perf_regression`` black boxes that name
the regressed component. ``MemoryLedger`` (telemetry/memledger.py)
keeps a byte-exact per-owner-class account of the serving KV pool —
conservation-checked every tick, with leak audits, exhaustion
forecasting, and Perfetto counter tracks (``memory_trace_events``).
``GoodputLedger`` (telemetry/goodput.py) is the wall-clock sibling:
every replica-second attributed to productive / badput classes
(conservation-exact), one ``Incident`` per failure episode with MTTR
and capacity-gap accounting, availability SLO counters
(``availability_slo_target``), Perfetto state bands
(``goodput_trace_events``), and the ``TrainerGoodput`` callback
mirroring the taxonomy onto training fit loops.

See docs/observability.md for the metric catalog and the MFU
methodology.
"""
from pipegoose_tpu.telemetry.callback import AuxRecorder, TelemetryCallback
from pipegoose_tpu.telemetry.chrometrace import (
    ChromeTraceExporter,
    goodput_trace_events,
    memory_trace_events,
    pipeline_trace_events,
    register_pipeline_gauges,
    router_trace_events,
    span_events_to_trace,
    trace_from_jsonl,
)
from pipegoose_tpu.telemetry.goodput import (
    GoodputLedger,
    Incident,
    TrainerGoodput,
    availability_slo_target,
)
from pipegoose_tpu.telemetry.fleet import (
    FleetRegistry,
    merge_histograms,
    merge_metrics,
)
from pipegoose_tpu.telemetry.fleettrace import (
    FleetTracer,
    TailSampler,
    fleet_trace_events,
)
from pipegoose_tpu.telemetry.opsserver import OpsServer, parse_prometheus_text
from pipegoose_tpu.telemetry.reqtrace import (
    RequestTimeline,
    RequestTracer,
    request_trace_events,
)
from pipegoose_tpu.telemetry.slo import (
    SLOMonitor,
    SLOTarget,
    default_serving_slos,
)
from pipegoose_tpu.telemetry.derived import (
    HBM_BYTES,
    PEAK_DCI_BYTES,
    PEAK_FLOPS,
    PEAK_ICI_BYTES,
    collective_bytes,
    dci_bytes_per_s_for,
    hbm_bytes_for,
    ici_bytes_per_s_for,
    compiled_step_stats,
    hbm_utilization,
    iter_collectives,
    mfu,
    peak_flops_for,
    step_flops,
    tokens_per_second,
)
from pipegoose_tpu.telemetry.doctor import (
    DoctorReport,
    MemoryReport,
    ShardingRegressionError,
    ShardingReport,
    assert_fully_sharded,
    assert_matches_intended,
    assert_no_resharding,
    diagnose,
    estimated_wire_bytes,
    set_doctor_gauges,
    wire_bytes_by_axes,
    wire_bytes_by_op,
)
from pipegoose_tpu.telemetry.exporters import (
    JSONLExporter,
    PrometheusTextfileExporter,
)
from pipegoose_tpu.telemetry.flightrec import FlightRecorder, TriggerEvent
from pipegoose_tpu.telemetry.memledger import MemoryLedger
from pipegoose_tpu.telemetry.health import health_stats, host_health
from pipegoose_tpu.telemetry.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    disable,
    enable,
    get_registry,
)
from pipegoose_tpu.telemetry.sentinel import PerfSentinel
from pipegoose_tpu.telemetry.spans import current_span_path, span
from pipegoose_tpu.telemetry.xprof import (
    StepProfile,
    profile_step,
    set_profile_gauges,
)

__all__ = [
    "ChromeTraceExporter",
    "Counter",
    "DoctorReport",
    "FleetRegistry",
    "FleetTracer",
    "FlightRecorder",
    "Gauge",
    "GoodputLedger",
    "Histogram",
    "Incident",
    "JSONLExporter",
    "MemoryLedger",
    "MemoryReport",
    "MetricsRegistry",
    "HBM_BYTES",
    "OpsServer",
    "PEAK_DCI_BYTES",
    "PEAK_FLOPS",
    "PEAK_ICI_BYTES",
    "PerfSentinel",
    "PrometheusTextfileExporter",
    "RequestTimeline",
    "RequestTracer",
    "StepProfile",
    "SLOMonitor",
    "SLOTarget",
    "TailSampler",
    "ShardingRegressionError",
    "ShardingReport",
    "AuxRecorder",
    "TelemetryCallback",
    "TrainerGoodput",
    "TriggerEvent",
    "assert_fully_sharded",
    "assert_matches_intended",
    "assert_no_resharding",
    "availability_slo_target",
    "collective_bytes",
    "compiled_step_stats",
    "current_span_path",
    "default_serving_slos",
    "diagnose",
    "disable",
    "enable",
    "fleet_trace_events",
    "get_registry",
    "goodput_trace_events",
    "hbm_utilization",
    "health_stats",
    "host_health",
    "iter_collectives",
    "memory_trace_events",
    "merge_histograms",
    "merge_metrics",
    "mfu",
    "parse_prometheus_text",
    "router_trace_events",
    "peak_flops_for",
    "pipeline_trace_events",
    "profile_step",
    "register_pipeline_gauges",
    "request_trace_events",
    "set_doctor_gauges",
    "set_profile_gauges",
    "estimated_wire_bytes",
    "wire_bytes_by_axes",
    "wire_bytes_by_op",
    "dci_bytes_per_s_for",
    "hbm_bytes_for",
    "ici_bytes_per_s_for",
    "span",
    "span_events_to_trace",
    "step_flops",
    "tokens_per_second",
    "trace_from_jsonl",
]
