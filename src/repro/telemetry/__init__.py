"""SLO telemetry subsystem: metrics registry, ring-buffer time series,
Prometheus/JSONL exporters, serving-stack instruments, and the
attainment-driven autoscaler.  Dependency-free by design (see
registry.py); zero overhead when ``REPRO_METRICS`` is off."""
from repro.telemetry.autoscaler import (Autoscaler, AutoscalerConfig,
                                        ScaleDecision)
from repro.telemetry.exporters import (StepTracer, parse_prometheus,
                                       prometheus_text,
                                       quantile_from_exposition,
                                       timeseries_prometheus_text)
from repro.telemetry.instruments import (ClusterTelemetry, PlanTimer,
                                         ReplicaTelemetry, slo_class_of)
from repro.telemetry.registry import (LATENCY_BUCKETS, Counter, Gauge,
                                      Histogram, MetricsRegistry,
                                      metrics_enabled)
from repro.telemetry.timeseries import RingBuffer, TimeSeriesSampler
