"""Metrics subsystem: typed metric store + Prometheus text exposition.

Mirrors the reference's metrics manager (pkg/gofr/metrics/register.go:14-24
defines the Manager contract: new_counter/new_updown_counter/new_histogram/
new_gauge + typed setters that error on absent or duplicate names, the typed
store lives in pkg/gofr/metrics/store.go). Instead of delegating to an OTel
meter + Prometheus exporter (pkg/gofr/metrics/exporters/exporter.go:14-29) we
implement the registry and the text exposition directly — no external
dependency, and TPU runtime metrics (step time, HBM occupancy) flow through
the same store.
"""

from __future__ import annotations

import math
import threading
import time
from typing import Iterable, Mapping

__all__ = [
    "Manager",
    "MetricsError",
    "DuplicateMetricError",
    "MetricNotFoundError",
    "DEFAULT_BUCKETS",
    "SamplerThread",
]

DEFAULT_BUCKETS = (
    0.001, 0.003, 0.005, 0.01, 0.02, 0.03, 0.05, 0.1, 0.2, 0.3, 0.5,
    0.75, 1, 2, 3, 5, 10, 30,
)


class MetricsError(Exception):
    pass


class DuplicateMetricError(MetricsError):
    def __init__(self, name: str) -> None:
        super().__init__(f"metric {name!r} already registered")


class MetricNotFoundError(MetricsError):
    def __init__(self, name: str) -> None:
        super().__init__(f"metric {name!r} is not registered")


def _label_key(labels: Mapping[str, str]) -> tuple[tuple[str, str], ...]:
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


def _fmt_value(v: float) -> str:
    if v == math.inf:
        return "+Inf"
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


def _fmt_labels(key: tuple[tuple[str, str], ...]) -> str:
    if not key:
        return ""
    inner = ",".join(
        '%s="%s"' % (k, v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n"))
        for k, v in key
    )
    return "{" + inner + "}"


class _Metric:
    kind = "untyped"

    def __init__(self, name: str, description: str) -> None:
        self.name = name
        self.description = description
        self._lock = threading.Lock()

    def expose(self, out: list[str]) -> None:  # pragma: no cover - abstract
        raise NotImplementedError


class _Counter(_Metric):
    kind = "counter"

    def __init__(self, name: str, description: str) -> None:
        super().__init__(name, description)
        self._values: dict[tuple, float] = {}

    def add(self, delta: float, labels: Mapping[str, str]) -> None:
        key = _label_key(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + delta

    def expose(self, out: list[str]) -> None:
        out.append(f"# HELP {self.name} {self.description}")
        out.append(f"# TYPE {self.name} counter")
        with self._lock:
            items = list(self._values.items()) or [((), 0.0)]
        for key, val in items:
            out.append(f"{self.name}{_fmt_labels(key)} {_fmt_value(val)}")


class _UpDownCounter(_Counter):
    kind = "updown"

    def expose(self, out: list[str]) -> None:
        out.append(f"# HELP {self.name} {self.description}")
        out.append(f"# TYPE {self.name} gauge")
        with self._lock:
            items = list(self._values.items()) or [((), 0.0)]
        for key, val in items:
            out.append(f"{self.name}{_fmt_labels(key)} {_fmt_value(val)}")


class _Gauge(_Metric):
    kind = "gauge"

    def __init__(self, name: str, description: str) -> None:
        super().__init__(name, description)
        self._values: dict[tuple, float] = {}

    def set(self, value: float, labels: Mapping[str, str]) -> None:
        with self._lock:
            self._values[_label_key(labels)] = float(value)

    def expose(self, out: list[str]) -> None:
        out.append(f"# HELP {self.name} {self.description}")
        out.append(f"# TYPE {self.name} gauge")
        with self._lock:
            items = list(self._values.items()) or [((), 0.0)]
        for key, val in items:
            out.append(f"{self.name}{_fmt_labels(key)} {_fmt_value(val)}")


class _Histogram(_Metric):
    kind = "histogram"

    def __init__(self, name: str, description: str, buckets: Iterable[float]) -> None:
        super().__init__(name, description)
        self.buckets = tuple(sorted(set(float(b) for b in buckets)))
        self._series: dict[tuple, list] = {}  # key -> [bucket_counts, sum, count]

    def record(self, value: float, labels: Mapping[str, str]) -> None:
        key = _label_key(labels)
        with self._lock:
            series = self._series.get(key)
            if series is None:
                series = [[0] * len(self.buckets), 0.0, 0]
                self._series[key] = series
            counts, _, _ = series
            for i, b in enumerate(self.buckets):
                if value <= b:
                    counts[i] += 1
            series[1] += value
            series[2] += 1

    def percentile(self, q: float, labels: Mapping[str, str] | None = None) -> float:
        """Approximate percentile from bucket boundaries (for in-process SLO
        checks; Prometheus does the real math server-side)."""
        key = _label_key(labels or {})
        with self._lock:
            series = self._series.get(key)
            if series is None or series[2] == 0:
                return float("nan")
            counts, _, total = series
            rank = q * total
            for i, b in enumerate(self.buckets):
                if counts[i] >= rank:
                    return b
            return self.buckets[-1]

    def expose(self, out: list[str]) -> None:
        out.append(f"# HELP {self.name} {self.description}")
        out.append(f"# TYPE {self.name} histogram")
        with self._lock:
            items = [(k, (list(v[0]), v[1], v[2])) for k, v in self._series.items()]
        for key, (counts, total_sum, count) in items:
            for i, b in enumerate(self.buckets):
                bkey = key + (("le", _fmt_value(b)),)
                out.append(f"{self.name}_bucket{_fmt_labels(tuple(sorted(bkey)))} {counts[i]}")
            inf_key = key + (("le", "+Inf"),)
            out.append(f"{self.name}_bucket{_fmt_labels(tuple(sorted(inf_key)))} {count}")
            out.append(f"{self.name}_sum{_fmt_labels(key)} {_fmt_value(total_sum)}")
            out.append(f"{self.name}_count{_fmt_labels(key)} {count}")


class Manager:
    """The typed metric store handed to handlers via ``ctx.metrics()``."""

    def __init__(self) -> None:
        self._metrics: dict[str, _Metric] = {}
        self._lock = threading.Lock()
        self._samplers: list = []

    # -- registration -------------------------------------------------------
    def _register(self, metric: _Metric) -> None:
        with self._lock:
            if metric.name in self._metrics:
                raise DuplicateMetricError(metric.name)
            self._metrics[metric.name] = metric

    def new_counter(self, name: str, description: str = "") -> None:
        self._register(_Counter(name, description))

    def new_updown_counter(self, name: str, description: str = "") -> None:
        self._register(_UpDownCounter(name, description))

    def new_gauge(self, name: str, description: str = "") -> None:
        self._register(_Gauge(name, description))

    def new_histogram(
        self, name: str, description: str = "", buckets: Iterable[float] = DEFAULT_BUCKETS
    ) -> None:
        self._register(_Histogram(name, description, buckets))

    # -- recording ----------------------------------------------------------
    def _get(self, name: str, kind: type) -> _Metric:
        metric = self._metrics.get(name)
        if metric is None or not isinstance(metric, kind):
            raise MetricNotFoundError(name)
        return metric

    def increment_counter(self, name: str, **labels: str) -> None:
        self._get(name, _Counter).add(1.0, labels)

    def add_counter(self, name: str, delta: float, **labels: str) -> None:
        """Counter += delta (token throughput counts tokens, not calls)."""
        self._get(name, _Counter).add(delta, labels)

    def delta_updown_counter(self, name: str, delta: float, **labels: str) -> None:
        self._get(name, _UpDownCounter).add(delta, labels)

    def set_gauge(self, name: str, value: float, **labels: str) -> None:
        self._get(name, _Gauge).set(value, labels)

    def record_histogram(self, name: str, value: float, **labels: str) -> None:
        self._get(name, _Histogram).record(value, labels)

    def percentile(self, name: str, q: float, **labels: str) -> float:
        metric = self._get(name, _Histogram)
        assert isinstance(metric, _Histogram)
        return metric.percentile(q, labels)

    def has(self, name: str) -> bool:
        return name in self._metrics

    # -- gauge samplers -----------------------------------------------------
    def register_sampler(self, fn) -> None:
        """Register a zero-arg callable that refreshes gauges from live
        runtime state (HBM occupancy, queue depths). Samplers run on every
        scrape (``expose_text``) and from a ``SamplerThread`` between
        scrapes, so dashboards never read minutes-stale device gauges."""
        with self._lock:
            self._samplers.append(fn)

    def run_samplers(self) -> None:
        with self._lock:
            samplers = list(self._samplers)
        for fn in samplers:
            try:
                fn()
            except Exception:
                pass  # a broken sampler must never break the scrape

    # -- exposition ---------------------------------------------------------
    def expose_text(self) -> str:
        """Render all metrics in Prometheus text exposition format 0.0.4."""
        self.run_samplers()
        out: list[str] = []
        with self._lock:
            metrics = sorted(self._metrics.values(), key=lambda m: m.name)
        for m in metrics:
            m.expose(out)
        return "\n".join(out) + "\n"


class SamplerThread:
    """Background loop running the manager's gauge samplers on an interval,
    so runtime gauges stay fresh even when nothing scrapes :2121 (push
    exporters, long scrape intervals, operators curling /debug/serving)."""

    def __init__(self, manager: Manager, interval_s: float = 10.0) -> None:
        self._manager = manager
        self._interval = max(0.1, float(interval_s))
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="gofr-metrics-sampler"
        )
        self._thread.start()

    def _run(self) -> None:
        while not self._stop.wait(self._interval):
            self._manager.run_samplers()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2)
            self._thread = None


class Timer:
    """Context manager recording elapsed seconds into a histogram."""

    def __init__(self, manager: Manager, name: str, **labels: str) -> None:
        self._m = manager
        self._name = name
        self._labels = labels
        self._start = 0.0

    def __enter__(self) -> "Timer":
        self._start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self._m.record_histogram(self._name, time.perf_counter() - self._start, **self._labels)
