"""Process-global metrics registry with Prometheus text exposition.

Reference roles: the JMX-backed counters the Java coordinator exports
and the native worker's Prometheus exporter
(presto_cpp/main/runtime-metrics/PrometheusStatsReporter.cpp, registered
at PrestoServer.cpp:562) — every operational counter in one scrapeable
registry instead of trapped inside its owning object. Both HTTP servers
(worker `server/http.py`, coordinator `server/statement.py`) render this
registry at `GET /v1/metrics`.

Three instrument kinds, all label-aware and thread-safe:

  Counter    monotonically increasing (`_total` names by convention)
  Gauge      settable point-in-time value; `set_max` keeps high-water
             marks without a read-modify-write race
  Histogram  fixed cumulative buckets (`le` label), plus `_sum`/`_count`

Registration is idempotent by name: a second `counter("x", ...)` call
returns the SAME instrument, so call sites register at module scope or
lazily inside hot paths without coordination. Re-registering a name as a
different kind or with different labels raises — that is a programming
error a scrape would otherwise surface as corrupt exposition output.
Metric and label names are validated against the Prometheus naming
grammar at registration time (and tests/test_metric_names.py guards the
source tree, so a bad name fails the suite rather than a scrape)."""

from __future__ import annotations

import re
import threading
from typing import Dict, List, Optional, Sequence, Tuple

#: Prometheus metric-name grammar (exposition format spec)
METRIC_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
LABEL_NAME_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")

#: wall-time seconds buckets: ~1ms .. ~2min covers everything from one
#: fused-kernel dispatch to a cold TPU compile
DEFAULT_TIME_BUCKETS_S = (0.001, 0.005, 0.025, 0.1, 0.5, 2.5, 10.0,
                          30.0, 120.0)
#: row-count buckets: decade-ish spacing from tiny dimension tables to
#: SF-scale fact scans
DEFAULT_ROWS_BUCKETS = (1.0, 100.0, 10_000.0, 100_000.0, 1_000_000.0,
                        10_000_000.0, 100_000_000.0)


def _escape_label_value(v: str) -> str:
    """Prometheus text-format label-value escaping: backslash, double
    quote and newline (exposition format spec)."""
    return (v.replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _escape_help(v: str) -> str:
    return v.replace("\\", "\\\\").replace("\n", "\\n")


def _format_value(v: float) -> str:
    if v == float("inf"):
        return "+Inf"
    f = float(v)
    return str(int(f)) if f.is_integer() else repr(f)


def _render_labels(names: Sequence[str],
                   values: Sequence[str]) -> str:
    if not names:
        return ""
    inner = ",".join(f'{n}="{_escape_label_value(str(v))}"'
                     for n, v in zip(names, values))
    return "{" + inner + "}"


class _Metric:
    """Shared plumbing: per-labelset series under one lock."""

    kind = "untyped"

    def __init__(self, name: str, help: str,
                 labelnames: Tuple[str, ...]):
        self.name = name
        self.help = help
        self.labelnames = labelnames
        self._lock = threading.Lock()
        # label-value tuple -> series state (subclass-defined)
        self._series: Dict[Tuple[str, ...], object] = {}

    def _key(self, labels: Dict[str, str]) -> Tuple[str, ...]:
        if set(labels) != set(self.labelnames):
            raise ValueError(
                f"metric {self.name} expects labels "
                f"{self.labelnames}, got {tuple(sorted(labels))}")
        return tuple(str(labels[n]) for n in self.labelnames)

    def samples(self) -> List[Tuple[str, Tuple[str, ...],
                                    Tuple[str, ...], float]]:
        """(sample_name, labelnames, labelvalues, value) rows."""
        raise NotImplementedError

    def render(self) -> List[str]:
        lines = [f"# HELP {self.name} {_escape_help(self.help)}",
                 f"# TYPE {self.name} {self.kind}"]
        for sname, lnames, lvalues, v in self.samples():
            lines.append(
                f"{sname}{_render_labels(lnames, lvalues)} "
                f"{_format_value(v)}")
        return lines


class Counter(_Metric):
    kind = "counter"

    def inc(self, amount: float = 1, **labels) -> None:
        if amount < 0:
            raise ValueError(f"counter {self.name} cannot decrease")
        key = self._key(labels)
        with self._lock:
            self._series[key] = self._series.get(key, 0.0) + amount

    def value(self, **labels) -> float:
        with self._lock:
            return float(self._series.get(self._key(labels), 0.0))

    def samples(self):
        with self._lock:
            items = sorted(self._series.items())
        if not items and not self.labelnames:
            items = [((), 0.0)]        # unlabeled counters render at 0
        return [(self.name, self.labelnames, k, float(v))
                for k, v in items]


class Gauge(_Metric):
    kind = "gauge"

    def set(self, value: float, **labels) -> None:
        with self._lock:
            self._series[self._key(labels)] = float(value)

    def inc(self, amount: float = 1, **labels) -> None:
        key = self._key(labels)
        with self._lock:
            self._series[key] = self._series.get(key, 0.0) + amount

    def dec(self, amount: float = 1, **labels) -> None:
        self.inc(-amount, **labels)

    def set_max(self, value: float, **labels) -> None:
        """High-water-mark update: keep the max ever seen (atomic
        read-modify-write under the metric lock)."""
        key = self._key(labels)
        with self._lock:
            cur = self._series.get(key)
            if cur is None or value > cur:
                self._series[key] = float(value)

    def value(self, **labels) -> float:
        with self._lock:
            return float(self._series.get(self._key(labels), 0.0))

    def samples(self):
        with self._lock:
            items = sorted(self._series.items())
        if not items and not self.labelnames:
            items = [((), 0.0)]
        return [(self.name, self.labelnames, k, float(v))
                for k, v in items]


class Histogram(_Metric):
    """Fixed-bucket cumulative histogram (`le` series + _sum/_count)."""

    kind = "histogram"

    def __init__(self, name, help, labelnames,
                 buckets: Sequence[float] = DEFAULT_TIME_BUCKETS_S):
        super().__init__(name, help, labelnames)
        bs = tuple(sorted(float(b) for b in buckets))
        if not bs:
            raise ValueError(f"histogram {name} needs >= 1 bucket")
        self.buckets = bs

    def observe(self, value: float, **labels) -> None:
        key = self._key(labels)
        v = float(value)
        with self._lock:
            state = self._series.get(key)
            if state is None:
                state = {"counts": [0] * len(self.buckets),
                         "sum": 0.0, "count": 0}
                self._series[key] = state
            for i, b in enumerate(self.buckets):
                if v <= b:
                    state["counts"][i] += 1
            state["sum"] += v
            state["count"] += 1

    def count(self, **labels) -> int:
        with self._lock:
            state = self._series.get(self._key(labels))
            return int(state["count"]) if state else 0

    def samples(self):
        with self._lock:
            items = sorted((k, dict(counts=list(v["counts"]),
                                    sum=v["sum"], count=v["count"]))
                           for k, v in self._series.items())
        out = []
        le_names = self.labelnames + ("le",)
        for k, st in items:
            for i, b in enumerate(self.buckets):
                out.append((f"{self.name}_bucket", le_names,
                            k + (_format_value(b),),
                            float(st["counts"][i])))
            out.append((f"{self.name}_bucket", le_names,
                        k + ("+Inf",), float(st["count"])))
            out.append((f"{self.name}_sum", self.labelnames, k,
                        float(st["sum"])))
            out.append((f"{self.name}_count", self.labelnames, k,
                        float(st["count"])))
        return out


class MetricsRegistry:
    """Thread-safe name -> instrument registry; `render()` emits the
    whole set in Prometheus text exposition format 0.0.4."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: "Dict[str, _Metric]" = {}

    def _register(self, cls, name: str, help: str,
                  labelnames: Sequence[str], **kwargs) -> _Metric:
        if not METRIC_NAME_RE.match(name):
            raise ValueError(f"invalid metric name {name!r}")
        lnames = tuple(labelnames)
        for ln in lnames:
            if not LABEL_NAME_RE.match(ln) or ln.startswith("__"):
                raise ValueError(
                    f"invalid label name {ln!r} on metric {name}")
        with self._lock:
            existing = self._metrics.get(name)
            if existing is not None:
                if type(existing) is not cls \
                        or existing.labelnames != lnames:
                    raise ValueError(
                        f"metric {name} already registered as "
                        f"{type(existing).__name__}"
                        f"{existing.labelnames}, conflicting with "
                        f"{cls.__name__}{lnames}")
                # per-histogram bucket overrides are part of the
                # registration contract: silently returning the
                # existing instrument under a DIFFERENT bucket layout
                # would hide the override the second call site asked
                # for, so an explicit bucket mismatch is the same
                # programming error a kind/label conflict is. A call
                # passing the DEFAULT set carries no opinion and stays
                # idempotent against any existing layout.
                want = kwargs.get("buckets")
                if want is not None and isinstance(existing, Histogram):
                    wb = tuple(sorted(float(b) for b in want))
                    if wb != existing.buckets \
                            and wb != tuple(DEFAULT_TIME_BUCKETS_S):
                        raise ValueError(
                            f"histogram {name} already registered "
                            f"with buckets {existing.buckets}, "
                            f"conflicting with {wb}")
                return existing
            m = cls(name, help, lnames, **kwargs)
            self._metrics[name] = m
            return m

    def counter(self, name: str, help: str = "",
                labelnames: Sequence[str] = ()) -> Counter:
        return self._register(Counter, name, help, labelnames)

    def gauge(self, name: str, help: str = "",
              labelnames: Sequence[str] = ()) -> Gauge:
        return self._register(Gauge, name, help, labelnames)

    def histogram(self, name: str, help: str = "",
                  labelnames: Sequence[str] = (),
                  buckets: Sequence[float] = DEFAULT_TIME_BUCKETS_S
                  ) -> Histogram:
        return self._register(Histogram, name, help, labelnames,
                              buckets=buckets)

    def get(self, name: str) -> Optional[_Metric]:
        with self._lock:
            return self._metrics.get(name)

    def names(self) -> List[str]:
        with self._lock:
            return sorted(self._metrics)

    def render(self) -> str:
        with self._lock:
            metrics = [self._metrics[n] for n in sorted(self._metrics)]
        lines: List[str] = []
        for m in metrics:
            lines.extend(m.render())
        return "\n".join(lines) + "\n"


#: the process-wide registry (Guice-singleton analog) — both HTTP
#: servers render it, every subsystem registers into it
REGISTRY = MetricsRegistry()


def counter(name: str, help: str = "",
            labelnames: Sequence[str] = ()) -> Counter:
    return REGISTRY.counter(name, help, labelnames)


def gauge(name: str, help: str = "",
          labelnames: Sequence[str] = ()) -> Gauge:
    return REGISTRY.gauge(name, help, labelnames)


def histogram(name: str, help: str = "",
              labelnames: Sequence[str] = (),
              buckets: Sequence[float] = DEFAULT_TIME_BUCKETS_S
              ) -> Histogram:
    return REGISTRY.histogram(name, help, labelnames, buckets=buckets)


def render_prometheus() -> str:
    return REGISTRY.render()
