"""User-defined metrics: Counter / Gauge / Histogram.

Reference: python/ray/util/metrics.py:173,318,240 — metrics defined in
any task/actor/driver, aggregated centrally, exported in Prometheus
text format (the reference scrapes via the dashboard agent's
/metrics endpoint; here `prometheus_text()` renders the same exposition
format and the dashboard module serves it).

Workers report through the control-plane KV channel (one message per
update — fine for control-path metrics; hot-loop counters should
aggregate locally and flush periodically).
"""

from __future__ import annotations

import bisect
import threading

from ray_tpu.devtools import locktrace
from typing import Dict, List, Optional, Sequence, Tuple

_DEFAULT_BOUNDARIES = [0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
                       2.5, 5.0, 10.0]


class _Registry:
    """Process-global metric state (driver holds the authoritative
    copy; workers forward updates to it)."""

    def __init__(self, lock_name: str = "util.metrics"):
        self.lock = locktrace.traced_lock(lock_name)
        # (name, tag_items) -> value
        self.counters: Dict[Tuple, float] = {}
        self.gauges: Dict[Tuple, float] = {}
        # (name, tag_items) -> (boundaries, bucket counts, sum, count)
        self.histograms: Dict[Tuple, list] = {}
        self.descriptions: Dict[str, str] = {}

    def apply(self, kind: str, name: str, tags: Tuple, value: float,
              boundaries: Optional[Sequence[float]] = None) -> None:
        with self.lock:
            self._apply_locked(kind, name, tags, value, boundaries)

    def _apply_locked(self, kind: str, name: str, tags: Tuple,
                      value: float,
                      boundaries: Optional[Sequence[float]] = None) -> None:
        key = (name, tags)
        if kind == "counter":
            self.counters[key] = self.counters.get(key, 0.0) + value
        elif kind == "gauge":
            self.gauges[key] = value
        elif kind == "histogram":
            entry = self.histograms.get(key)
            if entry is None:
                bounds = list(boundaries or _DEFAULT_BOUNDARIES)
                entry = [bounds, [0] * (len(bounds) + 1), 0.0, 0]
                self.histograms[key] = entry
            bounds, buckets, _, _ = entry
            buckets[bisect.bisect_left(bounds, value)] += 1
            entry[2] += value
            entry[3] += 1
        elif kind == "histogram_counts":
            # a histogram bucketed where it was observed (LocalBuffer):
            # value = (bucket counts, sum, count) over ``boundaries``
            counts, total, count = value
            bounds = list(boundaries)
            entry = self.histograms.get(key)
            if entry is None:
                entry = [bounds, [0] * (len(bounds) + 1), 0.0, 0]
                self.histograms[key] = entry
            if entry[0] != bounds or len(counts) != len(bounds) + 1:
                raise ValueError(
                    f"histogram {name}: merged buckets {bounds} do not "
                    f"match the series' {entry[0]}")
            for i, n in enumerate(counts):
                entry[1][i] += n
            entry[2] += total
            entry[3] += count
        else:
            raise ValueError(f"unknown metric kind {kind!r}")

    def apply_batch(self, items) -> None:
        """Apply many updates under ONE lock acquisition — the flush
        path for hot-loop producers (e.g. the LLM engine stepper) that
        aggregate locally instead of paying a lock/RPC per update."""
        with self.lock:
            for kind, name, tags, value, boundaries in items:
                self._apply_locked(kind, name, tuple(tags), value,
                                   boundaries)

    def drain(self) -> List[tuple]:
        """Take everything recorded so far, as ``apply_batch`` items
        (histograms pre-bucketed: ``histogram_counts``), and start
        empty again."""
        with self.lock:
            counters, self.counters = self.counters, {}
            gauges, self.gauges = self.gauges, {}
            histograms, self.histograms = self.histograms, {}
        items = [("counter", name, tags, value, None)
                 for (name, tags), value in counters.items()]
        items += [("gauge", name, tags, value, None)
                  for (name, tags), value in gauges.items()]
        items += [("histogram_counts", name, tags,
                   (buckets, total, count), bounds)
                  for (name, tags), (bounds, buckets, total, count)
                  in histograms.items()]
        return items

    def remove_series(self, name: str, tags: Tuple) -> None:
        """Drop one labeled series (a gauge whose subject — node,
        deployment — no longer exists must stop being exported, or
        scrapers chart zombie series forever). When the metric's last
        series goes, its description goes too — a dangling entry would
        keep exporting a header with no samples."""
        with self.lock:
            key = (name, tags)
            self.counters.pop(key, None)
            self.gauges.pop(key, None)
            self.histograms.pop(key, None)
            if not any(k[0] == name for table in (self.counters,
                                                  self.gauges,
                                                  self.histograms)
                       for k in table):
                self.descriptions.pop(name, None)


_registry = _Registry()


def remove_series(name: str, tags: Dict[str, str]) -> None:
    _registry.remove_series(name, tuple(sorted((tags or {}).items())))


def _record(kind: str, name: str, tags: Dict[str, str], value: float,
            boundaries=None) -> None:
    tag_items = tuple(sorted((tags or {}).items()))
    from ray_tpu.core import runtime as runtime_mod
    rt = runtime_mod.get_runtime_or_none()
    if rt is not None and not getattr(rt, "is_driver", False):
        # worker: forward to the driver-held registry via the GCS channel
        rt.gcs_call("metrics_apply", kind, name, tag_items, value,
                    list(boundaries) if boundaries else None)
        return
    _registry.apply(kind, name, tag_items, value, boundaries)


def record_local(kind: str, name: str, tags: Dict[str, str], value: float,
                 boundaries=None) -> None:
    """Apply one update to THIS process's registry, never the
    worker->driver forwarding channel. For code running on an IO/event
    thread (the core IO loop): forwarding is a synchronous
    control-plane request whose reply only that same thread could
    dispatch — a self-deadlock."""
    _registry.apply(kind, name, tuple(sorted((tags or {}).items())),
                    value, boundaries)


def record_batch(items) -> None:
    """Apply a batch of metric updates in one shot. ``items``: iterable
    of ``(kind, name, tags, value, boundaries)``, tags a dict or the
    sorted item tuple the registry keys by. On a worker the whole batch
    rides ONE control-plane RPC instead of one per update — the flush
    path for hot loops that aggregate locally. The kind
    ``histogram_counts`` merges a histogram bucketed by the sender
    (``value`` = (bucket counts, sum, count) over ``boundaries``); the
    registry ends as if each sample had been observed here."""
    normalized = [
        (kind, name,
         tags if isinstance(tags, tuple)
         else tuple(sorted((tags or {}).items())),
         value, list(boundaries) if boundaries else None)
        for kind, name, tags, value, boundaries in items]
    if not normalized:
        return
    from ray_tpu.core import runtime as runtime_mod
    rt = runtime_mod.get_runtime_or_none()
    if rt is not None and not getattr(rt, "is_driver", False):
        rt.gcs_call("metrics_apply_batch", normalized)
        return
    _registry.apply_batch(normalized)


class Metric:
    def __init__(self, name: str, description: str = "",
                 tag_keys: Sequence[str] = ()):
        self._name = name
        self._tag_keys = tuple(tag_keys)
        self._default_tags: Dict[str, str] = {}
        # Under the registry lock: metrics are defined from arbitrary
        # threads (serve replicas, train workers) concurrently with
        # prometheus_text() reads. Don't let a later blank-description
        # re-registration of the same name clobber a real one.
        with _registry.lock:
            if description or name not in _registry.descriptions:
                _registry.descriptions[name] = description

    def set_default_tags(self, tags: Dict[str, str]):
        self._default_tags = dict(tags)
        return self

    def _tags(self, tags: Optional[Dict[str, str]]) -> Dict[str, str]:
        out = dict(self._default_tags)
        out.update(tags or {})
        return out


class Counter(Metric):
    def inc(self, value: float = 1.0,
            tags: Optional[Dict[str, str]] = None) -> None:
        _record("counter", self._name, self._tags(tags), value)

    def inc_local(self, value: float = 1.0,
                  tags: Optional[Dict[str, str]] = None) -> None:
        """Loop-thread-safe inc: applies to this process's registry
        with no worker->driver RPC (see record_local). Required on any
        rtpu-io-loop code path (graftlint GL010)."""
        record_local("counter", self._name, self._tags(tags), value)


class Gauge(Metric):
    def set(self, value: float,
            tags: Optional[Dict[str, str]] = None) -> None:
        _record("gauge", self._name, self._tags(tags), value)

    def set_local(self, value: float,
                  tags: Optional[Dict[str, str]] = None) -> None:
        """Loop-thread-safe set: no RPC (see record_local / GL010)."""
        record_local("gauge", self._name, self._tags(tags), value)


class Histogram(Metric):
    def __init__(self, name: str, description: str = "",
                 boundaries: Optional[Sequence[float]] = None,
                 tag_keys: Sequence[str] = ()):
        super().__init__(name, description, tag_keys)
        self._boundaries = list(boundaries or _DEFAULT_BOUNDARIES)

    def observe(self, value: float,
                tags: Optional[Dict[str, str]] = None) -> None:
        _record("histogram", self._name, self._tags(tags), value,
                self._boundaries)

    def observe_local(self, value: float,
                      tags: Optional[Dict[str, str]] = None) -> None:
        """Loop-thread-safe observe: no RPC (see record_local /
        GL010)."""
        record_local("histogram", self._name, self._tags(tags), value,
                     self._boundaries)

    def percentile(self, q: float,
                   tags: Optional[Dict[str, str]] = None
                   ) -> Optional[float]:
        """Interpolated quantile (q in [0, 1]) of this histogram's
        labeled series, read straight from the registry — admission
        control and autoscaling policies use this instead of scraping
        the /metrics exposition text. Driver-side only: workers forward
        updates to the driver and hold no local counts. Returns None
        when the series has no observations."""
        return histogram_percentile(self._name, q, self._tags(tags))

    def snapshot(self, tags: Optional[Dict[str, str]] = None
                 ) -> Optional[tuple]:
        """(boundaries, bucket_counts, sum, count) copy of one labeled
        series, or None. Two snapshots' bucket-count difference feeds
        percentile_from_counts() for WINDOWED quantiles (lifetime
        histograms never forget a slow start; control loops need the
        recent distribution)."""
        return histogram_snapshot(self._name, self._tags(tags))


class LocalBuffer:
    """Metric updates aggregated in this process, for a hot loop that
    must not pay a lock shared with scrapers, let alone a worker->driver
    round trip, per update. ``observe`` / ``inc`` / ``set`` touch only
    the buffer; ``flush`` ships what gathered as ONE ``record_batch``
    whose size does not depend on how many updates there were: each
    histogram series travels as bucket counts, a sum and a count."""

    def __init__(self):
        self._pending = _Registry("util.metrics.local")

    def _apply(self, kind: str, metric: Metric, value: float, tags,
               boundaries=None) -> None:
        self._pending.apply(kind, metric._name,
                            tuple(sorted(metric._tags(tags).items())),
                            value, boundaries)

    def observe(self, hist: "Histogram", value: float,
                tags: Optional[Dict[str, str]] = None) -> None:
        self._apply("histogram", hist, value, tags, hist._boundaries)

    def inc(self, counter: "Counter", value: float = 1.0,
            tags: Optional[Dict[str, str]] = None) -> None:
        self._apply("counter", counter, value, tags)

    def set(self, gauge: "Gauge", value: float,
            tags: Optional[Dict[str, str]] = None) -> None:
        self._apply("gauge", gauge, value, tags)

    def drain(self) -> List[tuple]:
        """Take what gathered, as ``record_batch`` items, unshipped:
        for a buffer that another buffer's flush carries."""
        return self._pending.drain()

    def merge(self, items) -> None:
        """Take another buffer's drained items in among this one's."""
        self._pending.apply_batch(items)

    def flush(self) -> List[tuple]:
        """Ship and forget what gathered; returns the items sent."""
        items = self.drain()
        record_batch(items)
        return items


def histogram_snapshot(name: str, tags: Optional[Dict[str, str]] = None
                       ) -> Optional[tuple]:
    key = (name, tuple(sorted((tags or {}).items())))
    with _registry.lock:
        entry = _registry.histograms.get(key)
        if entry is None:
            return None
        bounds, buckets, total, count = entry
        return list(bounds), list(buckets), float(total), int(count)


def percentile_from_counts(bounds: Sequence[float],
                           buckets: Sequence[float],
                           q: float) -> Optional[float]:
    """Interpolated quantile from histogram bucket counts. ``buckets``
    has len(bounds)+1 entries (last = overflow). Linear interpolation
    inside the containing bucket; the unbounded overflow bucket reports
    the top boundary (the histogram can't resolve beyond it). Returns
    None — never raises — on an empty/all-zero snapshot or a series
    with no finite boundaries, so control loops (SLO autoscaler,
    whereis) can poll before traffic exists."""
    count = sum(buckets)
    if count <= 0 or not bounds:
        return None
    q = min(1.0, max(0.0, q))
    rank = q * count
    cumulative = 0.0
    for i, n in enumerate(buckets[:-1]):
        prev = cumulative
        cumulative += n
        if cumulative >= rank and n > 0:
            lo = bounds[i - 1] if i > 0 else 0.0
            hi = bounds[i]
            frac = (rank - prev) / n
            return lo + (hi - lo) * frac
    return float(bounds[-1])


def histogram_percentile(name: str, q: float,
                         tags: Optional[Dict[str, str]] = None
                         ) -> Optional[float]:
    snap = histogram_snapshot(name, tags)
    if snap is None:
        return None
    bounds, buckets, _total, _count = snap
    return percentile_from_counts(bounds, buckets, q)


def _esc_label(value) -> str:
    # Prometheus text-format label escaping: backslash, double-quote, and
    # newline must be escaped or scrapers reject the exposition.
    return (str(value).replace("\\", r"\\").replace('"', r"\"")
            .replace("\n", r"\n"))


def _fmt_tags(tags: Tuple, extra: str = "") -> str:
    parts = [f'{k}="{_esc_label(v)}"' for k, v in tags]
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}" if parts else ""


def _esc_help(text: str) -> str:
    # HELP text escaping per the exposition format: backslash + newline.
    return str(text).replace("\\", r"\\").replace("\n", r"\n")


def prometheus_text() -> str:
    """Prometheus exposition-format dump of every metric. ``# HELP`` /
    ``# TYPE`` headers are emitted once per metric family (not per
    labeled series — scrapers reject duplicate headers)."""
    reg = _registry
    lines: List[str] = []

    def header(name: str, kind: str) -> None:
        desc = reg.descriptions.get(name)
        if desc:
            lines.append(f"# HELP {name} {_esc_help(desc)}")
        lines.append(f"# TYPE {name} {kind}")

    with reg.lock:
        last = None
        for (name, tags), value in sorted(reg.counters.items()):
            if name != last:
                header(name, "counter")
                last = name
            lines.append(f"{name}{_fmt_tags(tags)} {value}")
        last = None
        for (name, tags), value in sorted(reg.gauges.items()):
            if name != last:
                header(name, "gauge")
                last = name
            lines.append(f"{name}{_fmt_tags(tags)} {value}")
        last = None
        for (name, tags), (bounds, buckets, total, count) in sorted(
                reg.histograms.items()):
            if name != last:
                header(name, "histogram")
                last = name
            cumulative = 0
            for bound, n in zip(bounds, buckets):
                cumulative += n
                le = 'le="%s"' % bound
                lines.append(f"{name}_bucket{_fmt_tags(tags, le)} "
                             f"{cumulative}")
            cumulative += buckets[-1]
            le_inf = 'le="+Inf"'
            lines.append(f"{name}_bucket{_fmt_tags(tags, le_inf)} "
                         f"{cumulative}")
            lines.append(f"{name}_sum{_fmt_tags(tags)} {total}")
            lines.append(f"{name}_count{_fmt_tags(tags)} {count}")
    return "\n".join(lines) + "\n"
