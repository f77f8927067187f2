"""Metrics export: periodic JSONL snapshots and Prometheus-style text
exposition.

``MetricsExporter`` hangs off an engine run loop: ``maybe_emit()`` is
called every iteration but only writes when ``interval_s`` elapsed on the
injected clock (fake clock in tests -> deterministic snapshot cadence).
Each line is strict JSON (``allow_nan=False``) so downstream ``json.loads``
round-trips, and windowed percentiles for every log histogram come from a
counts-delta against the previous emit — no samples stored.
"""
from __future__ import annotations

import json
import time
from typing import Any, Callable

from .stats import Counter, Gauge, LogHistogram, Registry


class MetricsExporter:
    """Periodic JSONL metrics snapshots with windowed histogram
    percentiles. ``path=None`` keeps lines in ``self.lines`` only (tests).
    """

    def __init__(self, path: str | None = None, *, interval_s: float = 1.0,
                 clock: Callable[[], float] | None = None,
                 registry: Registry | None = None) -> None:
        self.path = path
        self.interval_s = interval_s
        self.clock = clock if clock is not None else time.perf_counter
        self.registry = registry
        self.lines: list[dict] = []
        self._file = open(path, "w") if path else None
        self._last_emit: float | None = None
        self._hist_states: dict[str, dict] = {}
        self.seq = 0

    def _windowed(self, registry: Registry) -> dict:
        """p50/p99 over just the interval since the previous emit, from
        histogram counts-deltas (O(buckets), no samples retained)."""
        out: dict[str, dict] = {}
        for name in registry.names():
            m = registry[name]
            if not isinstance(m, LogHistogram):
                continue
            prev = self._hist_states.get(name)
            if prev is None:
                delta = m.state()
            else:
                delta = m.delta(prev)
            self._hist_states[name] = m.state()
            if delta["n"] > 0:
                out[name] = {"n": delta["n"],
                             "p50": m.percentile(50, **delta),
                             "p99": m.percentile(99, **delta)}
        return out

    def maybe_emit(self, metrics: Any = None, *, force: bool = False,
                   extra: dict | None = None) -> dict | None:
        """Emit one snapshot line if ``interval_s`` elapsed (or ``force``).

        ``metrics`` is anything with ``snapshot()`` + ``stats`` (a
        ``MetricsCollector``) or a bare ``Registry``; defaults to the
        registry bound at construction.
        """
        now = self.clock()
        if not force and self._last_emit is not None \
                and now - self._last_emit < self.interval_s:
            return None
        self._last_emit = now
        src = metrics if metrics is not None else self.registry
        registry = getattr(src, "stats", src)
        line = {"seq": self.seq, "t": round(now, 6)}
        self.seq += 1
        snap = src.snapshot() if hasattr(src, "snapshot") else {}
        line.update(snap)
        win = self._windowed(registry) if registry is not None else {}
        if win:
            line["window"] = win
        if extra:
            line.update(extra)
        self.lines.append(line)
        if self._file is not None:
            json.dump(line, self._file, sort_keys=True, allow_nan=False)
            self._file.write("\n")
            self._file.flush()
        return line

    def close(self, metrics: Any = None) -> None:
        """Final forced snapshot, then release the file."""
        self.maybe_emit(metrics, force=True)
        if self._file is not None:
            self._file.close()
            self._file = None


# -------------------------------------------------------------- prometheus


def _prom_name(name: str) -> str:
    out = []
    for ch in name:
        out.append(ch if ch.isalnum() or ch == "_" else "_")
    return "".join(out)


def prometheus_text(snapshot: dict, *, prefix: str = "repro") -> str:
    """Render a ``Registry.snapshot()`` / ``MetricsCollector.snapshot()``
    dict as Prometheus text exposition (counters -> _total, gauges ->
    last + _mean/_max, histograms -> quantile-labeled gauges)."""
    lines: list[str] = []
    for name in sorted(snapshot):
        v = snapshot[name]
        base = f"{prefix}_{_prom_name(name)}"
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            lines.append(f"# TYPE {base} gauge")
            lines.append(f"{base} {v}")
            continue
        if not isinstance(v, dict):
            continue
        kind = v.get("type")
        if kind == "counter":
            lines.append(f"# TYPE {base}_total counter")
            lines.append(f"{base}_total {v['value']}")
        elif kind == "gauge":
            lines.append(f"# TYPE {base} gauge")
            if v.get("last") is not None:
                lines.append(f"{base} {v['last']}")
            for stat in ("mean", "max"):
                if v.get(stat) is not None:
                    lines.append(f"{base}_{stat} {v[stat]}")
        elif kind == "histogram":
            lines.append(f"# TYPE {base} summary")
            for q, key in ((0.5, "p50"), (0.9, "p90"), (0.99, "p99")):
                if v.get(key) is not None:
                    lines.append(
                        f'{base}{{quantile="{q}"}} {v[key]}')
            lines.append(f"{base}_count {v['n']}")
            if v.get("mean") is not None:
                lines.append(f"{base}_sum {v['mean'] * v['n']}")
    return "\n".join(lines) + "\n"
