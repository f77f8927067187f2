"""Structured tracing for the serving stack: span / instant / async-span /
counter events in the Chrome trace-event JSON format, loadable directly by
Perfetto (https://ui.perfetto.dev) and ``chrome://tracing``.

Design constraints, in order:

  ~zero cost when disabled   The hot loop is instrumented unconditionally,
      so the disabled path must be a handful of no-op attribute calls.
      ``NullTracer`` (the engines' default) implements the full surface as
      no-ops and returns one shared null context manager from ``span`` —
      nothing allocates, nothing formats, nothing appends.

  profiler clock             ``phase()`` wraps one engine phase (a decode
      step's dispatch / sync / commit, a freeze flush or install, a
      prefill, an engine iteration) in a ``jax.profiler.TraceAnnotation``
      named ``repro:<track>:<name>``. With the JAX profiler on, the
      annotation lands on the profiler's own host clock, on the
      ``/host:CPU`` plane beside the device ops, so a device idle gap can
      be laid against the phase the host was in. ``PREFIX`` is the
      contract with trace readers. With the profiler off, a phase costs
      a profiler-state check, two ``perf_counter_ns`` reads and the
      tracer's ``now``/``complete`` (PERF.md gives its cost measured on
      the TPU v5e host).

  injectable clock           ``Tracer(clock=...)`` takes any zero-arg
      monotonic-seconds callable. Tests inject ``FakeClock`` (a fixed tick
      per call) so a seeded run emits byte-identical trace JSON — the
      observability analogue of the golden-trace fixture. Production uses
      ``time.perf_counter``.

  one track per component    Tracks are named strings ("router",
      "decode/w0", "freeze/w0", ...) mapped to Chrome ``tid``s in
      first-use order; ``to_dict`` emits the matching ``thread_name`` /
      ``thread_sort_index`` metadata so Perfetto shows one labeled lane
      per component.

Event kinds (Chrome ``ph`` phases):

  span          "X" complete event with ts+dur — a timed phase. Use the
                ``span()`` context manager when args are known up front, or
                ``t0 = tracer.now(); ...; tracer.complete(...)`` when args
                (e.g. payload bytes) only exist at the end.
  instant       "i" — a decision point (route, accept, reject).
  counter       "C" — a per-step gauge (cache occupancy, frozen pages).
  async span    "b"/"n"/"e" with an id — a lifecycle that outlives any one
                call frame and overlaps its neighbours on the same track.
                The page-freeze lifecycle (queued -> dispatched ->
                installed | dropped | rolled_back) and in-flight prefills
                are async spans keyed by a caller-chosen id.
"""
from __future__ import annotations

import json
import time
from typing import Any, Callable, Iterable

from jax.profiler import TraceAnnotation

#: prefix of every profiler annotation ``phase`` writes; trace readers
#: select the program's phases by it
PREFIX = "repro:"


class FakeClock:
    """Deterministic test clock: advances ``tick`` seconds per call.

    Timestamps become call counts, so a seeded run's trace depends only on
    its event sequence — byte-identical across runs and platforms.
    """

    def __init__(self, tick: float = 0.001, t0: float = 0.0) -> None:
        self.tick = tick
        self._t = t0

    def __call__(self) -> float:
        t = self._t
        self._t += self.tick
        return t


class _NullSpan:
    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc: object) -> bool:
        return False


_NULL_SPAN = _NullSpan()


class NullTracer:
    """No-op tracer: the default everywhere, so instrumentation points pay
    only an attribute call + early return when tracing is off."""

    enabled = False
    events: tuple = ()

    def now(self) -> float:
        return 0.0

    def span(self, track: str, name: str, **args: Any) -> "_NullSpan":
        return _NULL_SPAN

    def complete(self, track: str, name: str, t0: float,
                 **args: Any) -> None:
        pass

    def instant(self, track: str, name: str, **args: Any) -> None:
        pass

    def counter(self, track: str, name: str, **values: Any) -> None:
        pass

    def async_begin(self, track: str, name: str, id: Any,
                    **args: Any) -> None:
        pass

    def async_instant(self, track: str, name: str, id: Any,
                      **args: Any) -> None:
        pass

    def async_end(self, track: str, name: str, id: Any,
                  **args: Any) -> None:
        pass

    def to_dict(self) -> dict:
        return {"traceEvents": [], "displayTimeUnit": "ms"}

    def write(self, path: str) -> None:
        pass


NULL_TRACER = NullTracer()


class _Span:
    """Context manager emitting one complete ("X") event on exit."""

    __slots__ = ("tr", "track", "name", "args", "t0")

    def __init__(self, tr: "Tracer", track: str, name: str,
                 args: dict[str, Any]) -> None:
        self.tr, self.track, self.name, self.args = tr, track, name, args

    def __enter__(self) -> "_Span":
        self.t0 = self.tr.clock()
        return self

    def __exit__(self, *exc: object) -> bool:
        self.tr._emit_complete(self.track, self.name, self.t0,
                               self.tr.clock(), self.args)
        return False


class Tracer:
    """Collects trace events in memory; ``write()`` emits Perfetto-loadable
    Chrome trace JSON. All timestamps come from the injected ``clock``."""

    enabled = True

    def __init__(self, clock: Callable[[], float] | None = None, *,
                 pid: int = 0) -> None:
        self.clock = clock if clock is not None else time.perf_counter
        self.pid = pid
        self._t0 = self.clock()
        self.events: list[dict] = []
        self._tids: dict[str, int] = {}

    # ------------------------------------------------------------- clock

    def now(self) -> float:
        """Seconds on the tracer clock (pair with ``complete``)."""
        return self.clock()

    def _us(self, t: float) -> float:
        return round((t - self._t0) * 1e6, 3)

    def _tid(self, track: str) -> int:
        tid = self._tids.get(track)
        if tid is None:
            tid = self._tids[track] = len(self._tids) + 1
        return tid

    # ------------------------------------------------------------ events

    def _emit_complete(self, track: str, name: str, t0: float, t1: float,
                       args: dict[str, Any]) -> None:
        ev = {"ph": "X", "name": name, "pid": self.pid,
              "tid": self._tid(track), "ts": self._us(t0),
              "dur": round((t1 - t0) * 1e6, 3)}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def span(self, track: str, name: str, **args: Any) -> _Span:
        return _Span(self, track, name, args)

    def complete(self, track: str, name: str, t0: float,
                 **args: Any) -> None:
        """Close an explicitly-timed region opened at ``t0 = tracer.now()``
        — for spans whose args (payload bytes, ...) exist only at the end."""
        self._emit_complete(track, name, t0, self.clock(), args)

    def instant(self, track: str, name: str, **args: Any) -> None:
        ev = {"ph": "i", "s": "t", "name": name, "pid": self.pid,
              "tid": self._tid(track), "ts": self._us(self.clock())}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def counter(self, track: str, name: str, **values: Any) -> None:
        self.events.append({"ph": "C", "name": name, "pid": self.pid,
                            "tid": self._tid(track),
                            "ts": self._us(self.clock()), "args": values})

    def _async(self, ph: str, track: str, name: str, id: Any,
               args: dict[str, Any]) -> None:
        ev = {"ph": ph, "cat": track, "name": name, "id": str(id),
              "pid": self.pid, "tid": self._tid(track),
              "ts": self._us(self.clock())}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def async_begin(self, track: str, name: str, id: Any,
                    **args: Any) -> None:
        self._async("b", track, name, id, args)

    def async_instant(self, track: str, name: str, id: Any,
                      **args: Any) -> None:
        self._async("n", track, name, id, args)

    def async_end(self, track: str, name: str, id: Any,
                  **args: Any) -> None:
        self._async("e", track, name, id, args)

    # ------------------------------------------------------------ output

    def _metadata(self) -> list[dict]:
        meta = [{"ph": "M", "name": "process_name", "pid": self.pid,
                 "tid": 0, "args": {"name": "repro.serving"}}]
        for track, tid in sorted(self._tids.items(), key=lambda kv: kv[1]):
            meta.append({"ph": "M", "name": "thread_name", "pid": self.pid,
                         "tid": tid, "args": {"name": track}})
            meta.append({"ph": "M", "name": "thread_sort_index",
                         "pid": self.pid, "tid": tid,
                         "args": {"sort_index": tid}})
        return meta

    def to_dict(self) -> dict:
        return {"traceEvents": self._metadata() + self.events,
                "displayTimeUnit": "ms"}

    def write(self, path: str) -> None:
        """Write Perfetto-loadable JSON. ``sort_keys`` + fixed separators
        keep the bytes deterministic for the fake-clock golden tests."""
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, sort_keys=True,
                      separators=(",", ":"), allow_nan=False)


class phase:
    """One engine phase: a profiler annotation, an in-memory span and,
    optionally, a counter of its host nanoseconds, in one context manager.

    ``with phase(tracer, "decode/w0", "dispatch", counters,
    "decode_dispatch_ns", blocks=mb):`` opens the profiler annotation
    ``repro:decode/w0:dispatch`` (while a profiler session runs); on
    exit it closes the annotation, adds the phase's ``perf_counter_ns``
    duration to ``counters[key]`` (when ``key`` is given) and then calls
    ``tracer.complete(track, name, t0, **args)`` — the call a tracer sees
    for any span, so a tracer that acts on a span's end (and may raise
    there) has already seen its time counted. ``args`` known only at the
    end go into ``p.args`` of ``with ... as p``. A phase left by an
    exception still counts its time but records no span.
    """

    __slots__ = ("tr", "track", "name", "counters", "key", "args", "ann",
                 "t0", "ns")

    def __init__(self, tracer: Any, track: str, name: str,
                 counters: dict[str, int] | None = None,
                 key: str | None = None, **args: Any) -> None:
        self.tr, self.track, self.name = tracer, track, name
        self.counters, self.key, self.args = counters, key, args

    def __enter__(self) -> "phase":
        self.t0 = self.tr.now()
        # with no profiler session the annotation would record nothing:
        # skip building it (the off path is the serving default)
        if TraceAnnotation.is_enabled():
            self.ann = TraceAnnotation(PREFIX + self.track + ":" + self.name)
            self.ann.__enter__()
        else:
            self.ann = None
        self.ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type: type[BaseException] | None,
                 *exc: object) -> bool:
        ns = time.perf_counter_ns() - self.ns
        if self.ann is not None:
            self.ann.__exit__(exc_type, *exc)
        if self.counters is not None and self.key is not None:
            self.counters[self.key] += ns
        if exc_type is None:
            self.tr.complete(self.track, self.name, self.t0, **self.args)
        return False


# ------------------------------------------------------------ inspection


def count_events(events: Iterable[dict], *, track: str | None = None,
                 name: str | None = None, ph: str | None = None) -> int:
    """Count events matching the filters (trace-vs-counter reconciliation;
    ``track`` matches the async ``cat`` field or is resolved by callers that
    hold the tracer via ``select_events``)."""
    return len(select_events(events, track=track, name=name, ph=ph))


def select_events(events: Iterable[dict], *, track: str | None = None,
                  name: str | None = None,
                  ph: str | None = None) -> list[dict]:
    out = []
    for ev in events:
        if name is not None and ev.get("name") != name:
            continue
        if ph is not None and ev.get("ph") != ph:
            continue
        if track is not None and ev.get("cat") != track:
            continue
        out.append(ev)
    return out


def tracks_of(tracer: Tracer) -> dict[str, int]:
    """Track-name -> tid mapping of a live tracer (schema tests)."""
    return dict(tracer._tids)
