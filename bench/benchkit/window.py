"""The measured window: a tracer handed to the engine that closes it, and
the arithmetic that turns the engine's per-request records into metrics.

The engine calls its tracer's ``complete(track, "decode_step", t0)`` at the
end of every decode step, tracing on or off. ``WindowTracer`` keeps the
tracer's no-op surface (``enabled`` stays False, so the program does no
extra work for it) and, once armed, raises ``WindowClosed`` at the first
decode step that ends past the deadline. Nothing after that counts.

Times are seconds on the engine's clock, which starts when ``run()``
starts. A request is due at its ``due`` time; every latency is measured
from there, so a request that waited unseen behind a step still counts
the wait.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np


class WindowClosed(Exception):
    """Raised out of the engine's run loop when the window closes."""


class _NullSpan:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


class WindowTracer:
    """Harness-side tracer: closes the window and records per-step state.

    ``on_dispatch(worker)`` runs right after each decode step is
    dispatched (the table, lengths and frozen set are then the ones the
    step reads); ``on_step(t)`` at the end of each step. Both are optional
    and only the traced run sets them.
    """

    enabled = False

    def __init__(self):
        self.worker = None
        self.armed = False
        self.t0 = 0.0
        self.deadline = 0.0
        self.close_t = None
        self.step_s: list[float] = []
        self.counters_open: dict = {}
        self.counters_close: dict = {}
        self.on_dispatch = None
        self.on_step = None

    # -- control
    def arm(self, worker, seconds: float) -> None:
        """Open the window now: run() is called right after this."""
        self.worker = worker
        self.counters_open = dict(worker.counters)
        self.t0 = time.perf_counter()
        self.deadline = self.t0 + seconds
        self.armed = True

    # -- tracer surface used by the engine
    def now(self) -> float:
        return time.perf_counter()

    def complete(self, track, name, t0, **args) -> None:
        if not self.armed or not track.startswith("decode/"):
            return
        if name == "dispatch" and self.on_dispatch is not None:
            self.on_dispatch(self.worker)
        elif name == "decode_step":
            t = time.perf_counter()
            self.step_s.append(t - t0)
            if self.on_step is not None:
                self.on_step(t - self.t0)
            if t >= self.deadline:
                self.close_t = t - self.t0
                self.counters_close = dict(self.worker.counters)
                self.armed = False
                raise WindowClosed

    def span(self, track, name, **args):
        return _NullSpan()

    def instant(self, track, name, **args):
        pass

    def counter(self, track, name, **values):
        pass

    def async_begin(self, track, name, id, **args):
        pass

    def async_instant(self, track, name, id, **args):
        pass

    def async_end(self, track, name, id, **args):
        pass



@dataclasses.dataclass
class Record:
    """One request's times on the engine clock (None: did not happen)."""

    due: float
    arrival: float | None = None        # the engine noticed it
    prefill_start: float | None = None
    token_times: tuple = ()             # every output token, first included
    rejected: bool = False
    prompt_len: int = 0


def records_from_engine(items, traces: dict, rejected) -> list[Record]:
    """Re-base the engine's ``RequestTrace``s onto due times."""
    rej = set(rejected)
    out = []
    for it in items:
        tr = traces.get(it.id)
        rec = Record(due=it.due, rejected=it.id in rej,
                     prompt_len=len(it.prompt))
        if tr is not None:
            rec.arrival = tr.arrival_t
            rec.prefill_start = tr.prefill_start_t
            if tr.first_token_t is not None:
                rec.token_times = tuple(tr.first_token_t
                                        + np.concatenate([[0.0],
                                                          np.cumsum(tr.gaps)]))
        out.append(rec)
    return out


@dataclasses.dataclass
class Window:
    """What the window saw. ``offline``: only requests that got a slot in
    the window count as attempted (a backlog queued at t = 0)."""

    records: list
    close: float
    offline: bool = False

    def due(self) -> list[Record]:
        return [r for r in self.records if r.due <= self.close]

    def attempted(self) -> list[Record]:
        recs = self.due()
        if self.offline:
            recs = [r for r in recs if r.rejected or (
                r.prefill_start is not None and r.prefill_start <= self.close)]
        return recs

    def failed(self) -> int:
        return sum(r.rejected for r in self.attempted())

    def ttfts(self) -> list[float]:
        """First token - due, for every attempted request; one with no
        first token by the close (or refused) counts close - due."""
        out = []
        for r in self.attempted():
            first = r.token_times[0] if r.token_times else None
            end = first if first is not None and first <= self.close \
                else self.close
            out.append(end - r.due)
        return out

    def token_times(self) -> list[np.ndarray]:
        return [np.asarray([t for t in r.token_times if t <= self.close])
                for r in self.records]

    def output_tokens(self) -> int:
        return int(sum(len(t) for t in self.token_times()))

    def gaps(self) -> np.ndarray:
        parts = [np.diff(t) for t in self.token_times() if len(t) > 1]
        return np.concatenate(parts) if parts else np.zeros(0)

    def censored(self, field: str) -> list[float]:
        """``field`` - due over attempted requests, censored at the close."""
        out = []
        for r in self.attempted():
            t = getattr(r, field)
            out.append((t if t is not None and t <= self.close
                        else self.close) - r.due)
        return out


def counter_delta(open_: dict, close: dict, key: str) -> int:
    return int(close.get(key, 0)) - int(open_.get(key, 0))
