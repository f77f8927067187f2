"""Profiler trace -> per-layer numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a plain
structure: planes, their lines, and events ``[name, start_ns, dur_ns]``.
``reduce`` works on that structure only, so the recorded fixture in
``bench/fixtures`` exercises exactly the code a chip run uses.

Device planes are those named ``/device:TPU:<n>``. On each, busy time is
the union of the intervals of its op events (the line named "XLA Ops"),
inside the traced window: the host span named by ``marker``. Idle gaps are
the rest of the window; each piece of a gap is named by the innermost host
event running then, which says what the host was doing while the device
waited.
"""
from __future__ import annotations

import collections
import dataclasses
import gzip
import json
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
MARKER = "bench_traced_window"
_MODULE_ID = re.compile(r"\(\d+\)$")
# ops that only hold other ops (a loop's body runs as ops of its own)
CONTAINERS = re.compile(r"^(while|conditional|call)(\.|$)")


def op_name(event_name: str) -> str:
    """``%fusion.14 = bf16[...] fusion(...)`` -> ``fusion.14``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def module_name(event_name: str) -> str:
    """``jit__decode_step_fn(1263...)`` -> ``jit__decode_step_fn``."""
    return _MODULE_ID.sub("", event_name)


def load(path) -> dict:
    """Planes, lines and events of an xplane.pb (or of a JSON fixture)."""
    path = Path(path)
    if path.suffix == ".gz" or path.suffix == ".json":
        opener = gzip.open if path.suffix == ".gz" else open
        with opener(path, "rt") as f:
            return json.load(f)
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    planes = []
    for plane in pd.planes:
        keep = DEVICE_PLANE.match(plane.name) or plane.name == HOST_PLANE
        if not keep:
            continue
        lines = []
        for line in plane.lines:
            if DEVICE_PLANE.match(plane.name) and line.name not in (
                    OPS_LINE, MODULES_LINE):
                continue
            lines.append({"name": line.name,
                          "events": [[e.name, int(e.start_ns),
                                      int(e.duration_ns)]
                                     for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def find_xplane(directory) -> Path | None:
    found = sorted(Path(directory).rglob("*.xplane.pb"))
    return found[-1] if found else None


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(events, lo, hi):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


@dataclasses.dataclass
class Reduced:
    window_s: float                  # length of the traced window
    busy_s: float                    # device busy, mean over devices
    n_devices: int
    op_s: dict                       # "<program>/<op>" -> device seconds
    module_s: dict                   # program name -> device seconds
    idle_gaps: list                  # [[host activity, seconds], ...]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_time(self, pattern: str) -> float:
        """Device seconds of ops whose name matches ``pattern``."""
        rx = re.compile(pattern)
        return sum(v for k, v in self.op_s.items() if rx.search(k))

    def module_time(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(v for k, v in self.module_s.items() if rx.search(k))

    def breakdown(self, n: int = 10) -> dict:
        top = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": self.idle_gaps[:n]}


def window_of(trace: dict, marker: str = MARKER) -> tuple[int, int]:
    """[start, end) in ns of the host span ``marker``; without one, the
    extent of all device op events."""
    for plane in trace["planes"]:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            for name, s, d in line["events"]:
                if name == marker:
                    return s, s + d
    ev = [(s, s + d) for p in trace["planes"] if DEVICE_PLANE.match(p["name"])
          for ln in p["lines"] if ln["name"] == OPS_LINE
          for _, s, d in ln["events"]]
    if not ev:
        raise ValueError("trace holds no device op events")
    return min(s for s, _ in ev), max(e for _, e in ev)


def reduce(trace: dict, *, marker: str = MARKER) -> Reduced:
    lo, hi = window_of(trace, marker)
    devices = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    if not devices:
        raise ValueError("trace holds no TPU device plane")
    op_s: dict = collections.defaultdict(float)
    module_s: dict = collections.defaultdict(float)
    busy_total = 0.0
    gaps = []
    for plane in devices:
        ops, mods = [], []
        for line in plane["lines"]:
            evs = list(_clip(line["events"], lo, hi))
            if line["name"] == OPS_LINE:
                ops = evs
            elif line["name"] == MODULES_LINE:
                mods = sorted(evs, key=lambda e: e[1])
        for name, a, b in mods:
            module_s[module_name(name)] += (b - a) * 1e-9
        # each op is keyed by the program it ran in: "<program>/<op>"
        j = 0
        for name, a, b in sorted(ops, key=lambda e: e[1]):
            while j < len(mods) and mods[j][2] <= a:
                j += 1
            mod = (module_name(mods[j][0]) if j < len(mods)
                   and mods[j][1] <= a else "?")
            op = op_name(name)
            if not CONTAINERS.match(op):
                op_s[f"{mod}/{op}"] += (b - a) * 1e-9
        busy = _union([(a, b) for _, a, b in ops])
        busy_total += sum(b - a for a, b in busy) * 1e-9
        prev = lo
        for a, b in busy:
            if a > prev:
                gaps.append((prev, a))
            prev = max(prev, b)
        if hi > prev:
            gaps.append((prev, hi))
    host = sorted((s, s + d, name) for p in trace["planes"]
                  if p["name"] == HOST_PLANE for ln in p["lines"]
                  for name, s, d in ln["events"]
                  if name != marker and s < hi and s + d > lo)
    idle: dict = collections.defaultdict(float)
    active: list = []
    i = 0
    for a, b in sorted(gaps):
        # sweep: events that started before the gap ends and may still run
        while i < len(host) and host[i][0] < b:
            active.append(host[i])
            i += 1
        active = [ev for ev in active if ev[1] > a]
        # split the gap where host events begin or end; each piece goes to
        # the innermost (shortest) event that covers it
        cuts = sorted({a, b} | {t for s, e, _ in active for t in (s, e)
                                 if a < t < b})
        for x, y in zip(cuts, cuts[1:]):
            cover = [(e - s, name) for s, e, name in active
                     if s <= x and e >= y]
            idle[min(cover)[1] if cover else "no host event"] += (y - x) * 1e-9
    n = len(devices)
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy_total / n,
                   n_devices=n, op_s=dict(op_s), module_s=dict(module_s),
                   idle_gaps=[[k, v] for k, v in
                              sorted(idle.items(), key=lambda kv: -kv[1])])
