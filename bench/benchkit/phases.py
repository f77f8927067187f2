"""Device idle time by program phase.

The program wraps its engine phases in profiler annotations named
``repro:<track>:<phase>`` (``PREFIX``: the same literal as the program's
``repro.obs.trace.PREFIX``), which land on the host plane beside the
runtime's and the Python tracer's events. ``idle_spans`` lays the device's
idle gaps in the traced window (the gaps ``trace.reduce`` names by the
innermost host event of any kind) against those annotations alone: each
piece of idle goes to the innermost program phase open then, or to
``NO_SPAN``.
"""
from __future__ import annotations

import collections

from . import trace as tr

PREFIX = "repro:"
NO_SPAN = "(no program span)"


def idle_spans(trace: dict, *, marker: str = tr.MARKER) -> list:
    """[[phase, idle seconds], ...], largest first, summed over devices."""
    lo, hi = tr.window_of(trace, marker)
    gaps = []
    for plane in trace["planes"]:
        if not tr.DEVICE_PLANE.match(plane["name"]):
            continue
        ops = [(a, b) for ln in plane["lines"] if ln["name"] == tr.OPS_LINE
               for _, a, b in tr._clip(ln["events"], lo, hi)]
        prev = lo
        for a, b in tr._union(ops):
            if a > prev:
                gaps.append((prev, a))
            prev = max(prev, b)
        if hi > prev:
            gaps.append((prev, hi))
    spans = sorted((s, s + d, name) for p in trace["planes"]
                   if p["name"] == tr.HOST_PLANE for ln in p["lines"]
                   for name, s, d in ln["events"]
                   if name.startswith(PREFIX) and s < hi and s + d > lo)
    idle: dict = collections.defaultdict(float)
    active: list = []
    i = 0
    for a, b in sorted(gaps):
        while i < len(spans) and spans[i][0] < b:
            active.append(spans[i])
            i += 1
        active = [ev for ev in active if ev[1] > a]
        cuts = sorted({a, b} | {t for s, e, _ in active for t in (s, e)
                                 if a < t < b})
        for x, y in zip(cuts, cuts[1:]):
            cover = [(e - s, name) for s, e, name in active
                     if s <= x and e >= y]
            idle[min(cover)[1] if cover else NO_SPAN] += (y - x) * 1e-9
    return [[k, v] for k, v in sorted(idle.items(), key=lambda kv: -kv[1])]
