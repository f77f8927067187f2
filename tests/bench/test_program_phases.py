"""The program's own phase spans and counters, as the benchmark reads them.

The engine names its phases with profiler annotations (``repro:<track>:
<phase>``) and keeps per-layer counters beside them: host time of each
phase, the pages each decode step reads and how many are frozen, rows held
against rows reserved. Here: the phase prefix the program writes is the
one the benchmark reads, idle time is laid against those phases
(``benchkit/phases.py``), and a whole harness run reads the new per-layer
metrics, the program's frozen-read share equal to the harness's own count.
"""
import json
import time
from pathlib import Path

import pytest

from benchkit import harness, phases, trace as tr

REPO = Path(__file__).resolve().parents[2]
FIXTURES = Path(__file__).resolve().parent / "fixtures"
RECORDED = REPO / "bench" / "fixtures" / "trace_qwen3_longctx.json.gz"
COUNTED = ("decode_host_ms_per_step", "prefill_time_share",
           "freeze_host_ms_per_step", "kv_frozen_read_share",
           "kv_reserved_fill")
MS = 1_000_000


def test_phase_prefix_is_the_programs():
    from repro.obs.trace import PREFIX

    assert phases.PREFIX == PREFIX


def _synthetic():
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_step(1)", 10 * MS, 30 * MS]]},
        {"name": "XLA Ops", "events": [
            ["%fusion.1 = f32[8] fusion(x)", 10 * MS, 30 * MS],
            ["%fusion.2 = f32[8] fusion(y)", 60 * MS, 20 * MS]]}]}
    host = {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        [tr.MARKER, 0, 100 * MS],
        ["repro:engine:iteration", 0, 95 * MS],
        ["repro:decode/w0:decode_step", 5 * MS, 50 * MS],
        ["repro:decode/w0:commit", 42 * MS, 8 * MS],
        ["$workers.py:480 _count_reads", 44 * MS, 2 * MS],
        ["repro:freeze/w0:flush", 55 * MS, 10 * MS],
        ["AllocateBufferAwait", 56 * MS, 3 * MS]]}]}
    return {"planes": [dev, host]}


def test_idle_is_laid_against_the_innermost_program_phase():
    t = _synthetic()
    spans = dict(phases.idle_spans(t))
    # idle 0-10, 40-60, 80-100 ms: the step holds 5-10, 40-42 and 50-55;
    # its commit 42-50, Python frame included; the flush 55-60, the
    # allocator's wait included; the bare iteration 0-5 and 80-95; 95-100
    # is outside every program phase
    assert spans == pytest.approx({
        "repro:engine:iteration": 0.005 + 0.015,
        "repro:decode/w0:decode_step": 0.005 + 0.002 + 0.005,
        "repro:decode/w0:commit": 0.008,
        "repro:freeze/w0:flush": 0.005,
        phases.NO_SPAN: 0.005})
    r = tr.reduce(t)
    # the same idle, all of it, that the reducer names by host activity
    assert sum(spans.values()) == pytest.approx(sum(v for _, v in
                                                    r.idle_gaps))
    assert sum(spans.values()) == pytest.approx(r.window_s - r.busy_s)


def test_recorded_trace_has_no_program_phase():
    """The recorded chip trace predates the annotations: all of its idle
    is outside any program phase, and sums to the reducer's idle."""
    t = tr.load(RECORDED)
    spans = phases.idle_spans(t)
    r = tr.reduce(t)
    assert [k for k, _ in spans] == [phases.NO_SPAN]
    assert spans[0][1] == pytest.approx(r.window_s - r.busy_s)


def test_program_counters_agree_with_the_harness(own_cache):
    """A traced run of the tiny cell reads every counter-based metric, and
    the program's frozen-read share is the harness's own count."""
    spec = json.loads((FIXTURES / "BENCHMARK.json").read_text())
    accepted = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = spec["workloads"][0]["name"]
    spec["per_layer"] += [dict(m, workloads=[cell])
                          for m in accepted["per_layer"]
                          if m["name"] in COUNTED]
    out = harness.run_cell(cell, 2**35 + 13, 3.0, True,
                           t_start=time.perf_counter(), spec=spec,
                           files=FIXTURES, require_tpu=False)
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(COUNTED) <= set(m)
    assert m["kv_frozen_read_share"] == pytest.approx(
        m["frozen_page_share"], abs=1e-9)
    assert 0 < m["kv_frozen_read_share"] < 100
    assert 0 < m["kv_reserved_fill"] < 100
    assert m["decode_host_ms_per_step"] > 0
    assert m["freeze_host_ms_per_step"] > 0
    assert 0 < m["prefill_time_share"] < 100
