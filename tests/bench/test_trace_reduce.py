"""The profiler-trace reducer (bench/benchkit/trace.py).

One synthetic trace pins the arithmetic (busy union of overlapping ops,
the window from the marker span, idle gaps named by the host event that
overlaps them most); the recorded fixture, ~150 ms of a TPU v5e trace of
the qwen3-0.6b longctx cell, shows the reducer reads what a chip run
writes: programs, the fused paged-decode kernel, the freeze programs.
"""
from pathlib import Path

import pytest

from benchkit import trace as tr

FIXTURE = (Path(__file__).resolve().parents[2] / "bench" / "fixtures"
           / "trace_qwen3_longctx.json.gz")
MS = 1_000_000


def _synthetic():
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_step(1)", 10 * MS, 30 * MS],
                                           ["jit_freeze(7)", 50 * MS,
                                            20 * MS]]},
        {"name": "XLA Ops", "events": [
            ["%fusion.1 = f32[8] fusion(x)", 10 * MS, 20 * MS],
            ["%while.2 = (s32[]) while(t)", 10 * MS, 30 * MS],
            ["%my_kernel.3 = bf16[8] custom-call(y)", 25 * MS, 15 * MS],
            ["%fusion.1 = f32[4] fusion(z)", 50 * MS, 20 * MS]]}]}
    host = {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        [tr.MARKER, 0, 100 * MS],
        ["$engine.py:247 run", 0, 100 * MS],
        ["$workers.py:407 _decode_step", 40 * MS, 10 * MS],
        ["AllocateBufferAwait", 70 * MS, 25 * MS]]}]}
    return {"planes": [dev, host]}


def test_busy_union_window_and_idle_gaps():
    r = tr.reduce(_synthetic())
    assert r.window_s == pytest.approx(0.100)
    # ops cover 10-40 and 50-70 ms: 50 ms busy of 100
    assert r.busy_s == pytest.approx(0.050)
    assert r.idle_share == pytest.approx(0.5)
    # the loop container is not an op of its own
    assert r.op_s == pytest.approx({"jit_step/fusion.1": 0.020,
                                    "jit_step/my_kernel.3": 0.015,
                                    "jit_freeze/fusion.1": 0.020})
    assert r.module_s == pytest.approx({"jit_step": 0.030,
                                        "jit_freeze": 0.020})
    assert r.op_time("my_kernel") == pytest.approx(0.015)
    gaps = dict(r.idle_gaps)
    # 0-10 and 95-100 only under run(); 40-50 under _decode_step, the
    # innermost event then; 70-95 waiting on an allocation
    assert gaps == pytest.approx({"$engine.py:247 run": 0.015,
                                  "$workers.py:407 _decode_step": 0.010,
                                  "AllocateBufferAwait": 0.025})
    b = r.breakdown()
    assert [k for k, _ in b["device_ops"]][0] in ("jit_step/fusion.1",
                                                  "jit_freeze/fusion.1")
    assert len(b["idle_gaps"]) == 3


def test_recorded_chip_trace():
    r = tr.reduce(tr.load(FIXTURE))
    assert r.n_devices == 1
    assert 0.14 < r.window_s < 0.15
    assert 0 < r.busy_s <= r.window_s
    assert 0 < r.idle_share < 0.2
    assert r.op_time(r"paged_decode") > 0.01
    assert r.module_time(r"_solve_leaf_pages|_install_leaf") > 0.05
    assert r.module_time(r"_decode_step_fn") > 0.05
    b = r.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(isinstance(k, str) and v > 0 for k, v in b["device_ops"])
    # device op time never exceeds the window it was clipped to
    assert max(v for _, v in b["device_ops"]) <= r.window_s


def test_trace_without_device_plane_is_refused():
    t = _synthetic()
    t["planes"] = [p for p in t["planes"] if p["name"] == "/host:CPU"]
    with pytest.raises(ValueError):
        tr.reduce(t)
