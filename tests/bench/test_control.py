"""The correctness check fails what it must fail.

The harness's own run (``run_cell``, its look for a chip skipped) drives
the tiny CPU cell of ``tests/bench/fixtures`` with the timed path broken
underneath, and ``correct`` comes out false:

  * a step that returns its state unchanged: every prefill and decode
    step's KV writes are dropped (the engine keeps the pool it held);
  * a token altered where it is produced: the decode step's logits favour
    one fixed token;
  * the same fault in half of the slots only: the decode statistic is the
    worst request's, so half a batch gone wrong is not averaged away.

Nothing is exchanged between chips in a serving cell on one chip, so
that fault of the list does not exist here.

The control (the reference one precision lower) is read at the cells'
own sizes on the chip by ``bench/control.py``; here it is read at the
tiny cell's size, on three seeds, and the harness's own comparison
(``control_correct``) fails it on each.
"""
import json
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
FIXTURES = Path(__file__).resolve().parent / "fixtures"
sys.path.insert(0, str(REPO / "bench"))

from benchkit import harness  # noqa: E402

CELL = "qwen3-tiny-kv4.tiny"
SPEC = json.loads((FIXTURES / "BENCHMARK.json").read_text())


def _run(seed, **kw):
    return harness.run_cell(CELL, seed, 3.0, False,
                            t_start=time.perf_counter(), spec=SPEC,
                            files=FIXTURES, require_tpu=False,
                            **kw)


def test_sound_run_is_correct(own_cache):
    out = _run(2**34 + 1)
    assert out["correct"] is True
    for v in out["compared"].values():
        assert v["value"] >= v["limit"] if v["rule"] == ">=" else \
            v["value"] <= v["limit"]


def test_state_left_unchanged_is_not_correct(own_cache, monkeypatch):
    from repro.serving import workers

    monkeypatch.setattr(workers, "merge_pools", lambda held, returned: held)
    out = _run(2**34 + 2)
    assert out["correct"] is False
    gap = out["compared"]["decode_worst_request_mean_gap"]
    assert gap["value"] > gap["limit"]


def test_altered_token_is_not_correct(own_cache, monkeypatch):
    from repro.serving import workers

    real = workers._decode_step_fn

    def altered(params, toks, tree, lens, *, cfg):
        logits, new = real(params, toks, tree, lens, cfg=cfg)
        return logits.at[..., 3].add(1e4), new

    monkeypatch.setattr(workers, "_decode_step_fn", altered)
    out = _run(2**34 + 3)
    assert out["correct"] is False
    gap = out["compared"]["decode_worst_request_mean_gap"]
    assert gap["value"] > gap["limit"]


def test_altered_token_in_half_the_slots_is_not_correct(own_cache,
                                                        monkeypatch):
    from repro.serving import workers

    real = workers._decode_step_fn

    def altered(params, toks, tree, lens, *, cfg):
        logits, new = real(params, toks, tree, lens, cfg=cfg)
        return logits.at[0::2, ..., 3].add(1e4), new

    monkeypatch.setattr(workers, "_decode_step_fn", altered)
    out = _run(2**34 + 7)
    assert out["correct"] is False
    gap = out["compared"]["decode_worst_request_mean_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("seed", [2**34 + 4, 2**34 + 5, 2**34 + 6])
def test_control_is_not_correct(own_cache, seed):
    """The reference one precision lower, read at the served positions
    and put through the cell's own comparison, fails it while the program
    on the same run passes."""
    out = _run(seed, control=True)
    assert out["correct"] is True
    assert out["control_correct"] is False, out["control_compared"]
    assert set(out["control_compared"]) == set(out["compared"])
