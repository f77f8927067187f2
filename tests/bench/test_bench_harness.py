"""CPU tests of the benchmark harness's arithmetic (bench/benchkit).

Nothing here measures speed: the traffic generator, the window's
arithmetic (TTFT from due time, censoring at the close, counter deltas),
the shapes the warm-up covers, and the refusal to run without a TPU.
"""
import collections
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "bench"
FIXTURES = Path(__file__).resolve().parent / "fixtures"
sys.path.insert(0, str(BENCH))

from benchkit import harness, traffic, window  # noqa: E402

SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
MIXES = {p.stem: json.loads(p.read_text())
         for p in sorted((BENCH / "traffic").glob("*.json"))}


def _cell(mix_name):
    """The cell file of a cell that serves the mix; a mix kept for a
    later cell gets a nominal rate."""
    for w in SPEC["workloads"]:
        if w["traffic"] == mix_name:
            return json.loads((BENCH / "cells" / f"{w['name']}.json")
                              .read_text())
    return {"rate_per_s": 2.0}


# ----------------------------------------------------------------- traffic

@pytest.mark.parametrize("mix_name", sorted(MIXES))
def test_mix_is_deterministic_by_seed(mix_name):
    mix, cell = MIXES[mix_name], _cell(mix_name)
    a = traffic.generate(mix, cell, seed=2**33 + 5, seconds=30, vocab=1000)
    b = traffic.generate(mix, cell, seed=2**33 + 5, seconds=30, vocab=1000)
    c = traffic.generate(mix, cell, seed=2**33 + 6, seconds=30, vocab=1000)
    assert a == b
    assert a != c


@pytest.mark.parametrize("mix_name", sorted(MIXES))
def test_every_seed_gets_the_same_work(mix_name):
    """Seeds permute one fixed multiset of sizes and gaps."""
    mix, cell = MIXES[mix_name], _cell(mix_name)
    runs = [traffic.generate(mix, cell, seed=s, seconds=30, vocab=1000)
            for s in (1, 2**40 + 1)]
    sizes = [collections.Counter((len(i.prompt), i.max_new_tokens)
                                 for i in r) for r in runs]
    plens = [sorted(len(i.prompt) for i in r) for r in runs]
    olens = [sorted(i.max_new_tokens for i in r) for r in runs]
    gaps = [sorted(np.diff([0.0] + [i.due for i in r])) for r in runs]
    assert plens[0] == plens[1] and olens[0] == olens[1]
    np.testing.assert_allclose(gaps[0], gaps[1])
    assert sizes[0] != sizes[1] or mix["arrivals"]["kind"] == "backlog"
    b = traffic.length_bounds(mix)
    assert set(plens[0]) <= set(b["prompts"])
    assert min(olens[0]) >= b["out_low"] and max(olens[0]) <= b["out_high"]
    fits = [len(i.prompt) + i.max_new_tokens <= mix["max_seq_len"]
            for i in runs[0]]
    assert all(fits)


def test_backlog_stays_inside_the_queue_limit():
    for name, mix in MIXES.items():
        if mix["arrivals"]["kind"] == "backlog":
            conf = json.loads((BENCH / "configs" / "qwen3-0.6b-kv4.json")
                              .read_text())
            assert mix["arrivals"]["requests"] <= conf["serving"]["max_queue"]


# ------------------------------------------------------------ warm-up shapes

def _widths_visited(items, block_size):
    """Decode widths a batch of these requests visits when each runs
    alone: ceil((len + 1) / bs) for len from prompt to prompt + out - 2."""
    seen = set()
    for it in items:
        P = len(it.prompt)
        for n in range(P, P + it.max_new_tokens - 1):
            seen.add(-(-(n + 1) // block_size))
    return seen


@pytest.mark.parametrize("mix_name", sorted(MIXES))
def test_warmup_covers_every_reachable_shape(mix_name):
    mix, cell = MIXES[mix_name], _cell(mix_name)
    bs = 16
    warm = traffic.warmup_items(mix, vocab=1000, seed=3, id0=10**9)
    window_items = traffic.generate(mix, cell, seed=3, seconds=30,
                                    vocab=1000)
    # every prefill length of the window is warmed
    assert ({len(i.prompt) for i in window_items}
            <= {len(i.prompt) for i in warm})
    # every decode width the window's requests visit is warmed
    reach = set(traffic.decode_widths(mix, bs))
    assert _widths_visited(window_items, bs) <= reach
    # and every page count a finishing request can free is listed
    counts = set(traffic.page_counts(mix, bs))
    assert {-(-(len(i.prompt) + i.max_new_tokens) // bs)
            for i in window_items} <= counts
    assert all(len(i.prompt) + i.max_new_tokens <= mix["max_seq_len"]
               for i in warm)


def test_decode_widths_of_the_cells():
    assert traffic.decode_widths(MIXES["longctx"], 16) == range(225, 265)
    assert traffic.decode_widths(MIXES["chat"], 16) == range(5, 65)


# ------------------------------------------------------- window arithmetic

def _rec(due, tokens=(), arrival=None, start=None, rejected=False, P=8):
    return window.Record(due=due, arrival=arrival, prefill_start=start,
                         token_times=tuple(tokens), rejected=rejected,
                         prompt_len=P)


def test_ttft_is_from_due_time_and_censored_at_close():
    recs = [
        _rec(0.0, (0.5, 0.6, 0.7), arrival=0.2, start=0.3),   # 0.5
        _rec(1.0, (3.0,), arrival=1.1, start=2.5),           # censored: 1.0
        _rec(1.5, (), arrival=1.6),                          # censored: 0.5
        _rec(1.8, (), rejected=True, arrival=1.8),           # refused: 0.2
        _rec(2.5, (2.6,), arrival=2.55, start=2.55),         # after close
    ]
    w = window.Window(recs, close=2.0)
    assert len(w.attempted()) == 4 and w.failed() == 1
    np.testing.assert_allclose(sorted(w.ttfts()), [0.2, 0.5, 0.5, 1.0])
    np.testing.assert_allclose(sorted(w.censored("arrival")),
                               [0.0, 0.1, 0.1, 0.2])
    np.testing.assert_allclose(sorted(w.censored("prefill_start")),
                               [0.2, 0.3, 0.5, 1.0])
    assert w.output_tokens() == 3
    np.testing.assert_allclose(sorted(w.gaps()), [0.1, 0.1])


def test_offline_window_counts_only_slotted_requests():
    recs = [_rec(0.0, (1.0,), start=0.1), _rec(0.0, (), start=None),
            _rec(0.0, (), start=2.5)]
    w = window.Window(recs, close=2.0, offline=True)
    assert len(w.attempted()) == 1 and len(w.due()) == 3


def test_records_rebase_engine_traces_onto_due_times():
    Tr = collections.namedtuple("Tr", "arrival_t prefill_start_t "
                                      "first_token_t gaps")
    items = [traffic.Item(7, (1, 2, 3), 4, 0.25)]
    traces = {7: Tr(0.3, 0.35, 0.5, [0.1, 0.2, 0.05])}
    (r,) = window.records_from_engine(items, traces, rejected=[])
    np.testing.assert_allclose(r.token_times, [0.5, 0.6, 0.8, 0.85])
    assert r.due == 0.25 and r.prompt_len == 3


class _FakeWorker:
    def __init__(self):
        self.counters = {"decode_steps": 10, "seq_decode_steps": 40}


def test_tracer_closes_at_first_step_past_deadline_with_counter_deltas():
    tr = window.WindowTracer()
    w = _FakeWorker()
    tr.arm(w, seconds=0.05)
    tr.complete("decode/w0", "decode_step", tr.now())    # before deadline
    w.counters = {"decode_steps": 13, "seq_decode_steps": 58}
    time.sleep(0.06)
    tr.complete("prefill/w0", "dispatch", tr.now())      # not a decode step
    with pytest.raises(window.WindowClosed):
        tr.complete("decode/w0", "decode_step", tr.now())
    assert tr.close_t >= 0.05 and len(tr.step_s) == 2
    assert window.counter_delta(tr.counters_open, tr.counters_close,
                                "decode_steps") == 3
    assert window.counter_delta(tr.counters_open, tr.counters_close,
                                "seq_decode_steps") == 18
    tr.complete("decode/w0", "decode_step", tr.now())    # closed: no-op


# ------------------------------------------------------- what is checked

def test_check_sample_takes_longest_and_in_flight_requests():
    from benchkit import check

    items = [traffic.Item(i, tuple(range(10 + i)), 50, 0.0)
             for i in range(12)]
    # requests 0-3 finished, 4-8 still decoding at the close (fewer
    # tokens than asked for), 9 got only its first token, 10-11 none
    outputs = {i: [1] * 50 for i in range(4)}
    outputs.update({i: [1] * (20 + i) for i in range(4, 9)})
    outputs[9] = [1]
    picked = check.sample(items, outputs, seed=2**33 + 1, min_tokens=10**6,
                          max_requests=4, first_requests=3)
    whole, firsts = picked[:4], picked[4:]
    # the longest whole request leads; a request still decoding counts
    assert whole[0][0].id == 3
    assert len(firsts) == 3 and all(len(s) == 1 for _, s in firsts)
    ids = [it.id for it, _ in picked]
    assert len(set(ids)) == len(ids) and not {10, 11} & set(ids)
    again = check.sample(items, outputs, seed=2**33 + 1, min_tokens=10**6,
                         max_requests=4, first_requests=3)
    assert [it.id for it, _ in again] == ids
    other = check.sample(items, outputs, seed=2**33 + 2, min_tokens=10**6,
                         max_requests=4, first_requests=3)
    assert other[0][0].id == 3
    assert check.sample(items, {}, seed=1, min_tokens=1,
                        max_requests=1) == []


def test_decode_step_flops_agree_with_the_per_request_count():
    """One step over sequences at lengths P is what decoding one token
    after each prompt P costs, in the per-request count serve_mfu uses."""
    from benchkit import costs

    conf = json.loads((BENCH / "configs" / "qwen3-0.6b-kv4.json").read_text())
    m = harness.reference(conf).dims(conf)
    lens = [64, 3584, 1000]
    assert costs.decode_step_flops(m, lens) == sum(
        costs.decode_flops(m, n, 1) for n in lens)
    assert costs.decode_step_flops(m, []) == 0


# --------------------------------------------------------------- no chip

def test_run_without_tpu_exits_nonzero_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         SPEC["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=REPO, timeout=240)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_every_metric_and_cell_has_its_files():
    for w in SPEC["workloads"]:
        assert (BENCH / "cells" / f"{w['name']}.json").is_file()
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    for c in SPEC["configs"]:
        conf = json.loads((REPO / c["file"]).read_text())
        assert conf["name"] == c["name"]
        assert (BENCH / "references" / f"{conf['reference']}.py").is_file()
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()


# ------------------------------------------------ a whole run on the CPU

def test_tiny_cell_runs_end_to_end_without_compiling_in_window(own_cache):
    """The harness drives a real (tiny, CPU) engine through set-up, the
    window and the check: the warm-up leaves nothing to compile."""
    spec = json.loads((FIXTURES / "BENCHMARK.json").read_text())
    out = harness.run_cell("qwen3-tiny-kv4.tiny", 2**35 + 11, 3.0, True,
                           t_start=time.perf_counter(), spec=spec,
                           files=FIXTURES, require_tpu=False)
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    m = out["metrics"]
    assert m["compiles_in_window"]["value"] == 0
    assert 0 < m["batch_occupancy"]["value"] <= 100
    assert 0 <= m["frozen_page_share"]["value"] <= 100
    assert list(out)[-1] == "compared"
