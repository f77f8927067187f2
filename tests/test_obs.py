"""Observability subsystem tests: tracer schema + determinism, streaming
metrics, exporters, and trace-vs-counter reconciliation on real engine runs.

The engine tests are the observability analogue of the golden-trace
fixture: a seeded run with a ``FakeClock`` tracer must emit byte-identical
Perfetto JSON across runs (timestamps are event counts, args are
deterministic ids/byte-counts — never wall-clock), and every async
page-freeze span opened during a run must reach exactly one terminal state
(installed | dropped | rolled_back) by drain, reconciling with the
worker's freeze counters.
"""
import json
import time

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

from repro import models
from repro.configs import get_reduced_config
from repro.obs import (FakeClock, MetricsExporter, NULL_TRACER, NullTracer,
                       PREFIX, Registry, Tracer, count_events, phase,
                       prometheus_text, select_events, tracks_of)
from repro.obs.stats import LogHistogram
from repro.serving import ContinuousBatchingEngine, DisaggEngine, derive_draft
from repro.serving.metrics import MetricsCollector, percentile
from repro.serving.scheduler import make_requests

PROMPT_SEED = 42
N_REQ, PROMPT_LEN, GEN = 3, 12, 8
GEOM = dict(max_slots=2, block_size=8, max_seq_len=48)


# ===================================================================== unit


def _make_full_tracer():
    tr = Tracer(clock=FakeClock())
    with tr.span("decode/w0", "decode_step", step=1):
        pass
    t0 = tr.now()
    tr.complete("transfer", "extract", t0, bytes=1024, pages=2)
    tr.instant("router", "admit", rid=0)
    tr.counter("decode/w0", "cache", occupancy=0.5, frozen_pages=3)
    tr.async_begin("freeze/w0", "page_freeze", 7, page=7, slot=0)
    tr.async_instant("freeze/w0", "page_freeze", 7, state="dispatched")
    tr.async_end("freeze/w0", "page_freeze", 7, state="installed")
    return tr


def test_tracer_chrome_schema():
    tr = _make_full_tracer()
    d = tr.to_dict()
    json.dumps(d, allow_nan=False)          # strict JSON throughout
    assert d["displayTimeUnit"] == "ms"
    evs = d["traceEvents"]
    # one labeled lane per component: thread_name + sort metadata per track
    names = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert names == {"decode/w0", "transfer", "router", "freeze/w0"}
    assert any(e["ph"] == "M" and e["name"] == "process_name" for e in evs)
    for e in evs:
        assert {"ph", "name", "pid"} <= set(e)
        if e["ph"] == "X":
            assert "ts" in e and "dur" in e and e["dur"] >= 0
        elif e["ph"] == "i":
            assert e["s"] == "t"
        elif e["ph"] in ("b", "n", "e"):
            # async spans need (cat, id) so Perfetto can pair them
            assert isinstance(e["id"], str) and e["cat"] == "freeze/w0"
        elif e["ph"] == "C":
            assert set(e["args"]) == {"occupancy", "frozen_pages"}
    assert count_events(tr.events, track="freeze/w0", ph="b") == 1
    assert count_events(tr.events, track="freeze/w0", ph="e") == 1
    assert count_events(tr.events, name="decode_step", ph="X") == 1
    # identical event sequences on fake clocks serialize byte-identically
    a = json.dumps(tr.to_dict(), sort_keys=True, separators=(",", ":"))
    b = json.dumps(_make_full_tracer().to_dict(), sort_keys=True,
                   separators=(",", ":"))
    assert a == b


def test_null_tracer_is_inert(tmp_path):
    assert NULL_TRACER.enabled is False
    s1 = NULL_TRACER.span("decode/w0", "x", a=1)
    s2 = NULL_TRACER.span("router", "y")
    assert s1 is s2                       # one shared span: zero allocation
    with s1:
        pass
    NULL_TRACER.complete("t", "n", NULL_TRACER.now())
    NULL_TRACER.instant("t", "n")
    NULL_TRACER.counter("t", "n", v=1)
    NULL_TRACER.async_begin("t", "n", 1)
    NULL_TRACER.async_end("t", "n", 1)
    assert NULL_TRACER.events == ()
    assert NULL_TRACER.to_dict()["traceEvents"] == []
    path = tmp_path / "never.json"
    NULL_TRACER.write(str(path))
    assert not path.exists()


class _CountingTracer:
    """Records the counter's value at each span's end."""

    enabled = False

    def __init__(self, counters):
        self.counters, self.seen = counters, []

    def now(self):
        return 0.0

    def complete(self, track, name, t0, **args):
        self.seen.append((track, name, self.counters["k"], args))


def test_phase_counts_time_without_a_tracer():
    """With the no-op tracer a phase still adds its host time to the
    counter; a tracer sees each span end only after its time is counted,
    with the args set inside the phase."""
    c = {"k": 0}
    with phase(NULL_TRACER, "decode/w0", "dispatch", c, "k", blocks=3):
        time.sleep(0.002)
    assert c["k"] >= 2_000_000
    tr = _CountingTracer(c)
    with phase(tr, "decode/w0", "commit", c, "k") as p:
        time.sleep(0.001)
        p.args["finished"] = 2
    assert tr.seen == [("decode/w0", "commit", c["k"], {"finished": 2})]
    assert c["k"] >= 3_000_000
    # a phase left by an exception counts its time and records no span
    with pytest.raises(KeyError):
        with phase(tr, "decode/w0", "sync", c, "k"):
            raise KeyError("x")
    assert len(tr.seen) == 1
    # no counter key: only the span
    with phase(tr, "engine", "iteration"):
        pass
    assert tr.seen[-1][:2] == ("engine", "iteration")


def test_log_histogram_percentiles():
    h = LogHistogram()
    assert h.percentile(50) is None       # empty: None, never NaN
    vals = [i / 1000.0 for i in range(1, 101)]      # 1ms .. 100ms
    for v in vals:
        h.observe(v)
    assert h.n == 100
    assert h.vmin == vals[0] and h.vmax == vals[-1]
    assert abs(h.mean - np.mean(vals)) < 1e-12
    # interior percentiles answer within the bucket's relative error
    for p in (50, 90, 99):
        want = float(np.percentile(vals, p))
        assert abs(h.percentile(p) / want - 1) < 0.16, (p, h.percentile(p))
    # extremes clamp to the exact observed range
    assert h.percentile(0) >= h.vmin
    assert h.percentile(100) == h.vmax
    # out-of-range values land in under/overflow but keep exact min/max
    h.observe(1e-9)
    h.observe(1e9)
    assert h.underflow == 1 and h.overflow == 1
    assert h.percentile(100) == 1e9
    json.dumps(h.snapshot(), allow_nan=False)


def test_log_histogram_windowed_delta():
    h = LogHistogram()
    for _ in range(10):
        h.observe(0.01)
    prev = h.state()
    for _ in range(10):
        h.observe(1.0)
    d = h.delta(prev)
    assert d["n"] == 10
    # the window sees only the second batch
    assert abs(h.percentile(50, **d) / 1.0 - 1) < 0.16
    # the all-time view still covers both
    assert h.percentile(10) < 0.02


def test_registry_and_prometheus_text():
    reg = Registry()
    reg.counter("requests").inc(3)
    reg.gauge("occupancy").set(0.25)
    reg.gauge("occupancy").set(0.75)
    for v in (0.01, 0.02, 0.03):
        reg.histogram("ttft_s").observe(v)
    assert "requests" in reg and "missing" not in reg
    snap = reg.snapshot()
    json.dumps(snap, allow_nan=False)
    txt = prometheus_text(snap)
    assert "repro_requests_total 3" in txt
    assert "repro_occupancy 0.75" in txt
    assert "repro_occupancy_mean 0.5" in txt
    assert 'repro_ttft_s{quantile="0.5"}' in txt
    assert "repro_ttft_s_count 3" in txt
    # bare scalars (MetricsCollector.snapshot running totals) render too
    assert "repro_completed 4" in prometheus_text({"completed": 4})


def test_exporter_interval_and_windows(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    reg = Registry()
    exp = MetricsExporter(path, interval_s=1.0, clock=FakeClock(tick=0.4),
                          registry=reg)
    reg.histogram("itl_s").observe(0.01)
    assert exp.maybe_emit() is not None          # first call always emits
    reg.histogram("itl_s").observe(0.02)
    assert exp.maybe_emit() is None              # 0.4s < interval
    assert exp.maybe_emit() is None              # 0.8s
    line = exp.maybe_emit()                      # 1.2s elapsed
    assert line is not None and line["seq"] == 1
    # the window covers only what landed since the previous emit
    assert line["window"]["itl_s"]["n"] == 1
    exp.close()
    rows = [json.loads(ln) for ln in open(path)]
    assert [r["seq"] for r in rows] == [0, 1, 2]
    assert rows[0]["window"]["itl_s"]["n"] == 1
    assert "window" not in rows[2]               # nothing new at close


def test_summary_zero_token_guard():
    mc = MetricsCollector()
    mc.arrival(0, 0.0, prompt_len=4)
    mc.finish(0, 1.0)                     # finished without any token
    out = mc.summary()
    assert out == {"completed": 0, "completed_zero_token": 1}
    # mixed population: the zero-token finish is excluded from latencies
    mc.arrival(1, 0.0, prompt_len=4)
    mc.prefill_start(1, 0.1)
    mc.first_token(1, 0.2)
    mc.token(1, 0.3)
    mc.finish(1, 0.3)
    out = mc.summary()
    assert out["completed"] == 1 and out["completed_zero_token"] == 1
    json.dumps(out, allow_nan=False)


def test_percentile_empty_and_strict_json():
    assert percentile([], 50) is None
    mc = MetricsCollector()
    mc.arrival(0, 0.0, prompt_len=4)
    mc.first_token(0, 0.5)
    mc.finish(0, 0.5)                     # exactly one token: no tpot
    out = mc.summary()
    assert "tpot_p50_s" not in out and "tpot_p99_s" not in out
    # the regression this guards: bench artifacts must round-trip strict
    # JSON (json.dumps used to embed NaN here and poison BENCH_*.json)
    assert json.loads(json.dumps(out, allow_nan=False))["completed"] == 1


def test_summary_key_compat_and_streaming_bounds():
    """The rebuilt collector must emit the exact legacy summary() key set
    for a fully-populated run, from O(1)-memory aggregates."""
    mc = MetricsCollector()
    for rid in range(2):
        t = rid * 0.1
        mc.arrival(rid, t, prompt_len=8)
        mc.prefill_start(rid, t + 0.05)
        mc.first_token(rid, t + 0.1)
        for j in range(1, 5):
            mc.token(rid, t + 0.1 + 0.02 * j)
        mc.finish(rid, t + 0.18)
        mc.spec_step(2, 1, rolled_back=rid == 0)
    mc.sample_cache(0.5, 1000.0, 7000.0)
    mc.sample_cache(0.25, 500.0, 3500.0)
    tr = mc.traces[0]
    assert tr.queue_wait + tr.prefill_compute == pytest.approx(tr.ttft)
    out = mc.summary()
    assert set(out) == {
        "completed", "gen_tokens", "makespan_s", "throughput_tok_s",
        "ttft_mean_s", "ttft_p50_s", "ttft_p99_s",
        "tpot_p50_s", "tpot_p99_s",
        "queue_wait_mean_s", "queue_wait_p50_s", "queue_wait_p99_s",
        "prefill_compute_mean_s", "prefill_compute_p50_s",
        "prefill_compute_p99_s",
        "itl_p50_s", "itl_p99_s", "itl_max_s",
        "spec_steps", "spec_proposed", "spec_accepted", "spec_rollbacks",
        "spec_acceptance_rate",
        "cache_occupancy_mean", "cache_occupancy_max",
        "cache_bytes_final", "cache_bytes_fp_final",
        "cache_compression_mean", "cache_compression_final",
    }
    assert out["cache_compression_final"] == pytest.approx(7.0)
    # streaming: aggregate series live in fixed-size metrics, not lists
    assert not hasattr(mc, "occupancy") and not hasattr(mc, "cache_bytes")
    nbuckets = len(mc.stats["itl_s"].counts)
    for j in range(10_000):
        mc.token(0, 1.0 + j * 0.001)
    assert len(mc.stats["itl_s"].counts) == nbuckets
    # live snapshot() view stays JSON-safe mid-run
    snap = mc.snapshot()
    json.dumps(snap, allow_nan=False)
    assert snap["spec_steps"] == 2
    prometheus_text(snap)


# ================================================================== engines


@pytest.fixture(scope="module")
def model():
    cfg = get_reduced_config("qwen3_0_6b")
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(PROMPT_SEED)
    prompts = [rng.integers(0, cfg.vocab, PROMPT_LEN).tolist()
               for _ in range(N_REQ)]
    return cfg, params, prompts


def _trace_bytes(tracer) -> bytes:
    return json.dumps(tracer.to_dict(), sort_keys=True,
                      separators=(",", ":")).encode()


def _freeze_span_states(events):
    """(begin-count, {span id -> terminal state}) for page_freeze spans."""
    begins = select_events(events, name="page_freeze", ph="b")
    ends = select_events(events, name="page_freeze", ph="e")
    states = {}
    for e in ends:
        assert e["id"] not in states, f"span {e['id']} ended twice"
        states[e["id"]] = e["args"]["state"]
    return len(begins), states


@pytest.mark.serving
def test_colocated_trace_byte_identical(model, tmp_path):
    cfg, params, prompts = model

    def one(tag):
        tr = Tracer(clock=FakeClock())
        eng = ContinuousBatchingEngine(
            params, cfg, kv_quant="kmeans_ls@16", freeze_async=False,
            tracer=tr, **GEOM)
        s = eng.run(make_requests(prompts, GEN))
        path = tmp_path / f"{tag}.json"
        tr.write(str(path))
        return tr, s, path.read_bytes()

    tr, s, raw1 = one("a")
    _, _, raw2 = one("b")
    assert raw1 == raw2, "seeded colocated trace is not byte-deterministic"
    assert json.loads(raw1)["traceEvents"]       # and is real JSON
    # counters reconcile with the trace
    assert count_events(tr.events, name="decode_step", ph="X") \
        == s["decode_steps"]
    assert count_events(tr.events, name="flush", ph="X") \
        == s["freeze_dispatches"]
    nb, states = _freeze_span_states(tr.events)
    assert nb == len(states), "a freeze span never reached a terminal state"
    assert set(states.values()) <= {"installed", "dropped", "rolled_back"}


@pytest.mark.serving
def test_disagg_trace_byte_identical(model, tmp_path):
    cfg, params, prompts = model

    def one(tag):
        tr = Tracer(clock=FakeClock())
        # sync freezes, same as the colocated twin above: the async path's
        # install step is gated on a wall-clock is_ready() poll, so which
        # iteration installs (and hence the event order) is load-dependent
        eng = DisaggEngine(
            params, cfg, prefill_workers=1, decode_workers=1,
            migrate="frozen", kv_quant="kmeans_ls@16", freeze_async=False,
            tracer=tr, **GEOM)
        # one request: the prefill/harvest interleaving is trivially
        # serial, so even the disagg composition pins exact bytes
        eng.run(make_requests(prompts[:1], GEN))
        path = tmp_path / f"{tag}.json"
        tr.write(str(path))
        return tr, path.read_bytes()

    tr, raw1 = one("a")
    _, raw2 = one("b")
    assert raw1 == raw2, "seeded disagg trace is not byte-deterministic"
    got = set(tracks_of(tr))
    assert {"router", "prefill/w0", "decode/w0", "transfer"} <= got
    # frozen migration crosses the seam as codes+codebooks: the extract
    # span must record fewer wire bytes than the fp-equivalent rows
    ex = select_events(tr.events, name="extract", ph="X")
    assert ex and all(e["args"]["mode"] == "frozen" for e in ex)


@pytest.mark.serving
def test_freeze_spans_terminal_by_drain(model):
    """Async freezing: every page_freeze span opened anywhere in the run
    (incl. pages whose sequence finished with the solve in flight) must be
    closed terminally by drain, and installs must match the counter."""
    cfg, params, prompts = model
    tr = Tracer(clock=FakeClock())
    eng = ContinuousBatchingEngine(
        params, cfg, kv_quant="kmeans_ls@16", freeze_async=True,
        tracer=tr, **GEOM)
    s = eng.run(make_requests(prompts, GEN))
    nb, states = _freeze_span_states(tr.events)
    assert nb > 0, "run froze nothing — geometry no longer exercises freezes"
    assert nb == len(states)
    assert set(states.values()) <= {"installed", "dropped", "rolled_back"}
    assert count_events(tr.events, name="flush", ph="X") \
        == s["freeze_dispatches"]
    assert count_events(tr.events, name="install", ph="X") \
        == s["freeze_installs"]
    # dispatched markers never exceed opened spans
    assert count_events(tr.events, name="page_freeze", ph="n") <= nb


@pytest.mark.serving
def test_six_component_spec_disagg_trace(model, tmp_path):
    """The acceptance composition (disagg + speculative + frozen
    migration) emits all six component tracks and reconciles every
    speculative/freeze counter against the trace."""
    cfg, params, prompts = model
    draft = derive_draft(params, cfg)
    tr = Tracer(clock=FakeClock())
    eng = DisaggEngine(
        params, cfg, prefill_workers=1, decode_workers=1, migrate="frozen",
        kv_quant="kmeans_ls@16", speculate=2, draft=draft, tracer=tr,
        **GEOM)
    s = eng.run(make_requests(prompts, GEN))
    assert s["completed"] == N_REQ
    got = set(tracks_of(tr))
    assert {"router", "prefill/w0", "decode/w0", "freeze/w0", "spec/w0",
            "transfer"} <= got
    # speculative reconciliation: one accept instant per verified slice,
    # one rollback instant per rolled-back slice
    assert count_events(tr.events, name="accept", ph="i") == s["spec_steps"]
    assert count_events(tr.events, name="rollback", ph="i") \
        == s["spec_rollbacks"]
    assert count_events(tr.events, name="decode_step", ph="X") \
        == s["decode_steps"]
    assert count_events(tr.events, name="flush", ph="X") \
        == s["freeze_dispatches"]
    nb, states = _freeze_span_states(tr.events)
    assert nb == len(states)
    assert set(states.values()) <= {"installed", "dropped", "rolled_back"}
    path = tmp_path / "trace.json"
    tr.write(str(path))
    d = json.load(open(path))
    assert {e["args"]["name"] for e in d["traceEvents"]
            if e["ph"] == "M" and e["name"] == "thread_name"} == got


@pytest.mark.serving
def test_engine_exporter_jsonl(model, tmp_path):
    """An exporter hung off the run loop lands ≥1 strict-JSON line with
    the live totals and the per-step cache gauges."""
    cfg, params, prompts = model
    path = str(tmp_path / "m.jsonl")
    exp = MetricsExporter(path, interval_s=0.0)       # emit every step
    eng = ContinuousBatchingEngine(
        params, cfg, kv_quant="kmeans_ls@16", exporter=exp, **GEOM)
    eng.run(make_requests(prompts, GEN))
    exp.close(eng.metrics)
    rows = [json.loads(ln) for ln in open(path)]
    assert rows and rows[-1]["completed"] == N_REQ
    assert rows[-1]["gen_tokens"] == N_REQ * GEN
    assert "cache_occupancy" in rows[-1]
    prometheus_text(eng.metrics.snapshot())


def _host_phases(directory) -> list[tuple[str, int, int]]:
    """(name, start_ns, end_ns) of the program's annotations on the
    profiler's host plane."""
    path = sorted(directory.rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append((e.name, int(e.start_ns),
                                int(e.start_ns + e.duration_ns)))
    return out


@pytest.mark.serving
def test_phases_on_the_profiler_clock(model, tmp_path):
    """Under a profiler session the engine's phases are annotations on
    the profiler's host plane: every decode step holds its dispatch, sync
    and commit, and each freeze install is one span."""
    cfg, params, prompts = model
    eng = ContinuousBatchingEngine(
        params, cfg, kv_quant="kmeans_ls@16", freeze_async=True, **GEOM)
    with jax.profiler.trace(str(tmp_path)):
        s = eng.run(make_requests(prompts, GEN))
    ev = _host_phases(tmp_path)
    by = {}
    for name, a, b in ev:
        by.setdefault(name, []).append((a, b))
    steps = by["repro:decode/w0:decode_step"]
    assert len(steps) == s["decode_steps"] > 0
    for part in ("dispatch", "sync", "commit"):
        inner = by[f"repro:decode/w0:{part}"]
        assert len(inner) == len(steps)
        assert all(any(sa <= a and b <= sb for sa, sb in steps)
                   for a, b in inner), part
    assert len(by.get("repro:freeze/w0:install", [])) \
        == s["freeze_installs"] > 0
    assert len(by["repro:freeze/w0:flush"]) == s["freeze_dispatches"]
    assert {"repro:engine:iteration", "repro:engine:intake",
            "repro:prefill/w0:dispatch", "repro:prefill/w0:harvest",
            "repro:decode/w0:attach"} <= set(by)
    # the counters kept beside the phases
    for k in ("decode_dispatch_ns", "decode_sync_ns", "decode_commit_ns",
              "freeze_flush_ns", "freeze_install_ns", "prefill_ns"):
        assert s[k] > 0, k
    assert s["prefill_tokens"] == N_REQ * PROMPT_LEN


class _ReadsTracer(NullTracer):
    """At each decode dispatch, counts the step's page reads and rows the
    way a reader outside the program would: a loop over the live slots'
    tables against the worker's frozen set."""

    def __init__(self, W):
        self.W, self.worker = W, None
        self.reads = {"kv_pages_read": 0, "kv_pages_read_frozen": 0,
                      "kv_rows_used": 0, "kv_rows_reserved": 0}

    def complete(self, track, name, t0, **args):
        if name != "dispatch" or not track.startswith("decode/"):
            return
        w, r = self.worker, self.reads
        for i in w.sched.active_slots():
            n = int(w.lens[i])
            pages = -(-(n + self.W) // w.block_size)
            r["kv_pages_read"] += pages
            r["kv_pages_read_frozen"] += sum(
                int(b) in w.freezes.installed for b in w.table[i, :pages])
            r["kv_rows_used"] += n
            r["kv_rows_reserved"] += len(w.slots[i].blocks) * w.block_size


@pytest.mark.serving
@pytest.mark.parametrize("speculate", [0, 2])
def test_kv_read_counters_match_a_loop(model, speculate):
    """The decode dispatch's numpy counts of pages read, frozen pages
    read, rows held and rows reserved equal a per-page loop's."""
    cfg, params, prompts = model
    tr = _ReadsTracer(speculate + 1)
    kw = dict(speculate=speculate, draft=derive_draft(params, cfg)) \
        if speculate else {}
    eng = ContinuousBatchingEngine(
        params, cfg, kv_quant="kmeans_ls@16", freeze_async=True, tracer=tr,
        **GEOM, **kw)
    tr.worker = eng.worker
    s = eng.run(make_requests(prompts, GEN))
    assert {k: s[k] for k in tr.reads} == tr.reads
    assert 0 < s["kv_pages_read_frozen"] < s["kv_pages_read"]
    assert 0 < s["kv_rows_used"] < s["kv_rows_reserved"]


@pytest.mark.serving
def test_trace_reconcile_counts_install_spans(model):
    """``serve --trace-out``'s reconciliation holds one ``install`` span
    per counted freeze install: a trace missing one does not reconcile."""
    from repro.launch.serve import _trace_reconcile

    cfg, params, prompts = model
    tr = Tracer(clock=FakeClock())
    eng = ContinuousBatchingEngine(
        params, cfg, kv_quant="kmeans_ls@16", freeze_async=True, tracer=tr,
        **GEOM)
    s = eng.run(make_requests(prompts, GEN))
    assert s["freeze_installs"] > 0
    assert _trace_reconcile(tr, s, speculate=0)
    i = next(j for j, e in enumerate(tr.events)
             if e["name"] == "install" and e["ph"] == "X")
    del tr.events[i]
    assert not _trace_reconcile(tr, s, speculate=0)
