"""One run of one cell: set-up, the measured window, the correctness check
and the metrics, as ``bench/run.py`` prints them.

Everything specific to a configuration, a traffic mix, a cell or a metric
is a file found by its name in ``BENCHMARK.json``:

  bench/configs/<config>.json     sizes as run, serving options, reference
  bench/references/<ref>.py       the plain reference and weight maker
  bench/traffic/<traffic>.json    the mix (see ``traffic.py``)
  bench/cells/<workload>.json     the cell's rate and correctness limits
  bench/metrics/<metric>.py       ``read(ctx)`` -> number, or None
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import statistics
import sys
import time
import types
from pathlib import Path

from . import check, costs, system, traffic
from .window import (Window, WindowClosed, WindowTracer, counter_delta,
                     records_from_engine)

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
TRACE_S = 8.0            # traced part of a --trace 1 window (its end)
WARMUP_ID0 = 1 << 40     # request ids of the warm-up run


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(*a) -> None:
    print("[bench]", *a, file=sys.stderr, flush=True)


def _json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def _module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace("-", "_")
        .replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, spec: dict, files: Path = BENCH):
    """The cell's entry, configuration, mix, cell file and metrics."""
    ws = {w["name"]: w for w in spec["workloads"]}
    if workload not in ws:
        raise KeyError(f"no workload {workload!r}; known: {sorted(ws)}")
    w = ws[workload]
    confs = {c["name"]: c for c in spec["configs"]}
    conf = _json(files / "configs" / f"{w['config']}.json")
    if w["config"] not in confs or conf["name"] != w["config"]:
        raise ValueError(f"workload {workload!r} names configuration "
                         f"{w['config']!r}, which BENCHMARK.json or its file "
                         f"does not define")

    def mine(ms):
        return [m for m in ms if workload in m.get("workloads", [workload])]

    mix = _json(files / "traffic" / f"{w['traffic']}.json")
    if mix["max_seq_len"] > conf.get("max_position_embeddings",
                                     mix["max_seq_len"]):
        raise ValueError(f"mix {w['traffic']!r} needs {mix['max_seq_len']} "
                         f"positions; {w['config']!r} has "
                         f"{conf['max_position_embeddings']}")
    return types.SimpleNamespace(
        workload=w, conf=conf, mix=mix,
        cell=_json(files / "cells" / f"{workload}.json"),
        end_to_end=mine(spec["end_to_end"]), per_layer=mine(spec["per_layer"]))


def reference(conf: dict):
    return _module(BENCH / "references" / f"{conf['reference']}.py")


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if require_tpu and (d0.platform != "tpu" or len(devs) < chips):
        raise NoChip(f"cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {d0.platform} device(s)")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int | None:
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def use_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else one fixed directory inside the checkout. Every program
    is cached, however quick its compile."""
    import jax

    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return d


class CompileCount:
    """Executables JAX builds or loads (a persistent-cache hit counts: it
    is still a program the run did not have). JAX's monitoring listeners
    are process-wide, so the count is too: one listener, registered once,
    read as differences."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    n = 0
    _on = False

    @classmethod
    def start(cls) -> None:
        if not cls._on:
            import jax

            jax.monitoring.register_event_duration_secs_listener(cls._hit)
            cls._on = True

    @classmethod
    def _hit(cls, name, secs, **_):
        if name == cls.EVENT:
            cls.n += 1


class _Profile:
    """The traced part of a --trace 1 window, and the per-step page reads
    the harness tracer records for it."""

    def __init__(self, directory: Path, start_at: float, block_size: int,
                 dims: dict, num_values: int):
        self.dir, self.start_at = directory, start_at
        self.bs, self.dims, self.nv = block_size, dims, num_values
        self.on = False
        self.ann = None
        self.ok = True
        self.pages_read = self.pages_frozen = 0
        self.steps = 0
        self.bytes = self.flops = 0
        self.step_flops = 0

    def on_step(self, t: float) -> None:
        if not self.on and t >= self.start_at:
            import jax

            shutil.rmtree(self.dir, ignore_errors=True)
            jax.profiler.start_trace(str(self.dir))
            from .trace import MARKER

            self.ann = jax.profiler.TraceAnnotation(MARKER)
            self.ann.__enter__()
            self.on = True

    def on_dispatch(self, w) -> None:
        if not self.ok:
            return
        try:
            frozen = w._frozen_pages
            lens, fz = [], []
            for i in w.sched.active_slots():
                n = int(w.lens[i])
                pages = -(-(n + 1) // self.bs)
                f = sum(int(b) in frozen for b in w.table[i, :pages])
                lens.append(n)
                fz.append(f)
                self.pages_read += pages
                self.pages_frozen += f
            if self.on:
                self.steps += 1
                b, fl = costs.paged_decode_call(
                    self.dims, lens, fz, block_size=self.bs,
                    num_values=self.nv)
                self.bytes += b
                self.flops += fl
                self.step_flops += costs.decode_step_flops(self.dims, lens)
        except (AttributeError, KeyError, TypeError, IndexError) as e:
            log(f"page reads not readable from the engine: {e!r}")
            self.ok = False

    def stop(self):
        """Close the profile; returns the reduced trace or None."""
        if not self.on:
            return None
        import jax

        from . import trace as tr

        self.ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.on = False
        path = tr.find_xplane(self.dir)
        try:
            return tr.reduce(tr.load(path)) if path else None
        except ValueError as e:
            log(f"trace not reduced: {e}")
            return None
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def served_at_close(engine) -> dict:
    """Every window request's served tokens when the window closed: the
    finished ones' outputs and what each sequence still decoding had
    emitted."""
    out = {k: list(v) for k, v in engine.outputs.items() if k < WARMUP_ID0}
    w = engine.worker
    for i in w.sched.active_slots():
        rid = w.sched.active[i].req.id
        if rid < WARMUP_ID0:
            out.setdefault(rid, list(w.slots[i].out))
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, spec: dict | None = None, files: Path = BENCH,
             require_tpu: bool = True, warm: bool = True,
             control: bool = False, readings: bool = False,
             rate: float | None = None) -> dict:
    """One run. Only the scripts that set a cell's numbers use the
    keywords: ``warm=False`` skips the warm-up, ``control=True`` adds the
    control's readings (``out["control"]``) and puts them through the
    cell's limits (``out["control_correct"]``), ``readings=True`` adds
    the program's gap statistics (``out["gaps"]``), ``rate`` overrides the
    cell's arrival rate and adds the window's queue (``out["queue"]``)."""
    spec = spec if spec is not None else _json(REPO / "BENCHMARK.json")
    c = load_cell(workload, spec, files)
    conf, mix, cell = c.conf, c.mix, c.cell
    if rate is not None:
        cell = dict(cell, rate_per_s=rate)
    dev = device_info(c.workload["chips"], require_tpu)
    import jax

    cache_dir = use_compile_cache()
    CompileCount.start()
    ref = reference(conf)
    dims = ref.dims(conf)
    srv = conf["serving"]
    bs = srv["block_size"]

    # ---------------------------------------------------------- set-up
    n0 = CompileCount.n
    phases = {"imports": time.perf_counter() - t_start}
    t = time.perf_counter()

    def phase(name):
        nonlocal t
        now = time.perf_counter()
        phases[name] = now - t
        t = now

    cfg = system.program_config(conf)
    weights = ref.init_weights(conf, seed)
    jax.block_until_ready(weights)
    phase("weights")
    tracer = WindowTracer()
    engine = system.build_engine(cfg, system.program_params(weights), conf,
                                 mix, tracer)
    phase("engine")
    warm_items, calls = [], 0
    if warm:
        # serve one short request per prompt rung, then load the programs
        # of every decode width, finished-request page count and freeze
        # bucket the mix can reach
        warm_items = traffic.warmup_items(mix, vocab=dims["V"], seed=seed,
                                          id0=WARMUP_ID0)
        widths = traffic.decode_widths(mix, bs)
        system.warm_decode_widths(engine, widths)
        jax.block_until_ready(engine.worker.tree)
        phase("warm_widths")
        engine.run(system.requests(warm_items))
        phase("warm_requests")
        calls = len(widths) + system.warm_page_programs(
            engine, traffic.page_counts(mix, bs), srv["freeze_page_budget"])
        phase("warm_pages")
    items = traffic.generate(mix, cell, seed=seed, seconds=seconds,
                             vocab=dims["V"])
    reqs = system.requests(items)
    jax.block_until_ready(engine.worker.tree)
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s: {CompileCount.n - n0} programs built or "
        f"loaded ({cache_dir}); warm-up {len(warm_items)} requests, {calls} "
        f"page-program calls; window {len(items)} requests; phases "
        + " ".join(f"{k} {v:.1f}" for k, v in phases.items()))

    # ---------------------------------------------------------- window
    prof = None
    if trace:
        prof = _Profile(REPO / "bench" / ".out" / "trace" /
                        f"{workload}-{seed}",
                        max(seconds - TRACE_S, 0.0), bs, dims,
                        int(srv["kv_quant"].split("@")[1]))
        tracer.on_step, tracer.on_dispatch = prof.on_step, prof.on_dispatch
    n_open = CompileCount.n
    tracer.arm(engine.worker, seconds)
    try:
        engine.run(reqs)
        tracer.close_t = time.perf_counter() - tracer.t0
        tracer.counters_close = dict(engine.worker.counters)
        log("every request finished before the deadline")
    except WindowClosed:
        pass
    compiles = CompileCount.n - n_open
    reduced = prof.stop() if prof is not None else None
    mem = memory_peak_bytes()
    window = Window(records_from_engine(items, engine.metrics.traces,
                                        engine.worker.sched.rejected),
                    close=tracer.close_t,
                    offline=mix["arrivals"]["kind"] == "backlog")
    outputs = served_at_close(engine)
    max_slots = engine.worker.sched.max_slots
    tracer.worker = None
    del engine
    gc.collect()

    # ---------------------------------------------------------- correct
    chk = cell["check"]
    picked = check.sample(items, outputs, seed=seed,
                          min_tokens=chk["min_tokens"],
                          max_requests=chk["max_requests"],
                          first_requests=chk.get("first_requests", 0))
    t_ref = time.perf_counter()
    per = check.gaps(ref, weights, conf, picked, control=control)
    min_decode = chk.get("min_decode_tokens", 1)
    summ = check.summarize([g["served"] for g in per], min_decode=min_decode)
    log(f"reference over {len(picked)} requests, {summ['tokens']} tokens: "
        f"{time.perf_counter() - t_ref:.3f} s; gaps {json.dumps(summ)}")
    compared, correct = check.compare(summ, chk["limits"])

    # ---------------------------------------------------------- metrics
    ctx = types.SimpleNamespace(
        window=window, close=window.close, seconds=seconds, setup_s=setup_s,
        step_s=list(tracer.step_s), counters_open=tracer.counters_open,
        counters_close=tracer.counters_close, max_slots=max_slots,
        dims=dims, conf=conf, mix=mix, cell=cell, trace=reduced, prof=prof,
        compiles_in_window=compiles, peaks=(costs.peaks(dev["kind"])
                                            if dev["platform"] == "tpu"
                                            else None),
        delta=lambda k: counter_delta(tracer.counters_open,
                                      tracer.counters_close, k),
        median=statistics.median, gap_summary=summ)
    wanted = c.per_layer if trace else c.end_to_end
    metrics = {}
    for m in wanted:
        v = _module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev["memory_peak_bytes"] = mem
    out = {"correct": bool(correct), "attempted": len(window.attempted()),
           "failed": window.failed(), "metrics": metrics, "device": dev}
    if reduced is not None:
        dev["busy_s"] = reduced.busy_s
        dev["window_s"] = reduced.window_s
        out["breakdown"] = reduced.breakdown()
    log(f"window {window.close:.3f} s, {window.output_tokens()} output "
        f"tokens, {compiles} programs built or loaded in the window")
    for name, v in compared.items():
        log(f"compared {name} {v['value']} {v['rule']} {v['limit']}")
    if rate is not None:
        half = window.close / 2
        due = window.due()
        late = [r for r in due if r.prefill_start is None
                or r.prefill_start > window.close]
        first = [t for r, t in zip(due, window.ttfts()) if r.due <= half]
        second = [t for r, t in zip(due, window.ttfts()) if r.due > half]
        out["queue"] = {"due": len(due), "unstarted_at_close": len(late),
                        "ttft_p50_first_half": statistics.median(first)
                        if first else None,
                        "ttft_p50_second_half": statistics.median(second)
                        if second else None}
    if readings or control:
        out["gaps"] = summ
    if control:
        out["control"] = check.summarize([g["control"] for g in per],
                                         min_decode=min_decode)
        out["control_compared"], out["control_correct"] = check.compare(
            out["control"], chk["limits"])
    out["compared"] = compared
    return out
