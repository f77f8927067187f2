"""Serving launcher.

Static engine (one-shot fixed batch, the original path):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3_0_6b --reduced \
        --quantize kmeans_ls@16 --gen 16

Continuous-batching engine under Poisson arrivals, optionally with
codebook-quantized KV pages (the paper's solvers applied to the cache):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3_0_6b --reduced \
        --engine continuous --request-rate 4 --kv-quant kmeans_ls@16

Disaggregated prefill/decode serving — N prefill workers feed M decode
workers through a global router; finished prompts migrate as fp pages or
as packed codes + codebooks (``--migrate frozen``, ~7x fewer handoff
bytes):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3_0_6b --reduced \
        --engine disagg --prefill-workers 1 --decode-workers 1 \
        --kv-quant kmeans_ls@16 --migrate frozen --request-rate 4

``--quantize`` / ``--kv-quant`` take a QuantSpec string ("kmeans_ls@16",
"iter_l1@16", "l1_ls:lam=0.02"); the registry's device-batched methods
(kmeans_ls, kmeans, iter_l1) freeze KV pages without host solves. Legacy
bare method names still combine with --num-values / --kv-num-values.

Speculative decoding — a reduced draft model proposes k tokens per step,
the target verifies all k+1 positions in one batched window pass against
the paged cache, accept/rollback adjusts seq_lens in place:

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3_0_6b --reduced \
        --engine continuous --speculate 3 --draft-config auto \
        --kv-quant kmeans_ls@16 --request-rate 4

With --kv-quant (or --speculate) the run also replays a deterministic
subset against the fp, non-speculative paged cache (same engine
composition) and reports the logit deviation. Documented tolerance
(reduced configs, f32, per-page codebooks): max |dlogit| <= 2.5 and <= 8%
of the logit range at 16 values (per config: ``_KV_LOGIT_TOL``; qwen3-0.6b
at published widths allows abs 16); greedy tokens typically agree exactly,
with 0 host page solves for device-capable specs. Speculative decoding is
greedy-token-identical by construction (every emitted token is a target
argmax), so the same check covers its verify-window numerics.
"""
import argparse
import time

_EPILOG = """\
disaggregated serving (--engine disagg):
  --prefill-workers N / --decode-workers M   worker ratio = the TTFT/TPOT
        tradeoff knob: more prefill workers drain the prompt queue faster
        (TTFT), more decode workers hold more concurrent sequences (TPOT);
        decode iterations never wait on a prefill either way.
  --migrate fp|frozen   how finished prefill pages cross the handoff:
        "fp" ships full-width rows (baseline); "frozen" routes full pages
        through the batched device freeze (needs a device-capable
        --kv-quant spec) so they cross as packed 4-bit codes + per-block
        codebooks (~7x fewer bytes) and land directly servable by the
        fused kernel. The run reports measured handoff bytes both ways.
  --freeze-page-budget K   max pages quantized per decode step (colocated
        and disagg): the backpressure valve that keeps a prefill burst of
        full pages from backing up the device queue; deferred pages serve
        exact fp until their turn and are counted in the summary.
  --temperature T / --top-k K   engine-level sampling for the trace
        (temperature 0 = greedy, the default and the verification path;
        per-request seeds derive from --seed, so runs replay exactly).
  --staging-depth D     cap on prefills in flight past the waiting queue
        (assigned to a prefill worker or staged): a decode-capacity stall
        backpressures the prefill workers instead of growing the staged
        queue unboundedly. Default: unbounded.

speculative decoding (--speculate k, both engines):
  --speculate k         draft k tokens per step, verify all k+1 positions
        in one batched target pass; accepted tokens advance seq_lens in
        place, rejected suffixes roll back (never freezing a page past
        the accepted watermark). Greedy-only.
  --draft-config X      the draft model:
        auto      layer-truncate the target to its first half (shared
                  embed/head weights — a real reduced config at ~half the
                  decode FLOPs, ~90% greedy agreement on reduced configs)
        self      the target itself (acceptance ~100%: the upper bound)
        <arch>    an arch name (same --reduced flag; vocab must match)

observability (continuous + disagg engines):
  --trace-out PATH      write a Chrome trace-event / Perfetto-loadable
        JSON trace of the whole run: one track per component — router
        decisions, prefill dispatch/harvest, decode-step phases
        (dispatch/sync/commit), transfer extract/splice with payload
        bytes, the per-page freeze lifecycle (queued -> dispatched ->
        installed | dropped | rolled_back) as async spans, freeze
        flush/install spans, and speculative propose/verify/accept/
        rollback. Load it at https://ui.perfetto.dev (Open trace file) or
        chrome://tracing. The run prints a reconciliation of trace spans
        against the engine's freeze/step counters. Under a JAX profiler
        session the same phases are also profiler annotations named
        repro:<track>:<phase>.
  --metrics-jsonl PATH  append one JSON metrics snapshot per
        --metrics-interval seconds (streaming counters/gauges/histogram
        percentiles, windowed over each interval). A Prometheus text
        rendering of the final snapshot lands next to it at PATH + ".prom".
  --metrics-interval S  snapshot cadence in seconds (default 1.0).

overload survival (continuous + disagg engines):
  --offload-pages       demote preemption victims' frozen KV pages to a
        host-memory tier as packed codes + codebooks (~7x smaller than
        fp rows; bit-exact on restore). Victims resume greedy-token
        identical — restore splices the exact pages back.
  --preempt             when a latency-tier request is blocked on pages,
        evict the coldest (LRU by last-attended step) best_effort
        sequence at a step boundary; a cost model picks restore (host
        tier) vs recompute (re-prefill prompt + emitted tokens) and the
        scheduler re-admits preempted work ahead of the FCFS queue.
  --admission slo|fcfs  "slo" sheds or defers best_effort arrivals when
        the windowed itl_p99 (--itl-slo) is breached or occupancy is
        critical, protecting the latency tier; deferred requests retry
        under hysteresis. "fcfs" (default) admits in arrival order.
  --itl-slo S           inter-token p99 target in seconds for
        --admission slo (unset: occupancy-only shedding).
  --priority latency|best_effort   tier for the generated trace;
        --best-effort-frac F marks a seed-derived fraction best_effort
        instead (the tier SLO admission sheds first, and the only tier
        --preempt will victimize).
  The run epilog reports admission outcomes by reason
  (rejected_queue_full / rejected_pool_full / shed_slo / deferred) and
  the preempt/offload/restore counters with measured host-tier
  compression; --trace-out reconciles page_offload spans (terminal
  state "restored") against those counters.

prefix sharing (--prefix-cache, continuous engine):
  Sequences whose prompts share a page-aligned prefix splice the SAME
  resident KV pages instead of re-prefilling them: a rolling token-hash
  index keys every immutable full page (installed-frozen reconstructions
  under --kv-quant, exact-fp prompt pages otherwise) by its whole prefix
  chain, and each match bumps the page's refcount in the allocator — a
  page returns to the free list only when its last reference drops.
  The write-hot tail page is never shared: lookups stop one page short
  of the prompt end, so each sequence materializes its divergence
  privately (copy-on-write; cow_copies counts matches truncated at that
  boundary). Admission charges worst-case-minus-shareable pages, which
  is what turns sharing into extra concurrent sequences per pool.
  Composes with speculative decoding (rollback stays past the shared
  prompt prefix), preemption/offload (a victim drops refs on shared
  pages instead of demoting them; payloads carry only exclusively-owned
  pages), and chunked prefill (chunks start after the shared run).
  --shared-prefix-len N makes the generated trace share its first N
  prompt tokens across requests (the shared-prefix burst scenario).
  The summary reports prefix_hits / prefix_shared_pages / cow_copies,
  and --trace-out reconciles prefix_match spans against prefix_hits.

chunked prefill (--prefill-chunk N, continuous engine):
  Admission reserves the slot and worst-case pages up front, then the
  prompt enters the cache N tokens per engine iteration, interleaved with
  decode steps for the live batch — a long prompt costs each iteration
  one chunk instead of a whole prefill, which bounds itl_max under a
  long-prompt burst. Each chunk scores against every earlier page through
  the same attention path decode uses; with --attn-impl fused, earlier
  frozen pages cross HBM as packed 4-bit codes + codebooks through the
  double-buffered kernel DMA (the modeled prefill-bytes win on shared
  frozen context — see the prefill rows in BENCH_paged_attention.json).
  The chunk sequence is
  logit-identical to single-shot prefill — bitwise on the gather path,
  which the run replays and asserts — and freeze bids are identical
  (queued at attach, after the whole prompt is in cache).

quantized weight serving (--quantize, all engines):
  PTQ'd QuantizedTensor leaves serve undequantized through qmatmul: flat
  leaves hit the fused dequant matmul kernel, and stacked leaves (the
  lax.scan layer-group form) hit the stacked-group kernel with each
  group's codebook VMEM-resident — scanned attention/FFN groups serve
  from uint8 codes with zero per-call dequant. Every traced dense
  materialization bumps the summary's qmatmul_dequant_fallback counter;
  a PTQ run asserts it stays 0.

migration note (pre-spec flags -> QuantSpec strings):
  --quantize kmeans_ls --num-values 16   ->  --quantize kmeans_ls@16:weighted=true
                               (legacy PTQ always optimized the weighted
                                full-vector loss; spell it in the spec)
  --kv-quant kmeans_ls --kv-num-values 8 ->  --kv-quant kmeans_ls@8
  --kv-quant tv                          ->  --kv-quant tv_iter@16
  (lam methods)                          ->  --quantize l1_ls:lam=0.02
Options fold into the spec: kmeans_ls@16:weighted=true,seed=3,clip=-1.0..1.0
The old flag pairs keep working; QuantSpec strings are the canonical form
used by BENCH_*.json artifacts and the registry-validated serving engine.
"""


def _ptq_spec(args) -> str:
    """--quantize value -> spec string (legacy bare names combine with
    --num-values; PTQ historically optimizes the weighted objective)."""
    q = args.quantize
    if "@" in q or ":" in q:
        return q
    return f"{q}@{args.num_values}:weighted=true"


def _run_static(args):
    import jax
    import jax.numpy as jnp

    from repro import models
    from repro.configs import get_config, get_reduced_config
    from repro.launch.static_steps import static_decode_step, static_prefill
    from repro.quant.ptq import (compression_ratio, dequantize_tree,
                                 quantize_tree)

    cfg = (get_reduced_config if args.reduced else get_config)(args.arch)
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    if args.quantize:
        spec = _ptq_spec(args)
        qtree, report = quantize_tree(params, spec)
        print(f"[serve] PTQ {spec}: "
              f"{len(report)} tensors, {compression_ratio(report):.1f}x")
        params = dequantize_tree(qtree)

    B, P, G = args.batch, args.prompt_len, args.gen
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, P), 0, cfg.vocab)
    enc = (jax.random.normal(jax.random.PRNGKey(2), (B, P, cfg.d_model))
           if cfg.family == "encdec" else None)

    t0 = time.perf_counter()
    tok, cache = static_prefill(params, cfg, tokens, enc, G)
    out = [tok]
    for i in range(G - 1):
        tok, cache = static_decode_step(params, cfg, tok, cache,
                                        jnp.int32(P + i))
        out.append(tok)
    gen = jnp.concatenate(out, axis=1).block_until_ready()
    dt = time.perf_counter() - t0
    print(f"[serve] {B} requests x {G} tokens in {dt:.2f}s "
          f"({B*G/dt:.1f} tok/s incl. compile); sample: {gen[0][:10].tolist()}")


def _make_draft(params, cfg, args):
    """Resolve --draft-config into a (draft_params, draft_cfg) pair."""
    import jax

    from repro import models
    from repro.configs import get_config, get_reduced_config
    from repro.serving import derive_draft

    name = args.draft_config
    if name in (None, "auto"):
        return derive_draft(params, cfg)
    if name == "self":
        return params, cfg
    dcfg = (get_reduced_config if args.reduced else get_config)(name)
    if dcfg.vocab != cfg.vocab:
        raise SystemExit(f"[serve] draft {name} vocab {dcfg.vocab} != "
                         f"target vocab {cfg.vocab}")
    return models.init_params(dcfg, jax.random.PRNGKey(7)), dcfg


def _make_engine(params, cfg, args, *, kv_quant, record_logits=False,
                 freeze_async=True, speculate=None, draft=None,
                 tracer=None, exporter=None, overload=False,
                 prefix_cache=False):
    """Build the engine composition ``args`` asks for (colocated vs
    disaggregated) — verification replays run through the same one
    (with tracer/exporter AND the overload/prefix-sharing machinery left
    off: replays are correctness probes on an uncontended pool)."""
    from repro.serving import ContinuousBatchingEngine, DisaggEngine

    speculate = args.speculate if speculate is None else speculate
    kw = dict(max_slots=args.max_slots, block_size=args.block_size,
              max_seq_len=args.max_seq_len, kv_quant=kv_quant,
              kv_num_values=args.kv_num_values, attn_impl=args.attn_impl,
              record_logits=record_logits, freeze_async=freeze_async,
              freeze_page_budget=args.freeze_page_budget,
              speculate=speculate, draft=draft if speculate else None,
              tracer=tracer, exporter=exporter)
    if overload:
        kw.update(offload_pages=args.offload_pages, preempt=args.preempt,
                  admission=args.admission, itl_slo_s=args.itl_slo)
    if args.engine == "disagg":
        # fp pages are the only thing that can migrate without a spec
        migrate = args.migrate if kv_quant is not None else "fp"
        return DisaggEngine(params, cfg,
                            prefill_workers=args.prefill_workers,
                            decode_workers=args.decode_workers,
                            migrate=migrate,
                            staging_depth=args.staging_depth, **kw)
    return ContinuousBatchingEngine(params, cfg,
                                    prefill_chunk=args.prefill_chunk,
                                    prefix_cache=prefix_cache, **kw)


# (abs, rel) bounds on the verification replay's max|dlogit| at 16 values
# per page, by config name; rel is against the replay's largest fp logit.
# The default was calibrated on the reduced f32 presets (block 16, prompt
# 64, gen 32).
_KV_LOGIT_TOL = {
    # Published widths, bf16: the tied N(0, 1) embedding at d_model 1024
    # puts the largest logit near 250 (the reduced preset's: ~50), so the
    # abs bound scales with it while rel 8% stays. Set from one v5e run
    # (prompt 128, gen 32, 8 slots): max|dlogit| 11.36, rel 4.5%, 96/96
    # greedy tokens; abs 16 leaves ~1.4x headroom over it.
    "qwen3-0.6b": (16.0, 0.08),
}
_KV_LOGIT_TOL_DEFAULT = (2.5, 0.08)


def _verify_serving(params, cfg, args, draft=None) -> dict:
    """Replay a deterministic batch through the fp, non-speculative engine
    vs the engine as configured (quantized KV and/or speculative) and
    report the logit deviation the quantized cache, the frozen page
    migration (disagg), and the verify-window numerics introduce.
    Speculative decoding must be greedy token-identical here: every
    emitted token is a target argmax for its exact accepted context.
    Returns the measured deviation, agreement and verdict (``ok``)."""
    import numpy as np

    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(0, cfg.vocab, args.prompt_len).tolist()
               for _ in range(min(3, args.max_slots))]
    outs, engines = [], []
    for baseline in (True, False):
        eng = _make_engine(params, cfg, args,
                           kv_quant=None if baseline else args.kv_quant,
                           record_logits=True,
                           speculate=0 if baseline else args.speculate,
                           draft=draft,
                           freeze_async=False)  # deterministic install step
        outs.append(eng.generate(prompts, max_new_tokens=args.gen))
        engines.append(eng)
    fp, q = engines
    dmax = scale = dsum = dcount = 0.0
    agree, total = 0, 0
    for i in range(len(prompts)):
        a, b = fp.request_logits[i], q.request_logits[i]
        d = np.abs(a - b)
        dmax = max(dmax, float(d.max()))
        dsum += float(d.sum())
        dcount += d.size
        scale = max(scale, float(np.abs(a).max()))
        agree += sum(int(x == y) for x, y in zip(outs[0][i], outs[1][i]))
        total += len(outs[0][i])
    dmean = dsum / max(dcount, 1)
    rel = dmax / max(scale, 1e-9)
    host = (sum(w.counters["host_page_solves"] for w in q.decode)
            if args.engine == "disagg"
            else q.counters["host_page_solves"])
    tol_abs, tol_rel = _KV_LOGIT_TOL.get(cfg.name, _KV_LOGIT_TOL_DEFAULT)
    ok = dmax <= tol_abs and rel <= tol_rel
    if args.speculate:
        # token identity is the speculative acceptance bar, not a tolerance
        ok = ok and agree == total
    mig = f", migrate={q.migrate}" if args.engine == "disagg" else ""
    spec = f", speculate={args.speculate}" if args.speculate else ""
    print(f"[serve] serving check ({q.kv_spec or 'fp'}{mig}{spec}): "
          f"max|dlogit|={dmax:.3f} mean={dmean:.4f} rel={rel:.3%} "
          f"(tolerance: abs<={tol_abs}, rel<={tol_rel:.0%}) "
          f"greedy-token agreement {agree}/{total}, {host} host page solves "
          f"-> {'OK' if ok else 'EXCEEDED'}")
    result = {"ok": ok, "max_dlogit": dmax, "mean_dlogit": dmean,
              "rel_dlogit": rel, "tol_abs": tol_abs, "tol_rel": tol_rel,
              "agree": agree, "total": total, "host_page_solves": host}
    if args.speculate:
        s = q.metrics.summary()
        steps = (sum(w.counters["seq_decode_steps"] for w in q.decode)
                 if args.engine == "disagg"
                 else q.counters["seq_decode_steps"])
        tps = (s.get("gen_tokens", 0) - s.get("completed", 0)) / max(steps, 1)
        print(f"[serve] speculative check: acceptance "
              f"{s.get('spec_acceptance_rate', 0.0):.1%} over "
              f"{s.get('spec_proposed', 0)} drafts, "
              f"{s.get('spec_rollbacks', 0)} rollbacks, "
              f"tokens/step {tps:.2f}")
    return result


def _verify_chunked(params, cfg, args):
    """Replay a deterministic batch chunked (--prefill-chunk) vs
    single-shot through the gather read path and require BITWISE identity:
    same tokens, same recorded logits. A chunk sequence walks the same
    pages in the same order as one whole-prompt call, so equality is
    exact — any drift is a scheduler or masking bug, not numerics."""
    import numpy as np

    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(0, cfg.vocab, args.prompt_len).tolist()
               for _ in range(min(3, args.max_slots))]
    chunk, impl = args.prefill_chunk, args.attn_impl
    args.attn_impl = "gather"   # one read path for both -> bitwise bar
    outs, engines = [], []
    try:
        for args.prefill_chunk in (None, chunk):
            eng = _make_engine(params, cfg, args, kv_quant=args.kv_quant,
                               record_logits=True, speculate=0,
                               freeze_async=False)
            outs.append(eng.generate(prompts, max_new_tokens=args.gen))
            engines.append(eng)
    finally:
        args.prefill_chunk, args.attn_impl = chunk, impl
    single, chunked = engines
    ok = outs[0] == outs[1]
    for i in range(len(prompts)):
        ok = ok and bool(np.array_equal(single.request_logits[i],
                                        chunked.request_logits[i]))
    n = chunked.prefill.counters["prefill_chunks"]
    print(f"[serve] chunked-prefill check (chunk={chunk}, "
          f"kv={args.kv_quant or 'fp'}, gather replay): {n} chunks, "
          f"tokens+logits vs single-shot "
          f"{'bitwise identical -> OK' if ok else 'MISMATCH -> FAILED'}")
    return ok


def _trace_reconcile(tracer, s, speculate: int) -> bool:
    """Cross-check trace spans against the engine's counters: the trace is
    only trustworthy if its event counts ARE the counters."""
    from repro.obs import count_events

    ev = tracer.events
    n_step = count_events(ev, name="decode_step", ph="X")
    n_flush = count_events(ev, name="flush", ph="X")
    n_inst = count_events(ev, name="install", ph="X")
    nb = count_events(ev, name="page_freeze", ph="b")
    ne = count_events(ev, name="page_freeze", ph="e")
    states: dict = {}
    for e in ev:
        if e.get("ph") == "e" and e.get("name") == "page_freeze":
            st = e.get("args", {}).get("state", "?")
            states[st] = states.get(st, 0) + 1
    n_pc = count_events(ev, name="prefill_chunk", ph="X")
    ok = (n_step == s.get("decode_steps", 0)
          and n_flush == s.get("freeze_dispatches", 0)
          and n_inst == s.get("freeze_installs", 0) and nb == ne
          and n_pc == s.get("prefill_chunks", 0))
    if speculate:
        n_acc = count_events(ev, name="accept", ph="i")
        n_rb = count_events(ev, name="rollback", ph="i")
        ok = ok and (n_acc == s.get("spec_steps", 0)
                     and n_rb == s.get("spec_rollbacks", 0))
    # overload: every offloaded page's async span must close "restored",
    # and the preempt/restore instants must match the counters exactly
    ob = count_events(ev, name="page_offload", ph="b")
    oe = count_events(ev, name="page_offload", ph="e")
    o_restored = sum(1 for e in ev if e.get("name") == "page_offload"
                     and e.get("ph") == "e"
                     and e.get("args", {}).get("state") == "restored")
    ok = ok and (ob == oe == o_restored == s.get("offloaded_pages", 0)
                 == s.get("restored_pages", 0))
    ok = ok and (count_events(ev, name="preempt", ph="i")
                 == s.get("preemptions", 0))
    ok = ok and (count_events(ev, name="restore", ph="i")
                 == s.get("restored_seqs", 0))
    # prefix sharing: every counted hit carries exactly one prefix_match
    # span (prefill dispatch or restore re-attach), and vice versa
    n_pm = count_events(ev, name="prefix_match", ph="X")
    ok = ok and n_pm == s.get("prefix_hits", 0)
    state_txt = (", ".join(f"{k}={v}" for k, v in sorted(states.items()))
                 or "none")
    off_txt = f", page-offload spans {ob} -> {oe} restored" if ob else ""
    if n_pm or s.get("prefix_hits"):
        off_txt += (f", prefix_match spans {n_pm} "
                    f"(counter {s.get('prefix_hits', 0)})")
    if n_pc or s.get("prefill_chunks"):
        off_txt += (f", prefill_chunk spans {n_pc} "
                    f"(counter {s.get('prefill_chunks', 0)})")
    print(f"[serve] trace: {len(ev)} events | decode_step spans {n_step} "
          f"(counter {s.get('decode_steps', 0)}), freeze flushes {n_flush} "
          f"(counter {s.get('freeze_dispatches', 0)}), installs {n_inst} "
          f"(counter {s.get('freeze_installs', 0)}), page-freeze spans "
          f"{nb} opened -> {ne} terminal ({state_txt}){off_txt} "
          f"-> {'reconciled' if ok else 'MISMATCH'}")
    return ok


def _run_continuous(args) -> dict:
    """Serve the Poisson trace ``args`` describes; returns the engine
    summary, plus the verification replay's result under ``"verify"``
    when one ran. Raises SystemExit when no request completes or a
    check fails."""
    import jax

    from repro.configs import get_config, get_reduced_config
    from repro import models
    from repro.serving.scheduler import poisson_trace

    cfg = (get_reduced_config if args.reduced else get_config)(args.arch)
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    if args.quantize:
        from repro.quant.ptq import compression_ratio, quantize_tree

        # QuantizedTensor leaves are served as-is: attention/ffn projections
        # route through qmatmul's fused dequant path, never densifying.
        spec = _ptq_spec(args)
        params, report = quantize_tree(
            params, spec,
            skip_patterns=("ln", "norm", "router", "A_log", "mix", "dt_bias",
                           "D_skip", "w0", "embed", "lm_head"))
        print(f"[serve] PTQ {spec}: "
              f"{len(report)} tensors, {compression_ratio(report):.1f}x, "
              "serving undequantized via qmatmul")

    draft = _make_draft(params, cfg, args) if args.speculate else None
    if args.speculate and args.temperature > 0:
        raise SystemExit("[serve] --speculate serves the greedy path; "
                         "drop --temperature")
    tracer = exporter = None
    if args.trace_out or args.metrics_jsonl:
        from repro.obs import MetricsExporter, Tracer

        if args.trace_out:
            tracer = Tracer()
        if args.metrics_jsonl:
            exporter = MetricsExporter(args.metrics_jsonl,
                                       interval_s=args.metrics_interval)
    eng = _make_engine(params, cfg, args, kv_quant=args.kv_quant,
                       draft=draft, tracer=tracer, exporter=exporter,
                       overload=True, prefix_cache=args.prefix_cache)
    be_frac = (1.0 if args.priority == "best_effort"
               else args.best_effort_frac)
    trace = poisson_trace(args.num_requests, args.request_rate,
                          vocab=cfg.vocab, prompt_len=args.prompt_len,
                          max_new_tokens=args.gen, seed=args.seed,
                          temperature=args.temperature, top_k=args.top_k,
                          best_effort_frac=be_frac,
                          shared_prefix_len=args.shared_prefix_len)
    tag = (f"disagg {args.prefill_workers}P/{args.decode_workers}D "
           f"migrate={eng.migrate}" if args.engine == "disagg"
           else "continuous batching")
    spec_tag = (f", speculate={args.speculate} "
                f"(draft={draft[1].name})" if args.speculate else "")
    print(f"[serve] {tag}: {args.num_requests} requests, "
          f"Poisson rate {args.request_rate}/s, prompt {args.prompt_len}, "
          f"gen {args.gen}, {args.max_slots} slots x "
          f"{args.max_seq_len} tokens, block {args.block_size}, "
          f"kv={eng.kv_spec or 'fp'}{spec_tag}, sampling="
          f"{'greedy' if args.temperature <= 0 else f'T={args.temperature},top_k={args.top_k}'}")
    s = eng.run(trace)
    if exporter is not None:
        exporter.close(eng.metrics)
        from repro.obs import prometheus_text

        prom_path = args.metrics_jsonl + ".prom"
        with open(prom_path, "w") as f:
            f.write(prometheus_text(eng.metrics.snapshot()))
        print(f"[serve] metrics: {len(exporter.lines)} snapshots -> "
              f"{args.metrics_jsonl} (+ {prom_path})")
    if tracer is not None:
        tracer.write(args.trace_out)
        print(f"[serve] trace -> {args.trace_out} (load at "
              f"https://ui.perfetto.dev or chrome://tracing)")
        if not _trace_reconcile(tracer, s, args.speculate):
            raise SystemExit("[serve] trace/counter reconciliation failed")
    if not s["completed"]:
        raise SystemExit(
            f"[serve] no requests completed ({s['rejected']} rejected — "
            f"prompt+gen must fit --max-seq-len {args.max_seq_len})")
    print(f"[serve] completed {s['completed']}/{args.num_requests} "
          f"(rejected {s['rejected']}) in {s['makespan_s']:.2f}s: "
          f"{s['throughput_tok_s']:.1f} gen tok/s")
    print(f"[serve] TTFT mean {s['ttft_mean_s']*1e3:.0f}ms "
          f"(= queue wait {s['queue_wait_mean_s']*1e3:.0f}ms + prefill "
          f"compute {s['prefill_compute_mean_s']*1e3:.0f}ms) "
          f"p50 {s['ttft_p50_s']*1e3:.0f}ms p99 {s['ttft_p99_s']*1e3:.0f}ms | "
          f"TPOT p50 {s['tpot_p50_s']*1e3:.1f}ms p99 {s['tpot_p99_s']*1e3:.1f}ms")
    occ = s.get("cache_occupancy_mean", 0.0)
    print(f"[serve] cache occupancy mean {occ:.1%} "
          f"max {s.get('cache_occupancy_max', 0.0):.1%}")
    print(f"[serve] attn_impl={s['attn_impl']} | freeze: "
          f"{s['freeze_dispatches']} dispatches -> {s['freeze_installs']} "
          f"installs, {s['host_page_solves']} host page solves, "
          f"{s['freeze_overlap_steps']} decode steps ran between dispatch "
          f"and install, {s['freeze_deferred_pages']} pages deferred by the "
          f"per-step budget ({args.freeze_page_budget}) | gather window <= "
          f"{s['max_gather_blocks']} blocks")
    if args.prefill_chunk:
        print(f"[serve] chunked prefill: {s.get('prefill_chunks', 0)} chunks "
              f"of <= {args.prefill_chunk} tokens interleaved with decode "
              f"steps (one chunk per engine iteration)")
    if args.quantize:
        fb = s.get("qmatmul_dequant_fallback", 0)
        print(f"[serve] quantized weights: qmatmul_dequant_fallback={fb} "
              f"(every PTQ'd projection must serve from codes)")
        if fb:
            raise SystemExit("[serve] PTQ run traced a dense dequant "
                             "fallback in qmatmul")
    adm = {k: s[k] for k in ("rejected_queue_full", "rejected_pool_full",
                             "shed_slo", "deferred") if s.get(k)}
    if adm or args.admission == "slo":
        txt = ", ".join(f"{k}={v}" for k, v in adm.items()) or "none"
        print(f"[serve] admission ({args.admission}"
              + (f", itl_slo={args.itl_slo}s" if args.itl_slo else "")
              + f"): {txt}")
    if args.prefix_cache:
        print(f"[serve] prefix cache: {s.get('prefix_hits', 0)} hits, "
              f"{s.get('prefix_shared_pages', 0)} pages spliced shared, "
              f"{s.get('cow_copies', 0)} copy-on-write tail materializations")
    if s.get("preemptions"):
        comp = s.get("offload_compression", 0.0)
        print(f"[serve] overload: {s['preemptions']} preemptions "
              f"({s.get('preempt_offloads', 0)} offloaded to host, "
              f"{s.get('preempt_recomputes', 0)} recomputed); "
              f"{s.get('offloaded_pages', 0)} pages -> host tier at "
              f"{s.get('offload_bytes', 0)/1e6:.3f} MB"
              + (f" ({comp:.1f}x smaller than fp)" if comp else "")
              + f", {s.get('restored_seqs', 0)} sequences "
              f"({s.get('restored_pages', 0)} pages) restored bit-exact")
    if args.engine == "disagg":
        mb = s.get("migrate_bytes", 0)
        print(f"[serve] migration: {s['prefills_done']} prefills -> "
              f"{s['migrated_seqs']} handoffs, {s['migrated_pages']} pages, "
              f"{mb/1e6:.3f} MB crossed ({s['migrate_compression']:.1f}x "
              f"fewer than fp rows at {s.get('migrate_fp_equiv_bytes', 0)/1e6:.3f} MB)")
    if args.speculate:
        print(f"[serve] speculative: acceptance "
              f"{s.get('spec_acceptance_rate', 0.0):.1%} "
              f"({s.get('spec_accepted', 0)}/{s.get('spec_proposed', 0)} "
              f"drafts), {s.get('spec_rollbacks', 0)} rollbacks, "
              f"tokens/step {s.get('tokens_per_step', 1.0):.2f}")
    if args.kv_quant:
        print(f"[serve] cache bytes: frozen-page compression "
              f"{s['page_compression']:.1f}x per page; measured mean "
              f"{s.get('cache_compression_mean', 1.0):.1f}x, at last "
              f"occupied step {s.get('cache_compression_final', 1.0):.1f}x "
              f"(partial pages stay fp)")
    if args.kv_quant or args.speculate:
        s["verify"] = _verify_serving(params, cfg, args, draft=draft)
        if not s["verify"]["ok"]:
            raise SystemExit(1)     # tolerance breach must fail the run
    if args.prefill_chunk:
        if not _verify_chunked(params, cfg, args):
            raise SystemExit(1)     # bitwise breach must fail the run
    return s


def main(argv=None):
    """Command-line entry; ``argv`` defaults to ``sys.argv[1:]``. Returns
    the continuous/disagg run's summary (see ``_run_continuous``), None
    for the static engine."""
    ap = argparse.ArgumentParser(
        epilog=_EPILOG, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--arch", default="qwen3_0_6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--engine", choices=("static", "continuous", "disagg"),
                    default="static")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=None)
    ap.add_argument("--gen", type=int, default=None)
    ap.add_argument("--quantize", default=None,
                    help="PTQ QuantSpec for weights (e.g. kmeans_ls@16, "
                         "l1_ls:lam=0.02; bare method names combine with "
                         "--num-values)")
    ap.add_argument("--num-values", type=int, default=16,
                    help="legacy count budget for a bare --quantize method")
    # continuous engine
    ap.add_argument("--request-rate", type=float, default=4.0,
                    help="Poisson arrival rate, requests/s")
    ap.add_argument("--num-requests", type=int, default=12)
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--max-seq-len", type=int, default=256)
    ap.add_argument("--kv-quant", default=None,
                    help="page codebook QuantSpec (kmeans_ls@16, iter_l1@16, "
                         "tv_iter@16, dtc@16; bare method names combine "
                         "with --kv-num-values)")
    ap.add_argument("--kv-num-values", type=int, default=None,
                    help="legacy count budget for a bare --kv-quant method "
                         "(default 16; conflicts with a spec-form "
                         "--kv-quant)")
    ap.add_argument("--attn-impl", choices=("auto", "fused", "gather"),
                    default="auto",
                    help="decode read path: fused Pallas paged-attention "
                         "kernel vs dense gather (auto: fused on TPU)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="continuous engine: share page-aligned common "
                         "prompt prefixes across sequences via refcounted "
                         "copy-on-write pages (see epilog)")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="share the first N prompt tokens across every "
                         "request in the generated trace (the shared-prefix "
                         "burst scenario --prefix-cache exploits)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="continuous engine: admit prompts in N-token "
                         "chunks, one per engine iteration, interleaved "
                         "with decode steps (bounds itl_max under long-"
                         "prompt bursts; bit-identical to single-shot "
                         "prefill — see epilog)")
    # disaggregated engine
    ap.add_argument("--prefill-workers", type=int, default=1,
                    help="disagg: prefill worker count (the N of the N:M "
                         "TTFT/TPOT ratio knob)")
    ap.add_argument("--decode-workers", type=int, default=1,
                    help="disagg: decode worker count")
    ap.add_argument("--migrate", choices=("fp", "frozen"), default="fp",
                    help="disagg page handoff: fp rows vs packed codes + "
                         "codebooks via the device freeze path (needs a "
                         "device-capable --kv-quant)")
    ap.add_argument("--freeze-page-budget", type=int, default=4,
                    help="max KV pages quantized per decode step (prefill-"
                         "burst backpressure valve; deferred pages counted "
                         "in the summary)")
    ap.add_argument("--staging-depth", type=int, default=None,
                    help="disagg: cap on prefills in flight past the "
                         "waiting queue; a decode stall backpressures the "
                         "prefill workers (default: unbounded)")
    # speculative decoding
    ap.add_argument("--speculate", type=int, default=0,
                    help="draft k tokens per step and verify all k+1 "
                         "positions in one batched target pass (0 = off; "
                         "greedy only)")
    ap.add_argument("--draft-config", default="auto",
                    help="draft model for --speculate: 'auto' (layer-"
                         "truncated target, shared weights), 'self' (the "
                         "target itself), or an arch name with a matching "
                         "vocab")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="engine-level sampling temperature for the trace "
                         "(0 = greedy, the default and verification path)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k truncation when sampling (0 = full vocab)")
    # overload survival
    ap.add_argument("--offload-pages", action="store_true",
                    help="demote preemption victims' frozen KV pages to a "
                         "host tier as packed codes+codebooks; restore is "
                         "bit-exact (see epilog)")
    ap.add_argument("--preempt", action="store_true",
                    help="evict the coldest best_effort sequence when a "
                         "latency-tier request is blocked on pages "
                         "(restore-vs-recompute cost model; preempted work "
                         "re-admits ahead of FCFS)")
    ap.add_argument("--admission", choices=("fcfs", "slo"), default="fcfs",
                    help="slo: shed/defer best_effort arrivals off windowed "
                         "itl_p99 (--itl-slo) + occupancy, protecting the "
                         "latency tier")
    ap.add_argument("--itl-slo", type=float, default=None,
                    help="inter-token p99 target in seconds for "
                         "--admission slo (unset: occupancy-only)")
    ap.add_argument("--priority", choices=("latency", "best_effort"),
                    default="latency",
                    help="tier for every request in the generated trace")
    ap.add_argument("--best-effort-frac", type=float, default=0.0,
                    help="mark this (seed-derived) fraction of the trace "
                         "best_effort — the tier SLO admission sheds and "
                         "--preempt victimizes")
    # observability
    ap.add_argument("--trace-out", default=None,
                    help="write a Perfetto-loadable Chrome trace-event "
                         "JSON of the run (one track per component; see "
                         "epilog)")
    ap.add_argument("--metrics-jsonl", default=None,
                    help="append periodic JSON metrics snapshots here "
                         "(streaming percentiles windowed per interval; "
                         "final Prometheus text at PATH + '.prom')")
    ap.add_argument("--metrics-interval", type=float, default=1.0,
                    help="seconds between --metrics-jsonl snapshots")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if (args.trace_out or args.metrics_jsonl) \
            and args.engine not in ("continuous", "disagg"):
        ap.error("--trace-out/--metrics-jsonl instrument the continuous "
                 "and disagg engines")
    serving = args.engine in ("continuous", "disagg")
    if (args.offload_pages or args.preempt or args.admission == "slo") \
            and not serving:
        ap.error("--offload-pages/--preempt/--admission slo instrument the "
                 "continuous and disagg engines")
    if serving and args.request_rate <= 0:
        ap.error("--request-rate must be > 0 (requests per second)")
    if args.engine == "disagg" and args.migrate == "frozen" \
            and not args.kv_quant:
        ap.error("--migrate frozen needs --kv-quant (pages cross as "
                 "codes+codebooks)")
    if args.prefill_chunk is not None:
        if args.engine != "continuous":
            ap.error("--prefill-chunk interleaves the continuous engine's "
                     "decode loop (disagg already overlaps via workers)")
        if args.prefill_chunk < 1:
            ap.error("--prefill-chunk must be >= 1 token")
    if args.prefix_cache and args.engine != "continuous":
        ap.error("--prefix-cache shares pages within one colocated pool "
                 "(the continuous engine); disagg pools migrate pages out")
    if args.shared_prefix_len and not serving:
        ap.error("--shared-prefix-len shapes the continuous/disagg trace")
    if args.prompt_len is None:
        args.prompt_len = 64 if serving else 16
    if args.gen is None:
        args.gen = 32 if serving else 16
    from repro.launch.compile_cache import init_compile_cache

    init_compile_cache()
    if serving:
        return _run_continuous(args)
    _run_static(args)
    return None


if __name__ == "__main__":
    main()
