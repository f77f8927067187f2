"""Serving engines: thin run loops composed from the role-based workers in
``serving.workers`` (``PrefillWorker``/``DecodeWorker``), the page-handoff
layer in ``serving.transfer``, and the schedulers in ``serving.scheduler``.

``ContinuousBatchingEngine`` is the colocated composition — one decode
worker plus a prefill worker *borrowing its pool*, so prefill runs inline
per admission and the handoff is a no-op page-table splice. Its public
behavior is the original monolithic engine's: iteration-level batching,
FCFS admission, async budgeted page freezing, the clamped gather window.

``DisaggEngine`` is the disaggregated composition — N prefill workers with
their own pools feeding M decode workers through a global ``DisaggRouter``.
Prefill is dispatched asynchronously (a long prompt never blocks a decode
iteration; the worker-ratio N:M is the TTFT/TPOT tradeoff knob), and
finished pages migrate via ``transfer``:

    migrate="fp"      rows cross the handoff at full fp width (baseline)
    migrate="frozen"  full pages cross as packed 4-bit codes + per-block
                      sparse-LSQ codebooks (the paper's solvers via the
                      existing dispatch_freeze path, ~7x fewer bytes) and
                      land directly servable by the fused kernel

``attn_impl`` picks the decode read path: "fused" routes decode steps
through the Pallas paged-attention kernel (frozen pages dequantized in
VMEM), "gather" expands pages to dense K/V in HBM first, "auto" fuses on
TPU and gathers elsewhere (the kernel only interprets off-TPU).

``kv_quant`` is a QuantSpec (object or compact string like "kmeans_ls@16")
validated against the solver registry at construction, so an unfreezable
configuration fails here, naming the device-capable methods, rather than
mid-serve.

``speculate=k`` with ``draft=(params, cfg)`` (see
``serving.speculative.derive_draft``) turns every decode iteration into a
draft-propose / batched-verify / accept-rollback step: k draft tokens per
sequence are scored in ONE k+1-wide target pass against the paged cache,
accepted prefixes advance ``seq_lens`` in place, rejected suffixes roll
back (un-queueing any page-freeze bids past the accepted watermark). The
emitted trace is greedy-token-identical to non-speculative decoding by
construction; acceptance counters land in the metrics summary.

``prefill_chunk=C`` (colocated engine) splits every admitted prompt into
C-token chunks and advances ONE chunk per engine iteration, interleaved
with decode steps for the live batch — a long prompt no longer stalls
decode for its whole prefill, which is what bounds ``itl_max`` under a
long-prompt burst. The chunk's slot and worst-case pages are reserved at
admission (``scheduler.stage``) and the sequence joins the decode batch
only once its whole prompt is in cache; with ``attn_impl="fused"`` each
chunk reads earlier frozen pages as packed codes + codebooks through the
same double-buffered kernel path as decode. The chunk sequence is
logit-identical to a single-shot prefill (bitwise on the gather path), so
``--verify`` replays hold.

Weights flow through ``repro.quant.serve.qmatmul`` untouched: dense params
hit the plain matmul path, PTQ'd QuantizedTensor leaves hit the fused
dequant kernel — the engines are agnostic; each run's summary reports
``qmatmul_dequant_fallback``, the count of traced dense-materialization
fallbacks (0 certifies zero per-call weight dequants).
"""
from __future__ import annotations

import time
from collections import deque

import jax

from repro.core import registry as quant_registry
from repro.obs.trace import NULL_TRACER, phase
from repro.quant.serve import fallback_count

from .kv_cache import resolve_kv_spec
from .metrics import MetricsCollector
from .overload import OverloadManager, SLOAdmission
from .scheduler import DisaggRouter, Request, make_requests
from .workers import DecodeWorker, PrefillWorker


def _loop_phase(name: str) -> phase:
    """A run-loop phase (``engine:iteration``, ``engine:intake``) as a
    profiler annotation only: how many passes a run makes while it polls
    in-flight work or waits for arrivals follows the wall clock, and the
    in-memory trace stays a function of the run's events."""
    return phase(NULL_TRACER, "engine", name)


def _make_overload(metrics, *, offload_pages, preempt, admission, itl_slo_s,
                   router=None):
    """Overload machinery shared by both engine compositions: None when
    every overload feature is off (the pre-PR fast path), else an
    ``OverloadManager`` with an SLO policy iff admission == "slo"."""
    assert admission in ("fcfs", "slo"), admission
    if not (offload_pages or preempt or admission == "slo"):
        return None
    policy = (SLOAdmission(metrics, itl_slo_s=itl_slo_s)
              if admission == "slo" else None)
    return OverloadManager(offload_pages=offload_pages, policy=policy,
                           router=router)


def _resolve_attn_impl(attn_impl: str) -> str:
    assert attn_impl in ("auto", "fused", "gather"), attn_impl
    if attn_impl == "auto":
        return "fused" if jax.default_backend() == "tpu" else "gather"
    return attn_impl


class ContinuousBatchingEngine:
    """Colocated serving: decode worker + pool-borrowing prefill worker."""

    def __init__(self, params, cfg, *, max_slots: int = 8,
                 block_size: int = 16, max_seq_len: int = 256,
                 num_blocks: int | None = None, kv_quant: str | None = None,
                 kv_num_values: int | None = None, max_queue: int = 256,
                 eos_id: int | None = None, record_logits: bool = False,
                 attn_impl: str = "auto", freeze_async: bool = True,
                 freeze_page_budget: int = 4, speculate: int = 0,
                 draft: tuple | None = None, prefill_chunk: int | None = None,
                 tracer=None, exporter=None,
                 offload_pages: bool = False, preempt: bool = False,
                 admission: str = "fcfs", itl_slo_s: float | None = None,
                 prefix_cache: bool = False):
        assert cfg.family == "lm", "paged serving drives decoder-only LMs"
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.exporter = exporter
        self.attn_impl = _resolve_attn_impl(attn_impl)
        # fail fast at construction: resolve_kv_spec validates the spec
        # against the solver registry and raises naming the device-capable
        # methods when the configuration can't freeze pages
        self.kv_spec = (None if kv_quant is None else
                        resolve_kv_spec(kv_quant, num_values=kv_num_values))
        self.params, self.cfg = params, cfg
        self.kv_quant = None if self.kv_spec is None else self.kv_spec.method
        self.kv_num_values = (16 if self.kv_spec is None
                              else self.kv_spec.num_values)
        self.record_logits = record_logits
        self.speculate = speculate
        self.metrics = MetricsCollector()
        self.outputs: dict[int, list[int]] = {}
        self.request_logits: dict[int, object] = {}
        self.worker = DecodeWorker(
            params, cfg, max_slots=max_slots, block_size=block_size,
            max_seq_len=max_seq_len, num_blocks=num_blocks,
            kv_spec=self.kv_spec, attn_impl=self.attn_impl,
            freeze_async=freeze_async, freeze_page_budget=freeze_page_budget,
            max_queue=max_queue, eos_id=eos_id, record_logits=record_logits,
            speculate=speculate, draft=draft,
            metrics=self.metrics, outputs=self.outputs,
            request_logits=self.request_logits, tracer=self.tracer,
            prefix_cache=prefix_cache)
        # prefill worker inlined into the decode worker's pool: the handoff
        # payload is a no-op "splice" of already-resident block ids
        self.prefill = PrefillWorker(
            params, cfg, block_size=block_size, max_seq_len=max_seq_len,
            kv_spec=self.kv_spec, pool=self.worker,
            record_logits=record_logits, metrics=self.metrics,
            prefill_chunk=prefill_chunk, tracer=self.tracer)
        self.prefill_chunk = prefill_chunk
        # admitted sequences whose prompts are mid-chunk: staged out of the
        # decode batch (slot + pages reserved), one chunk advances per
        # engine iteration, interleaved with decode steps
        self._chunking: deque = deque()
        # fallback watermark: this engine's runs report only their own
        # traced dense-materialization fallbacks, not the process total
        self._fallbacks0 = fallback_count()
        self.block_size = block_size
        self.max_seq_len = self.worker.max_seq_len
        self.freeze_async = self.worker.freeze_async
        self.eos_id = eos_id
        self.preempt = preempt
        self.overload = _make_overload(
            self.metrics, offload_pages=offload_pages, preempt=preempt,
            admission=admission, itl_slo_s=itl_slo_s)

    # ------------------------------------------- legacy attribute surface

    @property
    def tree(self):
        return self.worker.tree

    @tree.setter
    def tree(self, t):
        self.worker.tree = t

    @property
    def alloc(self):
        return self.worker.alloc

    @property
    def sched(self):
        return self.worker.sched

    @property
    def counters(self):
        return self.worker.counters

    @property
    def slots(self):
        return self.worker.slots

    @property
    def num_blocks(self):
        return self.worker.num_blocks

    @property
    def max_blocks(self):
        return self.worker.max_blocks

    @property
    def _pb(self):
        return self.worker._pb

    # ------------------------------------------------------------ intake

    def submit(self, req: Request, now: float) -> bool:
        if self.speculate and req.temperature > 0.0:
            raise ValueError(
                "speculative decoding serves the greedy (temperature=0) "
                "verification path; submit sampled requests to a "
                "non-speculative engine")
        w = self.worker
        om = self.overload
        if om is not None and om.policy is not None and w.fits(req):
            # SLO door, after the hard never-fits door (a request no pool
            # could hold is a rejection, not a shed): consult windowed
            # itl_p99 + live occupancy, touch only best_effort requests
            occ = 1.0 - w.alloc.num_free / (w.num_blocks - 1)
            verdict = om.policy.decide(req, occupancy=occ)
            if verdict == "shed":
                w.sched.rejected.append(req.id)
                self.metrics.admission("shed_slo")
                self.tracer.instant("router", "reject", rid=req.id,
                                    reason="shed_slo")
                return False
            if verdict == "defer":
                om.deferred.append(req)
                self.metrics.arrival(req.id, now, req.prompt_len)
                self.metrics.admission("deferred")
                self.tracer.instant("router", "defer", rid=req.id,
                                    deferred=len(om.deferred))
                return True
        ok = w.submit(req, now)
        # no router here — the colocated scheduler's admission decision IS
        # the routing decision, so it lands on the same "router" track
        self.tracer.instant("router", "admit" if ok else "reject",
                            rid=req.id)
        return ok

    # ------------------------------------------------------------ run loop

    def run(self, requests: list[Request], *, poll_s: float = 0.002) -> dict:
        """Serve a trace of requests (arrival_time = seconds from start).

        Wall-clock driven: a request becomes visible when the loop's clock
        passes its arrival_time; the loop sleeps only when fully idle.
        """
        w = self.worker
        om = self.overload
        pending = deque(sorted(requests, key=lambda r: (r.arrival_time, r.id)))
        t0 = time.perf_counter()
        now_fn = lambda: time.perf_counter() - t0
        om_work = lambda: om is not None and om.has_work
        while pending or w.sched.has_work or self._chunking or om_work():
            # one pass: intake, admission (inline prefills), freezes and
            # one decode step; each named phase nests inside it
            with _loop_phase("iteration"):
                with _loop_phase("intake"):
                    now = now_fn()
                    while pending and pending[0].arrival_time <= now:
                        self.submit(pending.popleft(), now)
                if not (w.sched.has_work or self._chunking or om_work()):
                    if not pending:     # everything left was rejected
                        break
                    nxt = pending[0].arrival_time
                    time.sleep(min(max(nxt - now, 0.0), poll_s) or poll_s)
                    continue
                if om is not None:
                    # restore-ahead BEFORE admission: an offloaded sequence
                    # re-enters (pages re-installed while its first decode step
                    # is still an iteration away) ahead of every queued arrival
                    om.retry_deferred(w)
                    om.try_restore(w, now_fn)
                # with the prefix cache on, admission charges each request its
                # worst case minus the prompt pages already shareable — the
                # capacity side of sharing (attach splices those pages instead
                # of allocating them, so the discounted need is what prefill
                # actually draws from the pool)
                disc = w.prefix_probe if w.prefix is not None else None
                with _loop_phase("intake"):
                    admitted = w.sched.schedule(w.alloc.num_free,
                                                discount=disc)
                for st in admitted:
                    if self.prefill_chunk:
                        # chunked path: pages allocated now, prompt advances
                        # one chunk per iteration below; the slot stays out of
                        # the decode batch until the whole prompt is in cache
                        self._chunking.append(
                            (st, self.prefill.start_chunked(st.req, now_fn)))
                        w.sched.stage(st)
                    else:
                        # inline prefill straight into the decode worker's
                        # pool, then the no-op splice attaches the sequence
                        fin = self.prefill.run_inline(st.req, now_fn)
                        w.attach(st, fin, now_fn())
                if self._chunking:
                    # one chunk per iteration (FCFS head), so decode steps for
                    # live sequences interleave between chunks of a long prompt
                    st, state = self._chunking[0]
                    fin = self.prefill.advance_chunk(state, now_fn)
                    if fin is not None:
                        self._chunking.popleft()
                        w.sched.activate(st)
                        w.attach(st, fin, now_fn())
                if om is not None and self.preempt:
                    om.maybe_preempt(w, now_fn)
                # one batched (budgeted) solve for the pages the prefills (and
                # the previous iteration's decode) just filled, then this
                # iteration's decode step
                w.step(now_fn)
                if self.exporter is not None:
                    self.exporter.maybe_emit(self.metrics)
        w.drain()
        if om is not None:
            # host-tier retirement backstop: an entry still demoted when
            # the run ends (its request finished/was cancelled while
            # offloaded, or restore never fired) is reclaimed here so both
            # residency tiers provably drain to empty; its output is
            # whatever it emitted before eviction
            for entry in om.store.entries():
                om.retire(entry.req.id)
                self.outputs.setdefault(entry.req.id, list(entry.out))
        if self.exporter is not None:
            self.exporter.maybe_emit(self.metrics, force=True)
        out = self.metrics.summary()
        # steady-state per-page ratio: what a fully frozen cache saves
        out["page_compression"] = w._pb["fp"] / w._pb["frozen"]
        out["rejected"] = len(w.sched.rejected)
        out["attn_impl"] = self.attn_impl
        out.update(w.counters)
        out["prefill_chunks"] = self.prefill.counters["prefill_chunks"]
        # 0 certifies zero per-call weight dequants this run: every PTQ'd
        # matmul (scanned stacked leaves included) hit a fused kernel
        fallbacks = fallback_count() - self._fallbacks0
        self._fallbacks0 = fallback_count()
        out["qmatmul_dequant_fallback"] = fallbacks
        self.metrics.stats.counter("qmatmul_dequant_fallback").inc(fallbacks)
        if out.get("offload_bytes"):
            # what the frozen-page host tier saved vs demoting at fp width
            out["offload_compression"] = (out["offload_fp_equiv_bytes"]
                                          / out["offload_bytes"])
        # decode-generated tokens per per-sequence decode step (batching
        # factored out): exactly 1.0 for plain decoding, > 1 when
        # speculative verify windows accept drafts
        if out.get("seq_decode_steps"):
            out["tokens_per_step"] = ((out.get("gen_tokens", 0)
                                       - out.get("completed", 0))
                                      / out["seq_decode_steps"])
        out["speculate"] = self.speculate
        return out

    def generate(self, prompts: list[list[int]], max_new_tokens: int,
                 *, temperature: float = 0.0, top_k: int = 0,
                 seed: int | None = None) -> dict:
        """Batch convenience: all requests arrive at t=0; returns outputs
        (None for requests rejected by admission control). Sampling knobs
        apply to every request (per-request seeds derive from ``seed``)."""
        self.run(make_requests(prompts, max_new_tokens,
                               temperature=temperature, top_k=top_k,
                               seed=seed))
        return {i: self.outputs.get(i) for i in range(len(prompts))}


class DisaggEngine:
    """Disaggregated serving: N prefill workers (own pools) feed M decode
    workers through a global router; pages migrate fp or frozen."""

    def __init__(self, params, cfg, *, prefill_workers: int = 1,
                 decode_workers: int = 1, migrate: str = "fp",
                 max_slots: int = 8, block_size: int = 16,
                 max_seq_len: int = 256, num_blocks: int | None = None,
                 prefill_blocks: int | None = None,
                 kv_quant: str | None = None, kv_num_values: int | None = None,
                 max_queue: int = 256, staging_depth: int | None = None,
                 eos_id: int | None = None,
                 record_logits: bool = False, attn_impl: str = "auto",
                 freeze_async: bool = True, freeze_page_budget: int = 4,
                 speculate: int = 0, draft: tuple | None = None,
                 tracer=None, exporter=None,
                 offload_pages: bool = False, preempt: bool = False,
                 admission: str = "fcfs", itl_slo_s: float | None = None):
        assert cfg.family == "lm", "paged serving drives decoder-only LMs"
        assert prefill_workers >= 1 and decode_workers >= 1
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.exporter = exporter
        if migrate not in ("fp", "frozen"):
            raise ValueError(f"migrate must be 'fp' or 'frozen', got "
                             f"{migrate!r}")
        self.attn_impl = _resolve_attn_impl(attn_impl)
        self.kv_spec = (None if kv_quant is None else
                        resolve_kv_spec(kv_quant, num_values=kv_num_values))
        if migrate == "frozen":
            if self.kv_spec is None:
                raise ValueError(
                    "migrate='frozen' ships pages as codes+codebooks and "
                    "needs a kv_quant spec (e.g. kmeans_ls@16)")
            if not self.kv_spec.device_capable:
                raise ValueError(
                    f"migrate='frozen' routes pages through the batched "
                    f"device freeze path; {self.kv_spec.method} has no "
                    f"device solver — use one of: "
                    f"{', '.join(quant_registry.device_methods())}")
        self.params, self.cfg = params, cfg
        self.migrate = migrate
        self.kv_quant = None if self.kv_spec is None else self.kv_spec.method
        self.kv_num_values = (16 if self.kv_spec is None
                              else self.kv_spec.num_values)
        self.record_logits = record_logits
        self.speculate = speculate
        self.metrics = MetricsCollector()
        self.outputs: dict[int, list[int]] = {}
        self.request_logits: dict[int, object] = {}
        self.decode = [DecodeWorker(
            params, cfg, worker_id=i, max_slots=max_slots,
            block_size=block_size, max_seq_len=max_seq_len,
            num_blocks=num_blocks, kv_spec=self.kv_spec,
            attn_impl=self.attn_impl, freeze_async=freeze_async,
            freeze_page_budget=freeze_page_budget, eos_id=eos_id,
            record_logits=record_logits, speculate=speculate, draft=draft,
            metrics=self.metrics,
            outputs=self.outputs, request_logits=self.request_logits,
            tracer=self.tracer)
            for i in range(decode_workers)]
        self.prefills = [PrefillWorker(
            params, cfg, worker_id=i, block_size=block_size,
            max_seq_len=max_seq_len, kv_spec=self.kv_spec, migrate=migrate,
            num_blocks=prefill_blocks, record_logits=record_logits,
            metrics=self.metrics, tracer=self.tracer)
            for i in range(prefill_workers)]
        self.router = DisaggRouter(max_queue=max_queue,
                                   staging_depth=staging_depth,
                                   tracer=self.tracer)
        self._fallbacks0 = fallback_count()
        self.block_size = block_size
        self.max_seq_len = self.decode[0].max_seq_len
        self.freeze_async = self.decode[0].freeze_async
        self.eos_id = eos_id
        self.preempt = preempt
        self.overload = _make_overload(
            self.metrics, offload_pages=offload_pages, preempt=preempt,
            admission=admission, itl_slo_s=itl_slo_s, router=self.router)

    # ------------------------------------------------------------ intake

    def submit(self, req: Request, now: float) -> bool:
        if self.speculate and req.temperature > 0.0:
            raise ValueError(
                "speculative decoding serves the greedy (temperature=0) "
                "verification path; submit sampled requests to a "
                "non-speculative engine")
        d0, p0 = self.decode[0], self.prefills[0]
        if (req.prompt_len + req.max_new_tokens + self.speculate
                > self.max_seq_len
                or d0.sched.blocks_for(req) > d0.num_blocks - 1
                or -(-req.prompt_len // self.block_size)
                > p0.num_blocks - 1):
            # reject what no worker can ever hold — staging it would
            # head-of-line-block the router's queues forever
            self.router.rejected.append(req.id)
            self.metrics.admission("rejected_pool_full")
            self.tracer.instant("router", "reject", rid=req.id,
                                reason="never_fits")
            return False
        om = self.overload
        if om is not None and om.policy is not None:
            # the request may land on any decode worker, so gate on the
            # least-loaded one's occupancy
            occ = min(1.0 - d.alloc.num_free / (d.num_blocks - 1)
                      for d in self.decode)
            verdict = om.policy.decide(req, occupancy=occ)
            if verdict == "shed":
                self.router.rejected.append(req.id)
                self.metrics.admission("shed_slo")
                self.tracer.instant("router", "reject", rid=req.id,
                                    reason="shed_slo")
                return False
            if verdict == "defer":
                om.deferred.append(req)
                self.metrics.arrival(req.id, now, req.prompt_len)
                self.metrics.admission("deferred")
                self.tracer.instant("router", "defer", rid=req.id,
                                    deferred=len(om.deferred))
                return True
        ok = self.router.submit(req)
        if ok:
            self.metrics.arrival(req.id, now, req.prompt_len)
        else:
            self.metrics.admission("rejected_queue_full")
        return ok

    # ------------------------------------------------------------ run loop

    @property
    def _has_work(self) -> bool:
        return (self.router.has_work or any(p.busy for p in self.prefills)
                or any(d.sched.has_work or d.has_work for d in self.decode)
                or (self.overload is not None and self.overload.has_work))

    def run(self, requests: list[Request], *, poll_s: float = 0.002) -> dict:
        """Serve a trace of requests (arrival_time = seconds from start).

        One loop iteration: route waiting requests onto prefill workers,
        advance each prefill worker (async — dispatch or harvest), place
        finished prefills onto decode workers, then one decode step per
        decode worker with live sequences. Decode never waits on a prefill:
        a burst of long prompts costs each iteration at most the prefill
        workers' dispatch overhead, which is the TPOT-isolation property
        the worker split buys.
        """
        pending = deque(sorted(requests, key=lambda r: (r.arrival_time, r.id)))
        t0 = time.perf_counter()
        now_fn = lambda: time.perf_counter() - t0
        while pending or self._has_work:
            with _loop_phase("iteration"):
                with _loop_phase("intake"):
                    now = now_fn()
                    while pending and pending[0].arrival_time <= now:
                        self.submit(pending.popleft(), now)
                if not self._has_work:
                    if not pending:     # everything left was rejected
                        break
                    nxt = pending[0].arrival_time
                    time.sleep(min(max(nxt - now, 0.0), poll_s) or poll_s)
                    continue
                progressed = False
                om = self.overload
                if om is not None:
                    # restore-ahead: offloaded sequences re-enter (onto any
                    # decode worker with capacity — payloads are portable)
                    # before staged prefills or queued arrivals take the space
                    om.retry_deferred(max(self.decode,
                                          key=lambda d: d.alloc.num_free))
                    for dw in self.decode:
                        progressed |= bool(om.try_restore(dw, now_fn))
                self.router.route_prefill(self.prefills)
                for pw in self.prefills:
                    for fin in pw.step(now_fn):
                        self.router.stage(fin)
                        progressed = True
                def _place(dw, fin):
                    st = dw.sched.admit_direct(fin.req)
                    assert st is not None       # router checked can_accept
                    dw.attach(st, fin, now_fn())
                progressed |= bool(self.router.route_decode(self.decode,
                                                            _place))
                if om is not None and self.preempt:
                    for dw in self.decode:
                        progressed |= om.maybe_preempt(dw, now_fn)
                for dw in self.decode:
                    if dw.has_work:
                        dw.step(now_fn)
                        progressed = progressed or bool(dw.sched.active)
                if self.exporter is not None:
                    self.exporter.maybe_emit(self.metrics)
                if not progressed:
                    # only in-flight prefills to wait on: let the device work
                    time.sleep(poll_s / 4)
        for pw in self.prefills:
            assert not pw.busy
        for dw in self.decode:
            dw.drain()
        if self.overload is not None:
            # same host-tier retirement backstop as the colocated engine
            for entry in self.overload.store.entries():
                self.overload.retire(entry.req.id)
                self.outputs.setdefault(entry.req.id, list(entry.out))
        if self.exporter is not None:
            self.exporter.maybe_emit(self.metrics, force=True)
        return self._summary()

    def _summary(self) -> dict:
        out = self.metrics.summary()
        agg = {}
        for dw in self.decode:
            for k, v in dw.counters.items():
                agg[k] = max(agg.get(k, 0), v) if k == "max_gather_blocks" \
                    else agg.get(k, 0) + v
        out.update(agg)
        out["prefills_done"] = sum(p.counters["prefills"]
                                   for p in self.prefills)
        fallbacks = fallback_count() - self._fallbacks0
        self._fallbacks0 = fallback_count()
        out["qmatmul_dequant_fallback"] = fallbacks
        self.metrics.stats.counter("qmatmul_dequant_fallback").inc(fallbacks)
        out["rejected"] = len(self.router.rejected)
        out["attn_impl"] = self.attn_impl
        out["migrate"] = self.migrate
        if agg.get("seq_decode_steps"):
            out["tokens_per_step"] = ((out.get("gen_tokens", 0)
                                       - out.get("completed", 0))
                                      / agg["seq_decode_steps"])
        out["speculate"] = self.speculate
        out["prefill_workers"] = len(self.prefills)
        out["decode_workers"] = len(self.decode)
        pb = self.decode[0]._pb
        out["page_compression"] = pb["fp"] / pb["frozen"]
        out["migrate_compression"] = (
            out["migrate_fp_equiv_bytes"] / out["migrate_bytes"]
            if out.get("migrate_bytes") else 1.0)
        if out.get("offload_bytes"):
            out["offload_compression"] = (out["offload_fp_equiv_bytes"]
                                          / out["offload_bytes"])
        return out

    def generate(self, prompts: list[list[int]], max_new_tokens: int,
                 *, temperature: float = 0.0, top_k: int = 0,
                 seed: int | None = None) -> dict:
        """Batch convenience mirroring the colocated engine's."""
        self.run(make_requests(prompts, max_new_tokens,
                               temperature=temperature, top_k=top_k,
                               seed=seed))
        return {i: self.outputs.get(i) for i in range(len(prompts))}
