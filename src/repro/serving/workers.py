"""Role-based serving workers: model execution split into composable
prefill and decode roles.

``DecodeWorker`` owns a paged KV pool and the decode hot loop — iteration
batching over its slots, async page freezing (batched sparse-LSQ device
solves, rate-limited per decode step), eviction/recycling — behind a narrow
``step()`` / ``attach()`` interface. Sequences enter it only as finished
prefills (``transfer.FinishedPrefill``): pages are spliced into its pool
and decoding continues from the already-sampled first token.

``PrefillWorker`` turns queued prompts into finished prefills. It runs in
one of two compositions:

  owned pool (disaggregated)   The worker prefills into its *own* paged
      pool, then extracts the pages as a migration payload — fp rows, or
      codes + codebooks when migrating frozen — and frees its blocks. The
      dispatch is async: ``step()`` launches the prefill (and, for frozen
      migration, the freeze solve chained behind it) and only harvests once
      the device finished, so a long prompt never blocks the caller's loop.

  borrowed pool (colocated)    Constructed with ``pool=<DecodeWorker>``,
      the worker prefills straight into the decode worker's pool using
      blocks from its allocator; the handoff payload is a no-op "splice"
      carrying just the block ids. This is exactly the old monolithic
      engine's inline prefill, now expressed as the degenerate worker
      composition.

Both engines (`engine.ContinuousBatchingEngine`, `engine.DisaggEngine`)
are thin run loops over these two roles plus a scheduler/router.
"""
from __future__ import annotations

import dataclasses
import functools
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro import models
from repro.obs.trace import NULL_TRACER, phase

from .kv_cache import (BlockAllocator, PrefixIndex, dispatch_freeze,
                       freeze_blocks, init_paged_cache, install_freeze,
                       merge_pools, page_bytes, thaw_blocks,
                       with_prefill_fused, with_tables)
from .scheduler import ContinuousBatchingScheduler, Request, SeqState
from .speculative import DraftWorker, window_step
from .overload import ResumeEntry
from .transfer import (FinishedPrefill, PagePayload, extract_pages,
                       extract_resident_pages, splice_payload)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _prefill_step(params, toks, tree, *, cfg):
    return models.prefill(params, cfg, {"tokens": toks}, tree)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _prefill_chunk_step(params, toks, pos, tree, *, cfg):
    # positions are explicit (off + arange(C), same 2-D form lm_prefill
    # derives itself) so a chunk at token offset ``off`` ropes/masks exactly
    # as the matching slice of a single whole-prompt prefill
    return models.prefill(params, cfg, {"tokens": toks, "positions": pos},
                          tree)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _decode_step_fn(params, toks, tree, lens, *, cfg):
    return models.decode_step(params, cfg, toks, tree, lens)


def sample_token(row: np.ndarray, *, temperature: float = 0.0,
                 top_k: int = 0, rng=None) -> int:
    """Engine-level sampling over one vocab row of logits.

    temperature <= 0 is greedy argmax (the default and the path every
    logit-replay verification runs); otherwise softmax at ``temperature``
    over the ``top_k`` largest logits (0 = no truncation), drawn from the
    request's own Generator so traces replay deterministically per seed.
    """
    if temperature <= 0.0 or rng is None:
        return int(np.argmax(row))
    logits = np.asarray(row, np.float64) / temperature
    if 0 < top_k < logits.size:
        kth = np.partition(logits, -top_k)[-top_k]
        logits = np.where(logits >= kth, logits, -np.inf)
    p = np.exp(logits - logits.max())
    return int(rng.choice(logits.size, p=p / p.sum()))


class _Slot:
    """Decode-worker per-slot state (token io + page bookkeeping)."""

    def __init__(self):
        self.rid = None
        self.blocks: list[int] = []
        self.frozen_upto = 0          # block-table slots already quantized
        self.last_token = 0
        self.out: list[int] = []
        self.logits: list[np.ndarray] = []
        self.rng = None
        self.temperature = 0.0
        self.top_k = 0


class DecodeWorker:
    """The decode role: paged pool + iteration-batched decode loop + async
    freeze machinery, fed through ``attach(seq_state, finished_prefill)``.
    """

    def __init__(self, params, cfg, *, worker_id: int = 0, max_slots: int = 8,
                 block_size: int = 16, max_seq_len: int = 256,
                 num_blocks: int | None = None, kv_spec=None,
                 attn_impl: str = "gather", freeze_async: bool = True,
                 freeze_page_budget: int = 4, max_queue: int = 256,
                 eos_id: int | None = None, record_logits: bool = False,
                 speculate: int = 0, draft: tuple | None = None,
                 metrics=None, outputs=None, request_logits=None,
                 tracer=None, prefix_cache: bool = False):
        from .metrics import MetricsCollector

        self.worker_id = worker_id
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self._trk_decode = f"decode/w{worker_id}"
        self._trk_freeze = f"freeze/w{worker_id}"
        self._trk_spec = f"spec/w{worker_id}"
        self.params, self.cfg = params, cfg
        self.kv_spec = kv_spec
        self.attn_impl = attn_impl
        self.block_size = block_size
        self.max_blocks = -(-max_seq_len // block_size)
        self.max_seq_len = self.max_blocks * block_size
        self.num_blocks = (num_blocks if num_blocks is not None
                           else max_slots * self.max_blocks + 1)
        self.freeze_async = (freeze_async and kv_spec is not None
                             and kv_spec.device_capable)
        assert freeze_page_budget >= 1, "freeze budget must cover >= 1 page"
        self.freeze_page_budget = freeze_page_budget
        self.eos_id = eos_id
        self.record_logits = record_logits
        assert speculate >= 0
        if speculate:
            if draft is None:
                raise ValueError("speculate=k needs draft=(params, cfg) — "
                                 "see serving.speculative.derive_draft")
            draft_params, draft_cfg = draft
            if draft_cfg.vocab != cfg.vocab:
                raise ValueError(
                    f"draft vocab {draft_cfg.vocab} != target {cfg.vocab}; "
                    f"speculative verify compares token ids directly")
            if attn_impl == "fused" and speculate + 1 > block_size:
                raise ValueError(
                    f"speculate={speculate} verify window exceeds block "
                    f"size {block_size}; the fused-window gate would also "
                    f"catch prefill steps")
        self.speculate = speculate

        self.tree = init_paged_cache(
            cfg, num_blocks=self.num_blocks, block_size=block_size,
            batch=max_slots, max_blocks=self.max_blocks,
            quantized=kv_spec is not None,
            num_values=16 if kv_spec is None else kv_spec.num_values,
            fused=attn_impl == "fused", fused_window=speculate + 1)
        self.alloc = BlockAllocator(self.num_blocks)
        # `lookahead` reserves the verify window's optimistic write rows
        # past max_new_tokens in worst-case page accounting
        self.sched = ContinuousBatchingScheduler(
            max_slots=max_slots, block_size=block_size, max_queue=max_queue,
            lookahead=speculate)
        self.draft = None if not speculate else DraftWorker(
            draft[0], draft[1], max_slots=max_slots, block_size=block_size,
            max_blocks=self.max_blocks, worker_id=worker_id,
            tracer=self.tracer)
        self.metrics = metrics if metrics is not None else MetricsCollector()
        self.table = np.zeros((max_slots, self.max_blocks), np.int32)
        self.lens = np.zeros((max_slots,), np.int32)
        self.slots = [_Slot() for _ in range(max_slots)]
        self.outputs = outputs if outputs is not None else {}
        self.request_logits = (request_logits if request_logits is not None
                               else {})
        self._pb = page_bytes(cfg, block_size, quantized=kv_spec is not None,
                              num_values=16 if kv_spec is None
                              else kv_spec.num_values)
        # freeze/decode overlap + migration accounting; host_page_solves
        # counts fallback per-page numpy solves (0 in the device-solver
        # steady state), freeze_deferred_pages counts pages pushed past
        # their iteration by the per-step freeze budget.
        self.counters = {"freeze_dispatches": 0, "freeze_installs": 0,
                         "host_page_solves": 0, "decode_steps": 0,
                         "seq_decode_steps": 0,
                         "freeze_inflight_steps": 0, "freeze_overlap_steps": 0,
                         "freeze_pending_max": 0, "freeze_deferred_pages": 0,
                         "max_gather_blocks": 0, "migrated_seqs": 0,
                         "migrated_pages": 0, "migrate_bytes": 0,
                         "migrate_fp_equiv_bytes": 0,
                         # overload survival: whole-sequence evictions and
                         # the host-tier traffic they caused
                         "preemptions": 0, "preempt_offloads": 0,
                         "preempt_recomputes": 0, "offloaded_pages": 0,
                         "offload_bytes": 0, "offload_fp_equiv_bytes": 0,
                         "restored_seqs": 0, "restored_pages": 0,
                         "restore_bytes": 0,
                         # prefix sharing: attaches that matched a published
                         # prefix run, the pages they spliced instead of
                         # prefilling, and write-hot tail pages materialized
                         # privately instead of shared (copy-on-write)
                         "prefix_hits": 0, "prefix_shared_pages": 0,
                         "cow_copies": 0,
                         # host nanoseconds of each engine phase (the
                         # colocated prefill worker books its prefills
                         # here too) and the prompt tokens it prefilled
                         "decode_dispatch_ns": 0, "decode_sync_ns": 0,
                         "decode_commit_ns": 0, "freeze_flush_ns": 0,
                         "freeze_install_ns": 0, "prefill_ns": 0,
                         "prefill_tokens": 0,
                         # per decode step, summed: pages the step reads
                         # and how many of them serve 4-bit codes; rows
                         # the live sequences hold against the rows of
                         # the pages reserved for them
                         "kv_pages_read": 0, "kv_pages_read_frozen": 0,
                         "kv_rows_used": 0, "kv_rows_reserved": 0}
        # radix/hash prefix index over installed-frozen (or, unquantized,
        # sequence-passed) full prompt pages; sequences attach published
        # pages at rc > 1 instead of re-prefilling them
        self.prefix = PrefixIndex(block_size) if prefix_cache else None
        self._pending_freezes: list[tuple[int, object]] = []
        self._freeze_bids: list[int] = []   # queued for the next flush
        self._deferred_seen = 0    # queue suffix already counted deferred
        self._frozen_pages: set[int] = set()   # installed (codes serving)
        # the same set as a mask over block ids, for per-step read counts
        self._frozen_mask = np.zeros(self.num_blocks, bool)
        # freeze-lifecycle async spans: page id -> open span id. A span
        # opens when a bid is queued and MUST end in exactly one terminal
        # state — installed / dropped (seq finished first) / rolled_back
        # (speculative suffix rejected) — which the obs property test
        # checks against the dispatch/install counters.
        self._page_spans: dict[int, int] = {}
        self._span_seq = 0
        # overload machinery: per-slot LRU signal (decode step the slot
        # last attended) and, for recompute-path preemptions, the tokens
        # already emitted under the request's first life — merged back
        # into ``outputs`` when the resumed request finishes
        self.last_attended: dict[int, int] = {}
        self._resume_prefix: dict[int, tuple[list, list]] = {}

        # module-level jit keyed on the (hashable) config: workers of the
        # same geometry share compiles instead of retracing per instance
        self._decode_fn = functools.partial(_decode_step_fn, cfg=cfg)
        self._verify_fn = functools.partial(window_step, cfg=cfg)

    # ------------------------------------------------------------ intake

    def fits(self, req: Request) -> bool:
        """Whether this worker could EVER hold the request (sequence
        budget and whole page pool) — the never-admit door. Admitting a
        request that fails this would head-of-line-block the queue
        forever."""
        return not (req.prompt_len + req.max_new_tokens + self.speculate
                    > self.max_seq_len
                    or self.sched.blocks_for(req) > self.num_blocks - 1)

    def submit(self, req: Request, now: float) -> bool:
        """Colocated front door: admission control + queueing + arrival
        metric (the disaggregated router does this globally instead)."""
        if not self.fits(req):
            self.sched.rejected.append(req.id)
            self.metrics.admission("rejected_pool_full")
            return False
        ok = self.sched.submit(req)
        if ok:
            self.metrics.arrival(req.id, now, req.prompt_len)
        else:
            self.metrics.admission("rejected_queue_full")
        return ok

    def can_accept(self, req: Request) -> bool:
        """Router probe: a free slot and the request's worst-case pages."""
        return (bool(self.sched._free_slots)
                and self.sched.blocks_for(req) <= self.alloc.num_free)

    @property
    def free_slots(self) -> int:
        return len(self.sched._free_slots)

    @property
    def has_work(self) -> bool:
        return bool(self.sched.active or self._pending_freezes
                    or self._freeze_bids)

    # ------------------------------------------------------------ import

    def attach(self, st: SeqState, fin: FinishedPrefill, now: float) -> None:
        """Splice a finished prefill's pages into this worker's pool and
        start decoding it at slot ``st.slot``.

        "splice" payloads (colocated) carry block ids already living in
        this pool; migration payloads allocate the request's worst-case
        blocks here, land the prompt pages in the first of them (frozen
        pages through ``install_freeze``, directly servable by the fused
        kernel), and the rest fill during decode.
        """
        with phase(self.tracer, self._trk_decode, "attach", rid=st.req.id):
            req, s = st.req, self.slots[st.slot]
            payload = fin.payload
            if payload.mode == "splice":
                blocks = list(payload.blocks)
            else:
                blocks = self.alloc.alloc(self.sched.blocks_for(req))
                self.tree = splice_payload(self.tree, payload, blocks,
                                           tracer=self.tracer)
                self.counters["migrated_seqs"] += 1
                self.counters["migrated_pages"] += payload.n_pages
                self.counters["migrate_bytes"] += payload.nbytes
                self.counters["migrate_fp_equiv_bytes"] += (
                    payload.fp_equiv_bytes)
            P = req.prompt_len
            s.rid, s.blocks = req.id, blocks
            s.out, s.logits = [fin.first_token], []
            s.last_token = fin.first_token
            s.rng, s.temperature, s.top_k = fin.rng, req.temperature, req.top_k
            if self.record_logits and fin.last_logits is not None:
                s.logits.append(fin.last_logits)
            self.table[st.slot] = 0
            self.table[st.slot, :len(blocks)] = blocks
            self.lens[st.slot] = P
            st.length, st.generated = P, 1
            # a fresh attach is the coldest possible preemption candidate
            # at the current step — seed the LRU signal so pick_victim can
            # see it before its first decode step
            self.last_attended[st.slot] = self.counters["decode_steps"]
            if payload.mode == "frozen" and payload.n_full:
                # pages landed as codes+codebooks: already frozen, never queue
                # them for a second solve
                s.frozen_upto = payload.n_full
                self._mark_frozen(blocks[:payload.n_full])
            else:
                # a shared prefix splices installed-frozen pages: they start
                # the frozen watermark, so they are never queued for a second
                # solve (unquantized pools share exact-fp pages; the watermark
                # stays 0 because nothing ever freezes)
                s.frozen_upto = (payload.shared_pages
                                 if self.kv_spec is not None else 0)
                self._queue_freeze(st.slot)
            if self.draft is not None:
                # the draft prefills the same prompt on its own pool (cheap:
                # the draft config is the reduced one) and mirrors this slot
                self.draft.attach(st.slot, req.prompt, len(blocks))
            self._publish_prefixes()
            if st.done or fin.first_token == self.eos_id:
                self._finish(st, now)

    # ------------------------------------------------------ prefix sharing

    def _publish_prefixes(self) -> None:
        """(Re)publish every active slot's eligible full prompt pages into
        the prefix index. Quantized pools publish only installed-frozen
        pages (immutable reconstructions); unquantized pools publish every
        full prompt page — prompt rows never rewrite once the sequence's
        length passes them, so sharing them is bitwise-exact. Idempotent
        (the index dedupes on chain key), so calling after every attach /
        install keeps the index current without per-page bookkeeping."""
        if self.prefix is None:
            return
        frozen = self._frozen_pages if self.kv_spec is not None else None
        for i in self.sched.active_slots():
            st = self.sched.active[i]
            self.prefix.publish(st.req.prompt, self.slots[i].blocks, frozen)

    def shared_prefix_pages(self, slot: int) -> int:
        """Length of the slot's leading page run other sequences also
        reference (rc > 1). Sharing only ever splices *prefix* runs of
        published chains, so refcounts are monotone non-increasing along
        the table — the first rc == 1 page ends the run. Used by preemption
        to scope a victim's payload to pages it exclusively owns."""
        if self.prefix is None:
            return 0
        n = 0
        for b in self.slots[slot].blocks:
            if self.alloc.refcount(int(b)) <= 1:
                break
            n += 1
        return n

    def prefix_probe(self, req: Request) -> int:
        """Scheduler admission discount: pages of ``req``'s prompt already
        published (lookup only — no retain). Admission can charge the
        request worst-case-minus-shareable pages because its prefill will
        splice exactly these pages instead of allocating fresh ones."""
        if self.prefix is None:
            return 0
        return len(self.prefix.lookup(req.prompt,
                                      (req.prompt_len - 1) // self.block_size))

    # ------------------------------------------------------------ steps

    def step(self, now_fn) -> None:
        """One engine iteration over this worker: flush queued freezes
        (budgeted), one batched decode step, occupancy sample.

        With no live sequences the decode step is skipped but pending
        freezes are still polled — an async solve outliving its sequences
        must land (or be dropped) here, or a run loop keyed on
        ``has_work`` would wait on it forever."""
        self._flush_freezes()
        if self.sched.active_slots():
            if self.speculate:
                self._spec_decode_step(now_fn)
            else:
                self._decode_step(now_fn)
        else:
            self._poll_freezes()
        self._sample_cache()

    def _decode_step(self, now_fn) -> None:
        active = self.sched.active_slots()
        if not active:
            return
        tr, c, trk = self.tracer, self.counters, self._trk_decode
        c["decode_steps"] += 1
        c["seq_decode_steps"] += len(active)
        with phase(tr, trk, "decode_step", step=c["decode_steps"],
                   active=len(active)):
            self._poll_freezes()
            toks = np.zeros((len(self.slots), 1), np.int32)
            for i in active:
                toks[i, 0] = self.slots[i].last_token
            # gather only the blocks the longest live sequence needs this
            # step (idle slots sit at length 0); retraces are bounded by
            # max_blocks
            need = int(self.lens.max()) + 1
            mb_used = max(1, -(-need // self.block_size))
            c["max_gather_blocks"] = max(c["max_gather_blocks"], mb_used)
            with phase(tr, trk, "dispatch", c, "decode_dispatch_ns",
                       blocks=mb_used):
                self._count_reads(active, mb_used, 1)
                tree = with_tables(self.tree, self.table[:, :mb_used],
                                   self.lens)
                lens = jnp.asarray(self.lens)
                logits, new = self._decode_fn(self.params, jnp.asarray(toks),
                                              tree, lens)
                self.tree = merge_pools(self.tree, new)
            with phase(tr, trk, "sync", c, "decode_sync_ns"):
                # lint: sync(intentional step-end token sync for the scheduler)
                nxt = np.asarray(jnp.argmax(logits[:, -1], -1))
                sampling = any(self.slots[i].temperature > 0.0
                               for i in active)
                # lint: sync(host sampling/record path needs this step's row)
                rows = (np.asarray(logits[:, -1])
                        if self.record_logits or sampling else None)
            with phase(tr, trk, "commit", c, "decode_commit_ns") as p:
                now = now_fn()
                finished = []
                for i in active:
                    st = self.sched.active[i]
                    s = self.slots[i]
                    self.last_attended[i] = c["decode_steps"]
                    self.lens[i] += 1
                    st.length += 1
                    st.generated += 1
                    s.last_token = (sample_token(rows[i],
                                                 temperature=s.temperature,
                                                 top_k=s.top_k, rng=s.rng)
                                    if s.temperature > 0.0 else int(nxt[i]))
                    s.out.append(s.last_token)
                    if self.record_logits:
                        s.logits.append(rows[i])
                    self.metrics.token(st.req.id, now)
                    self._queue_freeze(i)
                    if st.done or s.last_token == self.eos_id:
                        finished.append(st)
                for st in finished:
                    self._finish(st, now)
                p.args["finished"] = len(finished)

    def _count_reads(self, active: list[int], mb_used: int, W: int) -> None:
        """Count what this step reads, from the table and lengths it is
        dispatched with: each live slot's pages up to its last written row
        (``ceil((len + W) / bs)`` for a W-token step), the installed-frozen
        ones among them, and its held rows against its reserved pages'."""
        bs, c = self.block_size, self.counters
        idx = np.asarray(active)
        lens = self.lens[idx]
        pages = (lens + W - 1 + bs) // bs
        frozen = self._frozen_mask[self.table[idx, :mb_used]]
        frozen &= np.arange(mb_used) < pages[:, None]
        c["kv_pages_read"] += int(pages.sum())
        c["kv_pages_read_frozen"] += int(np.count_nonzero(frozen))
        c["kv_rows_used"] += int(lens.sum())
        c["kv_rows_reserved"] += bs * sum(len(self.slots[i].blocks)
                                          for i in active)

    # ------------------------------------------------------- speculative

    def _spec_decode_step(self, now_fn) -> None:
        """One speculative iteration: k draft proposals per active slot,
        ONE batched verify window on the target over all k+1 positions,
        then per-slot accept/rollback.

        The verify pass writes all k+1 KV rows and this method advances
        ``lens`` (and queues page-freeze bids) *optimistically* before
        acceptance is known; ``_rollback_slot`` then shrinks every slot
        back to its accepted watermark, un-queueing bids for rolled-back
        pages. Bids flush at the *start* of the next ``step()``, so a bid
        queued here can never dispatch before its rollback — the invariant
        "no frozen page past the accepted seq_lens" holds at every step
        boundary. Every emitted token is the target's greedy argmax for
        its exact accepted context, so the trace is token-identical to
        non-speculative decoding by construction.
        """
        active = self.sched.active_slots()
        if not active:
            return
        tr, c, trk = self.tracer, self.counters, self._trk_decode
        k = self.speculate
        W = k + 1
        c["decode_steps"] += 1
        c["seq_decode_steps"] += len(active)
        with phase(tr, trk, "decode_step", step=c["decode_steps"],
                   active=len(active), window=W):
            self._poll_freezes()
            with phase(tr, self._trk_spec, "propose", k=k,
                       active=len(active)):
                proposals = self.draft.propose(active, self.slots, k)
            toks = np.zeros((len(self.slots), W), np.int32)
            for i in active:
                toks[i, 0] = self.slots[i].last_token
                toks[i, 1:] = proposals[i]
            # gather only the blocks the longest live sequence's window
            # needs
            need = int(self.lens.max()) + W
            mb_used = max(1, -(-need // self.block_size))
            c["max_gather_blocks"] = max(c["max_gather_blocks"], mb_used)
            with phase(tr, self._trk_spec, "verify", window=W,
                       active=len(active), blocks=mb_used):
                with phase(tr, trk, "dispatch", c, "decode_dispatch_ns",
                           blocks=mb_used):
                    self._count_reads(active, mb_used, W)
                    tree = with_tables(self.tree, self.table[:, :mb_used],
                                       self.lens)
                    logits, new = self._verify_fn(
                        self.params, jnp.asarray(toks), tree,
                        jnp.asarray(self.lens))
                    self.tree = merge_pools(self.tree, new)
                with phase(tr, trk, "sync", c, "decode_sync_ns"):
                    # lint: sync(step-end verify sync: acceptance runs on host)
                    preds = np.asarray(jnp.argmax(logits, -1))    # (B, W)
            sampling = any(self.slots[i].temperature > 0.0 for i in active)
            assert not sampling, (
                "speculative decoding serves the greedy verification path; "
                "sampled requests need the non-speculative engine")
            with phase(tr, trk, "commit", c, "decode_commit_ns") as p:
                # lint: sync(verification-only logit capture, off in serving)
                rows = np.asarray(logits) if self.record_logits else None
                now = now_fn()
                finished = []
                for i in active:
                    st = self.sched.active[i]
                    s = self.slots[i]
                    self.last_attended[i] = c["decode_steps"]
                    L = int(self.lens[i])
                    # optimistic: all W rows written; advance + queue
                    # freezes as if every draft were accepted, then roll
                    # back to the watermark
                    self.lens[i] = L + W
                    self._queue_freeze(i)
                    n_acc = 0
                    while (n_acc < k
                           and proposals[i][n_acc] == int(preds[i, n_acc])):
                        n_acc += 1
                    # row j of the verify logits is the target's next-token
                    # distribution after [ctx, last, d1..dj]: accepted
                    # drafts are emitted verbatim, and row n_acc supplies
                    # the correction (or the bonus token when every draft
                    # survived) — uniformly its argmax
                    emitted = [int(t) for t in proposals[i][:n_acc]]
                    emitted.append(int(preds[i, n_acc]))
                    emitted = emitted[:st.req.max_new_tokens - st.generated]
                    if self.eos_id is not None and self.eos_id in emitted:
                        emitted = emitted[:emitted.index(self.eos_id) + 1]
                    a = len(emitted)
                    self.metrics.spec_step(k, min(n_acc, a), a < W)
                    tr.instant(self._trk_spec, "accept", slot=i,
                               rid=st.req.id, proposed=k,
                               accepted=min(n_acc, a), emitted=a)
                    if a < W:
                        tr.instant(self._trk_spec, "rollback", slot=i,
                                   rid=st.req.id, to_len=L + a)
                    self._rollback_slot(i, L + a)
                    st.length = L + a
                    st.generated += a
                    for j, t in enumerate(emitted):
                        s.out.append(t)
                        if self.record_logits:
                            s.logits.append(rows[i, j])
                        self.metrics.token(st.req.id, now)
                    s.last_token = emitted[-1]
                    self.draft.sync(i, L + a)
                    if st.done or s.last_token == self.eos_id:
                        finished.append(st)
                for st in finished:
                    self._finish(st, now)
                p.args["finished"] = len(finished)

    def _rollback_slot(self, slot: int, new_len: int) -> None:
        """Shrink a slot to its accepted watermark ``new_len``: un-queue
        freeze bids for pages past it and drop them from any in-flight
        solve, so a rejected suffix can never leave a frozen page beyond
        the accepted ``seq_lens``. Rolled-back rows hold rejected drafts'
        KV — invisible to attention (masked past ``lens``) and rewritten
        in place by the next verify window before ``lens`` covers them."""
        s = self.slots[slot]
        full = int(new_len) // self.block_size
        if s.frozen_upto > full:
            stale = {int(self.table[slot, j])
                     for j in range(full, s.frozen_upto)}
            tr = self.tracer
            if tr.enabled:
                for b in sorted(stale):
                    sid = self._page_spans.pop(b, None)
                    if sid is not None:
                        tr.async_end(self._trk_freeze, "page_freeze", sid,
                                     state="rolled_back", page=b)
            self._freeze_bids = [b for b in self._freeze_bids
                                 if b not in stale]
            self._deferred_seen = min(self._deferred_seen,
                                      len(self._freeze_bids))
            for _, pending in self._pending_freezes:
                pending.drop(stale)
            s.frozen_upto = full
        self.lens[slot] = new_len

    # ------------------------------------------------------------ freezing

    def _poll_freezes(self, drain: bool = False) -> None:
        """Install completed freezes; count the ones still overlapping this
        decode step. drain=True blocks on the remainder (end of run)."""
        still = []
        installed_any = False
        tr, c = self.tracer, self.counters
        for step0, pending in self._pending_freezes:
            if drain and not pending.is_ready():
                # lint: sync(drain-only: end-of-run flush blocks by design)
                jax.block_until_ready(pending.markers())
            if pending.is_ready():
                wait = c["decode_steps"] - step0
                with phase(tr, self._trk_freeze, "install", c,
                           "freeze_install_ns", wait_steps=wait) as p:
                    self.tree = install_freeze(self.tree, pending)
                    kept = pending.kept_pages()
                    self._mark_frozen(kept)
                    installed_any = True
                    c["freeze_installs"] += 1
                    c["freeze_overlap_steps"] += wait
                    p.args["pages"] = len(kept)
                    if tr.enabled:
                        for b in kept:
                            sid = self._page_spans.pop(b, None)
                            if sid is not None:
                                tr.async_end(self._trk_freeze, "page_freeze",
                                             sid, state="installed", page=b)
            else:
                c["freeze_inflight_steps"] += 1
                still.append((step0, pending))
        self._pending_freezes = still
        if installed_any:
            # freshly installed pages just became shareable
            self._publish_prefixes()

    def _mark_frozen(self, blocks) -> None:
        blocks = [int(b) for b in blocks]
        self._frozen_pages.update(blocks)
        self._frozen_mask[blocks] = True

    def _unmark_frozen(self, blocks: set) -> None:
        self._frozen_pages -= blocks
        self._frozen_mask[list(blocks)] = False

    def _queue_freeze(self, slot: int) -> None:
        """Queue this sequence's just-filled pages for quantization; the
        worker iteration flushes the whole batch as ONE device dispatch
        (_flush_freezes), so slots whose pages fill at the same step share
        a solve."""
        if self.kv_spec is None:
            return
        s = self.slots[slot]
        full = int(self.lens[slot]) // self.block_size
        if full > s.frozen_upto:
            tr = self.tracer
            for j in range(s.frozen_upto, full):
                b = int(self.table[slot, j])
                # a shared page is already installed (or already bid by the
                # sequence that owns the solve) — never re-freeze: bids
                # dedupe on block id
                if b in self._frozen_pages or b in self._freeze_bids:
                    continue
                self._freeze_bids.append(b)
                if tr.enabled:
                    self._span_seq += 1
                    self._page_spans[b] = self._span_seq
                    tr.async_begin(self._trk_freeze, "page_freeze",
                                   self._span_seq, page=b, slot=slot)
            s.frozen_upto = full

    def _flush_freezes(self) -> None:
        """One batched solve for pages queued this iteration, rate-limited
        to ``freeze_page_budget`` pages per decode step.

        The budget is the backpressure valve: a prefill burst can queue a
        whole prompt's worth of full pages at once, and solving them as one
        chunk would run long enough to delay the next decode steps — the
        remainder flushes on later iterations (deferred pages keep serving
        exact fp until then, so correctness is unaffected) and
        ``freeze_deferred_pages`` counts how often the valve engaged."""
        if not self._freeze_bids:
            return
        tr = self.tracer
        take = min(len(self._freeze_bids), self.freeze_page_budget)
        with phase(tr, self._trk_freeze, "flush", self.counters,
                   "freeze_flush_ns", pages=take,
                   mode="async" if self.freeze_async else "sync"):
            bids, self._freeze_bids = (self._freeze_bids[:take],
                                       self._freeze_bids[take:])
            if tr.enabled:
                for b in bids:
                    sid = self._page_spans.get(b)
                    if sid is not None:
                        tr.async_instant(self._trk_freeze, "page_freeze", sid,
                                         state="dispatched")
            # count each page's deferral once: the flush consumed ``take``
            # pages off the queue front (the oldest, hence any already-counted
            # ones first), so shrink the counted watermark by that before
            # counting what now remains beyond it as newly deferred
            self._deferred_seen = max(self._deferred_seen - take, 0)
            newly = len(self._freeze_bids) - self._deferred_seen
            if newly > 0:
                self.counters["freeze_deferred_pages"] += newly
            self._deferred_seen = len(self._freeze_bids)
            if self.kv_spec.device_capable:
                # pad to a power-of-two page count (repeating one page is a
                # no-op at install) so the jitted solver compiles a handful of
                # shapes instead of one per distinct flush size; the host
                # fallback solves per page, where a duplicate is pure waste
                bucket = 1 << (len(bids) - 1).bit_length()
                bids = bids + [bids[-1]] * (bucket - len(bids))
            if self.freeze_async:
                pending = dispatch_freeze(self.tree, bids, self.kv_spec)
                self._pending_freezes.append(
                    (self.counters["decode_steps"], pending))
                self.counters["freeze_pending_max"] = max(
                    self.counters["freeze_pending_max"],
                    len(self._pending_freezes))
            else:
                # synchronous solve + install, inside the flush's own time
                with phase(tr, self._trk_freeze, "install",
                           pages=len(set(bids)), wait_steps=0):
                    self.tree = freeze_blocks(self.tree, bids, self.kv_spec,
                                              stats=self.counters)
                    self._mark_frozen(bids)
                self._publish_prefixes()    # installed: shareable now
                self.counters["freeze_installs"] += 1
                if tr.enabled:
                    # synchronous install: the lifecycle terminates here
                    for b in sorted(set(bids)):
                        sid = self._page_spans.pop(b, None)
                        if sid is not None:
                            tr.async_end(self._trk_freeze, "page_freeze", sid,
                                         state="installed", page=b)
            self.counters["freeze_dispatches"] += 1

    # ------------------------------------------------------------ teardown

    def _finish(self, st: SeqState, now: float) -> None:
        slot, s = st.slot, self.slots[st.slot]
        # a recompute-path resumption carries its first life's tokens as
        # prompt; stitch them back so the caller sees one output stream
        pre_out, pre_logits = self._resume_prefix.pop(st.req.id, ([], []))
        self.outputs[st.req.id] = pre_out + list(s.out)
        if self.record_logits and (pre_logits or s.logits):
            self.request_logits[st.req.id] = np.stack(pre_logits + s.logits)
        self.metrics.finish(st.req.id, now)
        # drop one reference per page; teardown side effects (span drops,
        # bid/frozen forgetting, thawing, index invalidation) scope to the
        # pages actually RELEASED — a shared prefix page another live table
        # still references keeps serving its frozen reconstruction
        released = set(self.alloc.free(s.blocks))
        if self.prefix is not None:
            self.prefix.invalidate(released)
        tr = self.tracer
        if tr.enabled:
            tr.instant(self._trk_decode, "finish", rid=st.req.id,
                       tokens=len(s.out))
            for b in sorted(released):
                sid = self._page_spans.pop(b, None)
                if sid is not None:
                    tr.async_end(self._trk_freeze, "page_freeze", sid,
                                 state="dropped", page=b)
        self._freeze_bids = [b for b in self._freeze_bids
                             if b not in released]
        self._deferred_seen = min(self._deferred_seen, len(self._freeze_bids))
        self._unmark_frozen(released)
        for _, pending in self._pending_freezes:
            pending.drop(released)
        self.tree = thaw_blocks(self.tree, released)
        if self.draft is not None:
            self.draft.release(slot)
        self.table[slot] = 0
        self.lens[slot] = 0
        s.rid, s.blocks, s.frozen_upto, s.out = None, [], 0, []
        s.rng, s.temperature, s.top_k = None, 0.0, 0
        self.last_attended.pop(slot, None)
        self.sched.release(st)
        # the finisher may have been a chain's first publisher: invalidate
        # dropped its keys even though an identical live copy (a survivor's
        # own pages, same chain) may still be resident — re-publish so the
        # NEXT lookup (prefill dispatch precedes any attach) still matches
        self._publish_prefixes()

    # ------------------------------------------------------------ overload

    def preempt(self, st: SeqState, mode: str, now: float) -> ResumeEntry:
        """Evict a live sequence at a step boundary (overload pressure).

        mode "restore": demote its pages to a host payload via the
        "resident" extraction — installed-frozen pages cross as their
        existing packed codes + codebooks (bit-exact on re-install), the
        rest fp — for exact resumption later. mode "recompute": drop the
        pages and return a requeue request whose prompt is the original
        plus everything emitted; the re-prefill re-derives the KV (only
        chosen for unquantized greedy runs, where it is value-exact).

        The teardown mirrors ``_finish`` minus the output/latency events —
        the request stays live, only its residency changes — so every pool
        invariant (freeze watermark, conservation, pending-solve staleness)
        holds exactly as for a finished sequence. Open ``page_freeze``
        spans terminate ``offloaded`` / ``dropped`` per mode.
        """
        assert mode in ("restore", "recompute"), mode
        slot, s, req = st.slot, self.slots[st.slot], st.req
        assert not st.done and s.out, "preempt targets a live sequence"
        n_tok = int(self.lens[slot])
        tr = self.tracer
        self.counters["preemptions"] += 1
        if mode == "restore":
            # pages other live tables still reference are NOT demoted —
            # they stay resident serving those tables and this victim just
            # drops its ref below; the payload captures only the
            # exclusively-owned page suffix (frozen_idx relative to it)
            sh = self.shared_prefix_pages(slot)
            full = n_tok // self.block_size
            frozen_idx = [j - sh for j in range(sh, full)
                          if int(self.table[slot, j]) in self._frozen_pages]
            payload = extract_resident_pages(
                self.tree, s.blocks[sh:], n_tok - sh * self.block_size,
                frozen_idx, block_size=self.block_size, tracer=tr)
            t_host = tr.now()
            payload.to_host()
            tr.complete("transfer", "to_host", t_host, rid=req.id,
                        mode=payload.mode, bytes=payload.nbytes,
                        fp_equiv_bytes=payload.fp_equiv_bytes,
                        pages=payload.n_pages)
            entry = ResumeEntry(req=req, out=list(s.out),
                                generated=st.generated, n_tokens=n_tok,
                                rng=s.rng, logits=list(s.logits),
                                payload=payload, frozen_idx=frozen_idx,
                                shared_pages=sh)
            self.counters["preempt_offloads"] += 1
            self.counters["offloaded_pages"] += payload.n_pages
            self.counters["offload_bytes"] += payload.nbytes
            self.counters["offload_fp_equiv_bytes"] += payload.fp_equiv_bytes
            if tr.enabled:
                for j in range(payload.n_pages):
                    self._span_seq += 1
                    entry.span_ids[j] = self._span_seq
                    tr.async_begin(self._trk_freeze, "page_offload",
                                   self._span_seq, rid=req.id, page_pos=j)
        else:
            rem = req.max_new_tokens - st.generated
            resume = dataclasses.replace(
                req, prompt=tuple(req.prompt) + tuple(s.out),
                max_new_tokens=rem)
            pre_out, pre_logits = self._resume_prefix.get(req.id, ([], []))
            self._resume_prefix[req.id] = (pre_out + list(s.out),
                                           pre_logits + list(s.logits))
            entry = ResumeEntry(req=resume, out=list(s.out),
                                generated=st.generated, n_tokens=n_tok)
            self.counters["preempt_recomputes"] += 1
        tr.instant(self._trk_decode, "preempt", rid=req.id, slot=slot,
                   mode=mode, tokens=n_tok, pages=len(s.blocks))
        # ref-drop every page; only the RELEASED ones (last reference was
        # this victim's) tear down — a still-shared prefix page keeps its
        # frozen install and index entries for the sequences serving it
        released = set(self.alloc.free(s.blocks))
        if self.prefix is not None:
            self.prefix.invalidate(released)
        if tr.enabled:
            # literal per-branch states keep the page_freeze lifecycle
            # statically checkable (repro.analysis span pass)
            for b in sorted(released):
                sid = self._page_spans.pop(b, None)
                if sid is None:
                    continue
                if mode == "restore":
                    tr.async_end(self._trk_freeze, "page_freeze", sid,
                                 state="offloaded", page=b)
                else:
                    tr.async_end(self._trk_freeze, "page_freeze", sid,
                                 state="dropped", page=b)
        self._freeze_bids = [b for b in self._freeze_bids
                             if b not in released]
        self._deferred_seen = min(self._deferred_seen, len(self._freeze_bids))
        self._unmark_frozen(released)
        for _, pending in self._pending_freezes:
            pending.drop(released)
        self.tree = thaw_blocks(self.tree, released)
        if self.draft is not None:
            self.draft.release(slot)
        self.table[slot] = 0
        self.lens[slot] = 0
        s.rid, s.blocks, s.frozen_upto, s.out, s.logits = None, [], 0, [], []
        s.rng, s.temperature, s.top_k = None, 0.0, 0
        self.last_attended.pop(slot, None)
        self.sched.release(st)
        # mirror _finish: re-register surviving duplicate chains whose keys
        # the invalidate above may have dropped with the victim's pages
        self._publish_prefixes()
        return entry

    def restore(self, st: SeqState, entry: ResumeEntry, now: float) -> None:
        """Re-install an offloaded sequence at slot ``st.slot`` and resume
        decoding exactly where it stopped.

        Restore-ahead: this runs at re-admission — before any decode
        window needs the pages — and the jit dataflow chains the next
        decode step behind the splice/install, so the resumed sequence is
        greedy-token-identical to one that never left. Frozen pages land
        through ``install_freeze`` (bit-exact codes), fp pages scatter
        verbatim; the stall the sequence suffered shows up honestly in its
        next inter-token gap."""
        req, s = st.req, self.slots[st.slot]
        tr = self.tracer
        m = entry.shared_pages
        shared: list[int] = []
        if m:
            t0 = tr.now()
            hit = (self.prefix.lookup(req.prompt, m)
                   if self.prefix is not None else [])
            if len(hit) == m:
                # the shared prefix survived the offload window: splice it
                # back at rc+1, exactly the pages this sequence decoded
                # against before eviction
                shared = [int(b) for b in hit]
                self.alloc.retain(shared)
                self.counters["prefix_hits"] += 1
                self.counters["prefix_shared_pages"] += m
                tr.complete(self._trk_decode, "prefix_match", t0,
                            rid=req.id, pages=m, cow=False)
            else:
                # its last referencer retired while this victim was
                # offloaded — rebuild privately (deterministic prefill +
                # deterministic freeze solver reproduce values identical to
                # the dead shared pages, so the resume stays token-exact)
                shared = self._rebuild_prefix(req, m)
        blocks = shared + self.alloc.alloc(self.sched.blocks_for(req) - m)
        self.tree = splice_payload(self.tree, entry.payload, blocks[m:],
                                   tracer=tr)
        s.rid, s.blocks = req.id, blocks
        s.out, s.logits = list(entry.out), list(entry.logits)
        s.last_token = entry.out[-1]
        s.rng, s.temperature, s.top_k = entry.rng, req.temperature, req.top_k
        self.table[st.slot] = 0
        self.table[st.slot, :len(blocks)] = blocks
        self.lens[st.slot] = entry.n_tokens
        st.length, st.generated = entry.n_tokens, entry.generated
        self.last_attended[st.slot] = self.counters["decode_steps"]
        self._mark_frozen(blocks[m + j] for j in entry.frozen_idx)
        # frozen_upto is the maximal frozen PREFIX; installs land in queue
        # order so the frozen set is a prefix in practice. If it ever
        # weren't, _queue_freeze would re-solve an already-frozen page —
        # value-exact (kmeans_ls on a 16-distinct-value reconstruction
        # reproduces it), so at most a redundant solve, never divergence.
        # A quantized shared prefix is installed-frozen by construction, so
        # it extends the watermark from page 0.
        fset = {m + j for j in entry.frozen_idx}
        if m and self.kv_spec is not None:
            fset |= set(range(m))
        upto = 0
        while upto in fset:
            upto += 1
        s.frozen_upto = upto
        self._queue_freeze(st.slot)
        self.counters["restored_seqs"] += 1
        self.counters["restored_pages"] += entry.payload.n_pages
        self.counters["restore_bytes"] += entry.payload.nbytes
        tr.instant(self._trk_decode, "restore", rid=req.id, slot=st.slot,
                   pages=entry.payload.n_pages, tokens=entry.n_tokens)
        if tr.enabled:
            for j, sid in sorted(entry.span_ids.items()):
                tr.async_end(self._trk_freeze, "page_offload", sid,
                             state="restored", rid=req.id, page_pos=j)
        if self.draft is not None:
            # the draft re-prefills the full accepted context (out[-1] has
            # no KV row yet, same as at attach); plen pins back to the
            # ORIGINAL prompt length because propose slices pending tokens
            # as out[lens - plen:]
            self.draft.attach(st.slot,
                              tuple(req.prompt) + tuple(entry.out[:-1]),
                              len(blocks))
            self.draft.plen[st.slot] = req.prompt_len
        self._publish_prefixes()

    def _rebuild_prefix(self, req: Request, m: int) -> list[int]:
        """Re-materialize the first ``m`` prompt pages of a restoring
        sequence whose shared prefix was released while it sat offloaded.

        Prefill is deterministic and the freeze solver is deterministic
        (canonical seed, sorted bids — see ``dispatch_freeze``), so the
        rebuilt pages carry values identical to the dead shared pages the
        sequence decoded against: the resumed trace stays token-exact. The
        chunk-prefill path used here is logit-identical to the slice of a
        single-shot prefill (tests/test_properties.py)."""
        bs = self.block_size
        blocks = self.alloc.alloc(m)
        toks = np.zeros((1, m * bs), np.int32)
        toks[0] = req.prompt[:m * bs]
        pos = jnp.asarray(np.arange(m * bs, dtype=np.int32)[None])
        table = np.asarray([blocks], np.int32)
        tree1 = with_tables(self.tree, table, np.zeros((1,), np.int32))
        if self.attn_impl == "fused":
            tree1 = with_prefill_fused(tree1)
        _, new = _prefill_chunk_step(self.params, jnp.asarray(toks), pos,
                                     tree1, cfg=self.cfg)
        self.tree = merge_pools(self.tree, new)
        if self.kv_spec is not None:
            # synchronous freeze: the restored watermark counts these pages
            # frozen from page 0, so they must be installed before decoding
            self.tree = freeze_blocks(self.tree, blocks, self.kv_spec,
                                      stats=self.counters)
            self._mark_frozen(blocks)
        return blocks

    def drain(self) -> None:
        """Flush every still-queued freeze and land in-flight solves (end
        of run — live sequences are gone, so latency no longer matters)."""
        while self._freeze_bids:
            self._flush_freezes()
        self._poll_freezes(drain=True)

    def _sample_cache(self) -> None:
        allocated = (self.num_blocks - 1) - self.alloc.num_free
        # count *installed* pages: queued/in-flight solves still serve fp
        # at full width, so they must not book frozen-page bytes yet
        frozen = len(self._frozen_pages)
        actual = (frozen * self._pb["frozen"]
                  + (allocated - frozen) * self._pb["fp"])
        occ = allocated / (self.num_blocks - 1)
        self.metrics.sample_cache(occ, actual, allocated * self._pb["fp"])
        tr = self.tracer
        if tr.enabled:
            extra = {}
            if self.prefix is not None:
                # physical pages saved by sharing right now: each extra
                # table reference on a page is a page NOT allocated
                extra["shared_saved_pages"] = sum(
                    rc - 1 for rc in self.alloc._rc.values() if rc > 1)
            tr.counter(self._trk_decode, "cache", occupancy=round(occ, 6),
                       frozen_pages=frozen, **extra)


@dataclasses.dataclass
class _ChunkedPrefill:
    """In-flight chunked prefill: one prompt advancing chunk-by-chunk so
    the engine can interleave decode steps between chunks."""

    req: Request
    blocks: list
    toks: np.ndarray          # (1, ppad) zero-padded prompt
    nblk: int
    off: int = 0              # tokens already in cache (shared prefix
    #                           pre-seeds this past the spliced pages)
    shared: int = 0           # leading pages spliced from the prefix index
    last_row: object = None   # device logits row at prompt position P-1

    @property
    def done(self) -> bool:
        return self.off >= self.toks.shape[1]


class PrefillWorker:
    """The prefill role: queued prompts -> finished-prefill artifacts.

    With ``pool=None`` the worker owns a small paged pool sized for
    in-flight prompts and emits migration payloads (mode fp/frozen); with
    ``pool=<DecodeWorker>`` it borrows the decode worker's pool and
    allocator (the colocated composition) and emits no-op "splice"
    payloads. ``step()`` is async in owned mode: it dispatches at most one
    prefill (plus, for frozen migration, the page-freeze solve chained
    behind it on device) and harvests on a later call once the device is
    done, so the caller's decode loop keeps running under a long prompt.
    """

    def __init__(self, params, cfg, *, worker_id: int = 0,
                 block_size: int = 16, max_seq_len: int = 256,
                 kv_spec=None, migrate: str = "fp",
                 num_blocks: int | None = None, pool: DecodeWorker | None = None,
                 record_logits: bool = False, metrics=None,
                 max_queue: int = 64, prefill_chunk: int | None = None,
                 tracer=None):
        from .metrics import MetricsCollector

        assert migrate in ("fp", "frozen"), migrate
        assert prefill_chunk is None or (prefill_chunk >= 1
                                         and pool is not None), (
            "chunked prefill interleaves with a colocated decode worker's "
            "pool — construct with pool=<DecodeWorker>")
        self.worker_id = worker_id
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self._trk = f"prefill/w{worker_id}"
        self.params, self.cfg = params, cfg
        self.block_size = block_size
        self.kv_spec = kv_spec
        self.migrate = migrate
        self.pool = pool
        self.record_logits = record_logits
        self.max_queue = max_queue
        self.prefill_chunk = prefill_chunk
        self.metrics = metrics if metrics is not None else MetricsCollector()
        self.max_prompt_blocks = -(-max_seq_len // block_size)
        self.queue: deque[Request] = deque()
        self._inflight = None  # (req, blocks, logits device array, payload,
        #                         token offset the prefill started at)
        self.counters = {"prefills": 0, "queue_peak": 0, "prefill_chunks": 0,
                         "prefill_ns": 0, "prefill_tokens": 0}
        # prefill time and tokens are booked where the decode counters of
        # the composition live: the borrowed pool's, else this worker's
        self._phase_counters = (pool.counters if pool is not None
                                else self.counters)
        self._prefill_fn = functools.partial(_prefill_step, cfg=cfg)
        self._chunk_fn = functools.partial(_prefill_chunk_step, cfg=cfg)
        if pool is None:
            frozen = migrate == "frozen" and kv_spec is not None
            self.num_blocks = (num_blocks if num_blocks is not None
                               else 2 * self.max_prompt_blocks + 1)
            self.tree = init_paged_cache(
                cfg, num_blocks=self.num_blocks, block_size=block_size,
                batch=1, max_blocks=self.max_prompt_blocks, quantized=frozen,
                num_values=kv_spec.num_values if frozen else 16, fused=False)
            self.alloc = BlockAllocator(self.num_blocks)
        else:
            self.num_blocks = pool.num_blocks

    # ------------------------------------------------------------ routing

    @property
    def load(self) -> int:
        return len(self.queue) + (1 if self._inflight else 0)

    @property
    def busy(self) -> bool:
        return self.load > 0

    def can_accept(self) -> bool:
        return self.load < self.max_queue

    def submit(self, req: Request) -> None:
        self.queue.append(req)
        self.counters["queue_peak"] = max(self.counters["queue_peak"],
                                          self.load)

    # ------------------------------------------------------ prefix sharing

    def _match_prefix(self, req: Request) -> list[int]:
        """Longest published-prefix match for a colocated prefill: retain
        the matched pages (rc+1 each) and return them for splicing into
        the new sequence's table — prefill then starts at the page-aligned
        offset past them instead of token 0.

        The match is capped one page short of the prompt's LAST token, so
        the page feeding the first-token logits row is always privately
        prefilled. A raw match past that cap is the copy-on-write event:
        the write-hot tail page exists in the index but is materialized
        privately (by prefilling it) instead of shared — ``cow_copies``
        counts these.
        """
        pool = self.pool
        if pool is None or pool.prefix is None:
            return []
        tr = self.tracer
        t0 = tr.now()
        cap = (req.prompt_len - 1) // self.block_size
        raw = pool.prefix.lookup(req.prompt, cap + 1)
        shared = [int(b) for b in raw[:cap]]
        if not shared:
            return []
        pool.alloc.retain(shared)
        pool.counters["prefix_hits"] += 1
        pool.counters["prefix_shared_pages"] += len(shared)
        cow = len(raw) > len(shared)
        if cow:
            pool.counters["cow_copies"] += 1
        tr.complete(self._trk, "prefix_match", t0, rid=req.id,
                    pages=len(shared), cow=cow)
        return shared

    # ------------------------------------------------------------ prefill

    def _dispatch(self, req: Request, now_fn) -> None:
        """Launch one prompt's prefill (and, when migrating frozen, the
        page-freeze solve chained behind it); returns without waiting."""
        tr = self.tracer
        P = req.prompt_len
        with phase(tr, self._trk, "dispatch", self._phase_counters,
                   "prefill_ns", rid=req.id, prompt_len=P) as p:
            self.metrics.prefill_start(req.id, now_fn())
            # async span across dispatch -> harvest: the device-side lifetime
            # of this prompt's prefill (and any chained freeze solve)
            tr.async_begin(self._trk, "prefill", req.id, rid=req.id,
                           prompt_len=P)
            ppad = -(-P // self.block_size) * self.block_size
            nblk = ppad // self.block_size
            off = 0
            if self.pool is not None:
                # borrowed pool: splice any published shared prefix, then
                # allocate the request's remaining worst-case pages where they
                # will be served; the handoff is a table splice
                shared = self._match_prefix(req)
                off = len(shared) * self.block_size
                blocks = shared + self.pool.alloc.alloc(
                    self.pool.sched.blocks_for(req) - len(shared))
                tree = self.pool.tree
            else:
                blocks = self.alloc.alloc(nblk)
                tree = self.tree
            toks = np.zeros((1, ppad - off), np.int32)
            toks[0, :P - off] = req.prompt[off:]
            table = np.asarray([blocks[:nblk]], np.int32)
            tree1 = with_tables(tree, table, np.full((1,), off, np.int32))
            if off:
                # mid-sequence start past the shared pages: explicit positions
                # rope/mask this exactly like the matching slice of a
                # whole-prompt prefill (the chunked-prefill q_offset path)
                if self.pool.attn_impl == "fused":
                    tree1 = with_prefill_fused(tree1)
                pos = jnp.asarray(np.arange(off, ppad, dtype=np.int32)[None])
                logits, new1 = self._chunk_fn(self.params, jnp.asarray(toks),
                                              pos, tree1)
            else:
                logits, new1 = self._prefill_fn(self.params, jnp.asarray(toks),
                                                tree1)
            merged = merge_pools(tree, new1)
            if self.pool is not None:
                self.pool.tree = merged
                payload = PagePayload(mode="splice",
                                      blocks=[int(b) for b in blocks],
                                      n_tokens=P, block_size=self.block_size,
                                      n_full=P // self.block_size,
                                      tail_rows=P % self.block_size,
                                      shared_pages=off // self.block_size)
            else:
                self.tree = merged
                payload = extract_pages(merged, blocks, P,
                                        block_size=self.block_size,
                                        mode=self.migrate, spec=self.kv_spec,
                                        tracer=tr)
            self._inflight = (req, blocks, logits, payload, off)
            self._phase_counters["prefill_tokens"] += P - off
            p.args.update(pages=nblk, shared=off // self.block_size)

    def _harvest(self, now_fn) -> FinishedPrefill:
        """Materialize the finished prefill: sample the first token, stage
        the payload to host, release this worker's blocks."""
        tr = self.tracer
        req, blocks, logits, payload, off = self._inflight
        self._inflight = None
        with phase(tr, self._trk, "harvest", self._phase_counters,
                   "prefill_ns", rid=req.id):
            last = np.asarray(logits[0, req.prompt_len - 1 - off])
            now = now_fn()                    # TTFT includes prefill time
            rng = req.make_rng()
            tok = sample_token(last, temperature=req.temperature,
                               top_k=req.top_k, rng=rng)
            self.metrics.first_token(req.id, now)
            if payload.mode == "splice":
                payload.to_host()  # lint: sync(splice mode stages no arrays)
            else:
                t_host = tr.now()
                # lint: sync(handoff staging is the wire; gated on is_ready)
                payload.to_host()
                tr.complete("transfer", "to_host", t_host, rid=req.id,
                            mode=payload.mode, bytes=payload.nbytes,
                            fp_equiv_bytes=payload.fp_equiv_bytes,
                            pages=payload.n_pages)
            if self.pool is None:
                self.alloc.free(blocks)       # pages left as a host payload
            self.counters["prefills"] += 1
        tr.async_end(self._trk, "prefill", req.id, rid=req.id)
        return FinishedPrefill(
            req=req, first_token=tok, payload=payload, rng=rng,
            last_logits=last if self.record_logits else None,
            worker_id=self.worker_id)

    def step(self, now_fn, block: bool = False) -> list[FinishedPrefill]:
        """Advance this worker: dispatch the queue head if idle (and its
        prompt pages fit), harvest the in-flight prefill once the device
        finished (immediately when ``block``). Returns 0 or 1 artifacts."""
        if self._inflight is None and self.queue:
            req = self.queue[0]
            nblk = -(-req.prompt_len // self.block_size)
            if self.pool is not None or nblk <= self.alloc.num_free:
                self.queue.popleft()
                self._dispatch(req, now_fn)
        if self._inflight is not None:
            logits, payload = self._inflight[2], self._inflight[3]
            # harvest only once the prefill AND any chained freeze solve
            # landed: to_host() on an in-flight solve would block this
            # loop — the exact stall the worker split exists to avoid
            if block or (logits.is_ready() and payload.is_ready()):
                return [self._harvest(now_fn)]
        return []

    def run_inline(self, req: Request, now_fn) -> FinishedPrefill:
        """Synchronous prefill of one request (the colocated engine's
        inline path): dispatch + blocking harvest."""
        assert self._inflight is None and not self.queue
        self._dispatch(req, now_fn)
        return self._harvest(now_fn)

    # ---------------------------------------------------------- chunked

    def start_chunked(self, req: Request, now_fn) -> _ChunkedPrefill:
        """Open a chunked prefill: allocate the request's worst-case pages
        in the colocated pool and return the chunk cursor. The engine then
        calls ``advance_chunk`` once per iteration, interleaved with decode
        steps — a long prompt costs each iteration one chunk instead of
        the whole prompt, which is what bounds ``itl_max`` under a
        long-prompt burst."""
        assert self.prefill_chunk and self.pool is not None
        tr = self.tracer
        self.metrics.prefill_start(req.id, now_fn())
        P = req.prompt_len
        tr.async_begin(self._trk, "prefill", req.id, rid=req.id,
                       prompt_len=P)
        ppad = -(-P // self.block_size) * self.block_size
        shared = self._match_prefix(req)
        blocks = shared + self.pool.alloc.alloc(
            self.pool.sched.blocks_for(req) - len(shared))
        toks = np.zeros((1, ppad), np.int32)
        toks[0, :P] = req.prompt
        # a matched prefix pre-seeds the chunk cursor past the spliced
        # pages — those tokens are already in cache, so chunking starts
        # mid-sequence exactly like any later chunk would
        return _ChunkedPrefill(req=req, blocks=blocks, toks=toks,
                               nblk=ppad // self.block_size,
                               off=len(shared) * self.block_size,
                               shared=len(shared))

    def advance_chunk(self, state: _ChunkedPrefill,
                      now_fn) -> FinishedPrefill | None:
        """Run ONE chunk of an open chunked prefill; returns the finished
        artifact once the whole (padded) prompt is in cache, else None.

        Each chunk scores its C tokens against every earlier page through
        the same attention path decode uses — with the fused impl, frozen
        pages cross HBM as packed codes + codebooks (the modeled-bytes win
        on shared frozen context); positions/q_offset are explicit, so the
        chunk sequence is logit-identical to one single-shot prefill
        (bitwise on the gather path; see tests/test_properties.py).
        """
        tr = self.tracer
        req, P = state.req, state.req.prompt_len
        ppad = state.toks.shape[1]
        off = state.off
        C = min(self.prefill_chunk, ppad - off)
        pool = self.pool
        with phase(tr, self._trk, "prefill_chunk", self._phase_counters,
                   "prefill_ns", rid=req.id, off=off, chunk=C):
            toks = jnp.asarray(state.toks[:, off:off + C])
            pos = jnp.asarray(np.arange(off, off + C, dtype=np.int32)[None])
            table = np.zeros((1, state.nblk), np.int32)
            table[0] = state.blocks[:state.nblk]
            tree1 = with_tables(pool.tree, table,
                                np.full((1,), off, np.int32))
            if pool.attn_impl == "fused":
                tree1 = with_prefill_fused(tree1)
            logits, new1 = self._chunk_fn(self.params, toks, pos, tree1)
            pool.tree = merge_pools(pool.tree, new1)
            if off <= P - 1 < off + C:
                state.last_row = logits[0, P - 1 - off]
            state.off = off + C
            self.counters["prefill_chunks"] += 1
            self._phase_counters["prefill_tokens"] += max(min(C, P - off), 0)
        if not state.done:
            return None
        # -------- harvest: mirrors _harvest's splice branch
        with phase(tr, self._trk, "harvest", self._phase_counters,
                   "prefill_ns", rid=req.id):
            last = np.asarray(state.last_row)   # first-token sampling sync
            now = now_fn()                      # TTFT includes all chunks
            rng = req.make_rng()
            tok = sample_token(last, temperature=req.temperature,
                               top_k=req.top_k, rng=rng)
            self.metrics.first_token(req.id, now)
            payload = PagePayload(mode="splice",
                                  blocks=[int(b) for b in state.blocks],
                                  n_tokens=P, block_size=self.block_size,
                                  n_full=P // self.block_size,
                                  tail_rows=P % self.block_size,
                                  shared_pages=state.shared)
            payload.to_host()               # splice mode stages no arrays
            self.counters["prefills"] += 1
        tr.async_end(self._trk, "prefill", req.id, rid=req.id)
        return FinishedPrefill(
            req=req, first_token=tok, payload=payload, rng=rng,
            last_logits=last if self.record_logits else None,
            worker_id=self.worker_id)
