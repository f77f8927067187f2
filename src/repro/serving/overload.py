"""Overload survival: tiered frozen-page host offload, preempt-and-requeue,
and SLO-aware admission.

Three cooperating mechanisms let a full pool degrade gracefully instead of
hard-429ing at the admission door (ROADMAP item 4):

  tiered paging    Under pressure a whole victim sequence's pages demote
      to a ``HostPageStore`` as a "resident" payload
      (``transfer.extract_resident_pages``): installed-frozen pages cross
      as their existing packed 4-bit codes + codebooks (~7x fewer bytes
      than fp — the sparse-LSQ codebooks are what make survival cheap),
      the rest fp. Restore is dispatched at re-admission — BEFORE the
      decode window needs the pages — and the jit dataflow chains the
      first decode step behind the install, so a restored sequence is
      greedy-token-identical to one that never left.

  preempt-and-requeue    ``DecodeWorker.preempt`` evicts a victim at a
      step boundary (mirroring ``_finish``'s cleanup, so the rollback/
      freeze-watermark and pool-conservation invariants hold), and the
      scheduler re-admits preempted requests ahead of FCFS. The
      ``choose_resume`` cost model picks restore (move the payload bytes
      back — exact) vs recompute (re-prefill prompt + emitted tokens —
      cheaper when almost nothing was frozen, but only value-exact on
      unquantized greedy runs, so quantized/sampled runs always restore).

  SLO-aware admission    ``SLOAdmission`` consults the *windowed* itl_s
      p99 from the streaming registry (PR 6's log-histogram counts-delta
      mechanism) plus live pool occupancy to shed or defer best_effort
      requests while latency-tier requests are only ever bounced by the
      hard queue/pool doors. Deferred requests park in the
      ``OverloadManager`` and retry when occupancy recedes.

``OverloadManager`` is the engine-side composition of the three: both
engine run loops call ``try_restore`` (drain the resume queue into freed
capacity, ahead of any FCFS admission) and ``maybe_preempt`` (evict a
best_effort victim when a latency-tier head is capacity-blocked) once per
iteration. All decision logic is host-side and deterministic — a whole
overload scenario replays exactly in a unit test.
"""
from __future__ import annotations

import dataclasses
from collections import deque

import numpy as np

from .transfer import PagePayload


@dataclasses.dataclass
class ResumeEntry:
    """Everything needed to resume a preempted sequence exactly where it
    stopped: the request, its emitted tokens (``out``; the last one has no
    KV row yet — the next decode step writes it, same as at attach), and
    the demoted pages. ``n_tokens`` is the KV length at eviction, which at
    a step boundary is prompt_len + generated - 1."""

    req: object
    out: list
    generated: int
    n_tokens: int
    rng: object = None
    logits: list = dataclasses.field(default_factory=list)
    payload: PagePayload | None = None         # None = recompute path
    frozen_idx: list = dataclasses.field(default_factory=list)
    span_ids: dict = dataclasses.field(default_factory=dict)  # page pos -> span
    # leading pages the victim shared with other live tables at eviction:
    # they dropped a ref instead of demoting (the payload never captures
    # them), and restore re-attaches them from the prefix index — or
    # rebuilds them deterministically if their last reference died
    shared_pages: int = 0

    @property
    def restore_bytes(self) -> int:
        return self.payload.nbytes if self.payload is not None else 0


class HostPageStore:
    """Host-memory tier holding demoted sequences' page payloads, keyed by
    request id. Pure bookkeeping over staged numpy payloads — this is
    where a second HBM tier (or a remote host) would sit; ``nbytes`` is
    the measured footprint of everything currently demoted."""

    def __init__(self):
        self._entries: dict[int, ResumeEntry] = {}
        self.put_total = 0          # lifetime payloads stored
        self.bytes_total = 0        # lifetime bytes staged in

    def put(self, entry: ResumeEntry) -> None:
        rid = entry.req.id
        assert rid not in self._entries, f"rid {rid} already demoted"
        assert entry.payload is not None and entry.payload.staged
        self._entries[rid] = entry
        self.put_total += 1
        self.bytes_total += entry.payload.nbytes

    def pop(self, rid: int) -> ResumeEntry:
        return self._entries.pop(rid)

    def release(self, rid: int) -> ResumeEntry | None:
        """Reclaim a retired request's entry (finished or cancelled while
        offloaded) — unlike ``pop``, absent is fine. Without this, a
        demoted payload whose request never restores leaks in the host
        tier forever."""
        return self._entries.pop(rid, None)

    def __contains__(self, rid: int) -> bool:
        return rid in self._entries

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def nbytes(self) -> int:
        return sum(e.payload.nbytes for e in self._entries.values())

    @property
    def pages(self) -> int:
        return sum(e.payload.n_pages for e in self._entries.values())

    def entries(self) -> list[ResumeEntry]:
        return list(self._entries.values())


def choose_resume(*, frozen_pages: int, total_pages: int, restore_bytes: int,
                  fp_equiv_bytes: int, exact_required: bool) -> str:
    """Restore-vs-recompute cost model for a preemption victim.

    Restore moves ``restore_bytes`` back across the host tier; recompute
    re-prefills the whole context, rewriting ``fp_equiv_bytes`` of KV at
    full width plus the prefill FLOPs. On the modeled roofline both reduce
    to bytes moved, so restore wins whenever the payload is meaningfully
    compressed — i.e. when enough pages were frozen (codes are ~7x
    smaller). ``exact_required`` forces restore: recompute re-prefills
    through exact fp where the original decode served quantized
    reconstructions (and a sampled request's rng cannot be rewound), so
    only restore keeps those runs token-identical.
    """
    if exact_required:
        return "restore"
    if total_pages == 0:
        return "recompute"          # nothing demotable — nothing to move
    # payload compressed below ~60% of a full fp re-write: moving it back
    # beats paying the re-prefill (which also burns compute the overloaded
    # box doesn't have)
    if restore_bytes <= 0.6 * fp_equiv_bytes:
        return "restore"
    return "recompute"


class SLOAdmission:
    """Shed/defer policy over the streaming registry's live signals.

    Consumes the windowed itl_s p99 (log-histogram counts-delta between
    policy snapshots — O(1) memory, no sample lists) and the device pool
    occupancy. latency-tier requests always pass; best_effort requests are
    shed while the latency SLO is breached and deferred while the pool is
    nearly full. Hysteresis: deferred requests re-admit only once
    occupancy recedes below ``occ_resume`` (or the worker goes idle), so
    the door doesn't flap at the threshold.
    """

    def __init__(self, metrics, *, itl_slo_s: float | None = None,
                 occ_defer: float = 0.95, occ_resume: float = 0.80,
                 window: int = 128, min_samples: int = 16):
        assert 0.0 < occ_resume <= occ_defer <= 1.0
        self.metrics = metrics
        self.itl_slo_s = itl_slo_s
        self.occ_defer = occ_defer
        self.occ_resume = occ_resume
        self.window = window
        self.min_samples = min_samples
        self._snap = None            # (histogram state) at window start

    # ------------------------------------------------------------ signals

    def windowed_itl_p99(self) -> float | None:
        """p99 of inter-token latency over the current window, from bucket
        count deltas; None until ``min_samples`` gaps landed in-window."""
        if "itl_s" not in self.metrics.stats:
            return None
        h = self.metrics.stats.histogram("itl_s")
        if self._snap is None:
            # first window starts EMPTY, not at the current counts —
            # snapshotting late would swallow every gap observed before
            # the policy's first decision
            self._snap = {"counts": [0] * len(h.counts), "underflow": 0,
                          "overflow": 0, "n": 0}
        d = h.delta(self._snap)
        if d["n"] >= self.window:
            # roll the window forward; answer over the closing window
            p = h.percentile(99, **d)
            self._snap = h.state()
            self._last = p
            return p
        if d["n"] >= self.min_samples:
            return h.percentile(99, **d)
        return getattr(self, "_last", None)

    # ------------------------------------------------------------ decisions

    def decide(self, req, *, occupancy: float) -> str:
        """'admit' | 'shed' | 'defer' for an arriving request."""
        if getattr(req, "priority", "latency") != "best_effort":
            return "admit"
        if self.itl_slo_s is not None:
            p99 = self.windowed_itl_p99()
            if p99 is not None and p99 > self.itl_slo_s:
                return "shed"
        if occupancy >= self.occ_defer:
            return "defer"
        return "admit"

    def may_resume(self, *, occupancy: float, idle: bool) -> bool:
        """Gate for re-admitting deferred requests (hysteresis band)."""
        return idle or occupancy <= self.occ_resume


class OverloadManager:
    """Engine-side overload state: the host tier, the restore queue, the
    deferred queue, and the preemption trigger. One instance per engine;
    methods take the decode worker they act on, so the disaggregated
    engine shares one manager across its decode workers (payloads are
    portable — a sequence may restore onto a different worker than it was
    evicted from)."""

    def __init__(self, *, offload_pages: bool = True, policy=None,
                 router=None):
        self.offload_pages = offload_pages
        self.policy = policy
        # disaggregated composition: recompute-requeues and deferred
        # retries go through the global router's queues, not a worker's
        # local scheduler (which the disagg import path bypasses)
        self.router = router
        self.store = HostPageStore()
        self.resume: deque[ResumeEntry] = deque()
        self.deferred: deque = deque()

    @property
    def has_work(self) -> bool:
        return bool(self.resume or self.deferred or len(self.store))

    def _queues(self, worker):
        """The admission queues this composition drains: the router's when
        disaggregated, the worker's scheduler's when colocated."""
        return self.router if self.router is not None else worker.sched

    # ------------------------------------------------------------ restore

    def try_restore(self, worker, now_fn) -> int:
        """Drain the resume queue head-first into the worker's free
        capacity. Runs BEFORE the scheduler's FCFS admission each
        iteration, so a preempted sequence re-enters ahead of every queued
        arrival. Stops at the first entry that doesn't fit (strict order —
        a later, smaller entry must not starve the head)."""
        n = 0
        while self.resume:
            entry = self.resume[0]
            req = entry.req
            if (not worker.sched._free_slots
                    or worker.sched.blocks_for(req) > worker.alloc.num_free):
                break
            if getattr(req, "priority", "latency") == "best_effort":
                # a best_effort victim must not re-absorb the capacity its
                # own eviction freed for a starved latency head: it only
                # restores when slots+pages suffice for BOTH, else it stays
                # demoted until the head admits (or finishes)
                head = self._queue_head(worker)
                if (head is not None
                        and getattr(head, "priority", "latency") == "latency"
                        and (len(worker.sched._free_slots) < 2
                             or worker.alloc.num_free
                             < worker.sched.blocks_for(req)
                             + worker.sched.blocks_for(head))):
                    break
            self.resume.popleft()
            self.store.pop(req.id)
            st = worker.sched.admit_direct(req)
            worker.restore(st, entry, now_fn())
            n += 1
        return n

    def retry_deferred(self, worker) -> int:
        """Re-admit deferred best_effort requests once pressure recedes
        (hysteresis: the policy's ``occ_resume`` band, or an idle worker).
        They rejoin the ordinary waiting queue — deferral bought them a
        later place in line, not a priority upgrade. Appends directly
        (their arrival was already metered at defer time) and respects the
        queue-depth door."""
        if not self.deferred or self.policy is None:
            return 0
        occ = 1.0 - worker.alloc.num_free / (worker.num_blocks - 1)
        if not self.policy.may_resume(occupancy=occ,
                                      idle=not worker.sched.active):
            return 0
        q = self._queues(worker)
        n = 0
        while self.deferred and len(q.waiting) < q.max_queue:
            q.waiting.append(self.deferred.popleft())
            n += 1
        return n

    # ------------------------------------------------------------ retire

    def retire(self, rid: int) -> ResumeEntry | None:
        """Release a request's host-tier residency on retirement (it
        finished, was cancelled, or the run is over while it sat demoted):
        drop its store entry and resume-queue slot. Returns the released
        entry (its ``out`` is everything the request ever emitted) or None
        if the request was not demoted."""
        entry = self.store.release(rid)
        if entry is not None:
            self.resume = deque(e for e in self.resume if e.req.id != rid)
        return entry

    # ------------------------------------------------------------ preempt

    def _queue_head(self, worker):
        """The first request waiting in the admission queues: staged
        prefills (disagg) outrank recompute-requeues outrank FCFS."""
        if self.router is not None and self.router.staged:
            return self.router.staged[0].req
        q = self._queues(worker)
        if q.preempted:
            return q.preempted[0]
        if q.waiting:
            return q.waiting[0]
        return None

    def _head(self, worker):
        """The highest-priority request waiting on this worker's capacity:
        resume entries outrank everything in the admission queues."""
        if self.resume:
            return self.resume[0].req
        return self._queue_head(worker)

    def pick_victim(self, worker):
        """A best_effort victim worth evicting for a capacity-blocked
        latency-tier head, or None.

        Coldness rank: least-recently-attended first (LRU by decode step),
        then highest frozen fraction (cheapest to demote — frozen pages
        move at ~4 bits/value), then slot for determinism. Only preempts
        across tiers, and only when the evictable best_effort capacity
        could actually unblock the head."""
        head = self._head(worker)
        if head is None or getattr(head, "priority", "latency") != "latency":
            return None
        need = worker.sched.blocks_for(head)
        slot_blocked = not worker.sched._free_slots
        page_blocked = need > worker.alloc.num_free
        if not (slot_blocked or page_blocked):
            return None
        # the LRU signal is seeded at attach/restore time, so a sequence is
        # visible to preemption from the moment it holds pages — a
        # just-attached best_effort victim (coldest possible: zero steps
        # attended) must not hide from a capacity-blocked latency head
        # behind a missing last_attended entry
        victims = [st for st in worker.sched.active.values()
                   if getattr(st.req, "priority", "latency") == "best_effort"
                   and st.slot in worker.last_attended]
        if not victims:
            return None
        if page_blocked:
            reclaimable = sum(len(worker.slots[st.slot].blocks)
                              for st in victims)
            if worker.alloc.num_free + reclaimable < need:
                return None          # eviction can't unblock — don't thrash

        def rank(st):
            s = worker.slots[st.slot]
            frozen = sum(1 for b in s.blocks
                         if b in worker.freezes.installed)
            frac = frozen / max(len(s.blocks), 1)
            return (worker.last_attended[st.slot], -frac, st.slot)

        return min(victims, key=rank)

    def maybe_preempt(self, worker, now_fn) -> bool:
        """Evict at most one victim per call (re-evaluated every iteration
        so pressure ramps rather than mass-evicting). The cost model picks
        offload-and-restore vs drop-and-recompute; with the host tier
        disabled, recompute is the only resume path."""
        st = self.pick_victim(worker)
        if st is None:
            return False
        s = worker.slots[st.slot]
        # shared prefix pages neither demote nor restore — they drop a ref
        # and re-attach from the index — so the cost model sees only the
        # exclusively-owned suffix on both sides of the tradeoff
        sh = worker.shared_prefix_pages(st.slot)
        full = int(worker.lens[st.slot]) // worker.block_size
        frozen = sum(1 for b in s.blocks[sh:full]
                     if b in worker.freezes.installed)
        n_pages = -(-int(worker.lens[st.slot]) // worker.block_size) - sh
        pb = worker._pb
        est = frozen * pb["frozen"] + (n_pages - frozen) * pb["fp"]
        exact = (worker.kv_spec is not None
                 or st.req.temperature > 0.0)
        mode = "recompute"
        if self.offload_pages:
            mode = choose_resume(
                frozen_pages=frozen, total_pages=n_pages, restore_bytes=est,
                fp_equiv_bytes=n_pages * pb["fp"], exact_required=exact)
        entry = worker.preempt(st, mode, now_fn())
        if mode == "restore":
            self.store.put(entry)
            self.resume.append(entry)
        else:
            # recompute: resume as a fresh request whose prompt is the
            # original plus everything emitted; the worker merges the
            # prefix back at finish. Re-admitted ahead of FCFS (through
            # the router's preempted queue when disaggregated — it must
            # re-prefill on a prefill worker first).
            self._queues(worker).preempted.append(entry.req)
        return True
