"""Speculative decoding, draft side.

``DraftWorker`` mirrors a decode worker's slot geometry on its own small
(unquantized, gather-path) paged pool and proposes ``k`` tokens per verify
step with a reduced draft model. The target-side verify/accept/rollback
lives in ``workers.DecodeWorker._spec_decode_step``; the contract between
the two is the per-slot length invariant

    draft.lens[slot] <= worker.lens[slot]            (always)
    rows 0..draft.lens-1 of the draft cache hold KV of the ACCEPTED
    context only (prompt + emitted tokens)

so a rejected suffix needs no explicit cache surgery on either side:
``sync`` just shrinks ``lens`` to the accepted watermark, and the next
propose call's catch-up window rewrites the stale rows in place (writes
always land contiguously at ``lens``).

Proposing is two jitted shapes regardless of k: one (B, 2) catch-up
window — after a fully-accepted step the draft is exactly one token behind
the target (the last draft's KV plus the bonus token), after a rollback
zero behind, so the pending suffix is never longer than 2 — followed by
k-1 single-token decode steps.

``derive_draft`` builds the default draft: the target model truncated to
its first scanned layer groups (embed / final norm / lm head shared).
Half-depth random-init reduced models greedy-agree with their full-depth
parent on ~90% of positions, which is what makes the acceptance rate (and
the tokens/step win) real without any trained checkpoint; the draft is a
genuine reduced config sharing the target's vocab, not a copy.

Composition with prefix sharing (``prefix_cache``): the draft always
prefills the full prompt on its OWN pool (``attach`` receives the whole
prompt, never a shared-page splice — draft pages are per-slot private), so
target-side page sharing is invisible here. On the target, a shared page
is installed-frozen before it is ever published, and rollback only touches
pages past the accepted watermark — which is always past the shared prompt
prefix — so speculative rollback can never un-freeze or mutate a page
another table references; ``FreezeQueue.bid``'s dedupe covers the rest.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import models
from repro.obs.trace import NULL_TRACER

from .kv_cache import (BlockAllocator, init_paged_cache, merge_pools,
                       with_tables)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _draft_prefill(params, toks, tree, *, cfg):
    return models.prefill(params, cfg, {"tokens": toks}, tree)


@functools.partial(jax.jit, static_argnames=("cfg",))
def window_step(params, toks, tree, lens, *, cfg):
    """Jitted multi-token decode window (the verify pass and the draft's
    catch-up/propose steps share this entry; W=1 is a plain decode step)."""
    return models.decode_window(params, cfg, toks, tree, lens)


def derive_draft(params, cfg, *, n_groups: int | None = None):
    """Layer-truncated draft from a target model: keep the first
    ``n_groups`` scanned groups (default: half, at least one) plus the
    shared embed / final norm / head weights. Returns (draft_params,
    draft_cfg) — a real reduced config (half the depth, half the decode
    FLOPs) that shares the target's vocab by construction."""
    assert cfg.family == "lm" and not cfg.head_layers, (
        "derive_draft truncates the scanned groups of a plain decoder LM")
    keep = n_groups if n_groups is not None else max(cfg.n_groups // 2, 1)
    assert 1 <= keep <= cfg.n_groups, (keep, cfg.n_groups)
    draft_cfg = dataclasses.replace(
        cfg, name=f"{cfg.name}-draft{keep}",
        n_layers=keep * len(cfg.group)).validate()
    draft_params = dict(params)
    draft_params["groups"] = jax.tree.map(lambda a: a[:keep],
                                          params["groups"])
    return draft_params, draft_cfg


class DraftWorker:
    """Draft-model mirror of one decode worker: same slot indexing, own
    page pool/allocator/table, fp cache only (draft KV is throwaway)."""

    def __init__(self, params, cfg, *, max_slots: int, block_size: int,
                 max_blocks: int, num_blocks: int | None = None,
                 worker_id: int = 0, tracer=None):
        self.params, self.cfg = params, cfg
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self._trk = f"draft/w{worker_id}"
        self.block_size = block_size
        self.max_blocks = max_blocks
        self.num_blocks = (num_blocks if num_blocks is not None
                           else max_slots * max_blocks + 1)
        self.tree = init_paged_cache(
            cfg, num_blocks=self.num_blocks, block_size=block_size,
            batch=max_slots, max_blocks=max_blocks)
        self.alloc = BlockAllocator(self.num_blocks)
        self.table = np.zeros((max_slots, max_blocks), np.int32)
        self.lens = np.zeros((max_slots,), np.int32)   # valid draft KV rows
        self.plen = np.zeros((max_slots,), np.int32)   # prompt length
        self.blocks: list[list[int]] = [[] for _ in range(max_slots)]
        self._prefill_fn = functools.partial(_draft_prefill, cfg=cfg)
        self._window_fn = functools.partial(window_step, cfg=cfg)

    # ------------------------------------------------------------ lifecycle

    def attach(self, slot: int, prompt, n_blocks: int) -> None:
        """Prefill the prompt on the draft model into this slot's pages
        (same worst-case block count as the target side, so the verify
        window's optimistic writes always fit here too)."""
        t0 = self.tracer.now()
        blocks = self.alloc.alloc(n_blocks)
        self.blocks[slot] = blocks
        self.table[slot] = 0
        self.table[slot, :len(blocks)] = blocks
        P = len(prompt)
        ppad = -(-P // self.block_size) * self.block_size
        toks = np.zeros((1, ppad), np.int32)
        toks[0, :P] = prompt
        tbl = np.asarray([blocks[:ppad // self.block_size]], np.int32)
        tree1 = with_tables(self.tree, tbl, np.zeros((1,), np.int32))
        _, new = self._prefill_fn(self.params, jnp.asarray(toks), tree1)
        self.tree = merge_pools(self.tree, new)
        self.lens[slot] = P
        self.plen[slot] = P
        self.tracer.complete(self._trk, "draft_prefill", t0, slot=slot,
                             prompt_len=P)

    def release(self, slot: int) -> None:
        self.alloc.free(self.blocks[slot])
        self.blocks[slot] = []
        self.table[slot] = 0
        self.lens[slot] = 0
        self.plen[slot] = 0

    def sync(self, slot: int, accepted_len: int) -> None:
        """Roll this slot back to the target's accepted watermark. Rows at
        or past it hold rejected drafts' KV; shrinking ``lens`` is the
        whole rollback — the next catch-up window overwrites them."""
        self.lens[slot] = min(int(self.lens[slot]), int(accepted_len))

    # ------------------------------------------------------------ proposing

    def _mb(self, W: int) -> int:
        need = int(self.lens.max()) + W
        return max(1, -(-need // self.block_size))

    def _step(self, toks: np.ndarray) -> np.ndarray:
        W = toks.shape[1]
        tree = with_tables(self.tree, self.table[:, :self._mb(W)], self.lens)
        logits, new = self._window_fn(self.params, jnp.asarray(toks), tree,
                                      jnp.asarray(self.lens))
        self.tree = merge_pools(self.tree, new)
        # lint: sync(draft tokens feed the host-side proposal loop)
        return np.asarray(jnp.argmax(logits, -1))          # (B, W)

    def propose(self, active, slots, k: int) -> dict[int, list[int]]:
        """k draft tokens per active slot, batched across slots.

        ``slots`` is the decode worker's slot list (``out`` carries the
        accepted token history; token j's KV row is ``plen + j``). First a
        fixed (B, 2) catch-up window writes whatever accepted rows this
        cache is missing and yields draft #1, then k-1 single-token steps
        yield the rest. Rows written past a slot's true pending length are
        scratch — contiguous writes at ``lens`` overwrite them before
        ``lens`` ever covers them.
        """
        t0 = self.tracer.now()
        B = self.table.shape[0]
        Wc = 2
        toks = np.zeros((B, Wc), np.int32)
        wlen = np.ones((B,), np.int32)
        for i in active:
            pend = slots[i].out[int(self.lens[i]) - int(self.plen[i]):]
            assert 1 <= len(pend) <= Wc, (len(pend), Wc)
            toks[i, :len(pend)] = pend
            toks[i, len(pend):] = pend[-1]
            wlen[i] = len(pend)
        preds = self._step(toks)
        out: dict[int, list[int]] = {}
        for i in active:
            out[i] = [int(preds[i, wlen[i] - 1])]
            self.lens[i] += int(wlen[i])
        for _ in range(k - 1):
            toks1 = np.zeros((B, 1), np.int32)
            for i in active:
                toks1[i, 0] = out[i][-1]
            preds = self._step(toks1)
            for i in active:
                out[i].append(int(preds[i, 0]))
                self.lens[i] += 1
        self.tracer.complete(self._trk, "draft_propose", t0, k=k,
                             active=len(active))
        return out
