"""KV page handoff between serving workers (prefill -> decode).

A finished prefill's pages leave the prefill worker's pool as a
``PagePayload`` and land in a decode worker's pool via ``splice_payload``:
the decode worker allocates fresh block ids, the payload's pages are
scattered into its pools at those ids, and the sequence's block table row
points at them — a page-table splice, not a pool copy.

Three migration modes:

  "splice"   Colocated no-op: prefill wrote directly into the decode
             worker's (shared) pool, so the payload carries block ids and
             no arrays. Zero bytes move.

  "fp"       Baseline: every written row crosses as full-width fp
             (full pages whole, the trailing partial page only its valid
             rows). This is what disaggregated serving without codebook
             compression pays per handoff.

  "frozen"   The sparse-LSQ payoff: full pages are routed through the
             existing ``dispatch_freeze`` spec path on the *source* pool,
             so they cross the wire as packed 4-bit codes + one per-block
             codebook (~7x fewer bytes than fp at 16 values) and are
             installed on the destination through the same
             ``install_freeze`` used by in-place freezing — which scatters
             codes/codebooks and flips ``blk_q``, so the landed pages are
             directly servable by both the fused kernel and the gather
             path, which both read frozen pages as codes. Their fp rows
             are left as the destination had them and never served. Only
             the trailing partial page still crosses fp.

  "resident" Overload demotion (``extract_resident_pages``): capture a
             LIVE sequence's pages exactly as currently served — pages
             already installed frozen cross as their existing codes +
             codebooks (read straight off the pool, NO re-solve, so the
             restored values are bit-identical to what attention was
             reading), everything else (unfrozen full pages + tail rows)
             crosses fp. ``frozen_idx`` records which sequence-order page
             positions carry codes. This is the tiered-paging wire format:
             re-solving would quantize not-yet-frozen pages early and
             diverge from the never-offloaded trace.

Payloads stage through host memory (``to_host``), which is both where the
byte accounting happens and where a NIC would sit in a multi-host
deployment; ``nbytes`` vs ``fp_equiv_bytes`` is the measured migration
compression.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.obs.trace import NULL_TRACER

from .kv_cache import (PagedKVCache, PendingFreeze, dispatch_freeze,
                       install_freeze, map_layers)


def collect_leaves(tree) -> list[PagedKVCache]:
    """Layer leaves in deterministic tree order (extract and splice must
    walk source and destination trees identically)."""
    out: list[PagedKVCache] = []
    map_layers(out.append, tree)
    return out


@dataclasses.dataclass
class PagePayload:
    """One migrated sequence's KV pages, staged for transfer.

    ``blocks`` are source-pool ids in sequence page order; array layouts
    per layer leaf (G? = stacked group axis when present):

      full    (2, G?, n_full, bs, Hkv, Dh)   fp full pages       [fp]
      frozen  ((2, G?, n_full, bs/2, Hkv, Dh), (2, G?, n_full, L)) [frozen]
      tail    (2, G?, tail_rows, Hkv, Dh)    partial-page rows   [fp+frozen]

    "resident" payloads split the full pages between ``full`` (unfrozen,
    fp) and ``frozen`` (already-installed codes); ``frozen_idx`` names the
    sequence-order page positions the ``frozen`` arrays cover, in order.

    ``shared_pages`` is refcount-aware ownership: the sequence's leading
    pages that came from (splice payloads) or stayed behind in (resident
    payloads) the prefix index — referenced by other live tables, so never
    captured in this payload's arrays; consumers account/queue-freeze only
    the owned remainder.
    """

    mode: str
    blocks: list[int]
    n_tokens: int
    block_size: int
    n_full: int
    tail_rows: int
    shared_pages: int = 0
    full: list | None = None
    frozen: list | None = None
    tail: list | None = None
    frozen_idx: list | None = None
    nbytes: int = 0
    fp_equiv_bytes: int = 0
    staged: bool = False

    @property
    def n_pages(self) -> int:
        return self.n_full + (1 if self.tail_rows else 0)

    def _arrays(self):
        for name in ("full", "tail"):
            v = getattr(self, name)
            if v is not None:
                yield from v
        if self.frozen is not None:
            for c, cb in self.frozen:
                yield c
                yield cb

    def is_ready(self) -> bool:
        """True once every device array (including a chained freeze solve)
        has landed — ``to_host`` would not block. Callers poll this before
        harvesting so a long solve never stalls their loop."""
        return (self.staged or self.mode == "splice"
                or all(a.is_ready() for a in self._arrays()
                       if hasattr(a, "is_ready")))

    def to_host(self) -> "PagePayload":
        """Materialize every array to host numpy (blocking on any still-
        computing source-side solve) and account the bytes crossing."""
        if self.staged or self.mode == "splice":
            self.staged = True
            return self

        def host(x):
            return np.asarray(x)

        n = 0
        for name in ("full", "tail"):
            arrs = getattr(self, name)
            if arrs is not None:
                arrs = [host(a) for a in arrs]
                setattr(self, name, arrs)
                n += sum(a.nbytes for a in arrs)
        if self.frozen is not None:
            self.frozen = [(host(c), host(cb)) for c, cb in self.frozen]
            n += sum(c.nbytes + cb.nbytes for c, cb in self.frozen)
        self.nbytes = n
        self.staged = True
        return self


@dataclasses.dataclass
class FinishedPrefill:
    """Artifact a prefill worker hands the router: sampled first token (+
    its logits when recorded), the sampler state to continue decoding with,
    and the staged pages."""

    req: object
    first_token: int
    payload: PagePayload
    rng: np.random.Generator
    last_logits: np.ndarray | None = None
    worker_id: int = -1


def _take_pages(leaf: PagedKVCache, bids) -> jnp.ndarray:
    """k and v fp rows of pages ``bids`` stacked on a leading axis:
    (2, G?, P, bs, Hkv, Dh). Callers take only pages that are not
    installed frozen (a frozen page's fp rows are not its value)."""
    axis = 1 if leaf.k_fp.ndim == 5 else 0
    jb = jnp.asarray(np.asarray(bids, np.int32))
    return jnp.stack([jnp.take(leaf.k_fp, jb, axis=axis),
                      jnp.take(leaf.v_fp, jb, axis=axis)])


def extract_pages(tree, blocks, n_tokens: int, *, block_size: int,
                  mode: str, spec=None, tracer=NULL_TRACER) -> PagePayload:
    """Pull one sequence's first ``n_tokens`` of KV out of ``tree``.

    ``blocks`` is the sequence's block-table prefix (sequence page order).
    Returns a payload of device arrays — the frozen-mode solve is one async
    ``dispatch_freeze`` per layer, so extraction does not block the host;
    ``to_host()`` is where the transfer (and any waiting) happens.
    """
    assert mode in ("fp", "frozen"), mode
    t0 = tracer.now()
    n_full, tail_rows = divmod(n_tokens, block_size)
    used = blocks[:n_full + (1 if tail_rows else 0)]
    leaves = collect_leaves(tree)
    payload = PagePayload(mode=mode, blocks=list(map(int, used)),
                          n_tokens=n_tokens, block_size=block_size,
                          n_full=n_full, tail_rows=tail_rows)

    fp_equiv = 0
    for leaf in leaves:
        G = leaf.k_fp.shape[0] if leaf.k_fp.ndim == 5 else 1
        _, _, Hkv, Dh = leaf.k_fp.shape[-4:]
        fp_equiv += (2 * G * (n_full * block_size + tail_rows)
                     * Hkv * Dh * leaf.k_fp.dtype.itemsize)
    payload.fp_equiv_bytes = fp_equiv

    full_bids = used[:n_full]
    if mode == "fp":
        if n_full:
            payload.full = [_take_pages(leaf, full_bids) for leaf in leaves]
    elif n_full:
        if spec is None:
            raise ValueError("frozen migration needs a kv_quant spec")
        # the existing freeze path IS the wire format: one batched device
        # solve over every (page, group, k/v) row, emitting packed codes +
        # per-block codebooks. Pad to a power-of-two page count (repeating
        # one page) like the in-place flush does, so varied prompt lengths
        # share a handful of solver compiles instead of one per distinct
        # page count; dispatch_freeze sorts its block ids, so map each
        # sequence-order page to its slot in the sorted padded batch (the
        # duplicate's first occurrence is fine — identical rows, identical
        # codes), which also drops the padding from the payload.
        bucket = 1 << (n_full - 1).bit_length()
        padded = list(full_bids) + [full_bids[-1]] * (bucket - n_full)
        pending = dispatch_freeze(tree, padded, spec)
        order = np.searchsorted(np.sort(np.asarray(padded)),
                                np.asarray(full_bids))
        frozen = []
        for (codes, cb), leaf in zip(pending.results, leaves):
            paxis = 2 if leaf.k_fp.ndim == 5 else 1
            frozen.append((jnp.take(codes, order, axis=paxis),
                           jnp.take(cb, order, axis=paxis)))
        payload.frozen = frozen
    if tail_rows:
        tail_bid = [used[n_full]]
        payload.tail = [_take_pages(leaf, tail_bid)[:, ..., 0, :tail_rows, :, :]
                        for leaf in leaves]
    tracer.complete("transfer", "extract", t0, mode=mode,
                    pages=payload.n_pages, n_tokens=n_tokens,
                    fp_equiv_bytes=payload.fp_equiv_bytes)
    return payload


def extract_resident_pages(tree, blocks, n_tokens: int, frozen_idx, *,
                           block_size: int,
                           tracer=NULL_TRACER) -> PagePayload:
    """Demote one LIVE sequence's first ``n_tokens`` of KV exactly as
    currently served (overload tiered paging).

    ``frozen_idx`` lists the sequence-order positions of pages already
    *installed* frozen: those cross as their existing packed codes +
    codebooks, read straight off the pool — never re-solved, so a restore
    reproduces the exact values attention was serving. Unfrozen full pages
    and the tail cross fp (their exact values ARE the fp rows; queued or
    in-flight solves for them are dropped by the caller and re-queued
    after restore). Pure gathers — no device solve — so ``to_host`` never
    waits on a solver.
    """
    t0 = tracer.now()
    n_full, tail_rows = divmod(n_tokens, block_size)
    fset = {int(j) for j in frozen_idx if int(j) < n_full}
    fidx = sorted(fset)
    used = blocks[:n_full + (1 if tail_rows else 0)]
    leaves = collect_leaves(tree)
    payload = PagePayload(mode="resident", blocks=list(map(int, used)),
                          n_tokens=n_tokens, block_size=block_size,
                          n_full=n_full, tail_rows=tail_rows,
                          frozen_idx=fidx)
    fp_equiv = 0
    for leaf in leaves:
        G = leaf.k_fp.shape[0] if leaf.k_fp.ndim == 5 else 1
        _, _, Hkv, Dh = leaf.k_fp.shape[-4:]
        fp_equiv += (2 * G * (n_full * block_size + tail_rows)
                     * Hkv * Dh * leaf.k_fp.dtype.itemsize)
    payload.fp_equiv_bytes = fp_equiv

    fp_pos = [j for j in range(n_full) if j not in fset]
    if fp_pos:
        fp_bids = [used[j] for j in fp_pos]
        payload.full = [_take_pages(leaf, fp_bids) for leaf in leaves]
    if fidx:
        jb = jnp.asarray(np.asarray([used[j] for j in fidx], np.int32))
        frozen = []
        for leaf in leaves:
            axis = 1 if leaf.k_fp.ndim == 5 else 0
            frozen.append((
                jnp.stack([jnp.take(leaf.k_codes, jb, axis=axis),
                           jnp.take(leaf.v_codes, jb, axis=axis)]),
                jnp.stack([jnp.take(leaf.k_cb, jb, axis=axis),
                           jnp.take(leaf.v_cb, jb, axis=axis)])))
        payload.frozen = frozen
    if tail_rows:
        tail_bid = [used[n_full]]
        payload.tail = [_take_pages(leaf, tail_bid)[:, ..., 0, :tail_rows, :, :]
                        for leaf in leaves]
    tracer.complete("transfer", "extract", t0, mode="resident",
                    pages=payload.n_pages, n_tokens=n_tokens,
                    frozen_pages=len(fidx),
                    fp_equiv_bytes=payload.fp_equiv_bytes)
    return payload


def splice_payload(tree, payload: PagePayload, new_blocks, *,
                   tracer=NULL_TRACER):
    """Land a staged payload in the destination pool at ``new_blocks``
    (sequence page order, already allocated by the caller). Returns the
    updated tree; the caller installs the block-table row."""
    if payload.mode == "splice":
        return tree          # pages already live in this pool
    t0 = tracer.now()
    payload.to_host()
    leaves = collect_leaves(tree)
    # "resident" payloads interleave fp and frozen full pages: the fp
    # arrays cover the positions NOT in frozen_idx, the frozen arrays the
    # rest — other modes are the frozen_idx = all-or-nothing special case
    if payload.mode == "resident":
        fset = set(payload.frozen_idx or ())
        fp_pos = [j for j in range(payload.n_full) if j not in fset]
        fp_full = np.asarray([new_blocks[j] for j in fp_pos], np.int32)
        fro_full = np.asarray([new_blocks[j] for j in sorted(fset)],
                              np.int32)
    else:
        fp_full = fro_full = np.asarray(new_blocks[:payload.n_full],
                                        np.int32)
    out: list[PagedKVCache] = []
    for li, leaf in enumerate(leaves):
        stacked = leaf.k_fp.ndim == 5
        k_fp, v_fp = leaf.k_fp, leaf.v_fp
        if payload.full is not None:
            both = jnp.asarray(payload.full[li])
            sel = (slice(None), fp_full) if stacked else (fp_full,)
            k_fp = k_fp.at[sel].set(both[0])
            v_fp = v_fp.at[sel].set(both[1])
        if payload.tail is not None:
            both = jnp.asarray(payload.tail[li])
            b = int(new_blocks[payload.n_full])
            r = payload.tail_rows
            sel = ((slice(None), b, slice(0, r)) if stacked
                   else (b, slice(0, r)))
            k_fp = k_fp.at[sel].set(both[0])
            v_fp = v_fp.at[sel].set(both[1])
        out.append(dataclasses.replace(leaf, k_fp=k_fp, v_fp=v_fp))
    it = iter(out)
    tree = map_layers(lambda _leaf: next(it), tree)
    if payload.frozen is not None:
        # same install path as in-place freezing: scatters codes/codebooks
        # and flips blk_q; the pages' fp rows are never read again
        pending = PendingFreeze(
            fro_full, [(jnp.asarray(c), jnp.asarray(cb))
                       for c, cb in payload.frozen])
        tree = install_freeze(tree, pending)
    tracer.complete("transfer", "splice", t0, mode=payload.mode,
                    pages=payload.n_pages, bytes=payload.nbytes,
                    fp_equiv_bytes=payload.fp_equiv_bytes)
    return tree
