"""Paged KV cache: fixed-size blocks, a free-list allocator, per-sequence
block tables, codebook-quantized pages, and a fused decode read path.

Layout (per attention layer, leading group axis added by the stacked model
cache exactly like ``transformer.init_lm_cache``):

  k_fp/v_fp     (nb, bs, Hkv, Dh)  fp pages — the write-hot pool; every
                token lands here first.
  k_codes/...   (nb, bs/2, Hkv, Dh) uint8 codes for quantized pages,
                two 4-bit codes per byte split-half along the token axis
                (see kernels.paged_attention.pack4); (nb, bs, Hkv, Dh)
                unpacked when codebooks exceed 16 values.
  k_cb/v_cb     (nb, L) f32        per-block codebooks from the paper's
                solvers (kmeans_ls / tv via repro.core.quantize).
  blk_q         (nb,) bool         page i is frozen: its codes and
                codebook are its value. Its fp rows keep what they held
                (the exact pre-freeze values, or nothing meaningful for a
                page that landed as codes) and no read serves them.
  block_table   (B, mb) int32      per-sequence page ids (0 = null page).
  seq_lens      (B,) int32         per-sequence lengths (write positions).

Block 0 is reserved as the null page: idle batch slots point every table
entry at it, so their (masked) decode writes land in the trash instead of a
live page.

Writes always go to the fp pool inside the jitted step. A full page
freezes under a ``QuantSpec`` (see ``resolve_kv_spec``) in two device
steps: ``dispatch_freeze`` batches every (page, group, k/v) row of the
event through the spec's registry device solver (kmeans_ls/kmeans via the
exact DP sketch, iter_l1 via batched FISTA + per-row lambda bisection) in
one async dispatch per layer, and ``install_freeze`` scatters the codes and
codebooks and flips ``blk_q`` (the fp pools are not written). Between the
two the pages serve exact fp, so decode steps overlap the solve; count
methods without a device entry solve per page on the host.

``FreezeQueue`` holds the host half for one pool. A page in it moves

  queued -> dispatched -> installed | dropped | rolled_back | offloaded

and its ``page_freeze`` span ends in one of the last four. The decode
worker keeps each slot's watermark and calls ``bid`` as the slot's pages
fill, ``flush`` at the start of every iteration (sync mode installs
there), ``poll`` before each decode step, ``release`` when a slot finishes
or is preempted (``dropped``/``offloaded``) or rolls back a speculative
suffix (``rolled_back``), ``mark_installed`` for pages that arrive frozen
(frozen migration, restore) and ``install_now`` to freeze a rebuilt
shared prefix.

Reads have two paths:

  fused (TPU decode hot path)   ``fused_decode`` hands the query plus the
      raw pools/table to ``kernels.paged_decode_attention``, which walks
      the block table on-core, DMAs frozen pages as packed codes +
      codebooks, dequantizes in VMEM, and runs online-softmax attention.
      Frozen pages cross the wire at ~4 bits/value.

  gather (CPU / prefill / fallback)   ``update`` expands every table page
      to full width and returns dense K/V for the caller's sdpa: fp rows
      for live pages, ``cb[codes]`` for frozen ones (dequantized in the
      graph, and only when the table holds a frozen page). It is the
      reference the fused kernel is validated against, paying fp
      bandwidth where the kernel pays ~4 bits/value.

``PagedKVCache`` implements the adapter protocol of ``repro.models.cache``
(plus its optional fused-decode extension); model code never learns about
pages.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import QuantSpec
from repro.core import registry as quant_registry
from repro.kernels import (default_interpret, pack4, paged_decode_attention,
                           paged_prefill_attention, unpack4)
from repro.kernels.paged_attention import codebook_lookup
from repro.obs.trace import phase

# ------------------------------------------------------------- allocator


class PoolExhausted(MemoryError):
    """Typed allocator failure carrying the shortfall, so overload-control
    code (preemption, admission deferral) can catch-and-react instead of
    pattern-matching a bare MemoryError message. Subclasses MemoryError for
    callers that only care that allocation failed."""

    def __init__(self, requested: int, free: int):
        self.requested = requested
        self.free = free
        super().__init__(f"asked {requested} blocks, {free} free")


class DoubleFree(ValueError):
    """Typed allocator failure for freeing a block that is already on the
    free list (or was never allocated). Subclasses ValueError so legacy
    callers that caught the old bare-ValueError message keep working; the
    offending id rides along for return-path audits."""

    def __init__(self, block: int):
        self.block = block
        super().__init__(f"double free / foreign block {block}")


class BlockAllocator:
    """Host-side free-list page allocator with per-page refcounts. Block 0
    is never handed out.

    Refcount protocol (prefix sharing): ``alloc`` hands out pages at rc 1;
    ``retain`` bumps rc for every table that splices an already-live page;
    ``free`` drops rc and releases a page to the free list only when its
    last reference goes away. ``free`` returns the ids actually released so
    callers can scope teardown side effects (thawing, span drops, frozen-set
    removal) to pages no other sequence still serves from."""

    def __init__(self, num_blocks: int):
        assert num_blocks >= 2, "need at least one allocatable block"
        self.num_blocks = num_blocks
        self._free = list(range(num_blocks - 1, 0, -1))   # pop() -> low ids first
        self._used: set[int] = set()
        self._rc: dict[int, int] = {}

    @property
    def num_free(self) -> int:
        return len(self._free)

    def refcount(self, b: int) -> int:
        return self._rc.get(int(b), 0)

    def alloc(self, n: int) -> list[int]:
        if n > len(self._free):
            raise PoolExhausted(n, len(self._free))
        out = [self._free.pop() for _ in range(n)]
        self._used.update(out)
        for b in out:
            self._rc[b] = 1
        return out

    def retain(self, ids) -> None:
        """Add one reference per id for a table sharing already-live pages."""
        for b in ids:
            b = int(b)
            if b not in self._used:
                raise ValueError(f"retain of non-live block {b}")
            self._rc[b] += 1

    def free(self, ids) -> list[int]:
        """Drop one reference per id; release pages whose rc hits 0.

        Returns the ids actually released (rc reached zero) in drop order.
        Freeing an id that is not live raises ``DoubleFree``.
        """
        released: list[int] = []
        for b in ids:
            b = int(b)
            if b not in self._used:
                raise DoubleFree(b)
            self._rc[b] -= 1
            if self._rc[b] == 0:
                del self._rc[b]
                self._used.remove(b)
                self._free.append(b)
                released.append(b)
        return released


# ------------------------------------------------------------- prefix index


class PrefixIndex:
    """Rolling token-hash index over installed-frozen full pages.

    Each published page is keyed by ``(chain_hash, page_tokens)`` where
    ``chain_hash`` rolls over every preceding page of the same prompt
    (``h_0 = 0``, ``h_{i+1} = hash((h_i, page_i_tokens))``), so a lookup
    walks the longest run of full pages whose *entire prefix* matches a
    published chain — a radix trie keyed one page per edge. Only immutable
    pages publish: installed-frozen codebook reconstructions on quantized
    pools, full prompt pages on unquantized pools (prompt rows never
    rewrite once written) — safe for any number of tables to reference.
    Entries die with their page: the worker calls ``invalidate`` with the
    ids ``BlockAllocator.free`` actually released.
    """

    def __init__(self, block_size: int):
        self.block_size = block_size
        self._map: dict[tuple, int] = {}          # (chain_hash, page) -> bid
        self._keys: dict[int, list] = {}          # bid -> keys published

    def __len__(self) -> int:
        return len(self._map)

    @staticmethod
    def _link(parent: int, page: tuple) -> int:
        # int/tuple hashing is unsalted in CPython, so chains are stable
        # across processes (tests may compare index sizes run-to-run)
        return hash((parent, page))

    def publish(self, tokens, blocks, frozen) -> int:
        """Register the full pages of ``tokens`` served by ``blocks`` whose
        ids are in ``frozen``, stopping at the first non-frozen page (a
        chain must be contiguous from the root). ``frozen=None`` marks every
        full page eligible — the unquantized-pool case, where full prompt
        pages are immutable exact-fp rows the moment prefill wrote them.
        Idempotent; first publisher of a (chain, page) key wins. Returns
        new entries added."""
        bs = self.block_size
        h, added = 0, 0
        for i in range(min(len(tokens) // bs, len(blocks))):
            bid = int(blocks[i])
            if frozen is not None and bid not in frozen:
                break
            page = tuple(int(t) for t in tokens[i * bs:(i + 1) * bs])
            key = (h, page)
            if key not in self._map:
                self._map[key] = bid
                self._keys.setdefault(bid, []).append(key)
                added += 1
            h = self._link(h, page)
        return added

    def lookup(self, tokens, max_pages: int) -> list[int]:
        """Longest run of published pages matching ``tokens`` from position
        0, capped at ``max_pages``; returns their block ids in order."""
        bs = self.block_size
        h, out = 0, []
        limit = min(len(tokens) // bs, max_pages)
        for i in range(limit):
            page = tuple(int(t) for t in tokens[i * bs:(i + 1) * bs])
            bid = self._map.get((h, page))
            if bid is None:
                break
            out.append(bid)
            h = self._link(h, page)
        return out

    def invalidate(self, released_ids) -> None:
        """Forget every entry served by a page whose last reference was
        just released (the id may be reallocated with different content)."""
        for bid in released_ids:
            for key in self._keys.pop(int(bid), ()):
                if self._map.get(key) == int(bid):
                    del self._map[key]


# ------------------------------------------------------------- paged cache


def _pack4(codes: np.ndarray) -> np.ndarray:
    """Host-side pack4 (same split-half token-axis layout as kernels.pack4)."""
    bs = codes.shape[-3]
    lo, hi = codes[..., : bs // 2, :, :], codes[..., bs // 2:, :, :]
    return (lo | (hi << 4)).astype(np.uint8)


def _unpack4(packed: jax.Array) -> jax.Array:
    return unpack4(packed)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedKVCache:
    """One attention layer's paged KV pools + this batch's table view."""

    k_fp: jax.Array
    v_fp: jax.Array
    k_codes: jax.Array
    v_codes: jax.Array
    k_cb: jax.Array
    v_cb: jax.Array
    blk_q: jax.Array
    block_table: jax.Array
    seq_lens: jax.Array
    # static
    block_size: int
    quantized: bool
    packed: bool
    fused: bool = False       # decode reads go through the Pallas kernel
    fused_window: int = 1     # max fused query window (speculative verify)
    prefill_fused: bool = False   # prefill chunks read through the kernel

    _LEAVES = ("k_fp", "v_fp", "k_codes", "v_codes", "k_cb", "v_cb",
               "blk_q", "block_table", "seq_lens")
    _POOL_LEAVES = ("k_fp", "v_fp", "k_codes", "v_codes", "k_cb", "v_cb",
                    "blk_q")
    # what a freeze install writes: a frozen page's whole value
    _CODE_LEAVES = ("k_codes", "v_codes", "k_cb", "v_cb", "blk_q")

    def tree_flatten(self):
        return (tuple(getattr(self, f) for f in self._LEAVES),
                (self.block_size, self.quantized, self.packed, self.fused,
                 self.fused_window, self.prefill_fused))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    # ---------------------------------------------- adapter protocol

    def _write(self, k, v):
        """Scatter k/v (B, S, Hkv, Dh) into the fp pool at per-sequence
        positions (block 0 absorbs idle slots' masked writes)."""
        B, S, Hkv, Dh = k.shape
        bs = self.block_size
        pos = self.seq_lens[:, None] + jnp.arange(S)[None]          # (B,S)
        blk = jnp.take_along_axis(self.block_table, pos // bs, axis=1)
        off = pos % bs
        return dataclasses.replace(
            self,
            k_fp=self.k_fp.at[blk.reshape(-1), off.reshape(-1)].set(
                k.reshape(B * S, Hkv, Dh).astype(self.k_fp.dtype)),
            v_fp=self.v_fp.at[blk.reshape(-1), off.reshape(-1)].set(
                v.reshape(B * S, Hkv, Dh).astype(self.v_fp.dtype)),
        )

    def update(self, k, v, cache_index):
        """Write k/v (B,S,Hkv,Dh) at per-sequence positions; gather pages.

        cache_index (the ring-cache scalar) is ignored: this cache carries
        its own per-sequence lengths.
        """
        del cache_index
        S = k.shape[1]
        new = self._write(k, v)
        k_all = new._gather(new.k_fp, new.k_codes, new.k_cb)
        v_all = new._gather(new.v_fp, new.v_codes, new.v_cb)
        return new, k_all, v_all, self.seq_lens, self.seq_lens + S

    @property
    def use_fused_decode(self) -> bool:
        """Fused-adapter extension flag (see repro.models.cache)."""
        return self.fused

    def fused_decode(self, q, k, v, *, softcap=None):
        """Decode write + fused paged attention over a 1..fused_window
        query window.

        Returns (new_cache, out (B, S, Hq, Dh)); frozen pages are read as
        packed codes and dequantized inside the kernel. S > 1 is the
        speculative verify window: query w attends causally through
        position ``seq_lens + w``.
        """
        B, S, Hq, Dh = q.shape
        assert S <= max(self.fused_window, 1), (
            f"fused_decode window {S} exceeds fused_window "
            f"{self.fused_window}")
        new = self._write(k, v)
        out = paged_decode_attention(
            q if S > 1 else q[:, 0], new.k_fp, new.v_fp, new.k_codes,
            new.v_codes, new.k_cb, new.v_cb, new.blk_q, new.block_table,
            new.seq_lens + S, softcap=softcap, quantized=new.quantized,
            packed=new.packed, interpret=default_interpret())
        return new, (out if S > 1 else out[:, None]).astype(q.dtype)

    @property
    def use_fused_prefill(self) -> bool:
        """Fused chunked-prefill extension flag (see repro.models.cache)."""
        return self.prefill_fused

    def fused_prefill(self, q, k, v, *, softcap=None):
        """Prefill-chunk write + fused paged attention.

        The chunk's C queries sit at absolute positions
        ``seq_lens .. seq_lens + C - 1`` — exactly the last C positions of
        the post-write valid length, so this is ``fused_decode`` with
        W = C and the causal chunk mask falls out of the existing windowed
        mask (``pos <= q_offset + w``). Earlier frozen pages are read as
        packed codes + codebooks through the same double-buffered DMA path
        as decode; splitting a prompt into chunks is bitwise identical to
        one whole-prompt call (the PR 5 verify-window discipline applied
        to prefill).
        """
        new = self._write(k, v)
        out = paged_prefill_attention(
            q, new.k_fp, new.v_fp, new.k_codes, new.v_codes, new.k_cb,
            new.v_cb, new.blk_q, new.block_table, self.seq_lens,
            softcap=softcap, quantized=new.quantized, packed=new.packed,
            interpret=default_interpret())
        return new, out.astype(q.dtype)

    def _gather(self, fp, codes, cb):
        """Pages for this batch: (B, mb*bs, Hkv, Dh).

        A frozen page reads as ``cb[codes]`` (the fused kernel's L-way
        compare-and-select) cast to the pool dtype, a live page as its fp
        rows; a frozen page's fp rows are never served. The lookup runs
        only when the table holds a frozen page, so a read of fresh pages
        (a whole-prompt prefill) is the plain fp gather."""
        t = self.block_table                                # (B, mb)
        B, mb = t.shape
        pages = fp[t]                                       # (B,mb,bs,H,D)
        nb, bs, H, D = fp.shape
        if self.quantized:
            frozen = self.blk_q[t]                          # (B, mb)
            c, cbt = codes[t], cb[t]                        # cbt (B, mb, L)

            def dequantize(pages):
                idx = _unpack4(c) if self.packed else c.astype(jnp.int32)
                deq = codebook_lookup(
                    idx, lambda l: cbt[..., l, None, None, None],
                    cbt.shape[-1])
                return jnp.where(frozen[..., None, None, None],
                                 deq.astype(fp.dtype), pages)

            pages = lax.cond(jnp.any(frozen), dequantize, lambda p: p, pages)
        return pages.reshape(B, mb * bs, H, D)


def init_paged_layer(cfg, *, num_blocks, block_size, batch, max_blocks,
                     quantized, num_values, dtype,
                     fused=False, fused_window=1) -> PagedKVCache:
    Hkv, Dh = cfg.n_kv_heads, cfg.head_dim
    packed = quantized and num_values <= 16
    assert block_size % 2 == 0 or not packed, (
        f"4-bit pages pack along the token axis: block size {block_size} "
        f"must be even")
    rows = block_size // 2 if packed else block_size
    cshape = (num_blocks, rows, Hkv, Dh) if quantized else (1, 1, 1, 1)
    cbshape = (num_blocks, num_values) if quantized else (1, 1)
    return PagedKVCache(
        k_fp=jnp.zeros((num_blocks, block_size, Hkv, Dh), dtype),
        v_fp=jnp.zeros((num_blocks, block_size, Hkv, Dh), dtype),
        k_codes=jnp.zeros(cshape, jnp.uint8),
        v_codes=jnp.zeros(cshape, jnp.uint8),
        k_cb=jnp.zeros(cbshape, jnp.float32),
        v_cb=jnp.zeros(cbshape, jnp.float32),
        blk_q=jnp.zeros((num_blocks if quantized else 1,), bool),
        block_table=jnp.zeros((batch, max_blocks), jnp.int32),
        seq_lens=jnp.zeros((batch,), jnp.int32),
        block_size=block_size, quantized=quantized, packed=packed,
        fused=fused, fused_window=fused_window,
    )


def init_paged_cache(cfg, *, num_blocks, block_size, batch, max_blocks,
                     quantized=False, num_values=16, fused=False,
                     fused_window=1):
    """Model-shaped cache tree mirroring ``transformer.init_lm_cache`` with
    PagedKVCache leaves (leading group axis on scanned groups)."""
    for spec in tuple(cfg.group) + tuple(cfg.head_layers):
        assert spec.mixer == "attn", (
            f"paged serving supports attention mixers only, got {spec.mixer}")
    dtype = cfg.dtype("compute")
    kw = dict(num_blocks=num_blocks, block_size=block_size, batch=batch,
              max_blocks=max_blocks, quantized=quantized,
              num_values=num_values, dtype=dtype, fused=fused,
              fused_window=fused_window)

    def stack(_spec):
        one = init_paged_layer(cfg, **kw)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_groups,) + a.shape).copy(),
            one)

    cache = {"groups": {f"l{i}": stack(s) for i, s in enumerate(cfg.group)}}
    for i, spec in enumerate(cfg.head_layers):
        cache[f"head_{i}"] = init_paged_layer(cfg, **kw)
    return cache


# ----------------------------------------------- tree-surgery helpers


def _is_leaf(x):
    return isinstance(x, PagedKVCache)


def map_layers(fn, tree):
    return jax.tree_util.tree_map(fn, tree, is_leaf=_is_leaf)


def with_tables(tree, block_table: np.ndarray, seq_lens: np.ndarray):
    """Install host-managed table/lens into every layer leaf (broadcast over
    the stacked group axis when present). The table may be narrower than
    ``max_blocks``: the engine clamps it to the blocks the longest live
    sequence actually needs, so short batches don't pay full-window reads."""
    bt = jnp.asarray(block_table, jnp.int32)
    sl = jnp.asarray(seq_lens, jnp.int32)

    def per(leaf: PagedKVCache):
        g = leaf.k_fp.ndim == 5            # stacked group axis present
        G = leaf.k_fp.shape[0] if g else None
        b = jnp.broadcast_to(bt, (G,) + bt.shape).copy() if g else bt
        s = jnp.broadcast_to(sl, (G,) + sl.shape).copy() if g else sl
        return dataclasses.replace(leaf, block_table=b, seq_lens=s)

    return map_layers(per, tree)


def with_prefill_fused(tree):
    """Flag every layer leaf so ``models.prefill`` routes chunk attention
    through the fused kernel (``fused_prefill``). Applied only to the
    chunked-prefill view of the tree — the default-False flag keeps every
    other jit cache key and golden trace unchanged."""
    return map_layers(
        lambda leaf: dataclasses.replace(leaf, prefill_fused=True), tree)


def merge_pools(held, returned):
    """Adopt jit-updated fp pools; keep host-managed quantization state and
    tables from ``held``."""
    return jax.tree_util.tree_map(
        lambda h, r: dataclasses.replace(h, k_fp=r.k_fp, v_fp=r.v_fp),
        held, returned, is_leaf=_is_leaf)


def freeze_markers(tree) -> list[jax.Array]:
    """One device array per layer whose readiness implies that layer's last
    freeze dispatch has completed (used by the engine's overlap counters)."""
    out = []
    map_layers(lambda leaf: out.append(leaf.k_cb), tree)
    return out


# ----------------------------------------------- spec resolution


def resolve_kv_spec(spec=None, *, method=None, num_values=None) -> QuantSpec:
    """Coerce the engine/freeze ``kv_quant`` argument to a validated
    QuantSpec.

    Accepts a QuantSpec, a compact spec string ("kmeans_ls@16",
    "iter_l1@16:seed=3"), or the legacy (method, num_values) pair —
    including the old "tv" alias, which maps to the exact-count ``tv_iter``
    (tv itself is lam-parameterised; freezing needs a count budget).
    Page freezing requires a count-parameterised method: anything else
    raises at construction, naming the registry's device-capable methods.
    """
    device = quant_registry.device_methods()
    host_only = sorted(set(quant_registry.count_methods()) - set(device))
    capable = (f"device-batched methods: {', '.join(device)}; count methods "
               f"with a per-page host fallback: {', '.join(host_only)}")
    try:
        if isinstance(spec, QuantSpec) or (
                isinstance(spec, str) and ("@" in spec or ":" in spec)):
            if num_values is not None or method is not None:
                raise TypeError(
                    f"got both a kv_quant spec ({spec!s}) and loose "
                    f"method=/num_values= arguments; fold them into the "
                    f"spec, e.g. 'kmeans_ls@{num_values or 16}'")
            out = QuantSpec.parse(spec)
        else:
            m = spec if isinstance(spec, str) else method
            if m is None:
                m = "kmeans_ls"
            m = {"tv": "tv_iter"}.get(m, m)
            out = QuantSpec(m, num_values=16 if num_values is None
                            else num_values)
    except ValueError as e:
        raise ValueError(f"bad kv_quant spec: {e}\npage freezing needs a "
                         f"count-parameterised method — {capable}") from None
    if out.param_kind != "count":
        raise ValueError(
            f"kv_quant spec {str(out)!r} is lam-parameterised; page "
            f"freezing needs a count budget (method@num_values) — {capable}")
    return out


# ----------------------------------------------- host-side quantization


def quantize_page(data: np.ndarray, spec, num_values: int | None = None):
    """Run the paper's solver on one page; returns (codes u8, codebook f32).

    Host fallback for methods without a batched device solver. ``spec`` is
    anything ``resolve_kv_spec`` accepts (legacy ``(method, num_values)``
    included). Pages always solve multiplicity-weighted: the page *is* the
    full vector being served.
    """
    from repro.core import quantize

    spec = resolve_kv_spec(spec, num_values=num_values)
    qt, _ = quantize(data.astype(np.float32), spec.replace(weighted=True))
    cb = np.asarray(qt.codebook, np.float32)
    codes = np.asarray(qt.indices, np.uint8).reshape(data.shape)
    if cb.shape[0] < spec.num_values:               # pad to the static width
        cb = np.concatenate([cb, np.full(spec.num_values - cb.shape[0],
                                         cb[-1], np.float32)])
    return codes, cb


#: count methods with a batched on-device solver (no host numpy per page);
#: declared per-method in core.registry
DEVICE_FREEZE_METHODS = quant_registry.device_methods()


def freeze_blocks(tree, block_ids, spec=None, *, method=None,
                  num_values=None, stats=None):
    """Quantize full pages ``block_ids`` in every attention layer and
    scatter codes/codebooks/flags back.

    ``spec`` is a QuantSpec / spec string (legacy ``method=``/
    ``num_values=`` kwargs still map). Methods with a registry
    ``device_batch`` entry (kmeans_ls, kmeans, iter_l1) batch every
    (page, group, k/v) row of the event through one async device dispatch
    per layer — the engine keeps decoding while it runs. Other count
    methods fall back to per-page host solves (``stats["host_page_solves"]``
    counts them, so serving tests can assert the steady state performs
    none).
    """
    if not len(block_ids):
        return tree
    spec = resolve_kv_spec(spec, method=method, num_values=num_values)
    bids = np.asarray(sorted(block_ids), np.int32)
    if spec.device_capable:
        # jax's dataflow still runs the solve async behind later dispatches
        return install_freeze(tree, dispatch_freeze(tree, bids, spec))
    return _freeze_blocks_host(tree, bids, spec, stats=stats)


@functools.partial(jax.jit, static_argnames=("spec",))
def _solve_leaf_pages(leaf: PagedKVCache, jb, *, spec: QuantSpec):
    """Gather pages ``jb`` from one layer leaf and solve their codebooks as
    a single jitted computation (one async dispatch per layer), keyed on
    the hashable spec. Returns (codes (2, G?, P, bs/2, Hkv, Dh) packed,
    cb (2, G?, P, L)) — k stacked over v on the leading axis — without
    touching the leaf."""
    solve = quant_registry.device_batch_solve(spec.method)
    stacked = leaf.k_fp.ndim == 5
    axis = 1 if stacked else 0
    kf = jnp.take(leaf.k_fp, jb, axis=axis)
    vf = jnp.take(leaf.v_fp, jb, axis=axis)
    both = jnp.stack([kf, vf])              # (2, G?, P, bs, Hkv, Dh)
    page_shape = both.shape[-3:]
    rows = both.reshape(-1, int(np.prod(page_shape)))
    codes, cb = solve(rows, spec)
    codes = codes.reshape(both.shape)
    cb = cb.reshape(both.shape[:-3] + (spec.num_values,))
    if leaf.packed:
        codes = pack4(codes)
    return codes, cb


@jax.jit
def _install_leaf(leaf: PagedKVCache, jb, keep, codes, cb):
    """Scatter one solve's outputs into a leaf's codes, codebooks and
    ``blk_q``, masked by ``keep`` (P,): dropped pages rewrite their current
    values and stay thawed. Returns only those leaves (``_CODE_LEAVES``),
    so the fp pools are neither written nor outputs of the program: a
    frozen page's value is its codes and codebook, and every read
    dequantizes them. One jit dispatch — eager scatter chains on
    still-computing operands can block the host."""
    stacked = leaf.k_fp.ndim == 5
    sel = (slice(None), jb) if stacked else (jb,)
    # align keep to the (G?, P, ...) result layout of _solve_leaf_pages
    kpage = keep[None, :, None, None, None] if stacked \
        else keep[:, None, None, None]
    kcb_m = keep[None, :, None] if stacked else keep[:, None]
    kc = jnp.where(kpage, codes[0], leaf.k_codes[sel])
    vc = jnp.where(kpage, codes[1], leaf.v_codes[sel])
    kcb = jnp.where(kcb_m, cb[0], leaf.k_cb[sel])
    vcb = jnp.where(kcb_m, cb[1], leaf.v_cb[sel])
    return dict(k_codes=leaf.k_codes.at[sel].set(kc),
                v_codes=leaf.v_codes.at[sel].set(vc),
                k_cb=leaf.k_cb.at[sel].set(kcb),
                v_cb=leaf.v_cb.at[sel].set(vcb),
                blk_q=leaf.blk_q.at[..., jb].max(keep))


class PendingFreeze:
    """Handle for an in-flight device freeze: the solver outputs (one
    (codes, cb) pair per layer leaf, still computing on device) and the
    page ids they target. ``drop`` forgets freed pages (they must not be
    installed over a reallocated page); it only flips a host-side mask, so
    it is free to call while the solve is still in flight."""

    def __init__(self, bids: np.ndarray, results: list):
        self.bids = np.asarray(bids, np.int32)
        self.keep = np.ones(self.bids.shape, bool)
        self.results = results

    def is_ready(self) -> bool:
        return all(cb.is_ready() for _, cb in self.results)

    def markers(self) -> list:
        return [cb for _, cb in self.results]

    def drop(self, freed_ids) -> None:
        self.keep &= ~np.isin(self.bids,
                              np.asarray(list(freed_ids), np.int32))

    def kept_pages(self) -> list[int]:
        """Distinct page ids an install will mark frozen — padding
        duplicates collapsed, dropped pages excluded. Sorted so callers
        (frozen-set updates, tracer span ends) iterate deterministically."""
        return sorted({int(b) for b in self.bids[self.keep]})


def dispatch_freeze(tree, block_ids, spec=None, *, num_values=None,
                    refit=True) -> PendingFreeze:
    """Start the batched device solve for ``block_ids`` in every layer;
    returns immediately with a PendingFreeze (the cache is unmodified).

    ``spec`` must name a device-capable method (legacy ``num_values=`` +
    ``refit=`` kwargs map to kmeans_ls / kmeans)."""
    if spec is None:
        spec = resolve_kv_spec(method="kmeans_ls" if refit else "kmeans",
                               num_values=num_values)
    else:
        spec = resolve_kv_spec(spec, num_values=num_values)
    # device solvers are deterministic — canonicalize the meaningless seed
    # so specs differing only there share one jit entry
    spec = spec.replace(seed=0)
    bids = np.asarray(sorted(block_ids), np.int32)
    jb = jnp.asarray(bids)
    results = []

    def per(leaf: PagedKVCache):
        assert leaf.quantized
        results.append(_solve_leaf_pages(leaf, jb, spec=spec))
        return leaf

    map_layers(per, tree)
    return PendingFreeze(bids, results)


def install_freeze(tree, pending: PendingFreeze):
    """Scatter a completed (or still-computing) freeze into the cache and
    flip ``blk_q``; from the next step the kept pages serve from codes.
    Stacked leaves broadcast ``keep``/``codes`` over the group axis inside
    ``_install_leaf`` via the (2, G, P, ...) result layout; each leaf keeps
    its fp pools as they were."""
    if not pending.keep.any():
        return tree
    jb = jnp.asarray(pending.bids)
    keep = jnp.asarray(pending.keep)
    it = iter(pending.results)

    def per(leaf: PagedKVCache):
        codes, cb = next(it)
        return dataclasses.replace(leaf, **_install_leaf(leaf, jb, keep,
                                                         codes, cb))

    return map_layers(per, tree)


def _freeze_blocks_host(tree, bids, spec: QuantSpec, *, stats=None):
    def per(leaf: PagedKVCache):
        assert leaf.quantized
        stacked = leaf.k_fp.ndim == 5
        groups = range(leaf.k_fp.shape[0]) if stacked else (None,)
        axis = 1 if stacked else 0
        # pull only the pages being frozen to host, not the whole pool
        jb = jnp.asarray(bids)
        kf = np.asarray(jnp.take(leaf.k_fp, jb, axis=axis))
        vf = np.asarray(jnp.take(leaf.v_fp, jb, axis=axis))
        kc, vc = leaf.k_codes, leaf.v_codes
        kcb, vcb = leaf.k_cb, leaf.v_cb
        for g in groups:
            sel = () if g is None else (g,)
            for pool, tag in ((kf, "k"), (vf, "v")):
                new_codes, new_cbs = [], []
                for bi in range(len(bids)):
                    codes, cb = quantize_page(pool[sel + (bi,)], spec)
                    if stats is not None:
                        stats["host_page_solves"] = (
                            stats.get("host_page_solves", 0) + 1)
                    if leaf.packed:
                        codes = _pack4(codes)
                    new_codes.append(codes)
                    new_cbs.append(cb)
                nc = jnp.asarray(np.stack(new_codes))
                ncb = jnp.asarray(np.stack(new_cbs))
                if tag == "k":
                    kc = kc.at[sel + (bids,)].set(nc)
                    kcb = kcb.at[sel + (bids,)].set(ncb)
                else:
                    vc = vc.at[sel + (bids,)].set(nc)
                    vcb = vcb.at[sel + (bids,)].set(ncb)
        blk_q = leaf.blk_q.at[..., bids].set(True)
        # like the device install, the fp rows are left as they are
        return dataclasses.replace(leaf, k_codes=kc, v_codes=vc, k_cb=kcb,
                                   v_cb=vcb, blk_q=blk_q)

    return map_layers(per, tree)


def thaw_blocks(tree, block_ids):
    """Clear the quantized flag for freed pages (reallocation starts fp)."""
    if not len(block_ids):
        return tree
    bids = np.asarray(sorted(block_ids), np.int32)

    def per(leaf: PagedKVCache):
        if not leaf.quantized:
            return leaf
        return dataclasses.replace(leaf,
                                   blk_q=leaf.blk_q.at[..., bids].set(False))

    return map_layers(per, tree)


# ----------------------------------------------- page-freeze lifecycle


class FreezeQueue:
    """The page-freeze lifecycle of one pool (module docstring): the bid
    queue, the in-flight solves, the installed set and its mask over block
    ids, the open ``page_freeze`` spans and the deferred-page watermark.
    ``counters`` is the worker's dict, where the freeze counters are
    registered; ``on_install`` runs whenever pages become installed."""

    def __init__(self, spec, *, budget: int, is_async: bool, num_blocks: int,
                 tracer, track: str, counters: dict, on_install):
        assert budget >= 1, "freeze budget must cover >= 1 page"
        self.spec, self.budget, self.is_async = spec, budget, is_async
        self.tracer, self.track = tracer, track
        self.counters, self.on_install = counters, on_install
        self.bids: list[int] = []          # queued for the next flush
        # (decode step dispatched at, solve), in dispatch order
        self.inflight: list[tuple[int, PendingFreeze]] = []
        self.installed: set[int] = set()   # pages serving codes
        self.mask = np.zeros(num_blocks, bool)
        # page id -> open page_freeze span id; page_offload spans draw
        # their ids from the same sequence (one per track)
        self._spans: dict[int, int] = {}
        self._span_seq = 0
        self._deferred_seen = 0    # queue suffix already counted deferred

    @property
    def idle(self) -> bool:
        """Nothing queued and nothing in flight."""
        return not (self.bids or self.inflight)

    def next_span_id(self) -> int:
        self._span_seq += 1
        return self._span_seq

    def bid(self, pages, slot: int) -> None:
        """Queue ``slot``'s ``pages`` for the next flush and open their
        spans. Bids dedupe on block id: an installed page, or one already
        bid (a shared page its owner bid), is skipped."""
        tr = self.tracer
        for b in pages:
            b = int(b)
            if b in self.installed or b in self.bids:
                continue
            self.bids.append(b)
            if tr.enabled:
                self._spans[b] = self.next_span_id()
                tr.async_begin(self.track, "page_freeze", self._spans[b],
                               page=b, slot=slot)

    def mark_installed(self, pages) -> None:
        """Record ``pages`` as serving codes and end their open spans
        ``installed`` (pages that arrive frozen have none)."""
        pages = sorted({int(b) for b in pages})
        self.installed.update(pages)
        self.mask[pages] = True
        if self.tracer.enabled:
            for b in pages:
                sid = self._spans.pop(b, None)
                if sid is not None:
                    self.tracer.async_end(self.track, "page_freeze", sid,
                                          state="installed", page=b)

    def install_now(self, tree, pages):
        """Solve and install ``pages`` synchronously."""
        tree = freeze_blocks(tree, pages, self.spec, stats=self.counters)
        self.mark_installed(pages)
        return tree

    def flush(self, tree):
        """One batched solve for the queue's first ``budget`` pages: async
        mode dispatches it, sync mode also installs it. The budget is the
        backpressure valve — a prefill burst's pages spread over later
        iterations, serving exact fp meanwhile — and
        ``freeze_deferred_pages`` counts each page it defers once."""
        if not self.bids:
            return tree
        tr, c = self.tracer, self.counters
        take = min(len(self.bids), self.budget)
        with phase(tr, self.track, "flush", c, "freeze_flush_ns",
                   pages=take, mode="async" if self.is_async else "sync"):
            bids, self.bids = self.bids[:take], self.bids[take:]
            if tr.enabled:
                for b in bids:
                    sid = self._spans.get(b)
                    if sid is not None:
                        tr.async_instant(self.track, "page_freeze", sid,
                                         state="dispatched")
            # the flush took the oldest pages, hence any already counted,
            # off the front: shrink the counted watermark by ``take``, then
            # count what remains beyond it as newly deferred
            self._deferred_seen = max(self._deferred_seen - take, 0)
            c["freeze_deferred_pages"] += max(
                len(self.bids) - self._deferred_seen, 0)
            self._deferred_seen = len(self.bids)
            if self.spec.device_capable:
                # pad to a power-of-two page count (repeating one page is a
                # no-op at install) so the jitted solver compiles a handful
                # of shapes instead of one per distinct flush size; the host
                # fallback solves per page, where a duplicate is pure waste
                bucket = 1 << (len(bids) - 1).bit_length()
                bids = bids + [bids[-1]] * (bucket - len(bids))
            if self.is_async:
                self.inflight.append((c["decode_steps"],
                                      dispatch_freeze(tree, bids, self.spec)))
                c["freeze_pending_max"] = max(c["freeze_pending_max"],
                                              len(self.inflight))
            else:
                with phase(tr, self.track, "install",
                           pages=len(set(bids)), wait_steps=0):
                    tree = self.install_now(tree, bids)
                self.on_install()
                c["freeze_installs"] += 1
            c["freeze_dispatches"] += 1
        return tree

    def poll(self, tree, drain: bool = False):
        """Install the solves that landed; count the ones still overlapping
        this decode step. ``drain=True`` blocks on the rest (end of run)."""
        still, c = [], self.counters
        for step0, pending in self.inflight:
            if drain and not pending.is_ready():
                # lint: sync(drain-only: end-of-run flush blocks by design)
                jax.block_until_ready(pending.markers())
            if pending.is_ready():
                wait = c["decode_steps"] - step0
                with phase(self.tracer, self.track, "install", c,
                           "freeze_install_ns", wait_steps=wait) as p:
                    tree = install_freeze(tree, pending)
                    kept = pending.kept_pages()
                    c["freeze_installs"] += 1
                    c["freeze_overlap_steps"] += wait
                    p.args["pages"] = len(kept)
                    self.mark_installed(kept)
            else:
                c["freeze_inflight_steps"] += 1
                still.append((step0, pending))
        landed = len(still) < len(self.inflight)
        self.inflight = still
        if landed:
            self.on_install()
        return tree

    def release(self, pages: set, state: str) -> None:
        """Forget ``pages``: end their open spans in ``state``, take them
        off the queue and out of in-flight solves (a landing solve leaves
        them thawed). Freed pages (``dropped``, ``offloaded``) leave the
        installed set; ``rolled_back`` pages lie past a slot's accepted
        length, where nothing is installed, and their marks are left."""
        assert state in ("dropped", "rolled_back", "offloaded"), state
        tr = self.tracer
        if tr.enabled:
            # a literal state= per branch keeps the lifecycle statically
            # checkable (repro.analysis span pass)
            for b in sorted(pages):
                sid = self._spans.pop(b, None)
                if sid is None:
                    continue
                if state == "offloaded":
                    tr.async_end(self.track, "page_freeze", sid,
                                 state="offloaded", page=b)
                elif state == "rolled_back":
                    tr.async_end(self.track, "page_freeze", sid,
                                 state="rolled_back", page=b)
                else:
                    tr.async_end(self.track, "page_freeze", sid,
                                 state="dropped", page=b)
        self.bids = [b for b in self.bids if b not in pages]
        self._deferred_seen = min(self._deferred_seen, len(self.bids))
        if state != "rolled_back":
            self.installed -= pages
            self.mask[list(pages)] = False
        for _, pending in self.inflight:
            pending.drop(pages)


# ----------------------------------------------- footprint accounting


def page_bytes(cfg, block_size: int, *, quantized: bool, num_values: int,
               n_layers_attn: int | None = None) -> dict:
    """Bytes one page costs across all attention layers, fp vs frozen."""
    n_attn = (n_layers_attn if n_layers_attn is not None
              else sum(1 for s in (tuple(cfg.head_layers)
                                   + tuple(cfg.group) * cfg.n_groups)
                       if s.mixer == "attn"))
    elems = block_size * cfg.n_kv_heads * cfg.head_dim
    fp = 2 * elems * cfg.dtype("compute").itemsize          # k and v
    if not quantized:
        return {"fp": n_attn * fp, "frozen": n_attn * fp, "n_attn": n_attn}
    bits = 4 if num_values <= 16 else 8
    frozen = 2 * ((elems * bits + 7) // 8 + num_values * 4)
    return {"fp": n_attn * fp, "frozen": n_attn * frozen, "n_attn": n_attn}
