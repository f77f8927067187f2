"""Continuous-batching serving subsystem tests: deterministic scheduler
simulation, paged-allocator invariants, paged-cache round-trip vs the dense
ring cache, and quantized-KV numerics."""
import dataclasses
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import models
from repro.configs import get_reduced_config
from repro.serving import (BlockAllocator, ContinuousBatchingEngine,
                           ContinuousBatchingScheduler, DoubleFree,
                           PrefixIndex, Request, freeze_blocks,
                           freeze_markers, thaw_blocks)
from repro.serving.kv_cache import (_pack4, _unpack4, init_paged_layer,
                                    quantize_page)

pytestmark = pytest.mark.serving


# ------------------------------------------------------------- scheduler


def _simulate(sched, free_blocks):
    """Drive the scheduler like the engine does (prefill emits token #1,
    one decode step per iteration); returns the exact iteration schedule."""
    log = []
    free = free_blocks
    guard = 0
    while sched.has_work:
        admitted = sched.schedule(free)
        for st in admitted:
            free -= sched.blocks_for(st.req)
            st.length = st.req.prompt_len
            st.generated = 1                       # prefill's first token
        finished = sched.step_decoded()
        for st in finished:
            free += sched.blocks_for(st.req)
            sched.release(st)
        log.append((sorted(st.req.id for st in admitted),
                    sorted(st.req.id for st in finished)))
        guard += 1
        assert guard < 100, "scheduler did not converge"
    return log


def test_scheduler_exact_schedule():
    """Arrival trace in -> exact admission/eviction schedule out."""
    sched = ContinuousBatchingScheduler(max_slots=2, block_size=4,
                                        max_queue=8)
    for i in range(4):
        # 8 prompt + 4 new = 12 tokens = 3 blocks each
        assert sched.submit(Request(id=i, prompt=(1,) * 8, max_new_tokens=4))
    log = _simulate(sched, free_blocks=6)
    # 2 slots, 6 pages: r0+r1 run together; r2+r3 wait for both to evict
    assert log == [
        ([0, 1], []), ([], []), ([], [0, 1]),
        ([2, 3], []), ([], []), ([], [2, 3]),
    ]


def test_scheduler_page_budget_limits_admission():
    """Only one request fits the page budget; the second joins mid-flight
    as soon as pages free up (iteration-level batching)."""
    sched = ContinuousBatchingScheduler(max_slots=2, block_size=4,
                                        max_queue=8)
    for i in range(2):
        sched.submit(Request(id=i, prompt=(1,) * 8, max_new_tokens=4))
    log = _simulate(sched, free_blocks=3)
    assert log == [
        ([0], []), ([], []), ([], [0]),
        ([1], []), ([], []), ([], [1]),
    ]


def test_scheduler_queue_admission_control():
    sched = ContinuousBatchingScheduler(max_slots=1, block_size=4,
                                        max_queue=1)
    assert sched.submit(Request(id=0, prompt=(1,), max_new_tokens=1))
    assert not sched.submit(Request(id=1, prompt=(1,), max_new_tokens=1))
    assert sched.rejected == [1]


# ------------------------------------------------------------- allocator


def test_allocator_invariants():
    alloc = BlockAllocator(8)            # block 0 reserved -> 7 allocatable
    assert alloc.num_free == 7
    a = alloc.alloc(3)
    b = alloc.alloc(2)
    assert len(set(a) | set(b)) == 5 and 0 not in a + b
    with pytest.raises(MemoryError):
        alloc.alloc(3)
    alloc.free(a)
    with pytest.raises(ValueError):      # double free
        alloc.free(a)
    with pytest.raises(ValueError):      # foreign block
        alloc.free([0])
    assert alloc.num_free == 5
    c = alloc.alloc(5)
    assert 0 not in c


def test_allocator_refcounts_and_typed_double_free():
    alloc = BlockAllocator(8)
    a = alloc.alloc(3)
    alloc.retain(a[:2])                   # a second table splices two pages
    assert [alloc.refcount(b) for b in a] == [2, 2, 1]
    released = alloc.free(a)              # first table detaches
    assert released == [a[2]], "shared pages must survive a ref drop"
    assert alloc.num_free == 5
    with pytest.raises(DoubleFree) as ei:
        alloc.free([a[2]])                # rc already hit zero
    assert ei.value.block == a[2]
    assert isinstance(ei.value, ValueError)   # callers catching ValueError
    with pytest.raises(ValueError):
        alloc.retain([a[2]])              # retain needs a live block
    assert alloc.refcount(a[2]) == 0
    released = alloc.free(a[:2])          # last references drop together
    assert sorted(released) == sorted(a[:2])
    assert alloc.num_free == 7


def test_prefix_index_chain_lookup_and_invalidate():
    idx = PrefixIndex(4)
    toks = list(range(12))                # 3 full pages at block size 4
    assert idx.publish(toks, [1, 2, 3], None) == 3
    assert len(idx) == 3
    assert idx.lookup(toks, 3) == [1, 2, 3]
    assert idx.lookup(toks, 2) == [1, 2]             # caller's CoW cap
    assert idx.lookup(toks[:8] + [99] * 4, 3) == [1, 2]   # tail diverges
    assert idx.lookup([99] + toks[1:], 3) == []      # first page differs
    assert idx.lookup(toks[:7], 3) == [1]            # partial page ignored
    # a chain must be contiguous from the root: frozen gating stops it
    gated = PrefixIndex(4)
    assert gated.publish(toks, [4, 5, 6], frozen={4, 6}) == 1
    assert gated.lookup(toks, 3) == [4]
    # idempotent + first-publisher-wins: duplicates add nothing
    assert idx.publish(toks, [7, 8, 9], None) == 0
    assert idx.lookup(toks, 3) == [1, 2, 3]
    idx.invalidate([2])                   # page 2's last ref dropped
    assert idx.lookup(toks, 3) == [1], "chain must break at a dead page"
    idx.invalidate([1, 3])
    assert len(idx) == 0


# ------------------------------------------------------------- paged cache


def _mini_cfg():
    return get_reduced_config("qwen3_0_6b")


def test_paged_layer_roundtrip_matches_dense():
    """Block-table scatter/gather == a dense (B, L, H, D) cache."""
    cfg = _mini_cfg()
    bs, mb, B, S = 4, 3, 2, 4
    Hkv, Dh = cfg.n_kv_heads, cfg.head_dim
    leaf = init_paged_layer(cfg, num_blocks=8, block_size=bs, batch=B,
                            max_blocks=mb, quantized=False, num_values=16,
                            dtype=jnp.float32)
    table = np.zeros((B, mb), np.int32)
    table[0] = [3, 1, 2]
    table[1] = [5, 4, 0]
    lens = np.array([1, 2], np.int32)
    leaf = dataclasses.replace(leaf, block_table=jnp.asarray(table),
                               seq_lens=jnp.asarray(lens))
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, Dh)), jnp.float32)
    new, k_all, v_all, q_off, valid = leaf.update(k, v, 0)
    assert np.array_equal(np.asarray(q_off), lens)
    assert np.array_equal(np.asarray(valid), lens + S)
    dense = np.zeros((B, mb * bs, Hkv, Dh), np.float32)
    for b in range(B):
        dense[b, lens[b]:lens[b] + S] = np.asarray(k[b])
    for b in range(B):
        np.testing.assert_allclose(np.asarray(k_all)[b, lens[b]:lens[b] + S],
                                   dense[b, lens[b]:lens[b] + S])
    # a second write continues where the first stopped
    new = dataclasses.replace(new, seq_lens=new.seq_lens + S)
    k2 = jnp.asarray(rng.normal(size=(B, 1, Hkv, Dh)), jnp.float32)
    _, k_all2, _, _, _ = new.update(k2, k2, 0)
    for b in range(B):
        np.testing.assert_allclose(
            np.asarray(k_all2)[b, lens[b]:lens[b] + S],
            np.asarray(k_all)[b, lens[b]:lens[b] + S])
        np.testing.assert_allclose(np.asarray(k_all2)[b, lens[b] + S],
                                   np.asarray(k2)[b, 0])


@pytest.mark.parametrize("bs", [6, 8, 32, 62])   # odd and even packed rows
def test_pack4_roundtrip(bs):
    """np pack -> jnp unpack and jnp pack -> jnp unpack are exact inverses
    for every 4-bit code value, at odd/even packed row counts (bs/2 token
    rows per page; the lane dim keeps its full width)."""
    from repro.kernels import pack4, unpack4

    rng = np.random.default_rng(0)
    codes = rng.integers(0, 16, (5, bs, 2, 8)).astype(np.uint8)
    # every code value in both nibble positions
    codes[0, :bs // 2, 0, 0] = np.arange(bs // 2) % 16
    codes[0, bs // 2:, 0, 0] = 15 - (np.arange(bs // 2) % 16)
    packed = _pack4(codes)
    assert packed.shape == (5, bs // 2, 2, 8)
    np.testing.assert_array_equal(np.asarray(_unpack4(jnp.asarray(packed))),
                                  codes)
    # device pack agrees with the host pack bit-for-bit
    np.testing.assert_array_equal(np.asarray(pack4(jnp.asarray(codes))),
                                  packed)
    np.testing.assert_array_equal(
        np.asarray(unpack4(pack4(jnp.asarray(codes)))), codes)


def _read_pages(leaf, pages):
    """The gather read (``PagedKVCache._gather``) of one table row
    ``pages`` from a flat or group-stacked leaf: k and v, each
    (G?, len(pages) * bs, Hkv, Dh)."""
    table = jnp.asarray([pages], jnp.int32)

    def one(layer):
        layer = dataclasses.replace(layer, block_table=table)
        return [np.asarray(layer._gather(fp, c, cb))[0] for fp, c, cb in (
            (layer.k_fp, layer.k_codes, layer.k_cb),
            (layer.v_fp, layer.v_codes, layer.v_cb))]

    if leaf.k_fp.ndim == 4:
        return one(leaf)
    groups = [one(jax.tree.map(lambda a, g=g: a[g], leaf))
              for g in range(leaf.k_fp.shape[0])]
    return [np.stack([kv[t] for kv in groups]) for t in range(2)]


def test_all_16_codes_dequantize_exactly():
    """Installing a freeze whose codes sweep all 16 values leaves the fp
    rows alone, stores the codes and codebook, and the gather read serves
    exactly cb[codes] (the packed install/gather path)."""
    from repro.serving.kv_cache import PendingFreeze, install_freeze

    cfg = _mini_cfg()
    bs = 4
    Hkv, Dh = cfg.n_kv_heads, cfg.head_dim
    leaf = init_paged_layer(cfg, num_blocks=3, block_size=bs, batch=1,
                            max_blocks=1, quantized=True, num_values=16,
                            dtype=jnp.float32)
    rng = np.random.default_rng(1)
    leaf = dataclasses.replace(
        leaf, k_fp=jnp.asarray(rng.normal(size=leaf.k_fp.shape), jnp.float32),
        v_fp=jnp.asarray(rng.normal(size=leaf.v_fp.shape), jnp.float32))
    codes = (np.arange(bs * Hkv * Dh) % 16).astype(np.uint8).reshape(
        bs, Hkv, Dh)
    cb = np.linspace(-2.0, 2.0, 16).astype(np.float32)
    packed = jnp.asarray(_pack4(codes))[None]          # (P=1, bs/2, H, Dh)
    cbj = jnp.asarray(cb)[None]                           # (P=1, L)
    pending = PendingFreeze(np.asarray([1], np.int32),
                            [(jnp.stack([packed, packed]),
                              jnp.stack([cbj, cbj]))])
    got = install_freeze(dataclasses.replace(
        leaf, block_table=jnp.asarray([[1]], np.int32),
        seq_lens=jnp.asarray([bs], np.int32)), pending)
    for f in ("k_fp", "v_fp"):                # the fp rows are not written
        np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                      np.asarray(getattr(leaf, f)))
    np.testing.assert_array_equal(np.asarray(got.k_codes)[1], packed[0])
    np.testing.assert_array_equal(np.asarray(got.k_cb)[1], cb)
    assert np.asarray(got.blk_q)[1]
    k_all = got._gather(got.k_fp, got.k_codes, got.k_cb)
    np.testing.assert_array_equal(np.asarray(k_all)[0], cb[codes])


def _install_leaf_ref(leaf, jb, keep, codes, cb):
    """The install, in numpy: kept pages' codes, codebooks and flags are
    scattered and their fp rows left alone. Also returns each bucket
    page's reconstruction, every element looked up in its page's codebook
    with take_along_axis and cast to the pool dtype: (2, G?, P, bs, H, D)."""
    from repro.serving.kv_cache import PagedKVCache

    stacked = leaf.k_fp.ndim == 5
    out = {f: np.array(getattr(leaf, f)) for f in PagedKVCache._POOL_LEAVES}
    deqs = []
    for t, tag in enumerate("kv"):
        c, cbt = np.asarray(codes[t]), np.asarray(cb[t])
        idx = (np.concatenate([c & 0xF, c >> 4], axis=-3) if leaf.packed
               else c).astype(np.int64)
        cbb = np.broadcast_to(cbt[..., None, None, :],
                              idx.shape[:-1] + cbt.shape[-1:])
        deqs.append(np.take_along_axis(cbb, idx, axis=-1).astype(
            out[f"{tag}_fp"].dtype))
        for p, b in enumerate(np.asarray(jb)):
            if not keep[p]:
                continue
            at = (slice(None), b) if stacked else (b,)
            src = (slice(None), p) if stacked else (p,)
            out[f"{tag}_codes"][at] = c[src]
            out[f"{tag}_cb"][at] = cbt[src]
            out["blk_q"][at] = True
    return out, np.stack(deqs)


@pytest.mark.parametrize("stacked", [False, True], ids=["flat", "stacked"])
@pytest.mark.parametrize("L", [16, 32, 256],
                         ids=["packed16", "unpacked32", "unpacked256"])
def test_install_leaf_matches_gather_bitwise(L, stacked):
    """_install_leaf writes the codes, codebooks and flags exactly as the
    numpy install does and returns no fp pool (``install_freeze`` keeps
    the leaf's fp rows byte for byte), and the gather read of every
    installed page equals the per-element take_along_axis reconstruction:
    packed and unpacked codebooks, flat and group-stacked leaves, a
    dropped page, and a bucket padded with a duplicate of its last page."""
    from repro.serving.kv_cache import (PagedKVCache, PendingFreeze,
                                        _install_leaf, install_freeze)

    cfg = _mini_cfg()
    bs, nb, G = 4, 6, 3
    leaf = init_paged_layer(cfg, num_blocks=nb, block_size=bs, batch=1,
                            max_blocks=2, quantized=True, num_values=L,
                            dtype=jnp.bfloat16)
    if stacked:
        leaf = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (G,) + a.shape).copy(), leaf)
    rng = np.random.default_rng(L + stacked)
    lead = leaf.k_fp.shape[:-4]                 # (G,) or ()

    def rand(a):                                 # a non-trivial pool state
        if a.dtype == jnp.uint8:
            return jnp.asarray(rng.integers(0, 256, a.shape), jnp.uint8)
        if a.dtype == jnp.bool_:
            return jnp.asarray(rng.random(a.shape) < 0.5)
        return jnp.asarray(rng.normal(size=a.shape), a.dtype)

    leaf = dataclasses.replace(leaf, **{
        f: rand(getattr(leaf, f)) for f in PagedKVCache._POOL_LEAVES})
    jb = jnp.asarray([1, 2, 4, 4], jnp.int32)   # padded bucket of 4
    keep = np.array([True, False, True, True])  # page 2 was dropped
    P = jb.shape[0]
    rows = leaf.k_codes.shape[-3]
    full = rng.integers(0, L, (2,) + lead + (P, bs) + leaf.k_fp.shape[-2:])
    page = full.shape[-3:]
    full[..., 0, :, :, :] = np.arange(np.prod(page)).reshape(page) % L
    codes = _pack4(full.astype(np.uint8)) if leaf.packed \
        else full.astype(np.uint8)
    assert codes.shape[-3] == rows and leaf.packed == (L <= 16)
    cb = rng.normal(size=(2,) + lead + (P, L)).astype(np.float32)
    codes[..., 3, :, :, :] = codes[..., 2, :, :, :]   # duplicate solves alike
    cb[..., 3, :] = cb[..., 2, :]

    out = _install_leaf(leaf, jb, jnp.asarray(keep), jnp.asarray(codes),
                        jnp.asarray(cb))
    assert sorted(out) == sorted(PagedKVCache._CODE_LEAVES)
    pending = PendingFreeze(np.asarray(jb),
                            [(jnp.asarray(codes), jnp.asarray(cb))])
    pending.keep = keep
    got = install_freeze(leaf, pending)
    want, deq = _install_leaf_ref(leaf, jb, keep, codes, cb)
    for f in PagedKVCache._POOL_LEAVES:
        g = np.asarray(getattr(got, f))
        assert g.dtype == want[f].dtype and g.shape == want[f].shape, f
        np.testing.assert_array_equal(g.view(np.uint8),
                                      want[f].view(np.uint8), err_msg=f)
        if f in out:
            np.testing.assert_array_equal(np.asarray(out[f]).view(np.uint8),
                                          want[f].view(np.uint8), err_msg=f)
    # the gather read of the installed pages, 1 and 4 (bucket slots 0, 2)
    for t, read in enumerate(_read_pages(got, [1, 4])):
        sel = (slice(None), [0, 2]) if stacked else ([0, 2],)
        ref = deq[t][sel].reshape(read.shape)
        np.testing.assert_array_equal(read.view(np.uint8),
                                      ref.view(np.uint8))


def test_gather_reads_fp_unless_frozen():
    """A table holding no frozen page reads its fp rows exactly; one
    holding frozen pages reads what the oracle's expansion reads
    (``kernels.ref.ref_paged_pages``, a per-element gather), live pages
    as fp and frozen ones as cb[codes], bit for bit."""
    from repro.kernels.ref import ref_paged_pages

    cfg = _mini_cfg()
    bs = 4
    leaf = init_paged_layer(cfg, num_blocks=7, block_size=bs, batch=2,
                            max_blocks=3, quantized=True, num_values=16,
                            dtype=jnp.bfloat16)
    rng = np.random.default_rng(11)
    leaf = dataclasses.replace(
        leaf, k_fp=jnp.asarray(rng.normal(size=leaf.k_fp.shape), jnp.bfloat16),
        v_fp=jnp.asarray(rng.normal(size=leaf.v_fp.shape), jnp.bfloat16))
    leaf = freeze_blocks(leaf, [1, 4])
    assert np.asarray(leaf.blk_q)[[1, 4]].all()
    for table, any_frozen in (([[2, 3, 5], [6, 5, 0]], False),
                              ([[1, 2, 4], [3, 4, 0]], True)):
        t = jnp.asarray(table, jnp.int32)
        cur = dataclasses.replace(leaf, block_table=t)
        for fp, c, cb in ((cur.k_fp, cur.k_codes, cur.k_cb),
                          (cur.v_fp, cur.v_codes, cur.v_cb)):
            got = np.asarray(cur._gather(fp, c, cb))
            want = np.asarray(ref_paged_pages(fp, c, cb, cur.blk_q, t,
                                              quantized=True, packed=True))
            np.testing.assert_array_equal(got.view(np.uint16),
                                          want.view(np.uint16))
            plain = np.asarray(fp)[np.asarray(table)].reshape(got.shape)
            assert np.array_equal(got.view(np.uint16),
                                  plain.view(np.uint16)) != any_frozen


def test_null_page_write_masking():
    """Idle slots (table all-null) write into block 0; live pages stay
    untouched."""
    cfg = _mini_cfg()
    bs, mb = 4, 2
    Hkv, Dh = cfg.n_kv_heads, cfg.head_dim
    leaf = init_paged_layer(cfg, num_blocks=4, block_size=bs, batch=2,
                            max_blocks=mb, quantized=False, num_values=16,
                            dtype=jnp.float32)
    rng = np.random.default_rng(3)
    k_fp0 = jnp.asarray(rng.normal(size=leaf.k_fp.shape), jnp.float32)
    leaf = dataclasses.replace(
        leaf, k_fp=k_fp0, v_fp=k_fp0,
        block_table=jnp.asarray([[1, 2], [0, 0]], np.int32),  # slot 1 idle
        seq_lens=jnp.asarray([2, 0], np.int32))
    k = jnp.asarray(rng.normal(size=(2, 1, Hkv, Dh)), jnp.float32)
    new, *_ = leaf.update(k, k, 0)
    got = np.asarray(new.k_fp)
    want = np.asarray(k_fp0).copy()
    want[1, 2] = np.asarray(k)[0, 0]          # live slot's write
    want[0, 0] = np.asarray(k)[1, 0]          # idle slot -> null page trash
    np.testing.assert_allclose(got, want)
    # every non-null page except the live write position is untouched
    np.testing.assert_allclose(got[3], np.asarray(k_fp0)[3])


def test_freeze_thaw_dequantizes_within_tolerance():
    cfg = _mini_cfg()
    bs = 4
    leaf = init_paged_layer(cfg, num_blocks=4, block_size=bs, batch=1,
                            max_blocks=2, quantized=True, num_values=16,
                            dtype=jnp.float32)
    rng = np.random.default_rng(0)
    kd = rng.normal(size=leaf.k_fp.shape).astype(np.float32)
    leaf = dataclasses.replace(
        leaf, k_fp=jnp.asarray(kd), v_fp=jnp.asarray(kd * 0.5),
        block_table=jnp.asarray([[1, 2]], np.int32),
        seq_lens=jnp.asarray([2 * bs], np.int32))
    frozen = freeze_blocks(leaf, [1, 2], method="kmeans_ls", num_values=16)
    # the freeze writes codes and codebooks only: the fp rows keep the
    # exact pre-freeze values
    np.testing.assert_array_equal(np.asarray(frozen.k_fp), kd)
    np.testing.assert_array_equal(np.asarray(frozen.v_fp), kd * 0.5)
    k_all = frozen._gather(frozen.k_fp, frozen.k_codes, frozen.k_cb)
    ref = np.concatenate([kd[1], kd[2]], axis=0)
    err = np.abs(np.asarray(k_all)[0] - ref)
    rms = np.sqrt((err ** 2).mean()) / np.sqrt((ref ** 2).mean())
    assert rms < 0.25, rms               # 16 shared values per page
    # the gather path serves exactly the codebook reconstruction (it
    # dequantizes the frozen pages' codes as it reads them)
    recon = np.take_along_axis(
        np.asarray(frozen.k_cb)[[1, 2]],
        np.asarray(_unpack4(frozen.k_codes[np.asarray([1, 2])])
                   ).reshape(2, -1), axis=-1).reshape(2, bs, *kd.shape[2:])
    np.testing.assert_array_equal(np.asarray(k_all)[0],
                                  recon.reshape(2 * bs, *kd.shape[2:]))
    # thaw: the flag clears and the pages read their fp rows again, which
    # still hold the exact pre-freeze values until the reallocated page is
    # overwritten by its next sequence
    thawed = thaw_blocks(frozen, [1, 2])
    assert not np.asarray(thawed.blk_q)[[1, 2]].any()
    k_fp = thawed._gather(thawed.k_fp, thawed.k_codes, thawed.k_cb)
    np.testing.assert_array_equal(np.asarray(k_fp)[0], ref)


def test_quantize_page_tv_method():
    data = np.random.default_rng(0).normal(size=(4, 2, 8)).astype(np.float32)
    codes, cb = quantize_page(data, "tv", 8)
    assert codes.shape == data.shape and cb.shape == (8,)
    err = np.abs(cb[codes] - data).mean()
    assert err < np.abs(data).mean()


# ------------------------------------------------------------- engine


@pytest.fixture(scope="module")
def qwen_reduced():
    cfg = get_reduced_config("qwen3_0_6b")
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _dense_reference(cfg, params, prompt, gen):
    P = len(prompt)
    toks = jnp.asarray([prompt], jnp.int32)
    cache = models.init_cache(cfg, 1, P + gen)
    logits, cache = models.prefill(params, cfg, {"tokens": toks}, cache)
    out = [int(jnp.argmax(logits[0, -1]))]
    lg = [np.asarray(logits[0, -1])]
    tok = jnp.asarray([[out[-1]]], jnp.int32)
    for i in range(gen - 1):
        logits, cache = models.decode_step(params, cfg, tok, cache,
                                           jnp.int32(P + i))
        out.append(int(jnp.argmax(logits[0, -1])))
        lg.append(np.asarray(logits[0, -1]))
        tok = jnp.asarray([[out[-1]]], jnp.int32)
    return out, np.stack(lg)


def test_paged_engine_matches_dense_cache(qwen_reduced):
    """Continuous-batching over the paged fp cache reproduces the dense
    ring-cache generation exactly (same argmax tokens, logits to 1e-3)."""
    cfg, params = qwen_reduced
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, 12).tolist() for _ in range(3)]
    gen = 6
    eng = ContinuousBatchingEngine(params, cfg, max_slots=2, block_size=8,
                                   max_seq_len=32, record_logits=True)
    out = eng.generate(prompts, max_new_tokens=gen)
    for i, p in enumerate(prompts):
        ref, ref_logits = _dense_reference(cfg, params, p, gen)
        assert out[i] == ref, f"request {i} diverged"
        np.testing.assert_allclose(eng.request_logits[i], ref_logits,
                                   atol=1e-3, rtol=0)
    s = eng.metrics.summary()
    assert s["completed"] == 3 and s["gen_tokens"] == 18
    # all pages recycled
    assert eng.alloc.num_free == eng.num_blocks - 1


def test_quantized_kv_within_tolerance(qwen_reduced):
    """Codebook-quantized pages track the fp paged cache within the
    documented tolerance (abs<=2.5, rel<=8% at 16 values/page). kv_quant
    is given as a QuantSpec string (the legacy method+kv_num_values pair is
    covered elsewhere)."""
    cfg, params = qwen_reduced
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, 16).tolist() for _ in range(2)]
    gen = 6
    runs = {}
    for kvq in (None, "kmeans_ls@16"):
        eng = ContinuousBatchingEngine(params, cfg, max_slots=2, block_size=8,
                                       max_seq_len=32, kv_quant=kvq,
                                       record_logits=True)
        eng.generate(prompts, max_new_tokens=gen)
        runs[kvq] = eng
    fp, q = runs[None], runs["kmeans_ls@16"]
    assert q.kv_quant == "kmeans_ls" and q.kv_num_values == 16
    for i in range(len(prompts)):
        d = np.abs(fp.request_logits[i] - q.request_logits[i])
        scale = np.abs(fp.request_logits[i]).max()
        assert d.max() <= 2.5, d.max()
        assert d.max() / scale <= 0.08, (d.max(), scale)
    s = q.metrics.summary()
    # frozen pages store 4-bit codes + codebook: >= 3x smaller than fp pages
    assert fp._pb["fp"] / q._pb["frozen"] >= 3.0
    assert s.get("cache_compression_final", 0.0) > 1.0


def test_engine_serves_quantized_weight_tree(qwen_reduced):
    """PTQ'd params (QuantizedTensor leaves, stacked per-group codebooks)
    serve through qmatmul's fused dequant path without densifying, matching
    the dequantized-dense reference exactly."""
    from repro.quant.ptq import dequantize_tree, quantize_tree

    cfg, params = qwen_reduced
    qtree, report = quantize_tree(
        params, method="kmeans_ls", num_values=16, weighted=True,
        skip_patterns=("ln", "norm", "router", "A_log", "mix", "dt_bias",
                       "D_skip", "w0", "embed", "lm_head"))
    assert any(r["bytes"] < r["dense_bytes"] for r in report.values())
    prompt = np.random.default_rng(2).integers(0, cfg.vocab, 8).tolist()
    out = {}
    for tag, p in (("q", qtree), ("d", dequantize_tree(qtree))):
        eng = ContinuousBatchingEngine(p, cfg, max_slots=1, block_size=8,
                                       max_seq_len=16, record_logits=True)
        eng.generate([prompt], max_new_tokens=4)
        out[tag] = eng
    np.testing.assert_allclose(out["q"].request_logits[0],
                               out["d"].request_logits[0], atol=1e-3, rtol=0)
    assert out["q"].outputs[0] == out["d"].outputs[0]


def test_fused_decode_matches_gather_reference():
    """Pallas flash-decode (interpret) == _gather + masked sdpa on mixed
    frozen/hot pages with per-sequence lengths."""
    from repro.kernels import ref_paged_decode
    from repro.models.attention import sdpa

    cfg = _mini_cfg()
    bs, mb, B = 8, 3, 2
    Hkv, Dh, Hq = cfg.n_kv_heads, cfg.head_dim, cfg.n_heads
    leaf = init_paged_layer(cfg, num_blocks=8, block_size=bs, batch=B,
                            max_blocks=mb, quantized=True, num_values=16,
                            dtype=jnp.float32, fused=True)
    rng = np.random.default_rng(0)
    leaf = dataclasses.replace(
        leaf,
        k_fp=jnp.asarray(rng.normal(size=leaf.k_fp.shape), jnp.float32),
        v_fp=jnp.asarray(rng.normal(size=leaf.v_fp.shape), jnp.float32),
        block_table=jnp.asarray([[3, 1, 2], [5, 4, 0]], np.int32),
        seq_lens=jnp.asarray([17, 9], np.int32))
    leaf = freeze_blocks(leaf, [3, 1, 5])          # hot pages 2 and 4 stay fp
    q = jnp.asarray(rng.normal(size=(B, 1, Hq, Dh)), jnp.float32)
    k1 = jnp.asarray(rng.normal(size=(B, 1, Hkv, Dh)), jnp.float32)
    v1 = jnp.asarray(rng.normal(size=(B, 1, Hkv, Dh)), jnp.float32)
    new, out = leaf.fused_decode(q, k1, v1)
    _, k_all, v_all, q_off, valid = leaf.update(k1, v1, 0)
    ref = sdpa(q, k_all, v_all, causal=True, q_offset=q_off,
               kv_valid_len=valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-4)
    oracle = ref_paged_decode(q[:, 0], new.k_fp, new.v_fp, new.k_codes,
                              new.v_codes, new.k_cb, new.v_cb, new.blk_q,
                              new.block_table, new.seq_lens + 1,
                              quantized=True, packed=True)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(oracle),
                               atol=1e-5, rtol=1e-4)


def test_engine_fused_matches_gather(qwen_reduced):
    """The fused-attention engine reproduces the gather engine's generation
    (same greedy tokens, logits to interpret-kernel precision)."""
    cfg, params = qwen_reduced
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab, 10).tolist() for _ in range(2)]
    runs = {}
    for impl in ("gather", "fused"):
        # sync freezing: codes take over at a deterministic step, so the two
        # engines see bit-identical cache state
        eng = ContinuousBatchingEngine(params, cfg, max_slots=2, block_size=8,
                                       max_seq_len=32, kv_quant="kmeans_ls",
                                       record_logits=True, attn_impl=impl,
                                       freeze_async=False)
        out = eng.generate(prompts, max_new_tokens=4)
        runs[impl] = (eng, out)
    (g_eng, g_out), (f_eng, f_out) = runs["gather"], runs["fused"]
    assert g_out == f_out
    for i in range(len(prompts)):
        np.testing.assert_allclose(f_eng.request_logits[i],
                                   g_eng.request_logits[i], atol=1e-3, rtol=0)


@pytest.mark.parametrize("prefix_cache", [False, True],
                         ids=["whole_prompt", "prefix_hit"])
def test_gather_prefill_counts_frozen_pages(qwen_reduced, prefix_cache):
    """``kv_gather_frozen_pages`` reads 0 when every prefill is a
    whole prompt over fresh pages, and counts the shared frozen pages a
    prefix-hit prefill on the gather path dequantizes."""
    cfg, params = qwen_reduced
    rng = np.random.default_rng(12)
    common = rng.integers(0, cfg.vocab, 16).tolist()    # two full pages
    # two slots: the short request's slot frees while the long one still
    # holds the (by then frozen and published) shared pages
    reqs = [Request(id=i, prompt=tuple(common + rng.integers(
                0, cfg.vocab, 5).tolist()), max_new_tokens=gen)
            for i, gen in enumerate((10, 2, 3))]
    eng = ContinuousBatchingEngine(
        params, cfg, max_slots=2, block_size=8, max_seq_len=40,
        kv_quant="kmeans_ls@16", attn_impl="gather", freeze_async=False,
        freeze_page_budget=8, prefix_cache=prefix_cache)
    s = eng.run(reqs)
    assert sorted(eng.outputs) == [0, 1, 2]
    if prefix_cache:
        assert s["prefix_hits"] >= 1 and s["prefix_shared_pages"] >= 2
        assert s["kv_gather_frozen_pages"] == s["prefix_shared_pages"]
    else:
        assert s["prefix_hits"] == 0
        assert s["kv_gather_frozen_pages"] == 0


def test_device_freeze_async_no_host_solves(qwen_reduced):
    """Steady-state freezing is an async device dispatch: no per-page host
    numpy solves, every dispatch eventually installs (or is dropped with
    its finished sequence), and decode steps run between dispatch and
    install with no data dependency on the solve."""
    cfg, params = qwen_reduced
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab, 16).tolist() for _ in range(2)]
    eng = ContinuousBatchingEngine(params, cfg, max_slots=2, block_size=8,
                                   max_seq_len=48, kv_quant="kmeans_ls")
    assert eng.freeze_async
    eng.generate(prompts, max_new_tokens=10)
    c = eng.counters
    assert c["freeze_dispatches"] > 0
    assert c["host_page_solves"] == 0, "kmeans_ls must not solve on host"
    assert c["freeze_installs"] == c["freeze_dispatches"]
    assert not eng.worker.freezes.inflight   # run() drains
    assert c["decode_steps"] > 0 and c["freeze_overlap_steps"] >= 0
    # non-device methods keep the host fallback and are counted (the
    # request must outlive the iteration flush or its queued pages are
    # dropped with the freed blocks)
    eng2 = ContinuousBatchingEngine(params, cfg, max_slots=1, block_size=8,
                                    max_seq_len=16, kv_quant="dtc")
    eng2.generate([prompts[0][:8]], max_new_tokens=4)
    assert eng2.counters["host_page_solves"] > 0


def test_pending_freeze_drop_and_install():
    """dispatch -> drop(freed pages) -> install only marks the surviving
    pages frozen, with the same codes a direct freeze produces."""
    from repro.serving.kv_cache import dispatch_freeze, install_freeze

    cfg = _mini_cfg()
    bs = 4
    leaf = init_paged_layer(cfg, num_blocks=6, block_size=bs, batch=1,
                            max_blocks=3, quantized=True, num_values=16,
                            dtype=jnp.float32)
    rng = np.random.default_rng(7)
    leaf = dataclasses.replace(
        leaf, k_fp=jnp.asarray(rng.normal(size=leaf.k_fp.shape), jnp.float32),
        v_fp=jnp.asarray(rng.normal(size=leaf.v_fp.shape), jnp.float32))
    dropped = dispatch_freeze(leaf, [1, 2, 3], num_values=16)
    dropped.drop([2])                       # sequence owning page 2 finished
    got = install_freeze(leaf, dropped)
    bq = np.asarray(got.blk_q)
    assert bq[1] and bq[3] and not bq[2]
    # identical dispatch without the drop: surviving pages install the same
    # codes/codebooks; the dropped page's slots stay untouched
    full = install_freeze(leaf, dispatch_freeze(leaf, [1, 2, 3],
                                                num_values=16))
    for p in (1, 3):
        np.testing.assert_array_equal(np.asarray(got.k_codes[p]),
                                      np.asarray(full.k_codes[p]))
        np.testing.assert_array_equal(np.asarray(got.v_cb[p]),
                                      np.asarray(full.v_cb[p]))
    np.testing.assert_array_equal(np.asarray(got.k_codes[2]),
                                  np.asarray(leaf.k_codes[2]))


def test_freeze_dispatch_returns_before_completion():
    """freeze_blocks with the device solver is async: the call returns with
    the result arrays still computing (decode work can be enqueued behind
    them), and the markers eventually complete."""
    cfg = _mini_cfg()
    bs = 32
    leaf = init_paged_layer(cfg, num_blocks=64, block_size=bs, batch=1,
                            max_blocks=4, quantized=True, num_values=16,
                            dtype=jnp.float32)
    rng = np.random.default_rng(6)
    leaf = dataclasses.replace(
        leaf, k_fp=jnp.asarray(rng.normal(size=leaf.k_fp.shape), jnp.float32),
        v_fp=jnp.asarray(rng.normal(size=leaf.v_fp.shape), jnp.float32))
    jax.block_until_ready(leaf.k_fp)
    # warm the jitted solve/install for this shape so the timed dispatch
    # below measures dispatch, not compilation
    jax.block_until_ready(freeze_markers(
        freeze_blocks(leaf, list(range(1, 51)), method="kmeans_ls",
                      num_values=16)))
    t0 = time.perf_counter()
    frozen = freeze_blocks(leaf, list(range(1, 51)), method="kmeans_ls",
                           num_values=16)
    t_dispatch = time.perf_counter() - t0
    markers = freeze_markers(frozen)
    jax.block_until_ready(markers)
    t_total = time.perf_counter() - t0
    assert all(m.is_ready() for m in markers)
    # 50 pages x k/v batched through one device solve: dispatch must come
    # back well before the result does (a blocking host path pays the whole
    # solve before returning). Timing-ratio based so a fast machine that
    # finishes the solve before we could poll is_ready() doesn't flake.
    assert t_dispatch < 0.5 * t_total, (t_dispatch, t_total)


def test_decode_clamps_gather_window(qwen_reduced):
    """Short batches must not pay max_blocks bandwidth: the gathered table
    is clamped to the longest live sequence's block count."""
    cfg, params = qwen_reduced
    eng = ContinuousBatchingEngine(params, cfg, max_slots=2, block_size=8,
                                   max_seq_len=128)     # 16 blocks/slot
    prompt = list(range(1, 9))
    eng.generate([prompt], max_new_tokens=6)
    assert eng.max_blocks == 16
    # 8 prompt + 6 generated = 14 tokens -> never more than 2 blocks gathered
    assert 0 < eng.counters["max_gather_blocks"] <= 2


def test_engine_rejects_oversized_request(qwen_reduced):
    cfg, params = qwen_reduced
    eng = ContinuousBatchingEngine(params, cfg, max_slots=1, block_size=8,
                                   max_seq_len=16)
    ok = eng.submit(Request(id=7, prompt=(1,) * 12, max_new_tokens=8), 0.0)
    assert not ok and 7 in eng.sched.rejected


# ------------------------------------------------------------- spec surface


def test_engine_fails_fast_on_unfreezable_spec(qwen_reduced):
    """Construction-time rejection with an error naming the registry's
    device-capable methods — no lazy import deep in the freeze path."""
    from repro.core import QuantSpec, registry

    cfg, params = qwen_reduced
    for bad in ("tv:lam=0.05",                 # lam method: no count budget
                QuantSpec("l1_ls", lam=0.01)):
        with pytest.raises(ValueError) as ei:
            ContinuousBatchingEngine(params, cfg, max_slots=1, block_size=8,
                                     max_seq_len=16, kv_quant=bad)
        msg = str(ei.value)
        for m in registry.device_methods():
            assert m in msg, (bad, msg)
    with pytest.raises(ValueError, match="registered methods"):
        ContinuousBatchingEngine(params, cfg, max_slots=1, block_size=8,
                                 max_seq_len=16, kv_quant="nosuch@16")


def test_engine_legacy_kv_args_and_tv_alias(qwen_reduced):
    """Legacy (method, kv_num_values) pairs and the old 'tv' alias resolve
    to validated specs."""
    cfg, params = qwen_reduced
    eng = ContinuousBatchingEngine(params, cfg, max_slots=1, block_size=8,
                                   max_seq_len=16, kv_quant="tv",
                                   kv_num_values=8)
    assert str(eng.kv_spec) == "tv_iter@8"
    assert eng.kv_quant == "tv_iter" and eng.kv_num_values == 8
    assert not eng.freeze_async            # tv_iter has no device backend


def test_quantized_kv_iter_l1_fista_device_path(qwen_reduced):
    """The lam-parameterised FISTA freeze path (iter_l1 spec, per-row
    lambda bisection to the 4-bit budget) serves within the documented
    tolerance and never solves pages on host. Geometry matches the serve
    verification contract (block 16, the context the tolerance is
    documented for — the l1 family runs ~1.5x the kmeans_ls deviation, so
    the harsher tiny-page unit geometry is reserved for kmeans)."""
    cfg, params = qwen_reduced
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg.vocab, 32).tolist() for _ in range(2)]
    gen = 8
    runs = {}
    for kvq in (None, "iter_l1@16"):
        eng = ContinuousBatchingEngine(params, cfg, max_slots=2,
                                       block_size=16, max_seq_len=64,
                                       kv_quant=kvq, record_logits=True)
        eng.generate(prompts, max_new_tokens=gen)
        runs[kvq] = eng
    fp, q = runs[None], runs["iter_l1@16"]
    assert q.freeze_async and q.kv_spec.device_capable
    assert q.counters["freeze_dispatches"] > 0
    assert q.counters["host_page_solves"] == 0
    for i in range(len(prompts)):
        d = np.abs(fp.request_logits[i] - q.request_logits[i])
        scale = np.abs(fp.request_logits[i]).max()
        assert d.max() <= 2.5, d.max()
        assert d.max() / scale <= 0.08, (d.max(), scale)


# ------------------------------------------------------------- launcher


@pytest.mark.parametrize("max_seq_len,completes", [(32, False), (256, True)],
                         ids=["all_rejected", "served"])
def test_serve_main_exit_status(max_seq_len, completes):
    """``serve.main(argv)`` returns the run's summary when requests
    complete, and exits non-zero when none does (every prompt+gen here
    overflows a 32-token sequence budget)."""
    from repro.launch import serve

    argv = ["--reduced", "--engine", "continuous", "--num-requests", "2",
            "--request-rate", "100", "--prompt-len", "16", "--gen", "24",
            "--max-seq-len", str(max_seq_len)]
    if not completes:
        with pytest.raises(SystemExit) as e:
            serve.main(argv)
        assert e.value.code not in (0, None), e.value.code
        return
    s = serve.main(argv)
    assert s["completed"] == 2 and s["rejected"] == 0, s


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "fixed"])
def test_compile_cache_dir(from_env, monkeypatch, tmp_path):
    """The persistent compile cache follows JAX_COMPILATION_CACHE_DIR when
    it is set (left to JAX, the config untouched) and otherwise one fixed
    directory inside the checkout."""
    from repro.launch.compile_cache import REPO_CACHE_DIR, init_compile_cache

    before = jax.config.jax_compilation_cache_dir
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = init_compile_cache()
        if from_env:
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            assert got == str(REPO_CACHE_DIR)
            assert jax.config.jax_compilation_cache_dir == got
            assert REPO_CACHE_DIR.parent == Path(__file__).resolve().parents[1]
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
