"""Property-based serving invariants.

Three differential/invariant suites over the paged serving stack:

  * fused/interpret paged attention == the dense ``ref.ref_paged_decode``
    oracle across randomized geometries (batch, kv heads, GQA factor, page
    size, frozen fraction, per-sequence lengths, verify-window width);
  * ``extract_pages`` -> ``to_host`` -> ``splice_payload`` round-trips
    BITWISE for ``migrate="fp"`` under randomized page counts/tails;
  * page-pool conservation: a randomized admit/decode/finish trace driven
    through the real engine (async freezes in flight, speculative or not)
    never leaks or double-books a page — the free list and the live block
    tables partition the pool at every step boundary.

Two further differential suites ride the same dual-driver pattern:

  * chunked prefill == single-shot prefill, BITWISE, at both levels: raw
    ``paged_prefill_attention`` chunk sequences vs one whole-prompt call
    (chunk boundaries crossing page boundaries, frozen and fp pages), and
    the continuous engine with ``prefill_chunk`` vs inline prefill (same
    tokens, same recorded logits, same frozen-page installs);
  * stacked-group ``quant_matmul_stacked`` vs the dense oracle and the
    flat per-group kernel, <= 1e-5, across padded/unpadded tile shapes.

Each property has two drivers sharing one check body: a seeded random
corpus that runs everywhere (no hypothesis required — the same pattern as
``test_spec``), and a hypothesis-randomized variant when hypothesis is
installed. The hypothesis run is bounded by default (profile "ci", the CI
fast-lane budget); set HYPOTHESIS_PROFILE=thorough for a deeper sweep.
"""
import dataclasses
import os
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import models
from repro.configs import get_reduced_config
from repro.kernels import (modeled_prefill_hbm_bytes_per_token, pack4,
                           paged_decode_attention, paged_prefill_attention,
                           quant_matmul, quant_matmul_stacked,
                           ref_paged_decode, ref_quant_matmul_stacked)
from repro.serving import (ContinuousBatchingEngine, Request, derive_draft,
                           extract_pages, init_paged_cache, splice_payload)
from repro.serving.transfer import collect_leaves

pytestmark = pytest.mark.serving

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYP = True
    settings.register_profile("ci", max_examples=12, deadline=None,
                              derandomize=True)
    settings.register_profile("thorough", max_examples=100, deadline=None)
    settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "ci"))
except ImportError:                                   # pragma: no cover
    HAVE_HYP = False

needs_hyp = pytest.mark.skipif(not HAVE_HYP, reason="hypothesis not installed")


@pytest.fixture(scope="module")
def qwen_reduced():
    cfg = get_reduced_config("qwen3_0_6b")
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


# ------------------------------------------------- fused vs dense oracle


def _check_paged_attention(bs, Hkv, G, Dh, B, mb, W, frozen, lens, softcap):
    nb, L, Hq = B * mb + 1, 16, Hkv * G
    rng = np.random.default_rng(0)
    kfp = jnp.asarray(rng.normal(size=(nb, bs, Hkv, Dh)), jnp.float32)
    vfp = jnp.asarray(rng.normal(size=(nb, bs, Hkv, Dh)), jnp.float32)
    kcodes = rng.integers(0, L, (nb, bs, Hkv, Dh)).astype(np.uint8)
    vcodes = rng.integers(0, L, (nb, bs, Hkv, Dh)).astype(np.uint8)
    kc = pack4(jnp.asarray(kcodes))
    vc = pack4(jnp.asarray(vcodes))
    kcb = jnp.asarray(rng.normal(size=(nb, L)), jnp.float32)
    vcb = jnp.asarray(rng.normal(size=(nb, L)), jnp.float32)
    blkq = np.zeros((nb,), np.int32)
    blkq[list(frozen)] = 1
    state = (kfp, vfp, kc, vc, kcb, vcb, jnp.asarray(blkq))
    table = jnp.asarray(1 + np.arange(B * mb).reshape(B, mb), jnp.int32)
    valid = jnp.asarray(lens, jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, W, Hq, Dh)), jnp.float32)
    out = paged_decode_attention(q, *state, table, valid, softcap=softcap,
                                 quantized=True, packed=True, interpret=True)
    ref = ref_paged_decode(q, *state, table, valid, softcap=softcap,
                           quantized=True, packed=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=1e-4)


def _random_attention_geom(rng):
    bs = int(rng.choice([4, 8, 16]))
    Hkv = int(rng.choice([1, 2]))
    G = int(rng.choice([1, 2, 4]))
    Dh = int(rng.choice([8, 16]))
    B = int(rng.integers(1, 4))
    mb = int(rng.integers(1, 4))
    W = int(rng.choice([1, 2, 4]))
    nb = B * mb + 1
    n_frozen = int(rng.integers(0, nb))
    frozen = rng.choice(np.arange(1, nb), size=min(n_frozen, nb - 1),
                        replace=False).tolist()
    # valid lengths in [W, mb*bs]: every window query sees >= 1 position
    lens = rng.integers(W, mb * bs + 1, size=B).tolist()
    softcap = None if rng.integers(2) else 30.0
    return bs, Hkv, G, Dh, B, mb, W, frozen, lens, softcap


def test_fused_matches_oracle_seeded_corpus():
    """Seeded random-geometry corpus — runs everywhere."""
    rng = np.random.default_rng(7)
    for _ in range(12):
        _check_paged_attention(*_random_attention_geom(rng))


if HAVE_HYP:
    @st.composite
    def attention_geoms(draw):
        bs = draw(st.sampled_from([4, 8, 16]))
        Hkv = draw(st.sampled_from([1, 2]))
        G = draw(st.sampled_from([1, 2, 4]))
        Dh = draw(st.sampled_from([8, 16]))
        B = draw(st.integers(1, 3))
        mb = draw(st.integers(1, 3))
        W = draw(st.sampled_from([1, 2, 4]))
        nb = B * mb + 1
        frozen = draw(st.lists(st.integers(1, nb - 1), unique=True,
                               max_size=nb - 1))
        lens = draw(st.lists(st.integers(min_value=W, max_value=mb * bs),
                             min_size=B, max_size=B))
        softcap = draw(st.sampled_from([None, 30.0]))
        return bs, Hkv, G, Dh, B, mb, W, frozen, lens, softcap

    @needs_hyp
    @given(attention_geoms())
    def test_fused_matches_oracle_property(geom):
        """Hypothesis-randomized geometries, incl. multi-query verify
        windows and ragged frozen pages."""
        _check_paged_attention(*geom)


# ------------------------------------------------- fp migration bitwise


def _check_fp_roundtrip(bs, max_blocks, n_tokens, seed):
    cfg = get_reduced_config("qwen3_0_6b")
    kw = dict(num_blocks=2 * max_blocks + 1, block_size=bs, batch=1,
              max_blocks=max_blocks, quantized=False)
    rng = np.random.default_rng(seed)
    src = init_paged_cache(cfg, **kw)
    src = jax.tree_util.tree_map(
        lambda l: dataclasses.replace(
            l, k_fp=jnp.asarray(rng.normal(size=l.k_fp.shape), jnp.float32),
            v_fp=jnp.asarray(rng.normal(size=l.v_fp.shape), jnp.float32)),
        src, is_leaf=lambda x: hasattr(x, "k_fp"))
    n_pages = -(-n_tokens // bs)
    perm = rng.permutation(np.arange(1, 2 * max_blocks + 1))
    blocks = [int(b) for b in perm[:n_pages]]
    new_blocks = [int(b) for b in perm[n_pages:2 * n_pages]]
    payload = extract_pages(src, blocks, n_tokens, block_size=bs,
                            mode="fp").to_host()
    assert payload.n_pages == n_pages
    assert payload.nbytes == payload.fp_equiv_bytes > 0
    dst = splice_payload(init_paged_cache(cfg, **kw), payload, new_blocks)
    for sl, dl in zip(collect_leaves(src), collect_leaves(dst)):
        stacked = sl.k_fp.ndim == 5
        ax = 1 if stacked else 0
        for s_pool, d_pool in ((sl.k_fp, dl.k_fp), (sl.v_fp, dl.v_fp)):
            s_rows = np.take(np.asarray(s_pool), blocks, axis=ax)
            d_rows = np.take(np.asarray(d_pool), new_blocks, axis=ax)
            # collapse (page, row) -> token rows; only the n_tokens
            # written rows must land (the tail page's padding rows keep
            # the destination's contents)
            lead = (s_rows.shape[0],) if stacked else ()
            s_tok = s_rows.reshape(lead + (-1,) + s_rows.shape[-2:])
            d_tok = d_rows.reshape(lead + (-1,) + d_rows.shape[-2:])
            np.testing.assert_array_equal(d_tok[..., :n_tokens, :, :],
                                          s_tok[..., :n_tokens, :, :])


def test_fp_migration_roundtrip_seeded_corpus():
    rng = np.random.default_rng(11)
    for _ in range(6):
        bs = int(rng.choice([4, 8]))
        n_tokens = int(rng.integers(1, bs * 4 + 1))
        _check_fp_roundtrip(bs, 4, n_tokens, int(rng.integers(2**16)))


if HAVE_HYP:
    @needs_hyp
    @given(st.sampled_from([4, 8]), st.integers(1, 32),
           st.integers(0, 2**16))
    def test_fp_migration_roundtrip_property(bs, n_tokens, seed):
        """extract -> to_host -> splice is bitwise for migrate="fp" at any
        token count (full pages, ragged tail, single-row prompt)."""
        _check_fp_roundtrip(bs, 4, min(n_tokens, bs * 4), seed)


# ------------------------------------------------- pool conservation


def assert_pool_partition(worker):
    """Free list + refcounted live block tables partition the page pool:
    no page leaked, allocator refcounts exactly the multiset of table
    references (a page in N tables has rc == N; without prefix sharing
    every rc is 1, the old no-double-booking invariant)."""
    free = set(worker.alloc._free)
    used = set(worker.alloc._used)
    live = Counter()
    for s in worker.slots:
        live.update(int(b) for b in s.blocks)
    assert dict(live) == dict(worker.alloc._rc), \
        "allocator refcounts != live table reference counts"
    assert set(live) == used, "allocator used-set != live tables"
    assert not (free & used), "page both free and used"
    assert free | used == set(range(1, worker.num_blocks)), "page leaked"
    # frozen bookkeeping never refers to an unallocated page
    assert worker.freezes.installed <= used
    assert set(worker.freezes.bids) <= used
    # sharing only splices published *prefix* runs, so refcounts are
    # monotone non-increasing along every table
    for s in worker.slots:
        rcs = [worker.alloc.refcount(int(b)) for b in s.blocks]
        assert all(x >= y for x, y in zip(rcs, rcs[1:])), rcs


def _check_conservation(qwen_reduced, reqs, speculate):
    cfg, params = qwen_reduced
    eng = ContinuousBatchingEngine(
        params, cfg, max_slots=2, block_size=8, max_seq_len=48,
        kv_quant="kmeans_ls@16", freeze_page_budget=1,   # keep solves queued
        speculate=speculate,
        draft=derive_draft(params, cfg) if speculate else None)
    w = eng.worker
    orig_step = w.step

    def checked_step(now_fn):
        orig_step(now_fn)
        assert_pool_partition(w)

    w.step = checked_step
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, p).tolist() for p, _ in reqs]
    requests = [Request(id=i, prompt=tuple(p), max_new_tokens=reqs[i][1])
                for i, p in enumerate(prompts)]
    eng.run(requests)
    assert_pool_partition(w)
    # everything completed and every page returned — including sequences
    # that finished with freeze solves still in flight (budget=1 defers)
    assert sorted(eng.outputs) == list(range(len(reqs)))
    assert eng.alloc.num_free == eng.num_blocks - 1
    assert w.freezes.idle
    if speculate:
        assert not any(w.draft.blocks)
        assert w.draft.alloc.num_free == w.draft.num_blocks - 1


def test_page_pool_conservation_seeded_corpus(qwen_reduced):
    """Randomized admit/decode/finish traces (ragged prompts and budgets,
    async freezes outliving sequences, with and without speculation) keep
    the free list + live page tables an exact partition of the pool at
    every worker step, and drain back to an empty pool."""
    rng = np.random.default_rng(3)
    for speculate in (0, 2):
        reqs = [(int(rng.integers(1, 21)), int(rng.integers(1, 9)))
                for _ in range(int(rng.integers(2, 6)))]
        _check_conservation(qwen_reduced, reqs, speculate)


if HAVE_HYP:
    @needs_hyp
    @settings(max_examples=4, deadline=None, derandomize=True)
    @given(st.lists(st.tuples(st.integers(1, 20), st.integers(1, 8)),
                    min_size=2, max_size=5),
           st.sampled_from([0, 2]))
    def test_page_pool_conservation_property(qwen_reduced, reqs, speculate):
        _check_conservation(qwen_reduced, reqs, speculate)


# --------------------------------------- tiered residency (host offload)


def assert_tiered_partition(worker, om):
    """Device free list + live tables + host-resident set partition the
    logical pool: the device-side invariants hold unchanged, and every
    demoted sequence's residency moved WHOLE to the host tier (no page of
    it left on device, its payload staged, its resume entry queued)."""
    assert_pool_partition(worker)
    active_rids = {st.req.id for st in worker.sched.active.values()}
    store_rids = {e.req.id for e in om.store.entries()}
    assert not (store_rids & active_rids), "sequence resident in both tiers"
    for e in om.store.entries():
        assert e.payload is not None and e.payload.staged
        assert e.payload.n_pages >= 1
        assert len(e.out) == e.generated
    assert {e.req.id for e in om.resume} == store_rids
    assert om.store.pages == sum(e.payload.n_pages
                                 for e in om.store.entries())


def _check_tiered_conservation(qwen_reduced, reqs, speculate):
    """Overloaded engine (pool ~half the demand) with preempt + offload
    on: the two-tier partition must hold at every step — including
    preemptions landing between speculative verify windows and offloaded
    payloads sitting in the host store while OTHER sequences finish and
    recycle their device pages — and the run must drain both tiers."""
    cfg, params = qwen_reduced
    eng = ContinuousBatchingEngine(
        params, cfg, max_slots=2, block_size=8, max_seq_len=48,
        kv_quant="kmeans_ls@16", freeze_page_budget=1, num_blocks=8,
        offload_pages=True, preempt=True, speculate=speculate,
        draft=derive_draft(params, cfg) if speculate else None)
    w, om = eng.worker, eng.overload
    orig_step = w.step
    outlived = [False]

    def checked_step(now_fn):
        orig_step(now_fn)
        assert_tiered_partition(w, om)
        if len(om.store) and eng.outputs:
            outlived[0] = True          # host entries outlive finished seqs

    w.step = checked_step
    rng = np.random.default_rng(0)
    requests = [Request(id=i,
                        prompt=tuple(rng.integers(0, cfg.vocab, p).tolist()),
                        max_new_tokens=n,
                        priority="best_effort" if i % 2 else "latency")
                for i, (p, n) in enumerate(reqs)]
    s = eng.run(requests)
    assert_tiered_partition(w, om)
    assert sorted(eng.outputs) == list(range(len(reqs)))
    assert eng.alloc.num_free == eng.num_blocks - 1
    assert len(om.store) == 0 and not om.resume and not om.deferred
    assert w.freezes.idle
    # quantized serving must never pick the recompute path (not exact)
    assert s["preempt_recomputes"] == 0
    return s["preemptions"], outlived[0]


def test_tiered_residency_conservation_seeded_corpus(qwen_reduced):
    rng = np.random.default_rng(5)
    preempted = outlived = 0
    for speculate in (0, 2):
        reqs = [(int(rng.integers(4, 21)), int(rng.integers(4, 9)))
                for _ in range(4)]
        p, o = _check_tiered_conservation(qwen_reduced, reqs, speculate)
        preempted += p
        outlived += o
    # the corpus must actually exercise the machinery it checks
    assert preempted >= 1
    assert outlived >= 1


if HAVE_HYP:
    @needs_hyp
    @settings(max_examples=4, deadline=None, derandomize=True)
    @given(st.lists(st.tuples(st.integers(4, 20), st.integers(4, 8)),
                    min_size=3, max_size=5),
           st.sampled_from([0, 2]))
    def test_tiered_residency_conservation_property(qwen_reduced, reqs,
                                                    speculate):
        _check_tiered_conservation(qwen_reduced, reqs, speculate)


# ----------------------------------------- prefix sharing / refcount CoW


def _shared_prefix_requests(cfg, rng, shapes, shared_tokens):
    """Requests whose prompts share a ``shared_tokens``-long prefix (page-
    aligned sharing is up to the engine; prompts just overlap)."""
    common = tuple(int(x) for x in rng.integers(0, cfg.vocab, shared_tokens))
    reqs = []
    for i, (extra, gen) in enumerate(shapes):
        tail = tuple(int(x) for x in rng.integers(0, cfg.vocab, extra))
        reqs.append(Request(id=i, prompt=common + tail, max_new_tokens=gen,
                            priority="best_effort" if i % 2 else "latency"))
    return reqs


def _check_refcount_conservation(qwen_reduced, shapes, speculate):
    """Prefix sharing under overload: shared attach/detach interleaved
    with preemption (victims drop refs on shared pages instead of
    demoting them), speculative rollback, and async freeze installs must
    keep "free list + refcounted live tables" an exact partition at every
    step, and drain pool, host tier, AND prefix index to empty."""
    cfg, params = qwen_reduced
    eng = ContinuousBatchingEngine(
        params, cfg, max_slots=2, block_size=8, max_seq_len=48,
        kv_quant="kmeans_ls@16", freeze_page_budget=2, num_blocks=10,
        offload_pages=True, preempt=True, prefix_cache=True,
        speculate=speculate,
        draft=derive_draft(params, cfg) if speculate else None)
    w, om = eng.worker, eng.overload
    orig_step = w.step

    def checked_step(now_fn):
        orig_step(now_fn)
        assert_pool_partition(w)

    w.step = checked_step
    rng = np.random.default_rng(0)
    requests = _shared_prefix_requests(cfg, rng, shapes, 16)
    s = eng.run(requests)
    assert_pool_partition(w)
    assert sorted(eng.outputs) == list(range(len(requests)))
    assert eng.alloc.num_free == eng.num_blocks - 1
    assert len(om.store) == 0 and not om.resume and not om.deferred
    assert w.freezes.idle
    assert len(w.prefix) == 0, "prefix index must drain with the pool"
    return s


def test_refcount_conservation_seeded_corpus(qwen_reduced):
    rng = np.random.default_rng(9)
    hits = preempted = 0
    for speculate in (0, 2):
        shapes = [(int(rng.integers(2, 9)), int(rng.integers(4, 13)))
                  for _ in range(4)]
        s = _check_refcount_conservation(qwen_reduced, shapes, speculate)
        hits += s["prefix_hits"]
        preempted += s["preemptions"]
    # the corpus must actually exercise the machinery it checks
    assert hits >= 1, "no prefill ever spliced shared pages"
    assert preempted >= 1, "no victim ever dropped refs under pressure"


if HAVE_HYP:
    @needs_hyp
    @settings(max_examples=4, deadline=None, derandomize=True)
    @given(st.lists(st.tuples(st.integers(2, 8), st.integers(4, 12)),
                    min_size=3, max_size=4),
           st.sampled_from([0, 2]))
    def test_refcount_conservation_property(qwen_reduced, shapes, speculate):
        _check_refcount_conservation(qwen_reduced, shapes, speculate)


def _check_cow_divergence(qwen_reduced, shapes, shared_tokens):
    """CoW divergence is invisible in the numerics: on unquantized pools
    (shared pages are exact-fp prompt rows) every sequence's recorded
    logits must be BITWISE identical to an unshared replay of the same
    trace — sharing changes which pages serve the prefix, never what the
    model sees."""
    cfg, params = qwen_reduced
    rng = np.random.default_rng(sum(e for e, _ in shapes) + shared_tokens)
    requests = _shared_prefix_requests(cfg, rng, shapes, shared_tokens)
    engines = []
    for pc in (False, True):
        eng = ContinuousBatchingEngine(
            params, cfg, max_slots=2, block_size=8, max_seq_len=64,
            kv_quant=None, record_logits=True, prefix_cache=pc)
        eng.run([dataclasses.replace(r) for r in requests])
        assert eng.alloc.num_free == eng.num_blocks - 1
        engines.append(eng)
    base, shared = engines
    assert base.outputs == shared.outputs
    for i in range(len(requests)):
        assert np.array_equal(base.request_logits[i],
                              shared.request_logits[i]), i
    return shared


def test_cow_divergence_bitwise_seeded_corpus(qwen_reduced):
    # staggered gens keep a shared-page holder live across admissions;
    # 24 shared tokens = 3 full pages at block size 8
    shared = _check_cow_divergence(qwen_reduced, [(5, 2), (5, 7), (5, 4)],
                                   24)
    s = shared.worker.counters
    assert s["prefix_hits"] >= 1 and s["prefix_shared_pages"] >= 3
    # page-aligned prompts: the raw match covers the whole prompt, the
    # splice stops one page short (the logits row must prefill privately)
    # and counts the truncation as a copy-on-write materialization
    shared = _check_cow_divergence(qwen_reduced, [(0, 2), (0, 7), (0, 4)],
                                   24)
    s = shared.worker.counters
    assert s["cow_copies"] >= 1
    assert s["prefix_shared_pages"] >= 2


if HAVE_HYP:
    @needs_hyp
    @settings(max_examples=4, deadline=None, derandomize=True)
    @given(st.lists(st.tuples(st.integers(0, 8), st.integers(2, 8)),
                    min_size=2, max_size=3),
           st.sampled_from([8, 16, 24]))
    def test_cow_divergence_bitwise_property(qwen_reduced, shapes,
                                             shared_tokens):
        _check_cow_divergence(qwen_reduced, shapes, shared_tokens)


# --------------------------------------------- chunked prefill == single

def _prefill_state(bs, Hkv, G, Dh, B, mb, frozen, seed=1):
    L = 16
    rng = np.random.default_rng(seed)
    nb = B * mb + 1
    kfp = jnp.asarray(rng.normal(size=(nb, bs, Hkv, Dh)), jnp.float32)
    vfp = jnp.asarray(rng.normal(size=(nb, bs, Hkv, Dh)), jnp.float32)
    kc = pack4(jnp.asarray(rng.integers(0, L, (nb, bs, Hkv, Dh))
                           .astype(np.uint8)))
    vc = pack4(jnp.asarray(rng.integers(0, L, (nb, bs, Hkv, Dh))
                           .astype(np.uint8)))
    kcb = jnp.asarray(rng.normal(size=(nb, L)), jnp.float32)
    vcb = jnp.asarray(rng.normal(size=(nb, L)), jnp.float32)
    blkq = np.zeros((nb,), np.int32)
    blkq[list(frozen)] = 1
    state = (kfp, vfp, kc, vc, kcb, vcb, jnp.asarray(blkq))
    table = jnp.asarray(1 + np.arange(B * mb).reshape(B, mb), jnp.int32)
    return state, table, rng


def _check_chunked_prefill_kernel(bs, Hkv, G, Dh, mb, chunk, frozen, P,
                                  softcap):
    """A chunk sequence must be BITWISE equal to one whole-prompt call:
    same pages walked in the same order, per-row online-softmax carry."""
    B, Hq = 2, Hkv * G
    state, table, rng = _prefill_state(bs, Hkv, G, Dh, B, mb, frozen)
    q = jnp.asarray(rng.normal(size=(B, P, Hq, Dh)), jnp.float32)
    whole = paged_prefill_attention(
        q, *state, table, jnp.zeros((B,), jnp.int32), softcap=softcap,
        quantized=True, packed=True, interpret=True)
    parts = []
    for off in range(0, P, chunk):
        C = min(chunk, P - off)
        parts.append(paged_prefill_attention(
            q[:, off:off + C], *state, table,
            jnp.full((B,), off, jnp.int32), softcap=softcap,
            quantized=True, packed=True, interpret=True))
    got = jnp.concatenate(parts, axis=1)
    assert np.array_equal(np.asarray(got), np.asarray(whole))


def _random_chunk_geom(rng):
    bs = int(rng.choice([4, 8]))
    Hkv = int(rng.choice([1, 2]))
    G = int(rng.choice([1, 2]))
    Dh = int(rng.choice([8, 16]))
    mb = int(rng.integers(1, 4))
    P = int(rng.integers(1, mb * bs + 1))
    chunk = int(rng.integers(1, P + 1))
    nb = 2 * mb + 1
    n_frozen = int(rng.integers(0, nb))
    frozen = rng.choice(np.arange(1, nb), size=min(n_frozen, nb - 1),
                        replace=False).tolist()
    softcap = None if rng.integers(2) else 30.0
    return bs, Hkv, G, Dh, mb, chunk, frozen, P, softcap


def test_chunked_prefill_kernel_bitwise_seeded_corpus():
    # chunk 5 over page size 8: every boundary case (chunk crossing a page,
    # chunk == page, ragged tail) plus fully-frozen and fully-fp prefixes
    _check_chunked_prefill_kernel(8, 2, 2, 8, 3, 5, [1, 2, 4, 6], 21, None)
    _check_chunked_prefill_kernel(8, 1, 2, 8, 2, 8, [], 16, 30.0)
    _check_chunked_prefill_kernel(4, 2, 1, 16, 3, 1, list(range(1, 7)), 12,
                                  None)
    rng = np.random.default_rng(11)
    for _ in range(8):
        _check_chunked_prefill_kernel(*_random_chunk_geom(rng))


if HAVE_HYP:
    @st.composite
    def chunk_geoms(draw):
        bs = draw(st.sampled_from([4, 8]))
        Hkv = draw(st.sampled_from([1, 2]))
        G = draw(st.sampled_from([1, 2]))
        Dh = draw(st.sampled_from([8, 16]))
        mb = draw(st.integers(1, 3))
        P = draw(st.integers(1, mb * bs))
        chunk = draw(st.integers(1, P))
        nb = 2 * mb + 1
        frozen = draw(st.lists(st.integers(1, nb - 1), unique=True,
                               max_size=nb - 1))
        softcap = draw(st.sampled_from([None, 30.0]))
        return bs, Hkv, G, Dh, mb, chunk, frozen, P, softcap

    @needs_hyp
    @given(chunk_geoms())
    def test_chunked_prefill_kernel_bitwise_property(geom):
        _check_chunked_prefill_kernel(*geom)


def test_modeled_prefill_bytes_frozen_reduction():
    """>=50%-frozen shared context must model >= 2x fewer prefill HBM
    bytes/token for the fused chunked path than the gather expand."""
    B, mb, bs = 2, 4, 8
    table = 1 + np.arange(B * mb).reshape(B, mb).astype(np.int32)
    lens = np.full((B,), mb * bs, np.int32)
    blkq = np.zeros((B * mb + 1,), np.int32)
    blkq[1:1 + B * mb // 2 + 1] = 1          # just over half the pages
    kw = dict(chunk=bs, block_size=bs, n_kv_heads=2, head_dim=16,
              num_values=16, quantized=True, packed=True)
    fused = modeled_prefill_hbm_bytes_per_token(table, lens, blkq,
                                                path="fused", **kw)
    gather = modeled_prefill_hbm_bytes_per_token(table, lens, blkq,
                                                 path="gather", **kw)
    assert gather / fused >= 2.0, (gather, fused)


def _check_chunked_prefill_engine(qwen_reduced, plens, chunk, kv_quant,
                                  gen):
    """Engine-level differential: prefill_chunk vs inline prefill must
    emit the same tokens, the same recorded logits (bitwise), and freeze
    the same number of pages, with chunks interleaving live decodes
    (max_slots < len(plens) forces it)."""
    from repro.obs import Tracer, count_events

    cfg, params = qwen_reduced
    rng = np.random.default_rng(1000 * chunk + sum(plens) + gen)
    prompts = [rng.integers(0, cfg.vocab, p).tolist() for p in plens]
    outs, engines, tracers = [], [], []
    for pc in (None, chunk):
        tr = Tracer()
        eng = ContinuousBatchingEngine(
            params, cfg, max_slots=2, block_size=8, max_seq_len=64,
            kv_quant=kv_quant, record_logits=True, freeze_async=False,
            prefill_chunk=pc, tracer=tr)
        outs.append(eng.generate(prompts, max_new_tokens=gen))
        engines.append(eng)
        tracers.append(tr)
    single, chunked = engines
    assert outs[0] == outs[1]
    for i in range(len(prompts)):
        assert np.array_equal(single.request_logits[i],
                              chunked.request_logits[i])
    # flush batching is a scheduling artifact (chunked admission lands
    # bids on different iterations), but the freeze BIDS — one page_freeze
    # span opens per queued page, at attach for the whole prompt in both
    # modes — must be identical
    if kv_quant is not None:
        bids = [count_events(tr.events, name="page_freeze", ph="b")
                for tr in tracers]
        assert bids[0] == bids[1], bids
    want = sum(-(-(-(-p // 8) * 8) // chunk) for p in plens)
    assert chunked.prefill.counters["prefill_chunks"] == want
    assert single.prefill.counters["prefill_chunks"] == 0


def test_chunked_prefill_engine_bitwise_seeded_corpus(qwen_reduced):
    # chunk 5 on block 8 crosses page boundaries; fp and frozen pages
    for kv_quant in (None, "kmeans_ls@16"):
        _check_chunked_prefill_engine(qwen_reduced, (21, 13, 17), 5,
                                      kv_quant, 6)
    _check_chunked_prefill_engine(qwen_reduced, (16, 9), 8, "kmeans_ls@16",
                                  4)


if HAVE_HYP:
    @needs_hyp
    @settings(max_examples=4, deadline=None, derandomize=True)
    @given(st.lists(st.integers(9, 25), min_size=2, max_size=3),
           st.integers(2, 9), st.sampled_from([None, "kmeans_ls@16"]))
    def test_chunked_prefill_engine_bitwise_property(qwen_reduced, plens,
                                                     chunk, kv_quant):
        _check_chunked_prefill_engine(qwen_reduced, tuple(plens), chunk,
                                      kv_quant, 4)


# ------------------------------------------- stacked quant_matmul oracle


def _check_stacked_qmatmul(G, M, K, N, L, seed):
    """Stacked-group kernel == dense oracle and == the flat kernel run
    group-by-group, <= 1e-5 (same fp32 accumulate, padded tiles)."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(G, M, K)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, L, (G, K, N)).astype(np.uint8))
    cb = jnp.asarray(rng.normal(size=(G, L)), jnp.float32)
    out = np.asarray(quant_matmul_stacked(x, idx, cb, interpret=True))
    oracle = np.asarray(ref_quant_matmul_stacked(x, idx, cb))
    np.testing.assert_allclose(out, oracle, atol=1e-5, rtol=1e-5)
    flat = np.stack([np.asarray(quant_matmul(x[g], idx[g], cb[g],
                                             interpret=True))
                     for g in range(G)])
    np.testing.assert_allclose(out, flat, atol=1e-5, rtol=1e-5)


def test_stacked_qmatmul_matches_oracle_seeded_corpus():
    # ragged shapes exercise the pad/unpad wrapper; 1-group degenerates to
    # the flat kernel's tiling
    _check_stacked_qmatmul(3, 5, 17, 9, 16, 0)
    _check_stacked_qmatmul(1, 1, 8, 8, 4, 1)
    rng = np.random.default_rng(13)
    for _ in range(6):
        _check_stacked_qmatmul(int(rng.integers(1, 5)),
                               int(rng.integers(1, 20)),
                               int(rng.integers(1, 33)),
                               int(rng.integers(1, 20)),
                               int(rng.choice([4, 16])),
                               int(rng.integers(0, 100)))


if HAVE_HYP:
    @needs_hyp
    @given(st.integers(1, 4), st.integers(1, 12), st.integers(1, 24),
           st.integers(1, 12), st.sampled_from([4, 16]),
           st.integers(0, 50))
    def test_stacked_qmatmul_matches_oracle_property(G, M, K, N, L, seed):
        _check_stacked_qmatmul(G, M, K, N, L, seed)
