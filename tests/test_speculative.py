"""Speculative decoding tests: greedy token-identity against the
non-speculative baseline (the acceptance bar — every emitted token is a
target argmax for its exact accepted context), acceptance accounting,
the rollback-vs-async-freeze watermark invariant, and the verify-window
paths (gather and fused/interpret, colocated and disaggregated)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import models
from repro.configs import get_reduced_config
from repro.serving import (ContinuousBatchingEngine, DisaggEngine, Request,
                           derive_draft)

pytestmark = pytest.mark.serving


@pytest.fixture(scope="module")
def qwen_reduced():
    cfg = get_reduced_config("qwen3_0_6b")
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def draft(qwen_reduced):
    cfg, params = qwen_reduced
    return derive_draft(params, cfg)


def _prompts(cfg, n, plen, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, plen).tolist() for _ in range(n)]


# ------------------------------------------------------------- identity


def test_decode_window_matches_sequential_steps(qwen_reduced):
    """The verify primitive itself: one (B, W) window pass == W sequential
    single-token decode steps, bit-for-bit on the paged gather path."""
    from repro.serving.kv_cache import (init_paged_cache, merge_pools,
                                        with_tables)

    cfg, params = qwen_reduced
    bs, P, W = 8, 11, 3
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab, (1, P + W))

    def prefill(tree, table):
        pad = -(-P // bs) * bs
        tp = np.zeros((1, pad), np.int32)
        tp[0, :P] = toks[0, :P]
        t1 = with_tables(tree, table, np.zeros((1,), np.int32))
        _, new = models.prefill(params, cfg, {"tokens": jnp.asarray(tp)}, t1)
        return merge_pools(tree, new)

    kw = dict(num_blocks=6, block_size=bs, batch=1, max_blocks=4)
    table = np.asarray([[1, 2, 3, 4]], np.int32)

    tree = prefill(init_paged_cache(cfg, **kw), table)
    win = with_tables(tree, table, np.asarray([P], np.int32))
    logits_w, _ = models.decode_window(
        params, cfg, jnp.asarray(toks[:, P:P + W]), win,
        jnp.asarray([P], np.int32))

    tree = prefill(init_paged_cache(cfg, **kw), table)
    seq = []
    for w in range(W):
        cur = with_tables(tree, table, np.asarray([P + w], np.int32))
        lg, new = models.decode_step(
            params, cfg, jnp.asarray(toks[:, P + w:P + w + 1]), cur,
            jnp.asarray([P + w], np.int32))
        tree = merge_pools(tree, new)
        seq.append(np.asarray(lg[0, 0]))
    np.testing.assert_array_equal(np.asarray(logits_w[0]), np.stack(seq))


def test_spec_token_identical_and_accepts(qwen_reduced, draft):
    """Truncated-draft speculation reproduces the baseline greedy trace
    exactly (tokens AND logits), accepts drafts (rate > 0), and needs
    fewer verify steps than the baseline needs decode steps."""
    cfg, params = qwen_reduced
    prompts = _prompts(cfg, 3, 12)
    gen = 8
    base = ContinuousBatchingEngine(params, cfg, max_slots=2, block_size=8,
                                    max_seq_len=48, record_logits=True)
    out_b = base.generate(prompts, max_new_tokens=gen)
    spec = ContinuousBatchingEngine(params, cfg, max_slots=2, block_size=8,
                                    max_seq_len=48, record_logits=True,
                                    speculate=3, draft=draft)
    out_s = spec.generate(prompts, max_new_tokens=gen)
    assert out_s == out_b
    for i in range(len(prompts)):
        np.testing.assert_allclose(spec.request_logits[i],
                                   base.request_logits[i], atol=1e-3,
                                   rtol=0)
    s = spec.metrics.summary()
    assert s["spec_acceptance_rate"] > 0
    assert s["spec_proposed"] == 3 * s["spec_steps"]
    assert spec.counters["decode_steps"] < base.counters["decode_steps"]
    # tokens/step: decode-generated tokens per per-sequence verify step
    tps = (s["gen_tokens"] - s["completed"]) / spec.counters["seq_decode_steps"]
    assert tps > 1.0


def test_spec_identical_under_random_draft_rollbacks(qwen_reduced):
    """A random-init draft (near-zero agreement) still yields the exact
    baseline trace — correctness never depends on draft quality — while
    rollbacks dominate."""
    cfg, params = qwen_reduced
    dcfg = get_reduced_config("qwen3_0_6b")
    dparams = models.init_params(dcfg, jax.random.PRNGKey(99))
    prompts = _prompts(cfg, 2, 10, seed=1)
    gen = 6
    base = ContinuousBatchingEngine(params, cfg, max_slots=2, block_size=8,
                                    max_seq_len=48)
    out_b = base.generate(prompts, max_new_tokens=gen)
    spec = ContinuousBatchingEngine(params, cfg, max_slots=2, block_size=8,
                                    max_seq_len=48, speculate=2,
                                    draft=(dparams, dcfg))
    out_s = spec.generate(prompts, max_new_tokens=gen)
    assert out_s == out_b
    s = spec.metrics.summary()
    assert s["spec_rollbacks"] > 0


def test_spec_disagg_matches_colocated(qwen_reduced, draft):
    """Speculation composes with disaggregated serving (draft prefill runs
    at the decode worker on import): same tokens as the colocated
    speculative engine and the plain baseline."""
    cfg, params = qwen_reduced
    prompts = _prompts(cfg, 3, 10, seed=2)
    gen = 6
    base = ContinuousBatchingEngine(params, cfg, max_slots=3, block_size=8,
                                    max_seq_len=48)
    out_b = base.generate(prompts, max_new_tokens=gen)
    dz = DisaggEngine(params, cfg, prefill_workers=1, decode_workers=1,
                      max_slots=3, block_size=8, max_seq_len=48,
                      speculate=3, draft=draft)
    out_d = dz.generate(prompts, max_new_tokens=gen)
    assert out_d == out_b
    s = dz._summary()
    assert s["spec_acceptance_rate"] > 0 and s["tokens_per_step"] > 1.0


def test_spec_fused_interpret_matches_gather(qwen_reduced, draft):
    """The fused verify window (Pallas kernel, interpret mode) reproduces
    the gather verify window on a frozen-page cache."""
    cfg, params = qwen_reduced
    prompts = _prompts(cfg, 2, 12, seed=3)
    gen = 6
    runs = {}
    for impl in ("gather", "fused"):
        eng = ContinuousBatchingEngine(
            params, cfg, max_slots=2, block_size=8, max_seq_len=48,
            kv_quant="kmeans_ls@16", record_logits=True, attn_impl=impl,
            freeze_async=False, speculate=3, draft=draft)
        runs[impl] = (eng, eng.generate(prompts, max_new_tokens=gen))
    (g_eng, g_out), (f_eng, f_out) = runs["gather"], runs["fused"]
    assert f_out == g_out
    for i in range(len(prompts)):
        np.testing.assert_allclose(f_eng.request_logits[i],
                                   g_eng.request_logits[i], atol=1e-3,
                                   rtol=0)


# ------------------------------------------------------------- watermark


def _frozen_watermark_ok(w):
    """No page is frozen, freeze-queued, or pending-kept beyond its slot's
    accepted seq_lens watermark."""
    page_slot = {}
    for slot, s in enumerate(w.slots):
        for j, b in enumerate(s.blocks):
            page_slot[int(b)] = (slot, j)
    suspect = w.freezes.installed | set(w.freezes.bids)
    for _, pending in w.freezes.inflight:
        suspect |= {int(b) for b in pending.bids[pending.keep]}
    for b in suspect:
        if b not in page_slot:      # just-freed page awaiting drop/install
            continue
        slot, j = page_slot[b]
        if not (j + 1) * w.block_size <= int(w.lens[slot]):
            return False, (b, slot, j, int(w.lens[slot]))
    return True, None


def test_rollback_never_freezes_past_watermark(qwen_reduced):
    """The tentpole invariant: with a disagreeing draft forcing rollbacks
    on a quantized cache with async freezing, no frozen/queued/pending
    page ever extends past the accepted seq_lens — checked at every step
    boundary."""
    cfg, params = qwen_reduced
    dcfg = get_reduced_config("qwen3_0_6b")
    dparams = models.init_params(dcfg, jax.random.PRNGKey(123))
    eng = ContinuousBatchingEngine(
        params, cfg, max_slots=2, block_size=4,      # small pages: many
        max_seq_len=64, kv_quant="kmeans_ls@16",     # freeze boundaries
        freeze_page_budget=1, speculate=3, draft=(dparams, dcfg))
    w = eng.worker
    orig_step = w.step
    violations = []

    def checked_step(now_fn):
        orig_step(now_fn)
        ok, info = _frozen_watermark_ok(w)
        if not ok:
            violations.append(info)

    w.step = checked_step
    out = eng.generate(_prompts(cfg, 3, 9, seed=4), max_new_tokens=10)
    assert not violations, violations
    s = eng.metrics.summary()
    assert s["spec_rollbacks"] > 0          # the invariant was exercised
    assert all(len(v) == 10 for v in out.values())
    # pages fully recycled afterwards
    assert eng.alloc.num_free == eng.num_blocks - 1


def test_rollback_unqueues_freeze_bids(qwen_reduced):
    """Unit-level rollback contract: optimistic bids for pages past the
    rolled-back watermark leave the queue (and in-flight keeps), frozen
    watermark shrinks, lens lands on the accepted length."""
    from repro.serving import DecodeWorker
    from repro.serving.kv_cache import resolve_kv_spec

    cfg, params = qwen_reduced
    dcfg = get_reduced_config("qwen3_0_6b")
    dparams = models.init_params(dcfg, jax.random.PRNGKey(5))
    w = DecodeWorker(params, cfg, max_slots=1, block_size=4, max_seq_len=32,
                     kv_spec=resolve_kv_spec("kmeans_ls@16"), speculate=2,
                     draft=(dparams, dcfg))
    s = w.slots[0]
    s.blocks = [3, 5, 7]
    w.table[0, :3] = [3, 5, 7]
    # pretend the verify wrote optimistically through 11 rows (3 pages)
    w.lens[0] = 11
    w._queue_freeze(0)
    assert w.freezes.bids == [3, 5] and s.frozen_upto == 2
    # rollback to 6 accepted rows: page 5 (rows 4..7) is past the
    # watermark and must leave the queue; page 3 (rows 0..3) stays
    w._rollback_slot(0, 6)
    assert w.freezes.bids == [3]
    assert s.frozen_upto == 1
    assert int(w.lens[0]) == 6


# ------------------------------------------------------------- guards


def test_spec_engine_guards(qwen_reduced, draft):
    """Fail-fast surface: speculation without a draft, vocab mismatch,
    sampled requests, and oversized fused windows are all named errors."""
    cfg, params = qwen_reduced
    with pytest.raises(ValueError, match="draft"):
        ContinuousBatchingEngine(params, cfg, speculate=2)
    import dataclasses
    bad_cfg = dataclasses.replace(draft[1], vocab=cfg.vocab + 1)
    with pytest.raises(ValueError, match="vocab"):
        ContinuousBatchingEngine(params, cfg, speculate=2,
                                 draft=(draft[0], bad_cfg))
    with pytest.raises(ValueError, match="window"):
        ContinuousBatchingEngine(params, cfg, block_size=4, speculate=4,
                                 attn_impl="fused", draft=draft)
    eng = ContinuousBatchingEngine(params, cfg, max_slots=2, block_size=8,
                                   max_seq_len=48, speculate=2, draft=draft)
    with pytest.raises(ValueError, match="greedy"):
        eng.submit(Request(id=0, prompt=(1, 2), max_new_tokens=2,
                           temperature=1.0), 0.0)
    # lookahead rows count against the sequence budget
    assert not eng.submit(Request(id=1, prompt=(1,) * 40,
                                  max_new_tokens=8), 0.0)
    assert eng.sched.rejected == [1]
