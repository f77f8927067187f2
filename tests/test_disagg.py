"""Disaggregated serving tests: router decision logic under worker
imbalance, fp/frozen page-migration round-trips vs the colocated engine,
engine-level sampling determinism, and the freeze-dispatch budget."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import models
from repro.configs import get_reduced_config
from repro.serving import (ContinuousBatchingEngine, DisaggEngine,
                           DisaggRouter, Request, extract_pages,
                           init_paged_cache, sample_token, splice_payload)
from repro.serving.kv_cache import resolve_kv_spec
from repro.serving.transfer import collect_leaves

pytestmark = pytest.mark.serving


# ------------------------------------------------------------- router


class _FakePrefill:
    def __init__(self, wid, load=0, cap=4):
        self.worker_id, self.load, self.cap = wid, load, cap
        self.got = []

    def can_accept(self):
        return self.load < self.cap

    def submit(self, req):
        self.got.append(req.id)
        self.load += 1


class _FakeDecode:
    def __init__(self, wid, free_slots=1, free_blocks=8, block_size=4):
        self.worker_id, self.free_slots = wid, free_slots
        self.free_blocks, self.block_size = free_blocks, block_size
        self.got = []

    def can_accept(self, req):
        need = -(-(req.prompt_len + req.max_new_tokens) // self.block_size)
        return self.free_slots > 0 and need <= self.free_blocks

    def place(self, fin):
        self.got.append(fin.req.id)
        self.free_slots -= 1
        self.free_blocks -= -(-(fin.req.prompt_len
                                + fin.req.max_new_tokens) // self.block_size)


def _req(i, plen=4, gen=4):
    return Request(id=i, prompt=(1,) * plen, max_new_tokens=gen)


class _FakeFin:
    def __init__(self, req):
        self.req = req


def test_router_prefill_least_loaded_under_imbalance():
    """Requests drain to the least-loaded prefill worker; a saturated
    worker is skipped entirely; ties break on worker id (deterministic)."""
    router = DisaggRouter()
    a, b, c = _FakePrefill(0, load=3), _FakePrefill(1, load=0), \
        _FakePrefill(2, load=0, cap=0)          # c: saturated from the start
    for i in range(5):
        assert router.submit(_req(i))
    router.route_prefill([a, b, c])
    assert c.got == []
    # b starts 3 lighter: takes the first three; then a and b alternate
    assert b.got == [0, 1, 2, 4] and a.got == [3]
    assert not router.waiting


def test_router_queue_admission_control():
    router = DisaggRouter(max_queue=2)
    assert router.submit(_req(0)) and router.submit(_req(1))
    assert not router.submit(_req(2))
    assert router.rejected == [2]


def test_router_decode_reevaluates_capacity_per_placement():
    """Two staged prefills must not both be routed against capacity the
    first is about to consume (regression: stale-capacity double-place)."""
    router = DisaggRouter()
    dw = _FakeDecode(0, free_slots=1, free_blocks=8)
    for i in range(2):
        router.stage(_FakeFin(_req(i)))
    placed = router.route_decode([dw], lambda w, fin: w.place(fin))
    assert [f.req.id for _, f in placed] == [0]
    assert dw.got == [0] and len(router.staged) == 1     # second one waits
    dw.free_slots = 1
    placed = router.route_decode([dw], lambda w, fin: w.place(fin))
    assert dw.got == [0, 1] and not router.staged


def test_router_decode_most_free_slots_and_hol_wait():
    """Placement prefers the emptiest decode worker; a head that fits
    nowhere blocks the queue (FCFS, no starvation)."""
    router = DisaggRouter()
    small = _FakeDecode(0, free_slots=2, free_blocks=2)   # big req never fits
    big = _FakeDecode(1, free_slots=1, free_blocks=64)
    router.stage(_FakeFin(_req(0, plen=32, gen=32)))      # needs 16 blocks
    router.stage(_FakeFin(_req(1)))                       # only fits `small`
    # head can't fit `small`: nothing places until it lands on `big`; then
    # the second head places on `small` (the only worker that fits it) in
    # the same sweep — FCFS order preserved, per-placement live capacity
    placed = router.route_decode([small, big],
                                 lambda w, fin: w.place(fin))
    assert [(w.worker_id, f.req.id) for w, f in placed] == [(1, 0), (0, 1)]
    assert not router.staged
    # a head that fits nowhere blocks the queue (FCFS, no starvation)
    router.stage(_FakeFin(_req(2, plen=32, gen=32)))
    router.stage(_FakeFin(_req(3)))
    assert router.route_decode([small, big],
                               lambda w, fin: w.place(fin)) == []
    assert len(router.staged) == 2


def test_staging_depth_backpressures_prefill():
    """With a staging depth, a decode-capacity stall stops route_prefill
    from feeding the prefill workers once in-flight prefills (worker load
    + staged artifacts) hit the limit; freeing decode capacity drains the
    staged queue and reopens prefill intake. Without the limit the staged
    queue grows unboundedly (the pre-limit behavior, kept as default)."""
    router = DisaggRouter(staging_depth=2)
    pw = _FakePrefill(0)
    dec = _FakeDecode(0, free_slots=0)              # decode stalled
    for i in range(6):
        assert router.submit(_req(i))
    assert len(router.route_prefill([pw])) == 2     # capped at depth
    assert pw.load == 2 and len(router.waiting) == 4
    # prefills finish -> staged; decode still stalled, nothing places
    for i in range(2):
        router.stage(_FakeFin(_req(i)))
        pw.load -= 1
    assert router.route_decode([dec], lambda w, f: w.place(f)) == []
    # in-flight (staged) still at depth: prefill intake stays closed
    assert router.route_prefill([pw]) == []
    assert pw.load == 0 and len(router.waiting) == 4
    # decode frees -> staged drains -> intake reopens
    dec.free_slots = 2
    assert len(router.route_decode([dec], lambda w, f: w.place(f))) == 2
    assert len(router.route_prefill([pw])) == 2
    # unbounded default: everything flows to the workers immediately
    router2 = DisaggRouter()
    pw2 = _FakePrefill(0, cap=64)
    for i in range(6):
        router2.submit(_req(i))
    assert len(router2.route_prefill([pw2])) == 6


def test_staging_depth_engine_bounds_inflight(qwen_reduced):
    """End to end: a DisaggEngine with staging_depth=1 never holds more
    than one prefill in flight past the waiting queue, yet completes the
    whole trace (backpressure, not starvation)."""
    cfg, params = qwen_reduced
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg.vocab, 8).tolist() for _ in range(4)]
    eng = DisaggEngine(params, cfg, prefill_workers=1, decode_workers=1,
                       max_slots=1, block_size=8, max_seq_len=16,
                       staging_depth=1)
    out = eng.generate(prompts, max_new_tokens=4)
    assert all(out[i] is not None and len(out[i]) == 4 for i in range(4))
    # queue_peak counts load at submit time: depth 1 means the worker
    # never saw a second prompt queued behind an in-flight one
    assert eng.prefills[0].counters["queue_peak"] <= 1


# ------------------------------------------------------------- sampling


def test_sample_token_greedy_and_determinism():
    row = np.asarray([0.1, 3.0, -1.0, 2.9])
    assert sample_token(row) == 1                       # temperature 0
    assert sample_token(row, temperature=0.0,
                        rng=np.random.default_rng(0)) == 1
    draws1 = [sample_token(row, temperature=1.0, top_k=0,
                           rng=np.random.default_rng(7)) for _ in range(8)]
    draws2 = [sample_token(row, temperature=1.0, top_k=0,
                           rng=np.random.default_rng(7)) for _ in range(8)]
    assert draws1 == draws2                             # per-seed replay
    # top_k=1 collapses to argmax whatever the temperature
    assert all(sample_token(row, temperature=5.0, top_k=1,
                            rng=np.random.default_rng(i)) == 1
               for i in range(5))
    # never samples outside the top-k support
    assert all(sample_token(row, temperature=2.0, top_k=2,
                            rng=np.random.default_rng(i)) in (1, 3)
               for i in range(20))


# ------------------------------------------------------------- model fixtures


@pytest.fixture(scope="module")
def qwen_reduced():
    cfg = get_reduced_config("qwen3_0_6b")
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _mini_cfg():
    return get_reduced_config("qwen3_0_6b")


# ------------------------------------------------------------- transfer


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


def _recon(codes, cb, pages, ax):
    """cb[codes] of packed 4-bit ``pages`` by numpy take_along_axis:
    (G?, len(pages), bs, Hkv, Dh) float32."""
    c = np.take(codes, pages, axis=ax)
    idx = np.concatenate([c & 0xF, c >> 4], axis=-3).astype(np.int64)
    cbp = np.take(cb, pages, axis=ax)
    return np.take_along_axis(
        cbp, idx.reshape(idx.shape[:ax + 1] + (-1,)), axis=-1
    ).reshape(idx.shape)


def test_transfer_roundtrip_fp_and_frozen():
    """extract -> to_host -> splice lands the same page content in a fresh
    pool: fp pages bit-exact, frozen pages as codes and codebooks identical
    to an in-place freeze, with blk_q set, fp rows untouched, and a gather
    read of the codebook reconstruction."""
    from repro.serving import freeze_blocks

    cfg = _mini_cfg()
    bs, P = 8, 20                                  # 2 full pages + 4 rows
    spec = resolve_kv_spec("kmeans_ls@16")
    kw = dict(num_blocks=8, block_size=bs, batch=1, max_blocks=4,
              quantized=True, num_values=16)
    src = init_paged_cache(cfg, **kw)
    rng = np.random.default_rng(0)
    src = jax.tree_util.tree_map(
        lambda l: dataclasses.replace(
            l, k_fp=jnp.asarray(rng.normal(size=l.k_fp.shape), jnp.float32),
            v_fp=jnp.asarray(rng.normal(size=l.v_fp.shape), jnp.float32)),
        src, is_leaf=lambda x: hasattr(x, "k_fp"))
    blocks, new_blocks = [3, 1, 4], [2, 5, 6]

    for mode in ("fp", "frozen"):
        payload = extract_pages(src, blocks, P, block_size=bs, mode=mode,
                                spec=spec).to_host()
        assert payload.n_full == 2 and payload.tail_rows == 4
        assert payload.nbytes > 0
        if mode == "fp":
            assert payload.nbytes == payload.fp_equiv_bytes
        else:
            # the partial tail page crosses fp in both modes, so compare
            # the full-page portion: codes+codebooks >= 5x under fp rows
            tail_fp = sum(a.nbytes for a in payload.tail)
            assert (payload.nbytes - tail_fp) * 5 < (payload.fp_equiv_bytes
                                                     - tail_fp)
        dst = splice_payload(init_paged_cache(cfg, **kw), payload,
                             new_blocks)
        for sl, dl in zip(collect_leaves(src), collect_leaves(dst)):
            s_k, d_k = np.asarray(sl.k_fp), np.asarray(dl.k_fp)
            ax = 1 if s_k.ndim == 5 else 0
            take = lambda a, ids: np.take(a, ids, axis=ax)
            if mode == "fp":
                np.testing.assert_array_equal(take(d_k, new_blocks[:2]),
                                              take(s_k, blocks[:2]))
                assert not np.asarray(dl.blk_q)[..., new_blocks[:2]].any()
            else:
                # frozen pages land as the codes and codebooks of freezing
                # the same pages in place on the source pool; their fp
                # rows stay as the destination had them (zeros)
                ref = freeze_blocks(sl, blocks[:2], spec)
                assert not take(d_k, new_blocks[:2]).any()
                assert not take(np.asarray(dl.v_fp), new_blocks[:2]).any()
                for f in ("k_codes", "v_codes", "k_cb", "v_cb"):
                    np.testing.assert_array_equal(
                        take(np.asarray(getattr(dl, f)), new_blocks[:2]),
                        take(np.asarray(getattr(ref, f)), blocks[:2]),
                        err_msg=f)
                assert np.asarray(dl.blk_q)[..., new_blocks[:2]].all()
                # and the gather read serves them as cb[codes], bit for bit
                # what the in-place freeze serves on the source
                read = _read_pages(dl, new_blocks[:2])
                for got, want in zip(read, _read_pages(ref, blocks[:2])):
                    np.testing.assert_array_equal(got, want)
                for got, tag in zip(read, "kv"):
                    recon = _recon(np.asarray(getattr(dl, f"{tag}_codes")),
                                   np.asarray(getattr(dl, f"{tag}_cb")),
                                   new_blocks[:2], ax)
                    np.testing.assert_array_equal(
                        got, recon.astype(got.dtype).reshape(got.shape))
            # the partial tail page crosses fp in both modes (valid rows)
            np.testing.assert_array_equal(
                take(d_k, [new_blocks[2]])[..., 0, :4, :, :],
                take(s_k, [blocks[2]])[..., 0, :4, :, :])


# ------------------------------------------------------------- engines


def test_smoke_colocated_vs_disagg_fp(qwen_reduced):
    """CI smoke gate: the disaggregated composition reproduces the
    colocated engine exactly on an fp cache (tokens and logits), including
    a non-block-aligned prompt (partial-page migration)."""
    cfg, params = qwen_reduced
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, p).tolist() for p in (12, 8)]
    gen = 5
    kw = dict(max_slots=2, block_size=8, max_seq_len=32, record_logits=True)
    co = ContinuousBatchingEngine(params, cfg, **kw)
    out_co = co.generate(prompts, max_new_tokens=gen)
    dz = DisaggEngine(params, cfg, prefill_workers=1, decode_workers=1,
                      migrate="fp", **kw)
    out_dz = dz.generate(prompts, max_new_tokens=gen)
    assert out_co == out_dz
    for i in range(len(prompts)):
        np.testing.assert_allclose(dz.request_logits[i],
                                   co.request_logits[i], atol=1e-4, rtol=0)
    s = dz.metrics.summary()
    assert s["completed"] == len(prompts)
    c = dz.decode[0].counters
    assert c["migrated_seqs"] == len(prompts)
    assert c["migrate_bytes"] == c["migrate_fp_equiv_bytes"] > 0
    # all pools drained
    assert dz.decode[0].alloc.num_free == dz.decode[0].num_blocks - 1
    assert dz.prefills[0].alloc.num_free == dz.prefills[0].num_blocks - 1


def test_frozen_migration_matches_colocated_sync_freeze(qwen_reduced):
    """migrate="frozen" (pages cross as codes+codebooks through the
    dispatch_freeze path) reproduces the colocated engine with synchronous
    freezing: the solver sees identical page content, so tokens and logits
    match. Budget covers the whole prompt so both freeze pre-decode."""
    cfg, params = qwen_reduced
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, 32).tolist() for _ in range(2)]
    gen = 6
    kw = dict(max_slots=2, block_size=8, max_seq_len=64,
              kv_quant="kmeans_ls@16", record_logits=True,
              freeze_async=False, freeze_page_budget=64)
    co = ContinuousBatchingEngine(params, cfg, **kw)
    out_co = co.generate(prompts, max_new_tokens=gen)
    dz = DisaggEngine(params, cfg, prefill_workers=1, decode_workers=1,
                      migrate="frozen", **kw)
    out_dz = dz.generate(prompts, max_new_tokens=gen)
    assert out_co == out_dz
    for i in range(len(prompts)):
        np.testing.assert_allclose(dz.request_logits[i],
                                   co.request_logits[i], atol=1e-4, rtol=0)
    c = dz.decode[0].counters
    assert c["host_page_solves"] == 0
    assert c["migrated_pages"] == 2 * (32 // 8)
    # codes+codebooks cross >= 5x cheaper than the fp rows would
    assert c["migrate_fp_equiv_bytes"] >= 5 * c["migrate_bytes"] > 0


def test_disagg_fused_interpret_matches_gather(qwen_reduced):
    """Frozen-migrated pages land directly servable by the fused decode
    kernel: the interpret-mode fused disagg engine reproduces the gather
    disagg engine."""
    cfg, params = qwen_reduced
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab, 10).tolist() for _ in range(2)]
    runs = {}
    for impl in ("gather", "fused"):
        eng = DisaggEngine(params, cfg, prefill_workers=1, decode_workers=1,
                           migrate="frozen", max_slots=2, block_size=8,
                           max_seq_len=32, kv_quant="kmeans_ls@16",
                           record_logits=True, attn_impl=impl,
                           freeze_async=False)
        runs[impl] = (eng, eng.generate(prompts, max_new_tokens=4))
    (g_eng, g_out), (f_eng, f_out) = runs["gather"], runs["fused"]
    assert g_out == f_out
    for i in range(len(prompts)):
        np.testing.assert_allclose(f_eng.request_logits[i],
                                   g_eng.request_logits[i], atol=1e-3,
                                   rtol=0)


def test_disagg_worker_ratio_and_multi_decode(qwen_reduced):
    """2 prefill + 2 decode workers: every request completes, sequences
    spread over both decode workers, and outputs match the colocated
    engine (fp migration is exact)."""
    cfg, params = qwen_reduced
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab, 8).tolist() for _ in range(4)]
    gen = 4
    co = ContinuousBatchingEngine(params, cfg, max_slots=4, block_size=8,
                                  max_seq_len=16)
    out_co = co.generate(prompts, max_new_tokens=gen)
    dz = DisaggEngine(params, cfg, prefill_workers=2, decode_workers=2,
                      migrate="fp", max_slots=2, block_size=8,
                      max_seq_len=16)
    out_dz = dz.generate(prompts, max_new_tokens=gen)
    assert out_dz == out_co
    assert sum(p.counters["prefills"] for p in dz.prefills) == 4
    assert all(d.counters["migrated_seqs"] > 0 for d in dz.decode)


def test_engine_sampling_determinism_per_seed(qwen_reduced):
    """Sampling replays token-identically per seed, differs across seeds,
    and temperature=0 stays exactly the greedy verification path."""
    cfg, params = qwen_reduced
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, cfg.vocab, 8).tolist() for _ in range(3)]
    gen = 8

    # temperature well above 1: a random-init model's logits are peaked
    # enough that mild temperatures still argmax every step, which would
    # make "different seeds diverge" vacuous
    def run(seed, temperature=5.0, top_k=16):
        eng = ContinuousBatchingEngine(params, cfg, max_slots=2,
                                       block_size=8, max_seq_len=32)
        return eng.generate(prompts, max_new_tokens=gen,
                            temperature=temperature, top_k=top_k, seed=seed)

    a, b, c = run(5), run(5), run(6)
    assert a == b, "same seed must replay token-identically"
    assert a != c, "different seeds should diverge somewhere"
    greedy_default = run(0, temperature=0.0, top_k=0)
    eng = ContinuousBatchingEngine(params, cfg, max_slots=2, block_size=8,
                                   max_seq_len=32)
    assert eng.generate(prompts, max_new_tokens=gen) == greedy_default


def test_freeze_page_budget_defers_burst(qwen_reduced):
    """A prompt burst queuing more full pages than the per-step budget
    defers the remainder to later iterations (counted), and every queued
    page still eventually freezes (installs == dispatches, run drains)."""
    cfg, params = qwen_reduced
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab, 32).tolist() for _ in range(2)]
    eng = ContinuousBatchingEngine(params, cfg, max_slots=2, block_size=8,
                                   max_seq_len=48, kv_quant="kmeans_ls@16",
                                   freeze_page_budget=2)
    eng.generate(prompts, max_new_tokens=8)       # 8 full prompt pages at once
    c = eng.counters
    assert c["freeze_deferred_pages"] > 0, "budget valve never engaged"
    assert c["freeze_installs"] == c["freeze_dispatches"] > 0
    assert not eng.worker.freezes.inflight
    # the same burst with an uncapped budget defers nothing
    eng2 = ContinuousBatchingEngine(params, cfg, max_slots=2, block_size=8,
                                    max_seq_len=48, kv_quant="kmeans_ls@16",
                                    freeze_page_budget=64)
    eng2.generate(prompts, max_new_tokens=8)
    assert eng2.counters["freeze_deferred_pages"] == 0


def test_disagg_async_freeze_outliving_sequences_drains(qwen_reduced):
    """Regression: an async freeze dispatched right before its sequence
    finishes must still land — the run loop keys on pending solves, and a
    worker with no live sequences has no decode step to piggyback the
    install poll on (this used to spin forever)."""
    cfg, params = qwen_reduced
    rng = np.random.default_rng(9)
    eng = DisaggEngine(params, cfg, prefill_workers=1, decode_workers=1,
                       migrate="fp", max_slots=2, block_size=8,
                       max_seq_len=32, kv_quant="kmeans_ls@16")
    assert eng.freeze_async
    out = eng.generate([rng.integers(0, cfg.vocab, 16).tolist()],
                       max_new_tokens=2)
    assert len(out[0]) == 2
    dw = eng.decode[0]
    assert dw.freezes.idle
    assert (dw.counters["freeze_installs"]
            == dw.counters["freeze_dispatches"] > 0)


def test_ttft_split_components(qwen_reduced):
    """queue_wait + prefill_compute == TTFT per request, on both engine
    compositions."""
    cfg, params = qwen_reduced
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, cfg.vocab, 8).tolist() for _ in range(2)]
    for eng in (ContinuousBatchingEngine(params, cfg, max_slots=2,
                                         block_size=8, max_seq_len=16),
                DisaggEngine(params, cfg, prefill_workers=1,
                             decode_workers=1, max_slots=2, block_size=8,
                             max_seq_len=16)):
        eng.generate(prompts, max_new_tokens=4)
        s = eng.metrics.summary()
        assert s["queue_wait_mean_s"] >= 0
        assert s["prefill_compute_mean_s"] > 0
        for tr in eng.metrics.traces.values():
            assert tr.queue_wait + tr.prefill_compute == pytest.approx(
                tr.ttft, abs=1e-9)


def test_disagg_rejects_oversized_and_validates_migrate(qwen_reduced):
    cfg, params = qwen_reduced
    eng = DisaggEngine(params, cfg, prefill_workers=1, decode_workers=1,
                       max_slots=1, block_size=8, max_seq_len=16)
    assert not eng.submit(Request(id=7, prompt=(1,) * 12, max_new_tokens=8),
                          0.0)
    assert 7 in eng.router.rejected
    with pytest.raises(ValueError, match="kv_quant"):
        DisaggEngine(params, cfg, migrate="frozen")
    with pytest.raises(ValueError, match="device"):
        DisaggEngine(params, cfg, migrate="frozen", kv_quant="dtc@16")
    with pytest.raises(ValueError, match="migrate"):
        DisaggEngine(params, cfg, migrate="codes")
