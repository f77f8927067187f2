"""``kv_cache.FreezeQueue``, the host half of the page-freeze lifecycle:
bid dedupe, the per-flush budget, release states and sync vs async
installs, on a paged pool of the reduced qwen3 config."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.obs.trace import Tracer
from repro.serving.kv_cache import (FreezeQueue, PagedKVCache,
                                    init_paged_cache, map_layers,
                                    resolve_kv_spec)

pytestmark = pytest.mark.serving

NB = 12            # pool pages; block 0 is the null page
COUNTERS = ("decode_steps", "freeze_dispatches", "freeze_installs",
            "freeze_inflight_steps", "freeze_overlap_steps",
            "freeze_pending_max", "freeze_deferred_pages",
            "host_page_solves", "freeze_flush_ns", "freeze_install_ns")


@pytest.fixture(scope="module")
def pool():
    """Quantized paged pool whose fp pages hold random values."""
    cfg = get_reduced_config("qwen3_0_6b")
    tree = init_paged_cache(cfg, num_blocks=NB, block_size=8, batch=1,
                            max_blocks=4, quantized=True, num_values=16)
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 64))

    def fill(leaf):
        return dataclasses.replace(
            leaf,
            k_fp=jax.random.normal(next(keys), leaf.k_fp.shape
                                   ).astype(leaf.k_fp.dtype),
            v_fp=jax.random.normal(next(keys), leaf.v_fp.shape
                                   ).astype(leaf.v_fp.dtype))

    return map_layers(fill, tree)


def _queue(*, budget=4, is_async=True, tracer=None):
    """A queue and the list its ``on_install`` hook appends to."""
    landings = []
    q = FreezeQueue(resolve_kv_spec("kmeans_ls@16"), budget=budget,
                    is_async=is_async, num_blocks=NB,
                    tracer=tracer if tracer is not None else Tracer(),
                    track="freeze/w0", counters=dict.fromkeys(COUNTERS, 0),
                    on_install=lambda: landings.append(sorted(q.installed)))
    return q, landings


def _spans(tr, ph):
    """page_freeze spans of phase ``ph`` ("b"/"e"): span id -> event."""
    return {ev["id"]: ev for ev in tr.events
            if ev["ph"] == ph and ev["name"] == "page_freeze"}


def _ended(tr):
    """page -> terminal state of every ended page_freeze span."""
    return {ev["args"]["page"]: ev["args"]["state"]
            for ev in _spans(tr, "e").values()}


def _leaves(tree):
    return jax.tree_util.tree_leaves(
        tree, is_leaf=lambda x: isinstance(x, PagedKVCache))


def test_bid_skips_installed_and_queued_pages():
    tr = Tracer()
    q, _ = _queue(tracer=tr)
    assert q.idle
    q.mark_installed([2])
    q.bid([1, 2, 3], slot=0)
    q.bid(np.asarray([3, 4, 1]), slot=1)
    assert q.bids == [1, 3, 4]
    assert not q.idle
    begun = _spans(tr, "b")
    assert sorted(ev["args"]["page"] for ev in begun.values()) == [1, 3, 4]
    assert [ev["args"]["slot"] for ev in begun.values()] == [0, 0, 1]
    # an installed page arriving frozen opens and ends no span
    assert not _spans(tr, "e")


def test_budget_defers_and_counts_each_page_once(pool):
    q, landings = _queue(budget=2)
    c = q.counters
    q.bid([1, 2, 3, 4, 5], slot=0)
    tree = q.flush(pool)
    assert q.bids == [3, 4, 5] and len(q.inflight) == 1
    assert c["freeze_deferred_pages"] == 3
    q.bid([6], slot=0)
    tree = q.flush(tree)          # 3, 4 out; 5 was counted, 6 is new
    assert q.bids == [5, 6]
    assert c["freeze_deferred_pages"] == 4
    tree = q.flush(tree)          # nothing left behind
    assert c["freeze_deferred_pages"] == 4
    assert q.flush(tree) is tree  # an empty queue dispatches nothing
    assert c["freeze_dispatches"] == 3
    assert c["freeze_pending_max"] == 3
    tree = q.poll(tree, drain=True)
    assert q.idle and q.installed == {1, 2, 3, 4, 5, 6}
    assert np.flatnonzero(q.mask).tolist() == [1, 2, 3, 4, 5, 6]
    assert c["freeze_installs"] == 3
    assert landings == [[1, 2, 3, 4, 5, 6]]    # one hook call per poll
    for leaf in _leaves(tree):
        assert np.flatnonzero(np.asarray(leaf.blk_q).reshape(
            -1, NB).all(0)).tolist() == [1, 2, 3, 4, 5, 6]


@pytest.mark.parametrize("state", ["dropped", "rolled_back", "offloaded"])
def test_release_ends_exactly_its_spans(pool, state):
    tr = Tracer()
    q, _ = _queue(budget=2, tracer=tr)
    q.bid([1, 2, 3, 4], slot=0)
    tree = q.flush(pool)          # 1, 2 in flight; 3, 4 queued
    q.release({2, 3}, state)
    assert _ended(tr) == {2: state, 3: state}
    assert q.bids == [4]
    tree = q.poll(tree, drain=True)
    # page 2 left its solve while in flight: the landing skips it
    assert _ended(tr) == {1: "installed", 2: state, 3: state}
    assert q.installed == {1}
    assert q.mask[1] and not q.mask[2]
    for leaf in _leaves(tree):
        blk_q = np.asarray(leaf.blk_q).reshape(-1, NB)
        assert blk_q[:, 1].all() and not blk_q[:, 2].any()
    # a freed installed page leaves the installed set; a rolled-back one
    # lies past its slot's accepted length, where nothing is installed,
    # so release leaves the marks as they are
    q.release({1}, state)
    assert (q.installed == {1}) == (state == "rolled_back")
    assert bool(q.mask[1]) == (state == "rolled_back")
    assert _ended(tr)[1] == "installed"


def test_sync_flush_matches_async_flush_and_drain(pool):
    trees, installed = {}, {}
    for is_async in (False, True):
        q, landings = _queue(budget=3, is_async=is_async)
        q.bid([1, 2, 3, 4, 5], slot=0)
        tree = pool
        while q.bids:
            tree = q.flush(tree)
        if is_async:
            assert q.inflight and not q.installed
        tree = q.poll(tree, drain=True)
        assert q.idle
        assert q.counters["freeze_installs"] == 2
        assert q.counters["freeze_dispatches"] == 2
        # sync: a hook call per flush; async: one for the draining poll
        assert len(landings) == (1 if is_async else 2)
        trees[is_async], installed[is_async] = tree, q.installed
    assert installed[False] == installed[True] == {1, 2, 3, 4, 5}
    for a, b in zip(_leaves(trees[False]), _leaves(trees[True])):
        for f in PagedKVCache._CODE_LEAVES:
            np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                          np.asarray(getattr(b, f)))
