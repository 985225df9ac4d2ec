"""``SlotPagedKVCache`` with two page groups (full layers + one sliding
window), as bookkeeping alone: no model, no pools. Pages never leak, a
window group gives blocks back during a request, eviction is LRU within a
group, and a cache of one group does on a recorded schedule exactly what
it did before it knew of groups."""
import hashlib
import json

import numpy as np
import pytest

from paddle_tpu.models.generation import (SlotPagedKVCache,
                                          block_hash_chain)

PAGE, WINDOW = 4, 8


def cache(window_pages=20, pages=40, slots=4, **kw):
    return SlotPagedKVCache(slots, page_size=PAGE, max_len=64,
                            num_pages=pages,
                            window_groups={WINDOW: window_pages}, **kw)


def step(c, spans):
    """One ragged step's bookkeeping: arm, advance (which releases)."""
    c.begin_ragged([(slot, off, n) for off, (slot, n) in zip(
        np.cumsum([0] + [n for _, n in spans[:-1]]), spans)])
    c.advance(sum(n for _, n in spans))


def check_no_leak(c):
    """A group: free + referenced = pages; a page's refcount is the slots
    that map it + 1 if the index knows it; tables name no freed page."""
    for g in c._groups:
        mapped = np.bincount(g.tables[g.tables > 0].reshape(-1),
                             minlength=g.num_pages)
        cached = np.zeros(g.num_pages, int)
        cached[list(g.page_digest)] = 1
        assert np.array_equal(g.ref, mapped + cached), g.label
        assert len(g.free) == len(set(g.free))
        assert sorted(g.free) == np.flatnonzero(
            g.ref[1:] == 0).__add__(1).tolist(), g.label
        assert set(g.index.values()) == set(g.page_digest)
        for slot in range(c.max_batch):
            lo, hi = int(g.first[slot]), int(g.n_blocks[slot])
            assert not g.tables[slot, :lo].any()
            assert g.tables[slot, lo:hi].all() or g.window is not None
            assert not g.tables[slot, hi:].any()


def test_no_page_leaks_over_random_admit_advance_free_reask():
    rng = np.random.default_rng(7)
    c = cache(window_pages=30, pages=70)     # four slots' worst + a little
    docs = [rng.integers(1, 50, int(n)) for n in rng.integers(9, 44, 6)]
    live = {}
    for _ in range(200):
        free = [s for s in range(4) if s not in live]
        r = rng.random()
        if free and r < 0.3:
            doc = docs[int(rng.integers(len(docs)))]
            prompt = np.concatenate([doc, rng.integers(1, 50, 3)])
            cached, hits, _ = c.assign(free[0], prompt)
            assert cached == hits * PAGE < len(prompt)
            live[free[0]] = (prompt, int(rng.integers(1, 10)))
        elif live and r < 0.9:
            spans = []
            for slot, (p, new) in sorted(live.items()):
                at = int(c.lens[slot])
                spans.append((slot, min(8, len(p) - at) if at < len(p)
                              else 1))
            step(c, spans)
            for slot, _ in spans:
                p, new = live[slot]
                if int(c.lens[slot]) == len(p):
                    c.commit_prefix(slot)
                if int(c.lens[slot]) >= min(len(p) + new, 62):
                    c.free(slot)
                    del live[slot]
        elif live:
            slot = sorted(live)[0]
            c.free(slot)
            del live[slot]
        check_no_leak(c)
    assert c.window_blocks_released > 50
    assert c.prefix_hits > 0
    for slot in list(live):
        c.free(slot)
    check_no_leak(c)
    for g in c._groups:                 # what is left is cached, evictable
        assert g.used == len(g.index)
        while c._evict_lru(g):
            pass
        assert g.used == 0


def test_release_happens_during_a_request_and_spares_the_full_group():
    c = cache()
    prompt = np.arange(1, 31)
    c.assign(0, prompt)
    full, window = c._groups
    seen = []
    for _ in range(4):                  # 4 chunks of 8: 32 > 30, so 8,8,8,6
        n = min(8, 30 - int(c.lens[0]))
        step(c, [(0, n)])
        seen.append((int(c.lens[0]), int(window.first[0]), window.used))
    # filled 8: a query at 8 sees from key 1: nothing to give back; at 16
    # from key 9: blocks 0, 1; at 24: 0..3; at 30 from key 23: 0..4
    assert [(n, first) for n, first, _ in seen] == [
        (8, 0), (16, 2), (24, 4), (30, 5)]
    assert int(full.n_blocks[0]) == 8 and full.used == 8
    # released prompt blocks stay cached in the window group (evictable)
    assert c.window_blocks_released == 5 and len(window.index) == 5
    assert window.used == 8
    # decode on: a block of answers goes back to the free list
    for _ in range(12):
        step(c, [(0, 1)])
    assert int(c.lens[0]) == 42 and int(window.first[0]) == 8
    assert len(window.index) == 7       # the prompt's 7 full blocks
    assert window.used == 7 + 3         # + live blocks 8, 9, 10
    check_no_leak(c)


def test_eviction_is_lru_within_a_group_and_counts_by_group():
    c = cache(window_pages=12, pages=40)
    a, b = np.arange(1, 18), np.arange(101, 118)     # 4 full blocks each
    for slot, p in ((0, a), (1, b)):
        c.assign(slot, p)
        step(c, [(slot, 8)])
        step(c, [(slot, 8)])
        step(c, [(slot, 1)])
        c.commit_prefix(slot)
    full, window = c._groups
    c.free(0)
    c.free(1)                           # b's blocks are the more recent
    assert [g.used for g in c._groups] == [8, 8]
    first_a = list(window.index)[0]
    assert first_a == list(full.index)[0]          # a's first block
    # the window group runs dry first (11 pages): a slot that needs 4 takes
    # the 3 free ones, then the OLDEST cached block, which is a's
    long = np.arange(201, 217)
    c.assign(2, long)
    step(c, [(2, 8)])
    step(c, [(2, 8)])
    assert c.window_blocks_evicted >= 1 and c.prefix_evictions_device == 0
    assert first_a not in window.index and first_a in full.index
    # what went is a's, oldest first; b's are all still there
    assert all(d in window.index for d in block_hash_chain(b, PAGE))
    check_no_leak(c)


def test_recency_is_registration_and_a_hit_in_either_kind_of_cache():
    """The index's order is the order of registration, refreshed by a hit
    (``assign``) and by nothing else: ``free`` does not touch (a touch at
    release and at ``free`` was tried on the chip and read worse: it made
    a cold document's interior blocks the most recent and pushed other
    documents' tails out; PERF.md section 6, PR 31)."""
    c = cache()
    a, b = np.arange(1, 18), np.arange(101, 118)
    for slot, p in ((0, a), (1, b)):
        c.assign(slot, p)
        step(c, [(slot, 8)])
        step(c, [(slot, 8)])
        step(c, [(slot, 1)])
        c.commit_prefix(slot)              # a registered first, then b
    c.free(1)
    c.free(0)                              # a let go of last: no matter
    chain_a = block_hash_chain(a, PAGE)
    for g in c._groups:
        assert list(g.index)[:4] == chain_a
    cached, hits, _ = c.assign(2, np.concatenate([a, [5]]))    # a hit on a
    assert hits == 4
    c.free(2)
    full, window = c._groups               # ... refreshes what it mapped:
    assert list(full.index)[4:] == chain_a            # the whole chain,
    assert list(window.index)[-2:] == chain_a[2:]     # the window's tail
    for g in c._groups:
        assert c._evict_lru(g)             # b's first block; a's first
    assert c.assign(3, np.concatenate([b, [5]]))[1] == 0
    assert c.assign(2, np.concatenate([a, [5]]))[1] == 4


def test_a_one_group_cache_does_what_it_always_did():
    """A recorded schedule (300 random admissions, steps, commits and
    frees over 39 pages, 108 evictions among them) on a cache that names no
    window: the scatter's page and slot ids of every step, every
    ``assign``'s answer and the closing counters and table hash to what the
    parent commit's cache gave (recorded there by this same function)."""
    rng = np.random.default_rng(11)
    c = SlotPagedKVCache(4, page_size=4, max_len=64, num_pages=40)
    assert len(c._groups) == 1 and c._free is c._groups[0].free
    prompts = [rng.integers(1, 50, int(n)) for n in rng.integers(6, 40, 12)]
    live, log = {}, []
    for _ in range(300):
        free = [s for s in range(4) if s not in live]
        r = rng.random()
        if free and r < 0.35:
            slot = free[0]
            p = prompts[int(rng.integers(len(prompts)))]
            cached, hits, misses = c.assign(slot, p)
            live[slot] = [p, int(rng.integers(1, 12))]
            log.append(("assign", slot, cached, hits, misses))
        elif live and r < 0.9:
            spans, off = [], 0
            for slot, (p, new) in sorted(live.items()):
                start = int(c.lens[slot])
                n = min(8, len(p) - start) if start < len(p) else 1
                if start + n > 64:
                    continue
                spans.append((slot, off, n))
                off += n
            if not spans:
                continue
            c.begin_ragged(spans)
            ids = c._ragged_index(off)
            log.append(("step", np.asarray(ids[0]).tolist(),
                        np.asarray(ids[1]).tolist()))
            c.advance(off)
            for slot, _, n in spans:
                p, new = live[slot]
                if int(c.lens[slot]) == len(p):
                    c.commit_prefix(slot)
                if int(c.lens[slot]) >= min(len(p) + new, 63):
                    c.free(slot)
                    del live[slot]
        elif live:
            slot = sorted(live)[0]
            c.free(slot)
            del live[slot]
            log.append(("free", slot))
    closing = (c.prefix_hits, c.prefix_misses, c.cached_tokens_total,
               c.cow_copies, c.prefix_evictions_device, c.used_page_count,
               c.free_page_count)
    log.append(("end",) + closing + (c._tables.tolist(),))
    assert closing == (184, 197, 736, 0, 108, 32, 7)
    assert hashlib.sha1(json.dumps(log).encode()).hexdigest() == \
        "b0b68edc1062e8c4c6275eb8246b67b4f36bc709"
    assert (c.window_blocks_released, c.window_blocks_evicted,
            c.prefix_hits_shortened_by_window) == (0, 0, 0)


@pytest.mark.parametrize("pages", [4, 6])
def test_a_window_group_must_hold_a_slots_window_and_a_step(pages):
    with pytest.raises(ValueError, match="do not cover"):
        cache(window_pages=pages)
