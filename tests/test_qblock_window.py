"""The q-block kernel under a sliding window (interpret mode, CPU): the
windowed job list and mask against ``ragged_paged_attention_reference(
window=...)`` and the XLA form, with the block-table entries that a window
group would have released set to 0; and ``window=None`` is the list, the
kernel and the warm-up they were."""
import importlib

import numpy as np
import jax.numpy as jnp
import pytest

from qblock_oracle import qblock_schedule
from paddle_tpu.ops.pallas.ragged_paged_attention import (
    _qblock_jobs, _ragged_paged_attention_xla, _token_descriptors,
    job_buckets, qblock_job_list, ragged_paged_attention,
    ragged_paged_attention_reference, warm_descriptors, window_pages)

KV, PAGES, PAGE, D, HEADS, SLOTS, PER_SEQ, WINDOW = 2, 40, 4, 8, 4, 4, 9, 8


@pytest.fixture(scope="module")
def pool():
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.standard_normal((KV, PAGES, PAGE, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((KV, PAGES, PAGE, D)), jnp.float32)
    perm = rng.permutation(np.arange(1, PAGES))
    tables = perm[:SLOTS * PER_SEQ].reshape(SLOTS, PER_SEQ).astype(np.int32)
    return k, v, tables


def released(tables, seq_slots, q_lens, ctx, window):
    """The tables with every block zeroed that no row of any span of its
    slot can see: what a window group's release leaves."""
    out, first = tables.copy(), {}
    for s, ql, c in zip(seq_slots, q_lens, ctx):
        lo = max(c - ql + 1 - window, 0) // PAGE
        first[int(s)] = min(first.get(int(s), PER_SEQ), lo)
    for s, lo in first.items():
        out[s, :lo] = 0
    return out


LAYOUTS = {
    # four decode rows, two of them far past the window
    "decode_rows": ([0, 1, 2, 3], [0, 1, 2, 3], [1, 1, 1, 1],
                    [30, 5, 17, 9], 8),
    # a 12-token chunk whose rows straddle the window's edge (its first
    # row sees keys 0.., its last only from key 6) + a decode row at 33
    "chunk_straddles_the_edge": ([0, 1], [0, 12], [12, 1], [14, 33], 16),
    # a chunk far behind its window's start: whole pages are skipped
    "chunk_past_the_window": ([2], [0], [8], [34], 8),
    # two spans of ONE sequence in one q-block (a verify span's shape)
    "two_spans_of_one_sequence": ([2, 2], [0, 3], [3, 2], [20, 22], 8),
}


@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_windowed_kernel_against_the_oracle(pool, name):
    k, v, tables = pool
    slots, starts, lens, ctx, tokens = (np.asarray(a) for a in LAYOUTS[name])
    tokens = int(tokens)
    q = jnp.asarray(np.random.default_rng(1).standard_normal(
        (tokens, HEADS, D)), jnp.float32)
    want = np.asarray(ragged_paged_attention_reference(
        q, k, v, tables, slots, starts, lens, ctx, window=WINDOW))
    freed = released(tables, slots, lens, ctx, WINDOW)
    got = np.asarray(ragged_paged_attention(
        q, k, v, freed, slots, starts, lens, ctx, interpret=True,
        window=WINDOW))
    tok_slot, tok_ctx = _token_descriptors(tokens, slots, starts, lens, ctx)
    xla = np.asarray(_ragged_paged_attention_xla(
        q, k, v, tables, tok_slot, tok_ctx, sm_scale=D ** -0.5,
        window=WINDOW))
    rows = np.concatenate([np.arange(a, a + n) for a, n in zip(starts, lens)])
    assert np.abs(got[rows] - want[rows]).max() < 2e-5
    assert np.abs(xla[rows] - want[rows]).max() < 2e-5
    # the window matters in this layout: the plain call differs
    plain = np.asarray(ragged_paged_attention(
        q, k, v, tables, slots, starts, lens, ctx, interpret=True))
    assert np.abs(plain[rows] - want[rows]).max() > 1e-3
    # ... and walks more: no windowed job reads a released block
    _, _, jobs, unwindowed = _qblock_jobs(tokens, slots, starts, lens, ctx,
                                          freed, 8, PAGE, WINDOW)
    assert jobs.shape[1] < unwindowed
    assert unwindowed == qblock_job_list(tokens, slots, starts, lens, ctx,
                                         tables, 8, PAGE)[2].shape[1]
    owned = jobs[2] >= 0
    assert (jobs[1][owned] > 0).all()


def test_the_windowed_list_is_the_plain_list_less_what_no_row_sees(pool):
    """Random ticks: every windowed job is a job of the plain list, in its
    order; a dropped job's page lies wholly behind the window of the first
    row of its (q-block, sequence) pair."""
    _, _, tables = pool
    rng = np.random.default_rng(3)
    for _ in range(30):
        n = int(rng.integers(1, 5))
        slots = rng.permutation(SLOTS)[:n]
        lens = rng.integers(1, 10, n)
        ctx = np.minimum(lens + rng.integers(0, 28, n), PER_SEQ * PAGE)
        starts = np.cumsum(lens) - lens
        tokens = int(-(-lens.sum() // 8) * 8)
        rs, rc, plain = qblock_job_list(tokens, slots, starts, lens, ctx,
                                        tables, 8, PAGE)
        rs2, rc2, jobs, unwindowed = _qblock_jobs(
            tokens, slots, starts, lens, ctx, tables, 8, PAGE, WINDOW)
        assert np.array_equal(rs, rs2) and np.array_equal(rc, rc2)
        assert unwindowed == plain.shape[1]
        keys = [tuple(c) for c in plain.T]
        kept = [tuple(c) for c in jobs.T]
        it = iter(keys)
        assert all(any(j == k for k in it) for j in kept)     # in order
        for blk, page, slot, kv in set(keys) - set(kept):
            rows = np.flatnonzero(rs[blk * 8:(blk + 1) * 8] == slot)
            first_bound = rc[blk * 8 + rows[0]]
            assert kv + PAGE <= first_bound - WINDOW


def test_no_window_is_the_list_it_always_was(pool):
    """``window=None``: the flat list equals the old double loop's, block
    by block (the oracle of PR 28), bit for bit, through either entry."""
    _, _, tables = pool
    slots, starts, lens, ctx, tokens = (np.asarray(a) for a in
                                        LAYOUTS["chunk_straddles_the_edge"])
    args = (int(tokens), slots, starts, lens, ctx, tables, 8, PAGE)
    a, b = qblock_job_list(*args), _qblock_jobs(*args, None)
    assert all(np.array_equal(x, y) for x, y in zip(a, b[:3]))
    assert b[3] == a[2].shape[1]
    row_slot, row_ctx, job_page, job_slot, job_kv = qblock_schedule(*args)
    real = a[0] >= 0            # the oracle gave padding rows (slot 0, ctx 1)
    assert np.array_equal(a[1][real], row_ctx[real])
    for blk in range(job_page.shape[0]):
        real = job_slot[blk] >= 0
        mine = a[2][:, a[2][0] == blk]
        assert np.array_equal(mine[1], job_page[blk][real])
        assert np.array_equal(mine[2], job_slot[blk][real])
        assert np.array_equal(mine[3], job_kv[blk][real])


def test_buckets_and_warm_descriptors_under_a_window():
    """At the new cell's widths: a (q-block, sequence) pair of a
    4,096-token window walks at most 34 pages of 128, so a 512-token tick
    of 24 sequences stays under the 32,768-job bucket that a full layer
    can reach; the warm descriptors make lists of the asked length whose
    single rows stay inside the window."""
    per_seq = 16384 // 128
    assert window_pages(None, 8, 128, per_seq) == 128
    assert window_pages(4096, 8, 128, per_seq) == 34
    assert job_buckets(512, 8, 24, 128) == [1024, 8192, 32768]
    assert job_buckets(512, 8, 24, 34) == [1024, 8192]
    for jobs in (1024, 8192):
        tables, slots, starts, lens, ctx = warm_descriptors(
            512, jobs, 8, 128, 34, window=4096)
        assert ctx.max() <= 4096 + 1
        got = _qblock_jobs(512, slots, starts, lens, ctx, tables, 8, 128,
                           4096)
        assert got[2].shape[1] == got[3] == jobs
    # without a window they are what they were
    plain = warm_descriptors(64, 1024, 8, 16, 128)
    assert (plain[4] % 16 == 0).all()
    assert qblock_job_list(64, *plain[1:], plain[0], 8, 16)[2].shape[1] == 1024


def test_a_latent_pool_takes_no_window():
    pool = jnp.zeros((1, 4, 8, 4), jnp.float32)
    with pytest.raises(NotImplementedError, match="no window"):
        ragged_paged_attention(
            jnp.zeros((8, 2, 8), jnp.float32), pool, None,
            np.zeros((1, 2), np.int32), np.array([0]), np.array([0]),
            np.array([1]), np.array([1]), value_dim=4, interpret=True,
            window=4)
