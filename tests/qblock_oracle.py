"""The q-block schedule as the grid ``(blocks, kv_heads, longest block's
jobs)`` built it until PR 28: a Python double loop, one row of jobs a block.
Kept as the oracle of the vectorized flat list
(``ragged_paged_attention.qblock_job_list``): same jobs, block by block, in
the same order. One difference by design: the matrix gave padding tokens
inside the bucket ``(slot 0, ctx 1)`` and so walked slot 0's first page for
them; in the flat list they own nothing (slot -1)."""
import numpy as np


def qblock_schedule(num_tokens, seq_slots, q_starts, q_lens, context_lens,
                    block_tables, q_block, page_size):
    """-> ``(row_slot [B*q_block], row_ctx [B*q_block], job_page [B, J],
    job_slot [B, J], job_kv [B, J])``; padding jobs are slot -2 / page 0."""
    ss = np.asarray(seq_slots, np.int32).reshape(-1)
    qs = np.asarray(q_starts, np.int32).reshape(-1)
    ql = np.asarray(q_lens, np.int32).reshape(-1)
    cl = np.asarray(context_lens, np.int32).reshape(-1)
    tbl = np.asarray(block_tables, np.int32)
    pages_per_seq = tbl.shape[1]
    T = int(num_tokens)

    tok = np.arange(T, dtype=np.int32)
    seq_of = np.clip(
        np.searchsorted(qs, tok, side="right").astype(np.int32) - 1,
        0, max(qs.shape[0] - 1, 0))
    off = tok - qs[seq_of]
    valid = (off >= 0) & (off < ql[seq_of])
    nblocks = -(-T // q_block)
    row_slot = np.full(nblocks * q_block, -1, np.int32)
    row_ctx = np.zeros(nblocks * q_block, np.int32)
    row_slot[:T] = np.where(valid, ss[seq_of], 0)
    row_ctx[:T] = np.where(valid, cl[seq_of] - ql[seq_of] + off + 1, 1)
    bs = row_slot.reshape(nblocks, q_block)
    bc = row_ctx.reshape(nblocks, q_block)

    jobs = []
    for b in range(nblocks):
        block_jobs, seen = [], []
        for r in range(q_block):
            slot = int(bs[b, r])
            if slot < 0 or slot in seen:
                continue
            seen.append(slot)
            cmax = int(bc[b][bs[b] == slot].max())
            n_pages = min(max(-(-cmax // page_size), 1), pages_per_seq)
            block_jobs += [(int(tbl[slot, p]), slot, p * page_size)
                           for p in range(n_pages)]
        jobs.append(block_jobs or [(0, -2, 0)])
    longest = max(len(j) for j in jobs)
    num_jobs = 1 << (longest - 1).bit_length()
    job_page = np.zeros((nblocks, num_jobs), np.int32)
    job_slot = np.full((nblocks, num_jobs), -2, np.int32)
    job_kv = np.zeros((nblocks, num_jobs), np.int32)
    for b, block_jobs in enumerate(jobs):
        for j, (page, slot, kv) in enumerate(block_jobs):
            job_page[b, j], job_slot[b, j], job_kv[b, j] = page, slot, kv
    return row_slot, row_ctx, job_page, job_slot, job_kv
