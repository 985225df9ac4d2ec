"""Autoregressive generation (reference behavior: PaddleNLP
``GenerationMixin.generate`` — greedy/sampling decode with KV cache; core
Paddle contributes the fused attention + cache kernels, SURVEY.md §2.4 note
on PaddleNLP being a separate repo → in-repo equivalent).

TPU notes: the eager cache is concat-grown (simple, correct); the compiled
serving path would preallocate [b, max_len, h, d] rings and use the Pallas
decode kernel — follow-up on the inference milestone.
"""
from __future__ import annotations

import functools
import os

import numpy as np
import jax
import jax.numpy as jnp

from ..framework.core import Tensor
from ..autograd.tape import no_grad
from ..framework import random as prandom
from ..profiler import spans as _spans

__all__ = ["KVCache", "PagedKVCache", "SlotPagedKVCache", "HostKVPool",
           "GenerationMixin", "block_hash_chain", "quantize_kv_rows",
           "dequantize_kv_rows", "scatter_kv_rows", "kv_page_nbytes"]

#: kv_dtype values SlotPagedKVCache understands (PADDLE_KV_DTYPE)
KV_DTYPES = ("auto", "int8", "native")


def quantize_kv_rows(x):
    """Symmetric int8 row codec for KV pages: abs-max over the head_dim
    axis, one fp32 scale per ``[..., d]`` row — the ``quant_matmul``
    per-output-channel discipline applied at (kv_head, page, slot)
    granularity. ``x [..., d]`` -> ``(int8 [..., d], f32 scales [...])``;
    round half-to-even matches the comm-layer wire codec."""
    xf = jnp.asarray(x).astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.rint(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_kv_rows(q, scale, dtype=jnp.float32):
    """Inverse of :func:`quantize_kv_rows` (error bound per element:
    ``scale / 2 = max|row| / 254``)."""
    return (jnp.asarray(q).astype(jnp.float32)
            * jnp.asarray(scale)[..., None]).astype(dtype)


@functools.partial(jax.jit, donate_argnums=(0,))
def _zero_slot(arrays, slot):
    """Row ``slot`` of each array zeroed (``SlotPagedKVCache.reset_state``)."""
    return [jax.lax.dynamic_update_index_in_dim(
        a, jnp.zeros(a.shape[1:], a.dtype), slot, 0) for a in arrays]


def _scatter_rows(pool, rows, page_ids, slot_ids):
    """``pool[:, page_ids, slot_ids] = rows`` for ``pool`` [kv, pages,
    page_size, ...] and ``rows`` [kv, *page_ids.shape, ...], written as a
    scatter of whole trailing rows into the pool flattened over its three
    leading axes: each update is then contiguous in the pool's own layout.
    (The three-axis form makes the TPU compiler re-lay the whole pool out
    around the scatter, two full copies a pool, once the step holds more
    than a few tokens.)"""
    kv, pages, page_size = pool.shape[:3]
    tail = pool.shape[3:]
    head = jnp.arange(kv, dtype=jnp.int32).reshape((kv,) + (1,) * page_ids.ndim)
    flat = (head * pages + page_ids[None]) * page_size + slot_ids[None]
    out = pool.reshape((-1,) + tail).at[flat.reshape(-1)].set(
        rows.reshape((-1,) + tail).astype(pool.dtype))
    return out.reshape(pool.shape)


def _scatter_latent_rows(pool, rows, slot_ids, pages, row_page):
    """Write a step's latent rows into a latent pool ``[1, num_pages, d,
    page_size]`` (a page holds its tokens as COLUMNS, see
    ``SlotPagedKVCache._pool``). ``rows`` [1, s, d]; ``pages`` [n]: the
    distinct pages the step writes, padded with the scratch page 0;
    ``row_page`` [s]: each row's index into ``pages``. The touched pages
    are gathered whole (the page axis is the pool's major one), the rows
    written into that small buffer, and the pages put back whole: a step
    touches a few dozen pages, and nothing is scattered column by column
    into the pool."""
    got = jnp.swapaxes(pool[0, pages], 1, 2)            # [n, page, d]
    got = got.at[row_page, slot_ids].set(rows[0].astype(pool.dtype))
    return pool.at[0, pages].set(jnp.swapaxes(got, 1, 2))


def scatter_kv_rows(pools, kt, vt=None, page_ids=None, slot_ids=None,
                    touched=None):
    """Write one forward's K/V rows into a layer's page pools and return
    the updated pools: ``(k_pages, v_pages)``, or ``(k_pages, v_pages,
    k_scales, v_scales)`` for int8 pools, which quantize on scatter (each
    ``[..., d]`` row gets its own fp32 scale, stored beside the pool), or
    ``(kv_pages,)`` for a latent layer's single pool, whose one row a
    token (``kt``; no ``vt``) holds keys and values alike (``touched``:
    ``(pages, row_page)`` of :func:`_scatter_latent_rows`). The leading
    shape of ``kt``/``vt`` past the kv axis must match
    ``page_ids``/``slot_ids``. Pure jnp: the eager cache calls it op by
    op, a compiled layer program traces it over donated pools."""
    slot_ids = jnp.asarray(slot_ids, jnp.int32)
    if len(pools) == 1:
        pages, row_page = touched
        return (_scatter_latent_rows(pools[0], kt, slot_ids,
                                     jnp.asarray(pages, jnp.int32),
                                     jnp.asarray(row_page, jnp.int32)),)
    page_ids = jnp.asarray(page_ids, jnp.int32)
    if len(pools) == 4:
        k_pages, v_pages, ks, vs = pools
        kt, ks_new = quantize_kv_rows(kt)
        vt, vs_new = quantize_kv_rows(vt)
        scales = (_scatter_rows(ks, ks_new, page_ids, slot_ids),
                  _scatter_rows(vs, vs_new, page_ids, slot_ids))
    else:
        (k_pages, v_pages), scales = pools, ()
    return (_scatter_rows(k_pages, kt, page_ids, slot_ids),
            _scatter_rows(v_pages, vt, page_ids, slot_ids)) + scales


def kv_page_nbytes(kv_heads, head_dim, page_size=16, kv_dtype="native",
                   native_dtype="float32", num_layers=1, latent=False):
    """HBM bytes ONE page pins across K+V (plus int8 row scales) for
    ``num_layers`` attention layers — the int8-KV capacity math:
    ``sessions_per_pool = pool_bytes // (pages_per_seq * this)``. int8
    vs fp32 is ``4d/(d+4)`` (~3.8x at d=64), vs bf16 ``2d/(d+4)``
    (~1.94x at d=128). ``latent``: one pool a layer (``head_dim`` is the
    latent row, keys and values in one), so no factor of two."""
    elems = int(kv_heads) * int(page_size) * int(head_dim)
    if str(kv_dtype) == "int8":
        per = elems + int(kv_heads) * int(page_size) * 4   # + f32 scales
    else:
        per = elems * np.dtype(native_dtype).itemsize
    return (1 if latent else 2) * per * int(num_layers)    # K and V


def block_hash_chain(tokens, page_size, parent=b""):
    """vLLM-style chained block hashes for prefix caching: block ``i``'s
    key is ``sha1(key_{i-1} || tokens_of_block_i)``, so a key identifies
    not just a block's tokens but its entire left context — two prompts
    share a cache entry iff they share the whole prefix up to and
    including that block. Returns one digest per FULL block (the trailing
    partial block has no key: it is never shared)."""
    import hashlib
    arr = np.ascontiguousarray(np.asarray(tokens, np.int64).reshape(-1))
    out = []
    for i in range(len(arr) // int(page_size)):
        h = hashlib.sha1()
        h.update(parent)
        h.update(arr[i * page_size:(i + 1) * page_size].tobytes())
        parent = h.digest()
        out.append(parent)
    return out


class HostKVPool:
    """Host-RAM second tier under the device prefix index (ROADMAP item 4;
    arxiv 2604.15464's HBM-capacity argument taken to its conclusion). At
    fleet scale the shared-prefix working set dwarfs device HBM: today an
    LRU-evicted prefix page is simply gone and the next tenant re-prefills
    it from scratch. This pool catches those evictions — a demoted page is
    one single-page blob in the :meth:`SlotPagedKVCache.export_pages`
    codec (int8 pools demote their quantized ints + fp32 row scales as-is,
    ~4x less copy traffic than fp32) — and promotion on an admission hit
    writes the bytes back verbatim, so the roundtrip is bit-exact.

    Capacity is bounded by ``PADDLE_KV_HOST_POOL_MB`` (0 = tier disabled,
    exact legacy eviction behavior) with its own second-level LRU: when a
    demotion would exceed the bound, the least-recently-touched host
    entries fall off the end of the world. The pool is deliberately
    cache-agnostic — the serving engine owns ONE pool across cache
    rebuilds (crash recovery keeps the warm tier) and hands it to every
    :class:`SlotPagedKVCache` it constructs."""

    def __init__(self, max_mb=None):
        if max_mb is None:
            max_mb = float(os.environ.get("PADDLE_KV_HOST_POOL_MB", "0")
                           or 0)
        self.max_bytes = int(float(max_mb) * 2 ** 20)
        from collections import OrderedDict
        self._entries = OrderedDict()     # digest -> page blob (LRU order)
        self.used_bytes = 0
        self.demotions = 0        # accepted puts
        self.promotions = 0       # takes that moved a page back to device
        self.hits = 0             # lookups that found an entry
        self.misses = 0           # lookups that came back empty
        self.evictions = 0        # second-level LRU drops

    @property
    def enabled(self):
        return self.max_bytes > 0

    def __len__(self):
        return len(self._entries)

    def __contains__(self, digest):
        return bytes(digest) in self._entries

    @staticmethod
    def entry_nbytes(entry):
        total = sum(a.nbytes for arrs in entry["layers"] for a in arrs)
        if entry.get("scales"):
            total += sum(ks.nbytes + vs.nbytes
                         for ks, vs in entry["scales"])
        return total

    def put(self, digest, entry):
        """Admit a demoted page under ``digest``, evicting LRU entries
        until the byte bound holds again (an entry bigger than the whole
        pool is admitted then immediately evicted — same contract).
        Returns True when the entry is resident after the call."""
        if not self.enabled:
            return False
        digest = bytes(digest)
        old = self._entries.pop(digest, None)
        if old is not None:
            self.used_bytes -= self.entry_nbytes(old)
        self._entries[digest] = entry
        self.used_bytes += self.entry_nbytes(entry)
        self.demotions += 1
        while self.used_bytes > self.max_bytes and self._entries:
            _, dropped = self._entries.popitem(last=False)
            self.used_bytes -= self.entry_nbytes(dropped)
            self.evictions += 1
        return digest in self._entries

    def get(self, digest):
        """Peek (LRU touch, entry stays resident) — used by read-only
        consumers like the disagg exporter."""
        entry = self._entries.get(bytes(digest))
        if entry is None:
            self.misses += 1
            return None
        self._entries.move_to_end(bytes(digest))
        self.hits += 1
        return entry

    def take(self, digest):
        """Remove and return the entry (promotion path: once the page is
        device-resident and index-registered, the device index is
        authoritative — keeping the host copy would double-count bytes;
        a later eviction demotes it again)."""
        entry = self._entries.pop(bytes(digest), None)
        if entry is not None:
            self.used_bytes -= self.entry_nbytes(entry)
        return entry

    def clear(self):
        self._entries.clear()
        self.used_bytes = 0


class KVCache:
    """Per-attention-layer concat cache. ``update`` returns the full K/V so
    far (including the new tokens); ``pos`` is the filled length, advanced
    once per model forward."""

    def __init__(self):
        self.pos = 0
        self._store = {}

    def update(self, layer, k_new, v_new):
        from ..ops import manipulation as manip
        key = id(layer)
        if key in self._store:
            k_old, v_old = self._store[key]
            k = manip.concat([k_old, k_new], axis=1)
            v = manip.concat([v_old, v_new], axis=1)
        else:
            k, v = k_new, v_new
        self._store[key] = (k.detach(), v.detach())
        return k, v

    def advance(self, s):
        self.pos += int(s)

    def reorder(self, idx):
        """Gather the cache along the batch axis (beam-search hop:
        beam b's continuation may extend a DIFFERENT parent beam)."""
        for key, (k, v) in self._store.items():
            self._store[key] = (Tensor(k._data[idx]), Tensor(v._data[idx]))

    def reset(self):
        self.pos = 0
        self._store.clear()

    def attend(self, layer, q, k, v, training=False, dropout_p=0.0):
        """Cache-aware attention: update the store with this step's K/V and
        return the attention output [b, s, heads, d]. The attention layer
        delegates here so cache layouts (concat vs paged) are swappable."""
        from ..nn import functional as F
        k, v = self.update(layer, k, v)
        return F.scaled_dot_product_attention(q, k, v, attn_mask=None,
                                              dropout_p=dropout_p,
                                              is_causal=True,
                                              training=training)


class PagedKVCache(KVCache):
    """Paged (block-table) KV cache for batched decode — the serving tier's
    cache (reference: ``block_multihead_attention``'s vLLM-style paged KV;
    VERDICT.md round-1 item 10).

    K/V live in fixed-size pages ``[kv_heads, num_pages, page_size, d]``
    (kv-head-major: each (head, page) block is one contiguous aligned
    slab, the layout the TPU decode kernel DMAs) per attention layer; a
    shared per-sequence block table maps positions to pages. Prefill
    scatters the prompt's K/V into pages and attends densely; each decode
    step writes one slot and runs the ``paged_attention`` kernel
    (ops/pallas/paged_attention.py)."""

    def __init__(self, page_size=16, max_len=2048):
        super().__init__()
        self.page_size = int(page_size)
        self.max_len = int(max_len)
        self.pages_per_seq = -(-self.max_len // self.page_size)
        self._pools = {}          # id(layer) -> (k_pages, v_pages)
        self._tables = None       # [batch, pages_per_seq] int32
        self._batch = None

    def reset(self):
        super().reset()
        self._pools.clear()
        self._tables = None
        self._batch = None

    def _ensure_tables(self, batch):
        if self._tables is None:
            self._batch = batch
            # contiguous static allocation: sequence b owns pages
            # [b*pps, (b+1)*pps) — correctness-first; a free-list
            # allocator can swap in without touching the kernel
            self._tables = (np.arange(batch)[:, None] * self.pages_per_seq
                            + np.arange(self.pages_per_seq)[None, :]
                            ).astype(np.int32)
        return jnp.asarray(self._tables)

    def _pool(self, layer, kv_heads, d, dtype, batch):
        key = id(layer)
        if key not in self._pools:
            n = batch * self.pages_per_seq
            shape = (kv_heads, n, self.page_size, d)
            self._pools[key] = (jnp.zeros(shape, dtype),
                                jnp.zeros(shape, dtype))
        return self._pools[key]

    def _step_indices(self, start, s, b):
        """Scatter/kernel indices for this step — identical for every
        layer, so compute once per (pos, s, batch)."""
        key = (start, s, b)
        if getattr(self, "_idx_key", None) != key:
            pos = np.arange(start, start + s)
            self._idx_cache = (
                jnp.asarray(self._tables[:, pos // self.page_size]),   # [b,s]
                jnp.asarray((pos % self.page_size)[None, :]
                            .repeat(b, axis=0)),
                jnp.asarray(self._tables),
                jnp.full((b,), start + s, jnp.int32),
            )
            self._idx_key = key
        return self._idx_cache

    def attend(self, layer, q, k, v, training=False, dropout_p=0.0):
        from ..autograd.tape import apply
        from ..nn import functional as F

        if dropout_p and training:
            raise ValueError("PagedKVCache is a serving cache: attention "
                             "dropout is not supported")
        b, s, kv_heads, d = (k.shape if not isinstance(k, Tensor)
                             else tuple(k.shape))
        if self._batch is not None and self._batch != b:
            raise ValueError(f"PagedKVCache was allocated for batch "
                             f"{self._batch}, got {b}; call reset() first")
        self._ensure_tables(b)
        k_pages, v_pages = self._pool(layer, kv_heads, d,
                                      k._data.dtype if isinstance(k, Tensor)
                                      else k.dtype, b)
        start = self.pos
        if start + s > self.max_len:
            raise ValueError(f"PagedKVCache overflow: {start}+{s} > "
                             f"{self.max_len}")
        page_ids, slot_ids, tables, ctx = self._step_indices(start, s, b)

        def scatter(kp, vp, ka, va):
            # pools are [kv, page, slot, d]; ka/va arrive [b, s, kv, d]
            kt = jnp.moveaxis(ka, 2, 0)            # [kv, b, s, d]
            vt = jnp.moveaxis(va, 2, 0)
            kp = kp.at[:, page_ids, slot_ids].set(kt)
            vp = vp.at[:, page_ids, slot_ids].set(vt)
            return kp, vp

        new_kp, new_vp = scatter(k_pages, v_pages,
                                 k._data if isinstance(k, Tensor) else k,
                                 v._data if isinstance(v, Tensor) else v)
        self._pools[id(layer)] = (new_kp, new_vp)

        if s > 1:
            # prefill: dense attention; with prior context (a reused cache,
            # chunked prefill) read the full prefix back from the pages —
            # sdpa's bottom-right causal alignment handles sq != sk
            if start > 0:
                n_pages = -(-(start + s) // self.page_size)
                tb = jnp.asarray(self._tables[:, :n_pages])
                # [kv, b, pages, slot, d] -> [b, seq, kv, d]
                kf = Tensor(jnp.moveaxis(new_kp[:, tb], 0, 3)
                            .reshape(b, n_pages * self.page_size, kv_heads,
                                     d)[:, :start + s])
                vf = Tensor(jnp.moveaxis(new_vp[:, tb], 0, 3)
                            .reshape(b, n_pages * self.page_size, kv_heads,
                                     d)[:, :start + s])
            else:
                kf, vf = k, v
            return F.scaled_dot_product_attention(q, kf, vf, attn_mask=None,
                                                  is_causal=True,
                                                  training=training)
        # decode: one token per sequence through the paged kernel
        from ..ops.pallas.paged_attention import paged_attention
        import jax as _jax
        interpret = _jax.default_backend() != "tpu"

        def fn(qa):
            out = paged_attention(qa[:, 0], new_kp, new_vp, tables, ctx,
                                  interpret=interpret)
            return out[:, None]          # [b, 1, heads, d]

        return apply(fn, q, op_name="paged_attention")


class _PageGroup:
    """The pages of the layers that share one ``window`` (None: layers of
    full causal attention, which keep a sequence's every block): pools of
    ``num_pages`` pages, a free list, refcounts, a prefix index (digest ->
    page, in LRU order) and a block table a slot. A window group's table
    has a slot's blocks at the same indices as the full group's; the
    entries below ``first[slot]`` were released and are 0."""

    def __init__(self, window, num_pages, max_batch, pages_per_seq):
        from collections import deque, OrderedDict
        self.window = None if window is None else int(window)
        self.num_pages = int(num_pages)
        self.free = deque(range(1, self.num_pages))
        self.ref = np.zeros(self.num_pages, np.int32)
        self.index = OrderedDict()
        self.page_digest = {}
        self.tables = np.zeros((max_batch, pages_per_seq), np.int32)
        self.n_blocks = np.zeros(max_batch, np.int32)
        self.first = np.zeros(max_batch, np.int32)

    @property
    def label(self):
        return "full" if self.window is None else f"window{self.window}"

    @property
    def used(self):
        return self.num_pages - 1 - len(self.free)


class SlotPagedKVCache:
    """Per-slot paged KV cache over a SHARED refcounted page pool — the
    continuous-batching serving cache (reference: the vLLM-style block
    cache behind ``block_multihead_attention``; VERDICT.md round-2 item 8,
    prefix caching per Ragged Paged Attention, arxiv 2604.15464).

    Unlike :class:`PagedKVCache` (one uniform batch filled in lockstep),
    every slot here has its own context length and lifecycle: a slot is
    **assigned** a prompt on admission (leading full blocks that hit the
    hash-chained prefix index map straight onto already-filled pages —
    refcount++, zero prefill work), takes part in **ragged** steps
    (:meth:`begin_ragged`: prefill spans of the uncached suffix and
    single decode tokens of many slots in one flat batch, each at its
    own position), and is **freed** on completion (refcount--, pages
    return to the free list at zero). The flat batch is padded to a
    bounded bucket set, so the serve loop stays on a small family of
    compiled programs while requests come and go.

    Pages are allocated from one free list shared by all slots; page 0
    is a scratch page — the write of a bucket-padding token is steered
    there so it can never corrupt a page a request owns. Writes into a
    shared page (refcount > 1 or registered in the prefix index) trigger
    copy-on-write.

    **Layer groups.** A model whose layers differ in what they may forget
    says so a layer (``layer.kv_window``: None, or a sliding window's
    length) and the cache is built with ``window_groups={window: pages}``:
    the layers of one window share a :class:`_PageGroup` (pools of that
    group's own page count, block table, free list, refcounts, prefix
    index; the full layers' group is the first and is the whole cache of a
    model that names no window). A window group gives a block back as
    soon as no query at or past the slot's filled length can see any of
    its tokens (after each step: :meth:`advance`); a block the prefix
    index knows stays cached and evictable, any other returns to the free
    list. A prefix hit of ``b`` blocks needs the chain ``[0, b)`` in the
    full group AND, in each window group, every block that a query at
    ``b * page_size`` still sees; else it is shortened to the longest
    ``b`` for which both hold. Eviction is LRU within a group. With a
    window group: no int8 pages, host tier, sep striping, page export /
    import or rollback (each refuses).

    **A state a slot.** A layer that keeps no keys and values but a
    recurrent state of fixed size (linear attention) says what it keeps
    with ``layer.state_spec() -> {name: (shape a slot, dtype)}`` where an
    attention layer says ``kv_pool_spec`` or ``kv_window``, and the cache
    is built with ``state_layers=True``: the arrays of such a layer
    (:meth:`layer_state`) are indexed by slot, with one more row, the
    scratch slot ``max_batch``, for a bucket's padding rows; no page table
    maps them. Admission zeroes a slot's rows on the device
    (:meth:`reset_state`, inside :meth:`assign`), every step overwrites
    them, :meth:`free` needs nothing. A ragged step hands such a layer its
    spans (:meth:`ragged_spans`) and a memo that lasts the step
    (``step_memo``), where the layers of a model share the plan they make
    of the spans. A state cannot be cut back or found again by a digest:
    with ``state_layers`` the prefix cache must be off, and rollback, page
    export / import, the host tier, int8 pages and sep striping refuse.
    """

    def __init__(self, max_batch, page_size=16, max_len=2048,
                 num_pages=None, enable_prefix_cache=True, kv_dtype=None,
                 host_pool=None, allow_page_overcommit=False,
                 window_groups=None, state_layers=False):
        self.max_batch = int(max_batch)
        self.page_size = int(page_size)
        self.max_len = int(max_len)
        self.pages_per_seq = -(-self.max_len // self.page_size)
        self.enable_prefix_cache = bool(enable_prefix_cache)
        # int8 KV pages (PADDLE_KV_DTYPE=auto|int8|native): pages store
        # int8 values + one fp32 scale per (kv_head, page, slot) row,
        # halving page bytes vs bf16 (quartering vs fp32) so the same
        # HBM holds ~2x the concurrent sessions; "auto" resolves to
        # native today (int8 is an explicit capacity opt-in)
        if kv_dtype is None:
            kv_dtype = os.environ.get("PADDLE_KV_DTYPE", "auto")
        kv_dtype = str(kv_dtype).lower()
        if kv_dtype not in KV_DTYPES:
            raise ValueError(f"kv_dtype {kv_dtype!r} not in {KV_DTYPES}")
        self.kv_dtype = "native" if kv_dtype == "auto" else kv_dtype
        self.kv_quant = self.kv_dtype == "int8"
        self._scales = {}       # id(layer) -> (k_scales, v_scales) if int8
        # +1: page 0 is the never-allocated scratch page, so capacity for
        # max_batch full-length sequences survives even with zero sharing
        self.num_pages = (int(num_pages) if num_pages is not None
                          else self.max_batch * self.pages_per_seq + 1)
        if allow_page_overcommit:
            # sep-parallel long-context serving deliberately overcommits:
            # the bulk of a 100k+ prompt's KV lives in host-side stripes,
            # only the decode tail needs device pages
            if self.num_pages < 2:
                raise ValueError("num_pages must be >= 2")
        elif self.num_pages < self.pages_per_seq + 1:
            raise ValueError("num_pages must cover one full sequence")
        # the full layers' group, then one group a window. The allocator's
        # methods take a group; the names below are the full group's state
        # for the paths that know one group alone (sep striping, export /
        # import, the host tier: each refuses a cache with a window group)
        # and for the callers outside that read them
        full = _PageGroup(None, self.num_pages, self.max_batch,
                          self.pages_per_seq)
        self._groups = [full]
        self._free = full.free
        self._ref = full.ref
        self._index = full.index          # block digest -> page (LRU order)
        self._page_digest = full.page_digest    # page -> digest (registered)
        self._tables = full.tables
        self._n_blocks = full.n_blocks
        if window_groups and (self.kv_quant or allow_page_overcommit):
            raise NotImplementedError(
                "a cache with a window group serves neither int8 pages nor "
                "sep striping")
        for window, pages in sorted((window_groups or {}).items()):
            # what a slot holds at the most: the window, one step's new
            # tokens up to the whole of it, the pages both ends straddle
            if int(window) < 1 or int(pages) < min(
                    self.pages_per_seq,
                    2 * -(-int(window) // self.page_size) + 2) + 1:
                raise ValueError(
                    f"window group {window}: {pages} pages do not cover "
                    "one slot's window and a step of new tokens")
            self._groups.append(_PageGroup(window, pages, self.max_batch,
                                           self.pages_per_seq))
        # layers that keep a state a slot (class docstring)
        self.state_layers = bool(state_layers)
        if self.state_layers:
            refused = [name for name, on in (
                ("the prefix cache (a hit would need a snapshot of the "
                 "state)", self.enable_prefix_cache),
                ("int8 pages", self.kv_quant),
                ("sep striping", allow_page_overcommit)) if on]
            if refused:
                raise NotImplementedError(
                    "a cache with a state a slot does not serve: "
                    + ", ".join(refused))
        self._states = {}           # id(layer) -> {name: [slots + 1, ...]}
        self.step_memo = {}         # what a step's layers share; per step
        self.state_resets = 0       # slots zeroed at admission
        self.state_counters = {}    # name -> what the layers counted
        self._pool_group = {}       # id(layer) -> its _PageGroup
        self._group_idx = {}        # group -> a ragged step's index memo
        self._chain = [None] * self.max_batch   # per-slot block digests
        self._pools = {}            # id(layer) -> (k_pages, v_pages)
        self.lens = np.zeros(self.max_batch, np.int32)   # filled ctx/slot
        self._mode = None            # ("ragged", spans) | ("sep_*", slot)
        self._idx = None             # a sep step's index memo
        self._touched = None         # ... and ragged_touched_pages' own
        self._prefill_valid = None   # real tokens in a sep prefill chunk
        # prefix-cache statistics (mirrored into the telemetry registry
        # by the serving engine)
        self.prefix_hits = 0          # full blocks served from the index
        self.prefix_misses = 0        # full blocks that had to prefill
        self.cached_tokens_total = 0
        self.cow_copies = 0
        # disagg handoff: pages imported before this pool ran its first
        # forward have no per-layer arrays to land in yet — their K/V is
        # staged here and applied as each layer's pool materializes (pool
        # creation order == layer forward order == export order)
        self._import_backlog: list = []     # (page, kv/layer, scales/layer)
        self.pages_imported = 0
        self.pages_exported = 0
        # speculative-decode rejection accounting (rollback())
        self.rollbacks = 0
        self.tokens_rolled_back = 0
        # decoder layers of ragged steps that a model ran as compiled
        # programs around the kernel entry (the model counts them here;
        # the serving engine mirrors the tick's delta)
        self.compiled_layer_calls = 0
        self._step_counters = {}     # add_step_counters / take_step_counters
        # a list while a warm-up wants to know which kernel calls a forward
        # makes (``ragged_attention`` notes each one's arguments there)
        self.attention_calls = None
        # tiered KV: host-RAM second level under the prefix index.
        # ``host_pool=None`` builds a private pool from the env knob
        # (PADDLE_KV_HOST_POOL_MB=0 keeps the tier off); the serving
        # engine passes its own long-lived pool so the warm tier
        # survives cache rebuilds.
        self.host_pool = host_pool if host_pool is not None else HostKVPool()
        if (len(self._groups) > 1 or self.state_layers) \
                and self.host_pool.enabled:
            raise NotImplementedError(
                "the host KV tier does not serve a cache with a window "
                "group or a state a slot")
        self.prefix_evictions_device = 0   # device-index LRU evictions
        # window groups: blocks given back during a request, cached tails
        # that eviction took, and prefix hits cut short for want of them
        self.window_blocks_released = 0
        self.window_blocks_evicted = 0
        self.prefix_hits_shortened_by_window = 0
        self.host_demotions = 0            # evictions caught by the tier
        self.host_promotions = 0           # host hits moved back to device
        self.host_promote_rejects = 0      # dtype/geometry mismatch drops
        # sep-parallel long-context prefill: per-slot stripe state — the
        # prompt span is chunked into fixed ``stripe`` token blocks whose
        # K/V lives as host-side stripes (the single-host stand-in for
        # pages striped across the sep ring's replicas), only the decode
        # tail occupies device pages
        self._sep = [None] * self.max_batch
        self._sep_pending = None     # per-layer K/V of the in-flight chunk
        self._sep_layer_i = 0        # forward-order layer cursor
        self.sep_stripes_stored = 0
        self.sep_chunks = 0
        self.sep_decode_steps = 0

    # -- page allocator ------------------------------------------------------
    def _alloc_page(self, group=None):
        g = group or self._groups[0]
        if not g.free:
            self._evict_lru(g)
        if not g.free:
            raise RuntimeError(
                f"KV page pool exhausted ({g.num_pages - 1} pages"
                + ("" if g.window is None else f" of group {g.label}")
                + ", all backing live sequences)")
        page = g.free.popleft()
        g.ref[page] = 1
        return int(page)

    def _evict_lru(self, group=None):
        """Reclaim the least-recently-used prefix-index entry of a group
        whose page has no live slot mapping (refcount 1 == the index's own
        ref). With the host tier enabled the page's bytes are demoted there
        before the device page frees — the prefix survives device churn
        and a later :meth:`assign` promotes it back."""
        g = group or self._groups[0]
        # the index is in LRU order: walk it from its old end, in place
        # (a copy of it a freed page was the cost of a full pool)
        victim = next(((d, p) for d, p in g.index.items()
                       if g.ref[p] == 1), None)
        if victim is None:
            return False
        digest, page = victim
        if g.window is None:
            self._demote(digest, page)
            self.prefix_evictions_device += 1
        else:
            self.window_blocks_evicted += 1
        del g.index[digest]
        del g.page_digest[page]
        g.ref[page] = 0
        g.free.append(page)
        return True

    def _page_entry(self, page):
        """Single-page host blob in the export_pages codec layout: one
        ``[kv, page_size, d]`` K/V pair per layer (pool/forward order; a
        latent layer's single pool gives a 1-tuple) plus the int8 row
        scales — np copies, device-independent."""
        layers = [tuple(np.asarray(pool[:, page]) for pool in pools)
                  for pools in self._pools.values()]
        scales = ([(np.asarray(ks[:, page]), np.asarray(vs[:, page]))
                   for ks, vs in self._scales.values()]
                  if self.kv_quant else None)
        return {"page_size": self.page_size, "kv_dtype": self.kv_dtype,
                "native_dtype": str(layers[0][0].dtype),
                "layers": layers, "scales": scales}

    def _demote(self, digest, page):
        """Eviction hook: copy the page into the host tier (no-op when
        the tier is off, or before the first forward materializes the
        pools — there is nothing to copy yet)."""
        hp = self.host_pool
        if hp is None or not hp.enabled or not self._pools:
            return False
        if hp.put(bytes(digest), self._page_entry(int(page))):
            self.host_demotions += 1
            return True
        return False

    def _promote(self, digest):
        """Admission hook: move a host-tier entry back onto a device page
        and register it in the prefix index (the index's own ref, like
        :meth:`commit_prefix`). Returns the page, or None on miss /
        mismatch / device pool exhaustion (entry stays host-resident in
        the last case so a later admission can retry)."""
        hp = self.host_pool
        if hp is None or not hp.enabled:
            return None
        entry = hp.get(bytes(digest))
        if entry is None:
            return None
        ok = (int(entry["page_size"]) == self.page_size
              and entry["kv_dtype"] == self.kv_dtype)
        if ok and self._pools:
            pool_dtype = str(next(iter(self._pools.values()))[0].dtype)
            ok = (entry["native_dtype"] == pool_dtype
                  and len(entry["layers"]) == len(self._pools))
        if not ok:
            # a stale entry from a differently-configured cache can never
            # land bit-exactly — drop it rather than poison the pool
            hp.take(bytes(digest))
            self.host_promote_rejects += 1
            return None
        entry = hp.take(bytes(digest))
        try:
            # may recursively _evict_lru -> _demote colder digests; our
            # entry is already off the host LRU so it cannot be a victim
            page = self._alloc_page()
        except RuntimeError:
            hp.put(bytes(digest), entry)
            return None
        if self._pools:
            scales = entry["scales"]
            for li, key in enumerate(list(self._pools)):
                self._pools[key] = tuple(
                    pool.at[:, page].set(blob) for pool, blob in
                    zip(self._pools[key], entry["layers"][li]))
                if self.kv_quant and scales is not None:
                    ks, vs = self._scales[key]
                    ksb, vsb = scales[li]
                    self._scales[key] = (ks.at[:, page].set(ksb),
                                         vs.at[:, page].set(vsb))
        else:
            self._import_backlog.append(
                (page, entry["layers"], entry["scales"]))
        self._index[bytes(digest)] = page     # MRU end, ref=1 = index's
        self._page_digest[page] = bytes(digest)
        self.host_promotions += 1
        hp.promotions += 1
        return page

    def _decref(self, page, group=None):
        g = group or self._groups[0]
        page = int(page)
        if page == 0:
            return
        if g.ref[page] <= 0:
            raise RuntimeError(f"page {page} refcount underflow")
        g.ref[page] -= 1
        if g.ref[page] == 0:
            # registered pages always carry the index's ref, so zero
            # means the page is unreachable — back to the free list
            g.free.append(page)

    def _ensure_blocks(self, slot, tokens):
        """Allocate fresh pages so ``slot`` can hold ``tokens`` context,
        in every group."""
        need = -(-int(tokens) // self.page_size)
        for g in self._groups:
            for i in range(int(g.n_blocks[slot]), need):
                g.tables[slot, i] = self._alloc_page(g)
            if need > g.n_blocks[slot]:
                g.n_blocks[slot] = need

    def _make_writable(self, slot, blk):
        """Copy-on-write: writing into a block whose page is shared
        (mapped by another slot, or registered in the prefix index) must
        first copy the page so the sharer's content survives."""
        for g in self._groups:
            page = int(g.tables[slot, blk])
            if page == 0:
                continue
            if g.ref[page] <= 1 and page not in g.page_digest:
                continue
            new = self._alloc_page(g)
            for key, pools in self._pools.items():
                if self._pool_group[key] is g:
                    self._pools[key] = tuple(
                        pool.at[:, new].set(pool[:, page]) for pool in pools)
            for key, (ks, vs) in self._scales.items():
                self._scales[key] = (ks.at[:, new].set(ks[:, page]),
                                     vs.at[:, new].set(vs[:, page]))
            self._decref(page, g)
            g.tables[slot, blk] = new
            self.cow_copies += 1

    def _group_of(self, layer):
        """The group of a layer's pages, by the window it declares
        (``layer.kv_window``; a layer that declares none is a full one)."""
        window = getattr(layer, "kv_window", None)
        for i, g in enumerate(self._groups):
            if g.window == window:
                return i
        raise ValueError(
            f"a layer declares kv_window {window} and the cache has the "
            f"groups {[g.label for g in self._groups]}: build it with "
            "window_groups={window: pages}")

    def group_usage(self):
        """``[(label, pages used, pages)]`` a group, the full one first."""
        return [(g.label, g.used, g.num_pages - 1) for g in self._groups]

    def _windowed(self, what):
        if len(self._groups) > 1:
            raise NotImplementedError(
                f"{what} does not serve a cache with a window group")
        if self.state_layers:
            raise NotImplementedError(
                f"{what} does not serve a cache with a state a slot (it "
                "would need a snapshot of the state)")

    # -- a state a slot ------------------------------------------------------
    @property
    def scratch_slot(self):
        """The row of a state array that a bucket's padding reads and
        writes."""
        return self.max_batch

    def layer_state(self, layer, spec):
        """``{name: array [max_batch + 1, *shape]}`` of a layer that keeps a
        state a slot, zeros when made, on its first forward; ``spec()`` ->
        ``{name: (shape a slot, dtype)}`` is asked then."""
        key = id(layer)
        if key not in self._states:
            if not self.state_layers:
                raise ValueError(
                    "a layer keeps a state a slot and the cache was built "
                    "without state_layers=True")
            self._states[key] = {
                name: jnp.zeros((self.max_batch + 1,) + tuple(shape), dtype)
                for name, (shape, dtype) in spec().items()}
        return self._states[key]

    def reset_state(self, slot):
        """Zero ``slot``'s rows of every state array, on the device (one
        program for all of them, the arrays donated)."""
        if not self._states:
            return
        with _spans.span("state/admit", slot=int(slot),
                         layers=len(self._states)):
            arrays = [a for st in self._states.values() for a in st.values()]
            arrays = iter(_zero_slot(arrays, jnp.int32(slot)))
            for st in self._states.values():
                for name in st:
                    st[name] = next(arrays)
            self.state_resets += 1

    def ragged_spans(self):
        """The armed step's spans as ``(slot, q_start, n_new, context tokens
        before the step)``."""
        return [(slot, qs, n, int(self.lens[slot]))
                for slot, qs, n in self._mode[1]]

    @property
    def free_page_count(self):
        return len(self._free)

    @property
    def used_page_count(self):
        return self.num_pages - 1 - len(self._free)

    @property
    def page_nbytes(self):
        """dtype-aware HBM bytes one page pins across every layer's K+V
        pools (and int8 scale arrays) — 0 until the first forward
        materializes the pools."""
        total = 0
        for key, pools in self._pools.items():
            total += (sum(pool.nbytes for pool in pools)
                      // self._pool_group[key].num_pages)
        for ks, vs in self._scales.values():
            total += (ks.nbytes + vs.nbytes) // self.num_pages
        return total

    def rollback(self, slot, n):
        """Truncate the last ``n`` context tokens of ``slot`` — the
        speculative-decode rejection path: a verify span wrote K/V for
        ``k`` drafted tokens, the target model accepted only ``m``, and
        positions past the accepted prefix must leave the context.
        Pages wholly past the truncation point are unmapped from the
        slot's table (refcount--): a page another slot still shares, or
        one the prefix index registered, keeps its other references and
        survives untouched; a private page returns to the free list.
        The kept partial block may hold stale K/V past the new length —
        masked by every reader's context bound and overwritten by the
        next write (which re-runs copy-on-write protection)."""
        slot = int(slot)
        n = int(n)
        if n <= 0:
            return 0
        self._windowed("rollback (a released block cannot come back)")
        if n > int(self.lens[slot]):
            raise ValueError(f"rollback {n} > slot context "
                             f"{int(self.lens[slot])}")
        new_len = int(self.lens[slot]) - n
        keep = -(-new_len // self.page_size)
        for blk in range(keep, int(self._n_blocks[slot])):
            self._decref(int(self._tables[slot, blk]))
            self._tables[slot, blk] = 0
        self._n_blocks[slot] = keep
        self.lens[slot] = new_len
        self.rollbacks += 1
        self.tokens_rolled_back += n
        return n

    # -- engine-facing lifecycle -------------------------------------------
    def assign(self, slot, prompt):
        """Admission: map the prompt's leading full blocks that hit the
        prefix index onto already-filled pages. Returns ``(cached_tokens,
        hit_blocks, missed_blocks)``; the caller only prefills
        ``prompt[cached_tokens:]``. Always leaves at least one token to
        prefill (the model must produce logits for the last prompt
        token)."""
        slot = int(slot)
        self.free(slot)                       # defensive: slot starts clean
        self.reset_state(slot)
        prompt = np.asarray(prompt).reshape(-1)
        chain = (block_hash_chain(prompt, self.page_size)
                 if self.enable_prefix_cache else [])
        self._chain[slot] = chain
        matchable = min(len(chain), (len(prompt) - 1) // self.page_size)
        matched = 0
        for i in range(matchable):
            page = self._index.get(chain[i])
            if page is not None:
                self._index.move_to_end(chain[i])      # LRU touch
            else:
                # device miss: the block may have been demoted to the
                # host tier — promote it back and keep matching
                page = self._promote(chain[i])
            if page is None:
                break
            self._ref[page] += 1
            self._tables[slot, i] = page
            matched += 1
        if len(self._groups) > 1:
            matched = self._match_windows(slot, chain, matched)
        self._n_blocks[slot] = matched
        cached = matched * self.page_size
        self.lens[slot] = cached
        # misses are real index lookups that came back empty — with the
        # cache disabled there are no lookups, so the hit rate stays
        # meaningful across mixed on/off runs
        missed = (max(len(prompt) // self.page_size - matched, 0)
                  if self.enable_prefix_cache else 0)
        self.prefix_hits += matched
        self.prefix_misses += missed
        self.cached_tokens_total += cached
        return cached, matched, missed

    def commit_prefix(self, slot):
        """Register the slot's now-filled full prompt blocks in the
        prefix index (digest chain computed at :meth:`assign`) so later
        prompts sharing the prefix reuse the pages. A digest another slot
        registered first wins — this slot's duplicate pages stay private
        and free normally. Returns the number of new registrations."""
        if not self.enable_prefix_cache:
            return 0
        slot = int(slot)
        chain = self._chain[slot] or []
        registered = 0
        for g in self._groups:
            for i in range(int(g.first[slot]),
                           min(len(chain), int(g.n_blocks[slot]))):
                registered += self._register(g, chain[i],
                                             int(g.tables[slot, i]))
        return registered

    def _register(self, g, digest, page):
        """Enter a filled block in a group's prefix index (the index's own
        reference); a digest or a page it already knows stays as it is."""
        if digest in g.index or page == 0 or page in g.page_digest:
            return 0
        g.index[digest] = page
        g.page_digest[page] = digest
        g.ref[page] += 1
        return 1

    # -- window groups -------------------------------------------------------
    def _window_first_live(self, g, filled):
        """The first block of a window group that a query at a position
        ``>= filled`` still sees: it sees keys ``j > i - window``, so from
        ``filled - window + 1`` on."""
        return max(int(filled) - g.window + 1, 0) // self.page_size

    def _match_windows(self, slot, chain, matched):
        """Cut a prefix hit of ``matched`` blocks (mapped in the full group
        already) to the longest ``b`` whose window tails the window groups
        hold, and map those: ``[first live block at b * page_size, b)``."""
        groups = self._groups[1:]
        runs = []      # a group: how many blocks in a row it has, ending at i
        for g in groups:
            run, out = 0, []
            for i in range(matched):
                run = run + 1 if chain[i] in g.index else 0
                out.append(run)
            runs.append(out)
        b = matched
        while b > 0 and not all(
                r[b - 1] >= b - self._window_first_live(g, b * self.page_size)
                for g, r in zip(groups, runs)):
            b -= 1
        if b < matched:
            self.prefix_hits_shortened_by_window += 1
            for i in range(b, matched):
                self._decref(self._tables[slot, i])
                self._tables[slot, i] = 0
        for g in groups:
            lo = self._window_first_live(g, b * self.page_size)
            for i in range(lo, b):
                page = g.index[chain[i]]
                g.index.move_to_end(chain[i])          # LRU touch
                g.ref[page] += 1
                g.tables[slot, i] = page
            g.first[slot], g.n_blocks[slot] = lo, b
        return b

    def _release_windows(self, slots):
        """Give back, in each window group, the blocks of ``slots`` that no
        later query can see (:meth:`_window_first_live` at the filled
        length). A full prompt block enters the group's prefix index first
        and so stays cached and evictable, as a finished request's pages
        do; any other returns to the free list."""
        released = 0
        for g in self._groups[1:]:
            for slot in slots:
                keep = min(self._window_first_live(g, self.lens[slot]),
                           int(g.n_blocks[slot]))
                chain = self._chain[slot] or []
                for blk in range(int(g.first[slot]), keep):
                    page = int(g.tables[slot, blk])
                    if blk < len(chain):
                        self._register(g, chain[blk], page)
                    self._decref(page, g)
                    g.tables[slot, blk] = 0
                    released += 1
                g.first[slot] = max(int(g.first[slot]), keep)
        self.window_blocks_released += released
        return released

    def begin_ragged(self, spans):
        """Arm the next forward as ONE ragged mixed prefill+decode step
        (Ragged Paged Attention, arxiv 2604.15464). ``spans`` is a list
        of ``(slot, q_start, n_new)``: slot's next ``n_new`` context
        tokens sit at ``q_start`` of the flat ``[1, tokens]`` batch
        (``n_new == 1`` is a decode token). ``q_start`` must be
        non-decreasing across spans; tokens outside every span are
        bucket padding — their K/V scatters to the scratch page and
        their output is discarded. Pages are allocated and
        copy-on-write-resolved here, once per step, for every span."""
        with _spans.span("kv/begin_ragged"):
            spans = [(int(s), int(qs), int(n)) for s, qs, n in spans]
            for slot, _, n_new in spans:
                start = int(self.lens[slot])
                if start + n_new > self.max_len:
                    raise ValueError(f"slot overflow: {start}+{n_new} > "
                                     f"{self.max_len}")
                self._ensure_blocks(slot, start + n_new)
                for blk in range(start // self.page_size,
                                 -(-(start + n_new) // self.page_size)):
                    self._make_writable(slot, blk)
            self._mode = ("ragged", spans)
            self._touched = None
            self._group_idx = {}
            self.step_memo = {}

    def free(self, slot):
        slot = int(slot)
        # sep slots own no device pages below their tail block — those
        # table entries stay 0 and _decref(0) is a no-op, so one loop
        # covers both lifecycles
        self._sep[slot] = None
        for g in self._groups:
            for i in range(int(g.first[slot]), int(g.n_blocks[slot])):
                self._decref(g.tables[slot, i], g)
            g.tables[slot, :] = 0
            g.n_blocks[slot] = g.first[slot] = 0
        self.lens[slot] = 0
        self._chain[slot] = None

    # -- prefill/decode disaggregation handoff -------------------------------
    def export_pages(self, digests):
        """Serialize the prefix-index pages backing the LEADING run of
        ``digests`` (a ``block_hash_chain``) — the prefill→decode
        disaggregation payload. Returns ``None`` when the first digest
        is not registered, else a dict with the digests actually
        exported and one host-side ``[kv, blocks, page_size, d]`` K/V
        array pair per attention layer (layer order == pool creation
        order == forward order, the cross-replica identity). On device
        tiers the ``np.asarray`` copies ARE the wire transfer.

        Tiered KV: a digest missing from the device index is looked up
        in the host tier — a demoted block still hands off (read-only,
        no promotion), so the disagg path survives device churn. The
        blob reports how many blocks came from host as ``host_pages``."""
        self._windowed("export_pages")
        entries, out_digests, host_pages = [], [], 0
        hp = self.host_pool
        for d in digests:
            page = self._index.get(d)
            if page is not None:
                if not self._pools:
                    break                 # device KV not materialized yet
                self._index.move_to_end(d)          # LRU touch
                entries.append(self._page_entry(int(page)))
            else:
                he = (hp.get(bytes(d))
                      if hp is not None and hp.enabled else None)
                if (he is None or int(he["page_size"]) != self.page_size
                        or he["kv_dtype"] != self.kv_dtype
                        or (entries and len(he["layers"]) !=
                            len(entries[0]["layers"]))):
                    break
                entries.append(he)
                host_pages += 1
            out_digests.append(bytes(d))
        if not entries:
            return None
        n_layers = len(entries[0]["layers"])
        if any(len(e["layers"]) != n_layers for e in entries):
            return None
        # stack per-page blobs into the [kv, blocks, page_size, d] wire
        # layout; int8 pools ship their quantized ints AS-IS plus the
        # per-row scales — the handoff blob shrinks with the pages and
        # the receiver re-registers bit-exactly (no requantization step)
        layers = [tuple(np.stack([e["layers"][li][a] for e in entries],
                                 axis=1)
                        for a in range(len(entries[0]["layers"][li])))
                  for li in range(n_layers)]
        scales = ([(np.stack([e["scales"][li][0] for e in entries], axis=1),
                    np.stack([e["scales"][li][1] for e in entries], axis=1))
                   for li in range(n_layers)] if self.kv_quant else None)
        self.pages_exported += len(entries)
        blob = {"page_size": self.page_size, "digests": out_digests,
                "layers": layers, "kv_dtype": self.kv_dtype,
                "native_dtype": str(layers[0][0].dtype), "scales": scales,
                "host_pages": host_pages}
        from ..profiler import ledger as _ledger
        if _ledger.is_enabled():
            # determinism ledger: seal the handoff payload so the
            # importer can verify it arrived bit-exact
            blob["ledger_digest"] = _ledger.seal_handoff(blob)
        return blob

    def import_pages(self, blob):
        """Receiver side of the disagg handoff: allocate pages for the
        exported blocks, write their K/V into this pool, and register
        the digests in the prefix index (holding the index's own ref,
        exactly like :meth:`commit_prefix`) so the next ``assign`` of a
        prompt sharing the chain maps straight onto them. Digests
        already registered are skipped — first writer wins. Returns the
        number of pages imported."""
        self._windowed("import_pages")
        if not blob or not self.enable_prefix_cache:
            return 0
        if int(blob["page_size"]) != self.page_size:
            raise ValueError(
                f"page_size mismatch: exporter {blob['page_size']} vs "
                f"importer {self.page_size}")
        blob_kv = blob.get("kv_dtype", "native")
        if blob_kv != self.kv_dtype:
            # an int8 blob landed in a native pool (or vice versa) would
            # silently de/re-quantize — reject instead; the disagg
            # handoff is best-effort and falls back to full prefill
            raise ValueError(f"kv_dtype mismatch: exporter {blob_kv} vs "
                             f"importer {self.kv_dtype}")
        if self._pools:
            pool_dtype = str(next(iter(self._pools.values()))[0].dtype)
            blob_native = blob.get("native_dtype", pool_dtype)
            if blob_native != pool_dtype:
                raise ValueError(
                    f"pool dtype mismatch: exporter {blob_native} vs "
                    f"importer {pool_dtype}")
        from ..profiler import ledger as _ledger
        if _ledger.is_enabled():
            # verify a sealed blob BEFORE any page registers — a
            # corrupted handoff must never serve tokens (raise mode) or
            # at least be on the record (warn mode)
            _ledger.check_handoff(blob)
        blob_scales = blob.get("scales")
        imported = 0
        for j, digest in enumerate(blob["digests"]):
            if digest in self._index:
                continue
            page = self._alloc_page()        # ref=1: the index's own ref
            per_layer = [tuple(a[:, j] for a in arrs)
                         for arrs in blob["layers"]]
            per_scales = ([(ks[:, j], vs[:, j]) for ks, vs in blob_scales]
                          if blob_scales is not None else None)
            if self._pools:
                if len(per_layer) != len(self._pools):
                    raise ValueError(
                        f"layer count mismatch: exporter "
                        f"{len(per_layer)} vs importer {len(self._pools)}")
                for li, key in enumerate(list(self._pools)):
                    self._pools[key] = tuple(
                        pool.at[:, page].set(blk) for pool, blk in
                        zip(self._pools[key], per_layer[li]))
                    if per_scales is not None:
                        ks, vs = self._scales[key]
                        ksb, vsb = per_scales[li]
                        self._scales[key] = (ks.at[:, page].set(ksb),
                                             vs.at[:, page].set(vsb))
            else:
                self._import_backlog.append((page, per_layer, per_scales))
            self._index[digest] = page
            self._page_digest[page] = digest
            imported += 1
        self.pages_imported += imported
        return imported

    # -- sep-parallel long-context prefill -----------------------------------
    def assign_sep(self, slot, prompt_tokens, stripe_tokens):
        """Arm ``slot`` for sep-parallel long-context serving: the prompt
        is prefilled in fixed ``stripe_tokens`` chunks whose K/V is kept
        as host-side stripes (ring order — stripe ``i``'s home replica is
        ``i % sep_ways``; see :meth:`export_stripes`) instead of device
        pages, so a prompt far larger than the page pool still serves.
        Only the trailing partial chunk and the decode tail land in
        device pages. No prefix-index interaction: a striped span is not
        page-granular shareable."""
        self._windowed("sep striping")
        slot = int(slot)
        self.free(slot)
        n = int(prompt_tokens)
        stripe = int(stripe_tokens)
        if stripe <= 0 or stripe % self.page_size:
            raise ValueError(f"stripe_tokens {stripe} must be a positive "
                             f"multiple of page_size {self.page_size}")
        if self.kv_quant:
            raise ValueError("sep prefill requires native KV pages "
                             "(PADDLE_KV_DTYPE=int8 is unsupported)")
        if n > self.max_len:
            raise ValueError(f"prompt {n} > max_len {self.max_len}")
        self._sep[slot] = {"stripe": stripe, "base": 0, "len": n,
                           "stripes": []}
        return -(-n // stripe)          # chunks the engine will drive

    def begin_sep_prefill(self, slot, n_valid=None):
        """Arm the next forward as one fixed-shape sep prefill chunk for
        ``slot`` (chunk length == stripe length; ``n_valid`` marks the
        real tokens of the trailing partial chunk)."""
        slot = int(slot)
        if self._sep[slot] is None:
            raise RuntimeError(f"slot {slot} is not sep-assigned")
        self._mode = ("sep_prefill", slot)
        self._idx = None
        self._prefill_valid = None if n_valid is None else int(n_valid)
        self._sep_pending = []
        self._sep_layer_i = 0
        self.sep_chunks += 1

    def begin_sep_decode(self, slot):
        """Arm the next forward as a [1, 1] decode step of a sep slot:
        the token's K/V lands in a device tail page; attention reads the
        stripes plus the tail through the same block table."""
        slot = int(slot)
        sep = self._sep[slot]
        if sep is None:
            raise RuntimeError(f"slot {slot} is not sep-assigned")
        self._mode = ("sep_decode", slot)
        self._idx = None
        self._sep_layer_i = 0
        blk0 = sep["base"] // self.page_size
        if int(self._n_blocks[slot]) < blk0:
            # blocks below the tail stay unallocated (stripes cover those
            # positions); start the allocator at the tail's first block
            self._n_blocks[slot] = blk0
        self._ensure_blocks(slot, int(self.lens[slot]) + 1)
        self._make_writable(slot, int(self.lens[slot]) // self.page_size)
        self.sep_decode_steps += 1

    def export_stripes(self, slot, sep_ways=None):
        """Striped-page disagg payload for a live sep slot: each stripe
        is tagged with its home replica on the sep ring (``i % ways``,
        ``PADDLE_SEP_WAYS``) — the layout a multi-process fleet shards
        by, and the single-host blob a migration ships whole."""
        slot = int(slot)
        sep = self._sep[slot]
        if sep is None:
            return None
        ways = int(sep_ways if sep_ways is not None
                   else os.environ.get("PADDLE_SEP_WAYS", "1") or 1)
        stripes = [{"home": j % max(ways, 1),
                    "layers": [(np.asarray(k), np.asarray(v))
                               for k, v in st]}
                   for j, st in enumerate(sep["stripes"])]
        native = (str(stripes[0]["layers"][0][0].dtype) if stripes
                  else None)
        # the decode tail [base, pos) lives in device pages — ship it as
        # raw [kv, n_tail, d] rows so the importer can resume mid-span
        base, pos = int(sep["base"]), int(self.lens[slot])
        tail = None
        if pos > base and self._pools:
            blk0 = base // self.page_size
            n_pages = -(-(pos - base) // self.page_size)
            tb = jnp.asarray(self._tables[slot, blk0:blk0 + n_pages])
            tail = [(np.asarray(kp[:, tb].reshape(
                         kp.shape[0], -1, kp.shape[-1])[:, :pos - base]),
                     np.asarray(vp[:, tb].reshape(
                         vp.shape[0], -1, vp.shape[-1])[:, :pos - base]))
                    for kp, vp in self._pools.values()]
        return {"page_size": self.page_size, "stripe": sep["stripe"],
                "base": base, "len": int(sep["len"]), "pos": pos,
                "native_dtype": native, "sep_ways": max(ways, 1),
                "stripes": stripes, "tail": tail}

    def import_stripes(self, slot, blob):
        """Receiver side of a striped handoff: arm ``slot`` with the
        exported stripes and resume at the exporter's position — the
        importer continues prefilling from ``pos`` (or decoding, if the
        span completed). Returns the number of stripes imported."""
        slot = int(slot)
        if not blob:
            return 0
        if int(blob["page_size"]) != self.page_size:
            raise ValueError(
                f"page_size mismatch: exporter {blob['page_size']} vs "
                f"importer {self.page_size}")
        stripe = int(blob["stripe"])
        if self.kv_quant:
            raise ValueError("sep stripes require a native KV pool")
        if self._pools and blob.get("native_dtype"):
            pool_dtype = str(next(iter(self._pools.values()))[0].dtype)
            if blob["native_dtype"] != pool_dtype:
                raise ValueError(
                    f"pool dtype mismatch: exporter "
                    f"{blob['native_dtype']} vs importer {pool_dtype}")
        base, pos = int(blob["base"]), int(blob["pos"])
        tail = blob.get("tail")
        if pos > base and tail is None:
            raise ValueError("striped blob resumes mid-span but carries "
                             "no tail rows")
        if tail is not None and not self._pools:
            # landing tail rows needs per-layer pools; stripes alone
            # (pos == base) import anywhere. Engines materialize pools
            # at warmup, so this only bites bare caches.
            raise ValueError("import_stripes needs materialized pools "
                             "to land a mid-span tail")
        if tail is not None and len(tail) != len(self._pools):
            raise ValueError(f"layer count mismatch: exporter "
                             f"{len(tail)} vs importer {len(self._pools)}")
        self.free(slot)
        self._sep[slot] = {
            "stripe": stripe, "base": base, "len": int(blob["len"]),
            "stripes": [[(np.asarray(k), np.asarray(v))
                         for k, v in st["layers"]]
                        for st in blob["stripes"]]}
        self.lens[slot] = pos
        if tail is not None:
            blk0 = base // self.page_size
            self._n_blocks[slot] = blk0
            self._ensure_blocks(slot, pos)
            n_pages = -(-(pos - base) // self.page_size)
            tb = jnp.asarray(self._tables[slot, blk0:blk0 + n_pages])
            pad = n_pages * self.page_size - (pos - base)
            for li, key in enumerate(list(self._pools)):
                kp, vp = self._pools[key]
                kb = jnp.pad(jnp.asarray(tail[li][0]),
                             ((0, 0), (0, pad), (0, 0)))
                vb = jnp.pad(jnp.asarray(tail[li][1]),
                             ((0, 0), (0, pad), (0, 0)))
                shape = (kp.shape[0], n_pages, self.page_size,
                         kp.shape[-1])
                self._pools[key] = (
                    kp.at[:, tb].set(kb.reshape(shape)),
                    vp.at[:, tb].set(vb.reshape(shape)))
        self.sep_stripes_stored += len(blob["stripes"])
        return len(blob["stripes"])

    def sep_view(self, slot):
        """Shape-relevant sep state for the engine's observatory
        signatures: the stripe count and the pow2 tail-page window the
        NEXT decode step would compile with."""
        sep = self._sep[int(slot)]
        if sep is None:
            return None
        n_tail = int(self.lens[slot]) + 1 - sep["base"]
        n_tp = -(-max(n_tail, 1) // self.page_size)
        return {"stripes": len(sep["stripes"]),
                "tail_pages": 1 << max(n_tp - 1, 0).bit_length(),
                "base": int(sep["base"]), "len": int(sep["len"])}

    @property
    def pos(self):
        # models read cache.pos for default position ids; the engine
        # always passes explicit per-slot positions instead
        m = self._mode
        if m and m[0] == "sep_prefill":
            return int(self.lens[m[1]])
        return 0

    def advance(self, s):
        mode, arg = self._mode
        if mode == "sep_prefill":
            sep = self._sep[arg]
            n = self._prefill_valid
            n = int(s) if n is None else min(int(s), n)
            if self._sep_pending:
                # a full chunk becomes the next stripe on the ring
                sep["stripes"].append(list(self._sep_pending))
                sep["base"] += sep["stripe"]
                self.sep_stripes_stored += 1
            self._sep_pending = None
            self.lens[arg] += n
        elif mode == "ragged":
            for slot, _, n_new in arg:
                self.lens[slot] += n_new
            if len(self._groups) > 1:
                with _spans.span("kv/release_window") as sp:
                    sp.set(blocks=self._release_windows(
                        [slot for slot, _, _ in arg]))
        else:                   # "sep_decode" slot
            self.lens[arg] += 1

    def _pool(self, layer, kv_heads, d, dtype, latent=False):
        """The layer's pools, made on its first forward: ``(k_pages,
        v_pages)`` of ``[kv_heads, pages, page_size, d]``, or for a
        ``latent`` layer ONE pool ``(kv_pages,)`` of ``[1, pages, d,
        page_size]``: ``d`` values a token, keys and values alike (the
        kernel reads the values as a prefix of them), a page's tokens as
        its columns. That order keeps the page size (a multiple of 128 on
        the chip) as the minor axis: a 576-wide row as the minor axis is
        no multiple of the TPU's 128 lanes, and the TPU then lays the pool
        out in an order of its own choosing which the scatter and the
        kernel each answer with a copy of the whole pool."""
        key = id(layer)
        if key not in self._pools:
            li = len(self._pools)       # this layer's forward-order index
            group = self._groups[self._group_of(layer)]
            self._pool_group[key] = group
            shape = ((kv_heads, group.num_pages, d, self.page_size)
                     if latent else
                     (kv_heads, group.num_pages, self.page_size, d))
            if latent and self.kv_quant:
                raise NotImplementedError(
                    "int8 pages for a latent pool are not built")
            pool_dtype = jnp.int8 if self.kv_quant else dtype
            kp = jnp.zeros(shape, pool_dtype)
            vp = None if latent else jnp.zeros(shape, pool_dtype)
            if self.kv_quant:
                # scale 1.0 everywhere: the scratch page (and any
                # never-written slot) dequantizes to finite garbage that
                # context bounds mask, never NaN/inf
                sshape = (kv_heads, self.num_pages, self.page_size)
                ks = jnp.ones(sshape, jnp.float32)
                vs = jnp.ones(sshape, jnp.float32)
            # land any pre-forward disagg imports (import_pages before the
            # first request) for this layer; entries whose page has since
            # been evicted from the index are dead — skip them
            for page, per_layer, per_scales in self._import_backlog:
                if li < len(per_layer) and page in self._page_digest:
                    kp = kp.at[:, page].set(per_layer[li][0])
                    if not latent:
                        vp = vp.at[:, page].set(per_layer[li][1])
                    if self.kv_quant and per_scales is not None:
                        ksb, vsb = per_scales[li]
                        ks = ks.at[:, page].set(ksb)
                        vs = vs.at[:, page].set(vsb)
            self._pools[key] = (kp,) if latent else (kp, vp)
            if self.kv_quant:
                self._scales[key] = (ks, vs)
        return self._pools[key]

    def layer_pools(self, layer, kv_spec):
        """This layer's pools as :func:`scatter_kv_rows` takes them.
        ``kv_spec()`` -> ``(kv_heads, head_dim, dtype[, latent])`` is asked
        only on the layer's first forward, which creates them."""
        key = id(layer)
        if key not in self._pools:
            self._pool(layer, *kv_spec())
        return self._pools[key] + (self._scales[key] if self.kv_quant
                                   else ())

    def set_layer_pools(self, layer, pools):
        key = id(layer)
        self._pools[key] = tuple(pools[:2])     # a latent layer's: one
        if self.kv_quant:
            self._scales[key] = tuple(pools[2:])

    def _scatter(self, layer, k_pages, v_pages, kt, vt, page_ids, slot_ids):
        """Write this forward's K/V rows into the pages
        (:func:`scatter_kv_rows`) and return the updated pools."""
        pools = (k_pages, v_pages) + (self._scales[id(layer)]
                                      if self.kv_quant else ())
        pools = scatter_kv_rows(pools, kt, vt, page_ids, slot_ids)
        self.set_layer_pools(layer, pools)
        return pools[0], pools[1]

    def _layer_scales(self, layer):
        """(k_scales, v_scales) for the paged kernels' dequant-gather
        tiers, or (None, None) on native pools."""
        if not self.kv_quant:
            return None, None
        return self._scales[id(layer)]

    # -- the ragged step's halves, shared by ``attend`` and by a model that
    # runs its layers as compiled programs around the kernel (llama.py) ----
    @property
    def ragged_armed(self):
        """True between :meth:`begin_ragged` and the next ``begin_*``."""
        return self._mode is not None and self._mode[0] == "ragged"

    def _ragged_index(self, s, group=0):
        """The armed step's indices over a flat batch of ``s`` tokens,
        built once a forward and group and shared by the group's layers:
        the scatter's ``page_ids`` / ``slot_ids`` on the device, and the
        kernel's descriptors as HOST arrays (block tables, then slot,
        q_start, q_len and context length a span) — the q-block schedule is
        built from them on the host, so a device copy would only be read
        back."""
        if group not in self._group_idx:
            self._group_idx[group] = self._build_ragged_index(
                s, self._groups[group])
        return self._group_idx[group]

    def _build_ragged_index(self, s, g):
        spans = self._mode[1]
        page_ids = np.zeros(s, np.int64)     # default: scratch
        slot_ids = np.zeros(s, np.int64)
        for slot, qs, n_new in spans:
            pos = np.arange(self.lens[slot], self.lens[slot] + n_new)
            page_ids[qs:qs + n_new] = g.tables[slot, pos // self.page_size]
            slot_ids[qs:qs + n_new] = pos % self.page_size
        return (
            jnp.asarray(page_ids), jnp.asarray(slot_ids),
            g.tables.copy(),
            np.asarray([sl for sl, _, _ in spans], np.int32),
            np.asarray([qs for _, qs, _ in spans], np.int32),
            np.asarray([n for _, _, n in spans], np.int32),
            np.asarray([int(self.lens[sl]) + n for sl, _, n in spans],
                       np.int32))

    def ragged_scatter_ids(self, s, layer=None):
        """``(page_ids, slot_ids)`` [s]: where the armed step's tokens
        land in the pools (of ``layer``'s group); bucket padding lands in
        the scratch page."""
        group = 0 if layer is None else self._group_of(layer)
        return self._ragged_index(s, group)[:2]

    def ragged_touched_pages(self, s):
        """``(pages [n], row_page [s])`` for a latent pool's scatter
        (:func:`_scatter_latent_rows`): the distinct pages the armed step
        writes, padded with the scratch page to a length that depends on
        ``s`` alone (a span is contiguous, so a step writes at most a page
        a span and ``s // page_size`` more), and each token's index into
        them. Built once a forward, on the host."""
        if self._touched is None:
            page_ids = np.asarray(self._ragged_index(s)[0])
            pages, row_page = np.unique(page_ids, return_inverse=True)
            n = self.max_batch + s // self.page_size + 2
            if len(pages) > n:
                raise RuntimeError(f"a step of {s} tokens writes "
                                   f"{len(pages)} pages, over {n}")
            self._touched = (
                jnp.asarray(np.pad(pages, (0, n - len(pages))), jnp.int32),
                jnp.asarray(row_page.reshape(-1), jnp.int32))
        return self._touched

    def ragged_attention(self, layer, qa, sm_scale=None, value_dim=None,
                         descriptors=None):
        """The armed step's attention for ``qa`` [tokens, heads, d] over
        this layer's pools as they stand (the step's K/V already
        scattered): the eager kernel entry, once a layer. A latent layer
        (one pool) gives ``value_dim``: the values are that prefix of each
        row, and the output is ``[tokens, heads, value_dim]``.
        ``descriptors`` (block tables, then slot, q_start, q_len and
        context length a span) stand in for the armed step's: a warm-up
        reaches the kernel's other job buckets with them."""
        from ..ops.pallas.ragged_paged_attention import (
            ragged_paged_attention)
        if self.attention_calls is not None:
            self.attention_calls.append(
                (layer, qa.shape, qa.dtype, sm_scale, value_dim))
        group = self._group_of(layer)
        tables, seq_slots, q_starts, q_lens, ctx_lens = (
            descriptors or self._ragged_index(qa.shape[0], group)[2:])
        pools = self._pools[id(layer)]
        k_pages, v_pages = pools if len(pools) == 2 else (pools[0], None)
        ksc, vsc = self._layer_scales(layer)
        return ragged_paged_attention(
            qa, k_pages, v_pages, tables, seq_slots, q_starts, q_lens,
            ctx_lens, sm_scale=sm_scale, value_dim=value_dim,
            k_scales=ksc, v_scales=vsc,
            interpret=jax.default_backend() != "tpu",
            window=self._groups[group].window)

    def attend_latent(self, layer, q, row, sm_scale, value_dim):
        """Eager attention of a latent layer: ``q`` [1, s, heads, d] (the
        absorbed query) and ``row`` [1, s, 1, d] (this forward's cache
        rows). Latent layers are served by the ragged scheduler alone:
        scatter, then the ragged kernel over the layer's one pool."""
        from ..autograd.tape import apply
        if not self.ragged_armed:
            raise NotImplementedError(
                "a latent layer attends through the ragged step only "
                f"(begin_ragged); the cache is armed for {self._mode}")
        ra = row._data if isinstance(row, Tensor) else row
        b, s, _, d = ra.shape
        assert b == 1, "ragged step packs one flat token batch"
        page_ids, slot_ids = self.ragged_scatter_ids(s)
        pools = self.layer_pools(layer, lambda: (1, d, ra.dtype, True))
        self.set_layer_pools(layer, scatter_kv_rows(
            pools, jnp.moveaxis(ra[0], 1, 0), page_ids=page_ids,
            slot_ids=slot_ids, touched=self.ragged_touched_pages(s)))

        def fn(qa):
            return self.ragged_attention(layer, qa[0], sm_scale,
                                         value_dim)[None]
        return apply(fn, q, op_name="ragged_paged_attention")

    # -- device counters of a step, read with the tick's one sync ----------
    def add_step_counters(self, found):
        """A model leaves device values here during a forward (name ->
        array; a name met again is stacked: one entry a layer)."""
        for name, value in found.items():
            self._step_counters.setdefault(name, []).append(value)

    def take_step_counters(self):
        """-> {name: [device arrays, one a layer that left one]}, emptied:
        whoever syncs the step reads them in the same transfer."""
        found, self._step_counters = self._step_counters, {}
        return found

    # -- attention ----------------------------------------------------------
    def attend(self, layer, q, k, v, training=False, dropout_p=0.0):
        from ..autograd.tape import apply

        if self._mode is None:
            raise RuntimeError(
                "SlotPagedKVCache.attend on an unarmed cache: arm the "
                "forward with begin_ragged (or begin_sep_prefill / "
                "begin_sep_decode for a sep slot) first")
        mode, arg = self._mode
        ka = k._data if isinstance(k, Tensor) else k
        va = v._data if isinstance(v, Tensor) else v
        b, s, kv_heads, d = ka.shape
        k_pages, v_pages = self._pool(layer, kv_heads, d, ka.dtype)

        if mode in ("sep_prefill", "sep_decode"):
            # long-context serving: attention over the slot's host-side
            # stripes (the ring-attention schedule run block-by-block —
            # each stripe is one ring step; see ops/pallas/ring_attention
            # .blockwise_causal_attention for the tiering) plus the
            # device-resident tail, online-softmax merged.
            assert b == 1, "sep serving admits one request at a time"
            slot = arg
            sep = self._sep[slot]
            stripe = sep["stripe"]
            li = self._sep_layer_i        # forward-order stripe index
            self._sep_layer_i += 1
            blocks = [(jnp.asarray(st[li][0])[None],
                       jnp.asarray(st[li][1])[None], j * stripe)
                      for j, st in enumerate(sep["stripes"])]
            kt = jnp.moveaxis(ka[0], 1, 0)          # [kv, s, d]
            vt = jnp.moveaxis(va[0], 1, 0)
            if mode == "sep_prefill":
                if s != stripe:
                    raise ValueError(f"sep chunk must be padded to the "
                                     f"stripe length: got {s}, expected "
                                     f"{stripe}")
                start = int(self.lens[slot])        # == sep["base"]
                n_valid = s if self._prefill_valid is None \
                    else min(self._prefill_valid, s)
                if start + n_valid > self.max_len:
                    raise ValueError(f"slot overflow: {start}+{n_valid} "
                                     f"> {self.max_len}")
                # the chunk itself: pad keys sit past every valid query's
                # causal window, so attending the raw [kv, s, d] is safe
                blocks.append((jnp.swapaxes(ka, 1, 2),
                               jnp.swapaxes(va, 1, 2), start))
                if n_valid == s:
                    # full chunk -> staged as the next ring stripe
                    # (host-side np copy) at advance()
                    self._sep_pending.append((np.asarray(kt),
                                              np.asarray(vt)))
                else:
                    # trailing partial chunk -> device tail pages, read
                    # by decode through the block table
                    if self._idx is None:
                        blk0 = start // self.page_size
                        if int(self._n_blocks[slot]) < blk0:
                            self._n_blocks[slot] = blk0
                        self._ensure_blocks(slot, start + n_valid)
                        pos = np.arange(start, start + s)
                        valid = pos < start + n_valid
                        blk_ids = np.minimum(pos // self.page_size,
                                             self.pages_per_seq - 1)
                        self._idx = (
                            jnp.asarray(np.where(
                                valid, self._tables[slot, blk_ids], 0)),
                            jnp.asarray(np.where(
                                valid, pos % self.page_size, 0)))
                    page_ids, slot_ids = self._idx
                    self._scatter(layer, k_pages, v_pages, kt, vt,
                                  page_ids, slot_ids)
                q_offset = start
            else:                          # sep_decode
                assert s == 1
                pos_tok = int(self.lens[slot])
                if self._idx is None:
                    self._idx = (
                        jnp.asarray(
                            [self._tables[slot,
                                          pos_tok // self.page_size]]),
                        jnp.asarray([pos_tok % self.page_size]))
                page_ids, slot_ids = self._idx
                new_kp, new_vp = self._scatter(layer, k_pages, v_pages,
                                               kt, vt, page_ids, slot_ids)
                base = sep["base"]
                blk0 = base // self.page_size
                n_tail = pos_tok + 1 - base
                n_tp = -(-n_tail // self.page_size)
                # pow2-bucketed tail window keeps the compiled-shape set
                # bounded (and declarable: always the pure power of two,
                # zero-padded past the table's end); entries past the
                # allocated tail are the scratch page, causally masked
                # (their positions exceed the query's)
                npp = 1 << max(n_tp - 1, 0).bit_length()
                tbl = self._tables[slot, blk0:blk0 + npp]
                if tbl.shape[0] < npp:
                    tbl = np.pad(tbl, (0, npp - tbl.shape[0]))
                tb = jnp.asarray(tbl)
                kf = new_kp[:, tb].reshape(kv_heads, -1, d)[None]
                vf = new_vp[:, tb].reshape(kv_heads, -1, d)[None]
                blocks.append((kf, vf, base))
                q_offset = pos_tok

            from ..ops.pallas.ring_attention import (
                blockwise_causal_attention)

            def fn(qa):
                out = blockwise_causal_attention(
                    jnp.swapaxes(qa, 1, 2), q_offset, blocks)
                return jnp.swapaxes(out, 1, 2)
            return apply(fn, q, op_name="sep_ring_attention")

        # ragged: ONE program for the whole tick: decode tokens and
        # prefill spans of several sequences packed into a flat
        # [1, tokens] batch (token-budget scheduler). K/V scatter first,
        # then the ragged kernel reads every span's full context back
        # from the pages — causal masking inside each span comes from
        # the kernel's per-token context bound.
        assert b == 1, "ragged step packs one flat token batch"
        page_ids, slot_ids = self.ragged_scatter_ids(s, layer)
        kt = jnp.moveaxis(ka[0], 1, 0)          # [kv, s, d]
        vt = jnp.moveaxis(va[0], 1, 0)
        self._scatter(layer, k_pages, v_pages, kt, vt, page_ids,
                      slot_ids)

        def fn(qa):
            return self.ragged_attention(layer, qa[0])[None]
        return apply(fn, q, op_name="ragged_paged_attention")


def _sample_logits(logits, do_sample, top_k, top_p, temperature, key=None):
    """logits [b, V] (jnp) -> token ids [b] (jnp).

    ``key`` is an explicit jax PRNG key for the categorical draw; with
    it the sample is a pure function of (logits, key) — the serving
    engine derives one key per (request seed, row, token index) so
    sampled decode is reproducible and speculative verification of
    sampled tokens is deterministic. ``None`` falls back to the global
    stateful generator (legacy call-order-dependent behavior)."""
    if not do_sample:
        return jnp.argmax(logits, axis=-1)
    logits = logits / max(temperature, 1e-6)
    if top_k:
        kth = jnp.sort(logits, axis=-1)[:, -int(top_k)][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p and top_p < 1.0:
        sorted_l = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jnp.cumsum(
            jnp.exp(sorted_l - jnp.max(sorted_l, -1, keepdims=True)) /
            jnp.sum(jnp.exp(sorted_l - jnp.max(sorted_l, -1, keepdims=True)),
                    -1, keepdims=True), axis=-1)
        cutoff_idx = jnp.sum(probs < top_p, axis=-1)
        kth = jnp.take_along_axis(sorted_l, cutoff_idx[:, None], axis=-1)
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    import jax
    if key is None:
        key = prandom.next_key()
    return jax.random.categorical(key, logits, axis=-1)


class GenerationMixin:
    """Adds ``generate`` to causal-LM models whose forward accepts
    ``cache=`` (``supports_cache=True``) or recomputes otherwise."""

    supports_cache = False

    @no_grad()
    def generate(self, input_ids, max_new_tokens=32, max_length=None,
                 do_sample=False, top_k=0, top_p=1.0, temperature=1.0,
                 eos_token_id=None, num_beams=1, length_penalty=1.0,
                 seed=None, **kw):
        """Returns generated ids [b, prompt + new] (prompt included,
        reference decode contract). ``num_beams > 1`` runs beam search
        (reference ``decode_strategy='beam_search'``) — greedy expansion
        over the top-``num_beams`` hypotheses with KV-cache reordering;
        requires ``do_sample=False``. ``seed`` makes sampled decode
        reproducible: step ``i`` draws with ``fold_in(key(seed), i)``
        instead of the global stateful generator."""
        input_ids = input_ids if isinstance(input_ids, Tensor) \
            else Tensor(np.asarray(input_ids, np.int64))
        if max_length is not None:
            max_new_tokens = max(max_length - input_ids.shape[1], 0)
            max_length = None
        if num_beams > 1:
            if do_sample:
                raise ValueError("beam search requires do_sample=False "
                                 "(reference beam_search is deterministic)")
            return self._beam_search(input_ids, max_new_tokens, num_beams,
                                     eos_token_id, length_penalty)
        was_training = self.training
        self.eval()
        try:
            ids = input_ids                   # prologue already normalized
            cache = kw.pop("cache", None)
            if cache is None and self.supports_cache:
                if kw.pop("use_paged_cache", False):
                    cache = PagedKVCache(
                        page_size=kw.pop("page_size", 16),
                        max_len=ids.shape[1] + max_new_tokens)
                else:
                    cache = KVCache()
            cur = ids
            all_ids = ids._data
            finished = jnp.zeros((ids.shape[0],), bool)
            base_key = None
            if seed is not None:
                import jax
                base_key = jax.random.key(int(seed))
            for step in range(max_new_tokens):
                logits = self.forward(cur, cache=cache) \
                    if cache is not None else self.forward(
                        Tensor(all_ids))
                lg = logits._data[:, -1].astype(jnp.float32)
                step_key = None
                if base_key is not None:
                    import jax
                    step_key = jax.random.fold_in(base_key, step)
                nxt = _sample_logits(lg, do_sample, top_k, top_p,
                                     temperature,
                                     key=step_key).astype(all_ids.dtype)
                if eos_token_id is not None:
                    nxt = jnp.where(finished,
                                    jnp.asarray(eos_token_id, nxt.dtype),
                                    nxt)
                    finished = jnp.logical_or(finished, nxt == eos_token_id)
                all_ids = jnp.concatenate([all_ids, nxt[:, None]], axis=1)
                cur = Tensor(nxt[:, None])
                if eos_token_id is not None and bool(finished.all()):
                    break
            return Tensor(all_ids)
        finally:
            if was_training:
                self.train()

    @no_grad()
    def _beam_search(self, input_ids, max_new_tokens, num_beams,
                     eos_token_id, length_penalty):
        """Batched beam search over the dense KV cache (paged pools are
        per-sequence-owned, so a beam hop would alias pages — the serving
        engines cover paged decode; beams use the concat cache)."""
        import jax

        was_training = self.training
        self.eval()
        try:
            ids = input_ids                   # generate() already normalized
            b, prompt = ids.shape
            n = int(num_beams)
            # expand rows to beams: [b*n, s]
            all_ids = jnp.repeat(ids._data, n, axis=0)
            cache = KVCache() if self.supports_cache else None
            # beam 0 carries the prompt; others start dead so step 1
            # doesn't pick n copies of the same continuation
            scores = jnp.tile(jnp.asarray([0.0] + [-jnp.inf] * (n - 1),
                                          jnp.float32), (b,))      # [b*n]
            finished = jnp.zeros((b * n,), bool)
            lengths = jnp.zeros((b * n,), jnp.float32)   # generated tokens
            cur = Tensor(all_ids)
            for step in range(max_new_tokens):
                logits = self.forward(cur, cache=cache) \
                    if cache is not None else self.forward(Tensor(all_ids))
                lp = jax.nn.log_softmax(
                    logits._data[:, -1].astype(jnp.float32), axis=-1)
                vocab = lp.shape[-1]
                if eos_token_id is not None:
                    # a finished beam only continues with EOS at no cost
                    frozen = jnp.full((vocab,), -jnp.inf
                                      ).at[int(eos_token_id)].set(0.0)
                    lp = jnp.where(finished[:, None], frozen[None, :], lp)
                total = scores[:, None] + lp                       # [b*n, V]
                flat = total.reshape(b, n * vocab)
                top_s, top_i = jax.lax.top_k(flat, n)              # [b, n]
                parent = (top_i // vocab + jnp.arange(b)[:, None] * n
                          ).reshape(-1)                            # [b*n]
                token = (top_i % vocab).reshape(-1)
                scores = top_s.reshape(-1)
                all_ids = jnp.concatenate(
                    [all_ids[parent], token[:, None].astype(all_ids.dtype)],
                    axis=1)
                # per-hypothesis true length: frozen at the step EOS fired
                lengths = jnp.where(finished[parent], lengths[parent],
                                    float(step + 1))
                finished = finished[parent]
                if eos_token_id is not None:
                    finished = jnp.logical_or(finished,
                                              token == eos_token_id)
                if cache is not None:
                    cache.reorder(parent)
                cur = Tensor(token[:, None].astype(all_ids.dtype))
                if eos_token_id is not None and bool(finished.all()):
                    break
            # each row's best hypothesis under the PER-HYPOTHESIS length
            # penalty (reference normalizes by the length at EOS)
            norm = scores / jnp.maximum(lengths, 1.0) ** float(
                length_penalty)
            best = jnp.argmax(norm.reshape(b, n), axis=-1) \
                + jnp.arange(b) * n
            return Tensor(all_ids[best])
        finally:
            if was_training:
                self.train()
