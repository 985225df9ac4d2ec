"""Ask the TPU v5e compiler, without a chip, whether the Pallas kernels of
the serving and training main paths compile at the widths
``chip_smoke.py`` runs them (Llama-3-8B head geometry: 32 q heads, 8 kv
heads, head_dim 128, bf16, the serving engine's default page pool).

Interpret mode cannot see what Mosaic refuses (block shapes off the
(8, 128) tiling, too much VMEM/SMEM), so these compiles are the guard
between the CPU tests and the first chip run. A compile that passes is
not a chip run: nothing here executes.

Everything that touches the TPU compiler happens inside fixtures/tests of
THIS file (never at import, never in conftest): only one process may
hold libtpu, and pytest-xdist workers all import every test module.
"""
import functools
import importlib
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

# Llama-3-8B attention geometry + ContinuousServingEngine defaults
HEADS, KV_HEADS, HEAD_DIM = 32, 8, 128
PAGE_SIZE, MAX_BATCH, MAX_LEN = 16, 8, 2048
PAGES_PER_SEQ = MAX_LEN // PAGE_SIZE              # 128
NUM_PAGES = MAX_BATCH * PAGES_PER_SEQ + 1         # 1025 (page 0 = scratch)
TOKEN_BUDGET = 256                                # ragged tick bucket
TRAIN_SEQ = 2048
SM_SCALE = HEAD_DIM ** -0.5


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compile_on_chip(one_chip):
    """compile(fn, *(shape, dtype)) -> compiled text for one v5e chip."""
    def _compile(fn, *specs):
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                for shape, dtype in specs]
        return jax.jit(fn).lower(*args).compile().as_text()
    return _compile


def _pool(dtype):
    return ((KV_HEADS, NUM_PAGES, PAGE_SIZE, HEAD_DIM), dtype)


_SCALES = ((KV_HEADS, NUM_PAGES, PAGE_SIZE), jnp.float32)


def _mixed_tick():
    """A ragged tick as the default scheduler packs it: 6 decode tokens +
    one 250-token prefill chunk of a sequence with 300 tokens of context
    already paged in, padded to the 256-token bucket."""
    rng = np.random.default_rng(0)
    tables = rng.integers(1, NUM_PAGES, (MAX_BATCH, PAGES_PER_SEQ)).astype(
        np.int32)
    n_dec = 6
    seq_slots = np.arange(n_dec + 1, dtype=np.int32)
    q_starts = np.arange(n_dec + 1, dtype=np.int32)
    q_lens = np.array([1] * n_dec + [TOKEN_BUDGET - n_dec], np.int32)
    ctx = np.array([700, 650, 400, 333, 128, 17, 300 + TOKEN_BUDGET - n_dec],
                   np.int32)
    return tables, seq_slots, q_starts, q_lens, ctx


def _has_kernel(text):
    return "tpu_custom_call" in text


def test_flash_fwd_compiles(compile_on_chip):
    from paddle_tpu.ops.pallas import flash_attention
    fn = functools.partial(flash_attention, causal=True, interpret=False)
    q = ((1, TRAIN_SEQ, HEADS, HEAD_DIM), jnp.bfloat16)
    kv = ((1, TRAIN_SEQ, KV_HEADS, HEAD_DIM), jnp.bfloat16)
    assert _has_kernel(compile_on_chip(fn, q, kv, kv))


def test_flash_fwd_bwd_compiles(compile_on_chip):
    from paddle_tpu.ops.pallas import flash_attention

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=False)
        return out.astype(jnp.float32).sum()

    q = ((1, TRAIN_SEQ, HEADS, HEAD_DIM), jnp.bfloat16)
    kv = ((1, TRAIN_SEQ, KV_HEADS, HEAD_DIM), jnp.bfloat16)
    text = compile_on_chip(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert text.count("tpu_custom_call") >= 3      # fwd + dq + dkv


@pytest.mark.parametrize("head_dim", [64, 192])
def test_flash_default_tiles_compile_at_other_head_widths(compile_on_chip,
                                                          head_dim):
    """The tiles come from the shapes (``flash_attention.tile_rule``), so a
    head of another width (64: GPT-2 / Whisper-class; 192: DeepSeek-V3's
    q / k heads) takes whatever the rule gives it at a training length: a
    tile that overflows VMEM there fails here and not in a user's run."""
    from paddle_tpu.ops.pallas import flash_attention

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=False)
        return out.astype(jnp.float32).sum()

    q = ((1, 2 * TRAIN_SEQ, 8, head_dim), jnp.bfloat16)
    kv = ((1, 2 * TRAIN_SEQ, 2, head_dim), jnp.bfloat16)
    text = compile_on_chip(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert len(_custom_calls(text)) == 3           # fwd + dq + dkv


def test_ring_partial_with_lse_compiles(compile_on_chip):
    """The blockwise/ring partial (sep long-context prefill): one
    512-token stripe of queries against one stripe of keys, returning
    (out, lse) for the online-softmax merge."""
    from paddle_tpu.ops.pallas.ring_attention import ring_partial
    stripe = 512
    fn = functools.partial(ring_partial, q_offset=stripe, kv_offset=0,
                           sm_scale=SM_SCALE, impl="kernel",
                           interpret=False)
    q = ((1, HEADS, stripe, HEAD_DIM), jnp.bfloat16)
    kv = ((1, KV_HEADS, stripe, HEAD_DIM), jnp.bfloat16)
    assert _has_kernel(compile_on_chip(fn, q, kv, kv))


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_decode_compiles(compile_on_chip, kv_dtype):
    pa = importlib.import_module("paddle_tpu.ops.pallas.paged_attention")
    q = ((MAX_BATCH, HEADS, HEAD_DIM), jnp.bfloat16)
    tables = ((MAX_BATCH, PAGES_PER_SEQ), jnp.int32)   # SMEM-prefetched
    lens = ((MAX_BATCH,), jnp.int32)
    if kv_dtype == "int8":
        def fn(q, kp, vp, ks, vs, tbl, ln):
            return pa._paged_attention_pallas_quant(
                q, kp, vp, ks, vs, tbl, ln, sm_scale=SM_SCALE,
                interpret=False)
        text = compile_on_chip(fn, q, _pool(jnp.int8), _pool(jnp.int8),
                               _SCALES, _SCALES, tables, lens)
    else:
        def fn(q, kp, vp, tbl, ln):
            return pa._paged_attention_pallas(
                q, kp, vp, tbl, ln, sm_scale=SM_SCALE, interpret=False)
        text = compile_on_chip(fn, q, _pool(jnp.bfloat16),
                               _pool(jnp.bfloat16), tables, lens)
    assert _has_kernel(text)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_ragged_qblock_compiles(compile_on_chip, kv_dtype):
    """The q-block grid builds its job schedule host-side, so the
    descriptors are concrete (as in the eager serving tick) and only the
    tensors are described."""
    rpa = importlib.import_module(
        "paddle_tpu.ops.pallas.ragged_paged_attention")
    tables, seq_slots, q_starts, q_lens, ctx = _mixed_tick()
    q = ((TOKEN_BUDGET, HEADS, HEAD_DIM), jnp.bfloat16)
    if kv_dtype == "int8":
        def fn(q, kp, vp, ks, vs):
            return rpa._ragged_paged_attention_pallas_qblock(
                q, kp, vp, tables, seq_slots, q_starts, q_lens, ctx,
                sm_scale=SM_SCALE, interpret=False, k_scales=ks,
                v_scales=vs)
        text = compile_on_chip(fn, q, _pool(jnp.int8), _pool(jnp.int8),
                               _SCALES, _SCALES)
    else:
        def fn(q, kp, vp):
            return rpa._ragged_paged_attention_pallas_qblock(
                q, kp, vp, tables, seq_slots, q_starts, q_lens, ctx,
                sm_scale=SM_SCALE, interpret=False)
        text = compile_on_chip(fn, q, _pool(jnp.bfloat16),
                               _pool(jnp.bfloat16))
    assert _has_kernel(text)


def _roofline_patterns(metric):
    """The module of a roofline reader, for the regexes it tells its
    kernels' device events by (a benchmark file no program PR may edit)."""
    return importlib.import_module("benchmark.layer_metrics." + metric)


def _custom_calls(text):
    """A device event's name in a trace is its whole HLO instruction: the
    compiled text's custom-call lines are those names."""
    return [ln.strip() for ln in text.splitlines()
            if "tpu_custom_call" in ln and " = " in ln]


def test_qblock_device_event_name_is_the_one_its_roofline_matches(
        compile_on_chip):
    """``qblock_roofline`` finds the kernel's device time by the name the
    jitted wrapper ``_qblock_device`` gives its Mosaic call. A rename (a
    ``named_scope``, a ``pallas_call(name=)``, another wrapper name)
    silences the metric, and a traced run that lacks it is refused: it
    fails here first, on the CPU."""
    rpa = importlib.import_module(
        "paddle_tpu.ops.pallas.ragged_paged_attention")
    tables, seq_slots, q_starts, q_lens, ctx = _mixed_tick()

    def fn(q, kp, vp):
        return rpa._ragged_paged_attention_pallas_qblock(
            q, kp, vp, tables, seq_slots, q_starts, q_lens, ctx,
            sm_scale=SM_SCALE, interpret=False)

    text = compile_on_chip(fn, ((TOKEN_BUDGET, HEADS, HEAD_DIM),
                                jnp.bfloat16),
                           _pool(jnp.bfloat16), _pool(jnp.bfloat16))
    calls = _custom_calls(text)
    kernel = re.compile(_roofline_patterns("qblock_roofline").KERNEL)
    assert calls and all(kernel.search(c) for c in calls), calls


# -- the device half at serve_chat_closed's widths --------------------------
# (benchmark/configs/mistral-7b-serve-16l.json: 32 slots, max_len 2048,
# 2,049 pages of 16 tokens, float32 pools under a bf16 model)
CELL_SLOTS, CELL_PAGES = 32, 2049


@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
def test_qblock_device_half_at_the_cells_widths_and_largest_job_bucket(
        compile_on_chip, kv_dtype):
    """The flat-list grid ``(jobs,)`` with every KV head of a page in one
    step: Mosaic accepts the ``(kv_heads, 1, 16, 128)`` page block, the
    head axis on the three scratch arrays and a grid bound read on the
    device, at the largest job bucket the engine declares for the cell's
    256-token tick, on float32 pools; the call is still the one
    ``qblock_roofline`` finds. The int8-KV variant at least compiles
    there."""
    rpa = importlib.import_module(
        "paddle_tpu.ops.pallas.ragged_paged_attention")
    jobs = rpa.job_buckets(TOKEN_BUDGET, rpa.DEFAULT_QBLOCK, CELL_SLOTS,
                           PAGES_PER_SEQ)[-1]
    assert jobs == 8192                 # 64 (block, sequence) pairs x 128
    blocks = TOKEN_BUDGET // rpa.DEFAULT_QBLOCK
    pool = ((KV_HEADS, CELL_PAGES, PAGE_SIZE, HEAD_DIM), jnp.dtype(kv_dtype))
    scales = ((KV_HEADS, CELL_PAGES, PAGE_SIZE), jnp.float32)
    # the job list with its own length, the grid's bound, in a last column
    # of its own (128 KB of SMEM), and slot / context bound a token
    rows = ((2, blocks, rpa.DEFAULT_QBLOCK), jnp.int32)
    specs = [((4, jobs + 1), jnp.int32), rows,
             ((TOKEN_BUDGET, HEADS, HEAD_DIM), jnp.bfloat16), pool, pool]

    def plain(jobs, rows, q, kp, vp):
        return rpa._qblock_device(jobs, rows, q, kp, vp, None, None,
                                  sm_scale=SM_SCALE, interpret=False)

    def quant(jobs, rows, q, kp, vp, ks, vs):
        return rpa._qblock_device(jobs, rows, q, kp, vp, ks, vs,
                                  sm_scale=SM_SCALE, interpret=False)

    if kv_dtype == "int8":
        text = compile_on_chip(quant, *specs, scales, scales)
    else:
        text = compile_on_chip(plain, *specs)
    calls = _custom_calls(text)
    kernel = re.compile(_roofline_patterns("qblock_roofline").KERNEL)
    assert len(calls) == 1 and kernel.search(calls[0]), calls
    # no copy of a pool around the call (a page block that the compiler
    # could not read in place would show as one)
    shape = r"\[%d,%d,%d,%d\]" % pool[0]
    assert not [ln for ln in text.splitlines()
                if re.search(r"= \w+" + shape + r"\S* (copy|transpose)\(", ln)]


# -- the device half at serve_mixed_window_closed's widths -------------------
# (benchmark/configs/smallthinker-21b-serve-12l.json: 28 query / 4 KV heads
# of 128, bf16 pools of 128-token pages in two groups, 512-token ticks)
WINDOW_CELL = dict(heads=28, kv_heads=4, page=128, full_pages=1537,
                   window_pages=705, window=4096, tokens=512)


@pytest.mark.parametrize("window", [None, 4096])
def test_qblock_device_half_at_the_window_cells_widths(compile_on_chip,
                                                       window):
    """bf16 pools through ``_qblock_device``: a ``(4, 1, 128, 128)`` bf16
    page block, 56 bf16 query rows a q-block (8 tokens x a group of 7: no
    multiple of bf16's 16-row tile, and the block is the array's whole
    axis), at each group's pool and largest job bucket; the window is
    static to the kernel (a lower bound in the mask) and the call keeps the
    name the two q-block rooflines find. No copy of a pool."""
    rpa = importlib.import_module(
        "paddle_tpu.ops.pallas.ragged_paged_attention")
    c = WINDOW_CELL
    per_seq = rpa.window_pages(window, rpa.DEFAULT_QBLOCK, c["page"],
                               16384 // c["page"])
    jobs = rpa.job_buckets(c["tokens"], rpa.DEFAULT_QBLOCK, 24, per_seq)[-1]
    assert jobs == (8192 if window else 32768)
    pages = c["window_pages"] if window else c["full_pages"]
    pool = ((c["kv_heads"], pages, c["page"], HEAD_DIM), jnp.bfloat16)
    specs = [((4, jobs + 1), jnp.int32),
             ((2, c["tokens"] // rpa.DEFAULT_QBLOCK, rpa.DEFAULT_QBLOCK),
              jnp.int32),
             ((c["tokens"], c["heads"], HEAD_DIM), jnp.bfloat16), pool, pool]
    kw = {} if window is None else {"window": window}

    def call(jobs, rows, q, kp, vp):
        return rpa._qblock_device(jobs, rows, q, kp, vp, None, None,
                                  sm_scale=SM_SCALE, interpret=False, **kw)

    text = compile_on_chip(call, *specs)
    calls = _custom_calls(text)
    pattern = _roofline_patterns("qblock_roofline").KERNEL
    assert len(calls) == 1 and re.search(pattern, calls[0]), calls
    # ``qblock_roofline.windowed`` (a dotted file name, no module path)
    # tells the call by the same pattern
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "layer_metrics",
            "qblock_roofline.windowed.py")) as f:
        assert f'KERNEL = r"{pattern}"' in f.read()
    shape = r"\[%d,%d,%d,%d\]" % pool[0]
    assert not [ln for ln in text.splitlines()
                if re.search(r"= \w+" + shape + r"\S* (copy|transpose)\(", ln)]


def test_flash_device_event_names_are_the_ones_their_roofline_matches(
        compile_on_chip):
    """``flash_roofline`` tells the forward kernel and the backward's two
    (dq, dkv) by the names the jitted wrappers ``_fwd`` / ``_bwd`` give
    them under ``jax.grad``."""
    from paddle_tpu.ops.pallas import flash_attention

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=False)
        return out.astype(jnp.float32).sum()

    q = ((1, TRAIN_SEQ, HEADS, HEAD_DIM), jnp.bfloat16)
    kv = ((1, TRAIN_SEQ, KV_HEADS, HEAD_DIM), jnp.bfloat16)
    calls = _custom_calls(
        compile_on_chip(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv))
    mod = _roofline_patterns("flash_roofline")
    fwd = [c for c in calls if re.search(mod.FWD, c)]
    bwd = [c for c in calls if re.search(mod.BWD, c)]
    assert len(fwd) == 1 and len(bwd) == 2, calls
    assert len(calls) == 3, calls


# -- the latent (MLA) serving path at GigaChat3.1-702B-A36B widths ----------
# (benchmark/configs/gigachat3.1-702b-serve-ep16-5l.json: 64 heads on one
# 576-wide latent row a token, 128-token pages, a 512-token tick)
LAT_HEADS, LAT_DIM, LAT_VALUE = 64, 576, 512
LAT_PAGE, LAT_PAGES, LAT_LEN, LAT_TOKENS = 128, 5121, 17408, 512


def _latent_tick(rpa):
    """15 decode rows at 8 k and one 497-token chunk at 9 k of context."""
    rng = np.random.default_rng(0)
    tables = rng.integers(1, LAT_PAGES, (16, LAT_LEN // LAT_PAGE)).astype(
        np.int32)
    slots = np.arange(16, dtype=np.int32)
    q_lens = np.array([1] * 15 + [LAT_TOKENS - 15], np.int32)
    ctx = np.array([8000 + 100 * i for i in range(15)] + [9000], np.int32)
    return rpa.latent_job_list(LAT_TOKENS, slots, slots, q_lens, ctx, tables,
                               8, LAT_PAGE)


#: the latent pool: a page's tokens are its columns (generation.py ``_pool``)
LAT_POOL = ((1, LAT_PAGES, LAT_DIM, LAT_PAGE), jnp.bfloat16)


def _pool_sized_copies(text):
    """Instructions that copy or re-lay-out a whole latent pool."""
    shape = r"bf16\[1,%d,%d,%d\]" % LAT_POOL[0][1:]
    return [ln.strip()[:120] for ln in text.splitlines()
            if re.search(r"= " + shape + r"\S* (copy|transpose)\(", ln)]


def test_latent_qblock_compiles_in_place_and_under_its_rooflines_name(
        compile_on_chip):
    """``latent_attn_roofline`` finds the kernel by the name the jitted
    wrapper ``_latent_qblock_device`` gives its Mosaic call, and
    ``qblock_roofline`` must not count it. The program holds no copy of the
    pool: with a 576-wide row as the pool's minor axis the TPU lays the
    pool out in an order of its own and the call holds a 755 MB copy."""
    rpa = importlib.import_module(
        "paddle_tpu.ops.pallas.ragged_paged_attention")
    row_slot, row_ctx, jobs = _latent_tick(rpa)

    def fn(jobs, row_slot, row_ctx, q, pool):
        return rpa._latent_qblock_jit(jobs, row_slot, row_ctx, q, pool,
                                      LAT_DIM ** -0.5, False, LAT_VALUE, 8)

    text = compile_on_chip(
        fn, (jobs.shape, jnp.int32), (row_slot.shape, jnp.int32),
        (row_ctx.shape, jnp.int32),
        ((LAT_TOKENS, LAT_HEADS, LAT_DIM), jnp.bfloat16), LAT_POOL)
    calls = _custom_calls(text)
    mine = re.compile(_roofline_patterns("latent_attn_roofline").KERNEL)
    llamas = re.compile(_roofline_patterns("qblock_roofline").KERNEL)
    assert calls and all(mine.search(c) for c in calls), calls
    assert not any(llamas.search(c) for c in calls), calls
    assert not _pool_sized_copies(text)


def test_latent_scatter_updates_the_pool_in_place(one_chip):
    """A step's rows go into the latent pool a touched page at a time:
    no copy of the pool, temporaries of a few dozen pages."""
    from paddle_tpu.models.generation import scatter_kv_rows
    spec = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    touched = 16 + LAT_TOKENS // LAT_PAGE + 2

    def scatter(pools, rows, slot_ids, pages, row_page):
        return scatter_kv_rows(pools, rows, slot_ids=slot_ids,
                               touched=(pages, row_page))

    compiled = jax.jit(scatter, donate_argnums=(0,)).lower(
        (spec(*LAT_POOL),), spec((1, LAT_TOKENS, LAT_DIM), jnp.bfloat16),
        spec((LAT_TOKENS,), jnp.int32), spec((touched,), jnp.int32),
        spec((LAT_TOKENS,), jnp.int32)).compile()
    assert not _pool_sized_copies(compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


def test_expert_sum_device_events_are_the_ones_moe_device_pct_matches(
        compile_on_chip):
    """The grouped products of the expert sum (``jax.lax.ragged_dot``, three
    a layer) lower to Mosaic calls named ``ragged-dot...`` whatever program
    they are fused into: ``moe_device_pct`` tells them by that name."""
    from paddle_tpu.incubate.distributed.models.moe.held import (
        held_expert_sum, sigmoid_group_route)

    def fn(x, wr, br, wg, wu, wd):
        with jax.named_scope("moe/route"):
            idx, w = sigmoid_group_route(x, wr, br, n_group=8, topk_group=4,
                                         top_k=8, scale=2.5)
        with jax.named_scope("moe/experts"):
            return held_expert_sum(x, idx, w, wg, wu, wd, 0)

    h, m = 7168, 2048
    # the suite pins "highest" for its CPU parities; the chip runs the
    # products at the backend's default, and Mosaic refuses the other
    with jax.default_matmul_precision("default"):
        text = compile_on_chip(
            fn, ((LAT_TOKENS, h), jnp.bfloat16), ((h, 256), jnp.float32),
            ((256,), jnp.float32), ((16, h, m), jnp.bfloat16),
            ((16, h, m), jnp.bfloat16), ((16, m, h), jnp.bfloat16))
    kernel = re.compile(_roofline_patterns("moe_device_pct").KERNEL)
    products = [c for c in _custom_calls(text)
                if kernel.search(c) and "metadata" not in c.split(" = ")[0]]
    assert len(products) == 3, _custom_calls(text)


#: the three expert cells' widths (hidden, expert, held experts, choices a
#: token) with their configuration's file, whose engine's token budget
#: gives the buckets a tick is padded to
EXPERT_CELLS = {
    "gigachat": (7168, 2048, 16, 8, "gigachat3.1-702b-serve-ep16-5l.json"),
    "smallthinker": (2560, 768, 64, 6, "smallthinker-21b-serve-12l.json"),
    "ling": (2560, 768, 128, 8, "ling-3.0-flash-serve-ep4-7l.json"),
}


def _token_buckets(config_file):
    """``declared_token_buckets()`` of the engine the cell builds."""
    import types
    from paddle_tpu.inference import ContinuousServingEngine
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs", config_file)) as f:
        text = f.read()
    budget = int(re.search(r'"token_budget":\s*(\d+)', text).group(1))
    return sorted(ContinuousServingEngine.declared_token_buckets(
        types.SimpleNamespace(token_budget=budget)))


def _compile_expert_sum(compile_on_chip, tokens, h, m, held, top_k):
    from paddle_tpu.incubate.distributed.models.moe.held import (
        held_expert_sum)

    def fn(x, idx, w, wg, wu, wd):
        return held_expert_sum(x, idx, w, wg, wu, wd, 0)

    with jax.default_matmul_precision("default"):
        return compile_on_chip(
            fn, ((tokens, h), jnp.bfloat16), ((tokens, top_k), jnp.int32),
            ((tokens, top_k), jnp.float32), ((held, h, m), jnp.bfloat16),
            ((held, h, m), jnp.bfloat16), ((held, m, h), jnp.bfloat16))


#: ``declared_token_buckets()`` at the three engines' budget of 512
TOKEN_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512)


@pytest.mark.parametrize("tokens", TOKEN_BUCKETS)
@pytest.mark.parametrize("cell", sorted(EXPERT_CELLS))
def test_expert_sum_compiles_with_the_rules_tile_at_every_token_bucket(
        compile_on_chip, cell, tokens):
    """``held_expert_sum`` at the cell's widths and each token bucket of
    its engine: the TPU compiler takes the tile ``grouped_tiling`` hands it
    (no VMEM refusal, no row tile that does not divide the buffer), all
    three grouped products carry it, and at these loads a row tile is 128
    rows at the most."""
    from paddle_tpu.incubate.distributed.models.moe.held import (
        grouped_tiling)
    from paddle_tpu.profiler import hlo_fusions

    h, m, held, top_k, config_file = EXPERT_CELLS[cell]
    assert _token_buckets(config_file) == list(TOKEN_BUCKETS)
    rows = tokens * top_k
    products = hlo_fusions.grouped_products(_compile_expert_sum(
        compile_on_chip, tokens, h, m, held, top_k))
    if rows % 8:               # XLA's dense masked form, no grouped matmul
        assert not products and (cell, tokens) in (("smallthinker", 1),
                                                   ("smallthinker", 2))
        return
    assert len(products) == 3, products
    want = {f"bf16[{held},{k},{n}]": grouped_tiling(rows, k, n, 2, out)
            for k, n, out in ((h, m, 2), (m, h, 4))}
    for p in products:
        tile = want[p["rhs"]]
        assert tile and tile[0] <= 128, tile
        assert p["tiling"] == list(tile), p


def _plain_grouped_product(rows, w, sizes, out_dtype):
    return jax.lax.ragged_dot(rows, w, sizes,
                              preferred_element_type=out_dtype)


def test_expert_sum_tile_is_blind_to_the_load_and_absent_where_none_fits(
        compile_on_chip, monkeypatch):
    """All 16 of 16 experts held at GigaChat's widths under a 512-token
    bucket (256 rows a group, the deployment's load) compile under the
    tile a 16-row share gets: the rule reads shapes, and measured best at
    both. Where it gives none the program is byte for byte the one
    ``ragged_dot`` alone compiles to."""
    from paddle_tpu.incubate.distributed.models.moe import held
    from paddle_tpu.profiler import hlo_fusions

    def program(rule=held.grouped_tiling):
        monkeypatch.setattr(held, "grouped_tiling", rule)
        return _program_text(_compile_expert_sum(
            compile_on_chip, 512, 7168, 2048, 16, 8))

    ours = program()
    assert sorted(p["tiling"] for p in hlo_fusions.grouped_products(ours)) \
        == [[128, 2048, 1024], [128, 7168, 256], [128, 7168, 256]]
    none = program(lambda *a: None)
    assert [p["tiling"][0] for p in hlo_fusions.grouped_products(none)] \
        == [512, 512, 512]
    monkeypatch.setattr(held, "_grouped_product", _plain_grouped_product)
    assert none == program() != ours


@pytest.mark.parametrize("cell", sorted(EXPERT_CELLS))
def test_expert_sum_gradient_compiles_and_its_transposes_get_no_tile(
        compile_on_chip, monkeypatch, cell):
    """``jax.grad`` through ``held_expert_sum`` at the cell's widths and
    512-token bucket: the TPU compiler takes it (under the forward's tile a
    transposed product, whose contraction and columns are other
    dimensions, is refused for VMEM), the three forward products carry the
    rule's tile, and the six transposed ones are byte for byte what
    ``ragged_dot``'s own derivative compiles to, the compiler's tile
    included."""
    from paddle_tpu.incubate.distributed.models.moe import held
    from paddle_tpu.profiler import hlo_fusions

    h, m, n_held, top_k, _ = EXPERT_CELLS[cell]

    def loss(x, idx, w, wg, wu, wd):
        out, _, _ = held.held_expert_sum(x, idx, w, wg, wu, wd, 0)
        return (out.astype(jnp.float32) ** 2).sum()

    def compiled():
        # a new function a compile: jit caches by the function it is given
        with jax.default_matmul_precision("default"):
            return hlo_fusions.grouped_products(compile_on_chip(
                jax.grad(lambda *a: loss(*a), argnums=(0, 3, 4, 5)),
                ((512, h), jnp.bfloat16), ((512, top_k), jnp.int32),
                ((512, top_k), jnp.float32), ((n_held, h, m), jnp.bfloat16),
                ((n_held, h, m), jnp.bfloat16),
                ((n_held, m, h), jnp.bfloat16)))

    def shape(p):
        return p["lhs"], p["rhs"], p["result"]

    ours = compiled()
    rows = 512 * top_k
    rule = {held.grouped_tiling(rows, k, n, 2, out)
            for k, n, out in ((h, m, 2), (m, h, 4))}
    forward = [p for p in ours if tuple(p["tiling"]) in rule]
    assert len(ours) == 9 and len(forward) == 3, ours
    assert {p["rhs"] for p in forward} == {f"bf16[{n_held},{h},{m}]",
                                           f"bf16[{n_held},{m},{h}]"}
    monkeypatch.setattr(held, "_grouped_product", _plain_grouped_product)
    theirs = compiled()
    assert not [p for p in theirs if tuple(p["tiling"]) in rule]
    assert sorted((shape(p), p["tiling"]) for p in ours
                  if p not in forward) == sorted(
        (shape(p), p["tiling"]) for p in theirs
        if shape(p) not in [shape(f) for f in forward])


def test_int8_matmul_compiles(compile_on_chip):
    """Weight-only int8 GEMM at the 8B MLP up-projection: a 256-token
    tick against [hidden 4096, intermediate 14336]."""
    from paddle_tpu.ops.pallas.quant_matmul import int8_matmul
    fn = functools.partial(int8_matmul, interpret=False)
    text = compile_on_chip(fn, ((TOKEN_BUDGET, 4096), jnp.bfloat16),
                           ((4096, 14336), jnp.int8),
                           ((14336,), jnp.float32))
    assert _has_kernel(text)


def test_sdpa_flash_under_a_mesh_compiles(topo, monkeypatch):
    """GSPMD refuses to partition a Mosaic kernel ("wrap the call in a
    shard_map"): under a sharding x mp mesh SDPA must hand the flash kernel
    per-device shards. The CPU suite never reaches this branch with a real
    kernel, so the four-device compile is its guard."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.framework.core import Tensor
    from paddle_tpu.nn import functional as F

    # SDPA picks flash by platform; the compiler is described, not attached
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = mesh_mod.init_mesh({"sharding": 2, "mp": 2}, devices=topo.devices)
    try:
        sh = NamedSharding(mesh, P(("dp", "sharding"), None, "mp", None))
        q = jax.ShapeDtypeStruct((2, 1024, HEADS, HEAD_DIM), jnp.bfloat16,
                                 sharding=sh)
        kv = jax.ShapeDtypeStruct((2, 1024, KV_HEADS, HEAD_DIM),
                                  jnp.bfloat16, sharding=sh)

        def fn(q, k, v):
            return F.scaled_dot_product_attention(
                Tensor(q), Tensor(k), Tensor(v), is_causal=True,
                training=False)._data

        with mesh:
            text = jax.jit(fn).lower(q, kv, kv).compile().as_text()
    finally:
        mesh_mod.reset_mesh()
    assert _has_kernel(text)


# -- the training step's products read ready-made operands (PR 32) ---------

CELL_SEQ, CELL_HIDDEN, CELL_FFN, CELL_VOCAB = 4096, 4096, 14336, 32768


def _program_text(text):
    """A compiled program's text without what names its source: metadata
    (op names, stack frames), the module's own name and its frame tables."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    return "\n".join(line for line in text.splitlines()
                     if not line.startswith(("HloModule", "FileNames",
                                             "FunctionNames", "FileLocations",
                                             "StackFrames"))
                     and not re.match(r"^\d+ ", line)).strip()


def test_train_step_products_read_ready_made_operands(one_chip, monkeypatch):
    """The benchmark's own step at ``train_dense_1chip``'s widths, cut to
    one layer, for one v5e chip: no product fusion carries an
    ``exponential`` / ``divide`` / ``log`` in a producer of its operands
    (at the seed all of the MLP's and the head's gradient products did, and
    recomputed SwiGLU or the softmax on every pass of their tiling), and the
    head's weight gradient reads no float32 logits-sized array."""
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmark import harness
    from benchmark.drivers import train
    from paddle_tpu.framework.functional import FunctionalModule
    from paddle_tpu.profiler import compile_observatory, hlo_fusions

    config = harness.load_json(os.path.join(
        harness.HERE, "configs", "mistral-7b-train-4l.json"))
    assert (config["hidden_size"], config["intermediate_size"],
            config["vocab_size"]) == (CELL_HIDDEN, CELL_FFN, CELL_VOCAB)
    config["num_hidden_layers"] = 1
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = train.build_model(config, config["trainer"]["dtype"])
    model.train()
    fm = FunctionalModule(model, training=True)
    step = train.make_train_step(fm, config["trainer"]["optimizer"])
    state = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
             for a in fm.param_arrays()]
    for _, p in model.named_parameters():
        p._data = None                    # shapes are enough from here on
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((1, CELL_SEQ), jnp.int32, sharding=one_chip)
    # the suite's conftest asks for "highest" products; the cell runs jax's
    # default, and the program differs (float32 operands, other fusions)
    with jax.default_matmul_precision(None):
        text = jax.jit(step, donate_argnums=(0, 1, 2)).lower(
            state, state, state, key, ids, ids).compile().as_text()

    products = hlo_fusions.product_fusions(text)
    hot = [(r["name"], r["operand_side"]) for r in products
           if r["operand_side"]]
    assert len(products) >= 10 and not hot
    # the SwiGLU backward and AdamW are there, once an element: as epilogues
    assert any(r["anywhere"] for r in products)
    logits = re.compile(rf"f32\[(1,)?{CELL_SEQ},{CELL_VOCAB}\]")
    head_grads = [r for r in products
                  if f"bf16[{CELL_SEQ},{CELL_VOCAB}]" in r["result"]]
    assert head_grads, [r["result"][:60] for r in products]
    for r in head_grads:
        assert not [t for t in r["inputs"] + r["operand_types"]
                    if logits.match(t)], r
    seen = compile_observatory.get_observatory().record_program(
        "train.test_step", text)
    assert seen["operand_side_transcendental"] == 0
    assert seen["product_fusions"] == len(products)
    assert compile_observatory.snapshot()["programs"][
        "train.test_step"] == seen


def test_undifferentiated_swiglu_compiles_to_the_seeds_program(one_chip):
    """``LlamaMLP``'s forward at the chat cell's 256-token bucket, not
    differentiated: the program is the one the op compiled to when its
    primal WAS its forward rule (the seed's registration, rebuilt here)."""
    from paddle_tpu.framework.core import Tensor
    from paddle_tpu.ops import fused
    from paddle_tpu.utils import register_op

    def seed_fwd(a, g):
        s = 1.0 / (1.0 + jnp.exp(-a))
        return jnp.asarray(a * s * g, a.dtype), (a, s, g)

    seed_op = register_op(seed_fwd, name="t_seed_swiglu",
                          vjp=lambda res, ct: (ct, ct), override=True)

    def mlp(act):
        def fn(x, wg, wu, wd):
            return (act(Tensor(x @ wg), Tensor(x @ wu))._data) @ wd
        args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
                for s in ((TOKEN_BUDGET, CELL_HIDDEN),
                          (CELL_HIDDEN, CELL_FFN), (CELL_HIDDEN, CELL_FFN),
                          (CELL_FFN, CELL_HIDDEN))]
        with jax.default_matmul_precision(None):
            return _program_text(
                jax.jit(fn).lower(*args).compile().as_text())

    now, seed = mlp(fused.fused_swiglu), mlp(seed_op)
    assert "exponential" in now and now == seed


# -- the two KDA kernels at serve_reason_state_closed's widths ---------------
KDA_TOKENS, KDA_HEADS, KDA_DIM, KDA_SLOTS = 512, 32, 128, 96
KDA_STATE = ((KDA_SLOTS + 1, KDA_HEADS, KDA_DIM, KDA_DIM), jnp.float32)


def _kda_rows():
    rows = ((KDA_TOKENS, KDA_HEADS, KDA_DIM), jnp.bfloat16)
    return [rows] * 3 + [(rows[0], jnp.float32),
                         ((KDA_TOKENS, KDA_HEADS), jnp.float32), KDA_STATE]


def _state_sized_copies(text):
    shape = r"f32\[%d,%d,%d,%d\]" % KDA_STATE[0]
    return [ln.strip()[:120] for ln in text.splitlines()
            if re.search(r"= " + shape + r"\S* (copy|transpose)\(", ln)]


@pytest.mark.parametrize("kernel", ["step", "chunk"])
def test_kda_kernels_compile_in_place_under_their_rooflines_names(
        one_chip, kernel):
    """``kda_step_roofline`` / ``kda_chunk_roofline`` / ``linear_attn_
    device_pct`` find the kernels by the name ``pallas_call(name=)`` gives
    the Mosaic call; each matches its own kernel alone; the donated state
    (203 MB a layer) is updated in place: no copy of it in the program."""
    from paddle_tpu.ops.pallas import kda
    spec = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    rows = [spec(*r) for r in _kda_rows()]
    if kernel == "step":
        args = rows + [spec((KDA_SLOTS,), jnp.int32)] * 2 + [False]
        fn, static, other = kda._kda_step_device, (8,), "kda_chunk_roofline"
    else:
        jobs = kda.chunk_jobs_bound(KDA_TOKENS, 8)
        args = rows + [spec(*_kda_rows()[0]),
                       spec((jobs * kda.SUB,), jnp.int32),
                       spec((KDA_TOKENS,), jnp.int32),
                       spec((2, jobs), jnp.int32), False]
        fn, static, other = kda._kda_chunk_device, (10,), "kda_step_roofline"
    text = jax.jit(fn, static_argnums=static, donate_argnums=(5,)).lower(
        *args).compile().as_text()
    calls = _custom_calls(text)
    mine = re.compile(_roofline_patterns(f"kda_{kernel}_roofline").KERNEL)
    both = re.compile(_roofline_patterns("linear_attn_device_pct").KERNEL)
    assert len(calls) == 1 and mine.search(calls[0]) and both.search(calls[0])
    assert not re.compile(_roofline_patterns(other).KERNEL).search(calls[0])
    assert not _state_sized_copies(text)
