"""Long-context sep-parallel serving (ISSUE 19): ring-attention
blockwise prefill over fixed stripes — kernel-tier parity, cache-level
stripe lifecycle, striped disagg handoff, and engine greedy parity
against the single-device oracle for prompts that exceed the device
page pool."""
import numpy as np
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework.core import Tensor
from paddle_tpu.inference import ContinuousServingEngine
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.models.generation import HostKVPool, SlotPagedKVCache
from paddle_tpu.ops.pallas.flash_attention import mha_reference
from paddle_tpu.ops.pallas.ring_attention import (
    SEP_RING_IMPLS, blockwise_causal_attention, sep_ring_impl)


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    return LlamaForCausalLM(llama_tiny(num_hidden_layers=2))


def _oracle(model, p, n):
    return np.asarray(model.generate(paddle.to_tensor(p),
                                     max_new_tokens=n)._data)


# ---------------------------------------------------------------------------
# kernel tier: blockwise ring schedule == dense causal reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["auto", "xla"])
def test_blockwise_matches_dense_reference(impl):
    """Splitting the KV into ring blocks and merging the per-block
    partials with the online-softmax combine reproduces dense causal
    attention — for the kernel tier (interpret-pallas off-TPU) and the
    pure-XLA fallback alike, including a fully-masked future block."""
    rng = np.random.default_rng(0)
    b, h, d = 1, 4, 16
    sq, skv = 8, 32
    q = jnp.asarray(rng.standard_normal((b, h, sq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, skv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, skv, d)), jnp.float32)
    q_off = 16                      # q rows sit at positions 16..23
    blocks = [(k[:, :, i:i + 8], v[:, :, i:i + 8], i)
              for i in range(0, skv, 8)]     # last block fully masked
    got = blockwise_causal_attention(q, q_off, blocks, impl=impl)
    ref, _ = mha_reference(q, k, v, causal=True,
                           sm_scale=1.0 / np.sqrt(d), q_offset=q_off,
                           kv_offset=0, with_lse=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ring_impl_env_knob(monkeypatch):
    monkeypatch.setenv("PADDLE_SEP_RING_IMPL", "xla")
    assert sep_ring_impl() == "xla"
    monkeypatch.setenv("PADDLE_SEP_RING_IMPL", "kernel")
    assert sep_ring_impl() == "kernel"
    assert "auto" in SEP_RING_IMPLS
    monkeypatch.setenv("PADDLE_SEP_RING_IMPL", "bogus")
    with pytest.raises(ValueError):
        sep_ring_impl()


# ---------------------------------------------------------------------------
# cache level: stripe lifecycle + striped handoff
# ---------------------------------------------------------------------------

def _mk_sep_cache():
    return SlotPagedKVCache(2, page_size=4, max_len=64, num_pages=9,
                            allow_page_overcommit=True,
                            host_pool=HostKVPool(0))


def _drive_sep(cache, layer, q_all, k_all, v_all, prompt_len, stripe,
               new_tokens):
    """Chunked sep prefill + per-token decode, returning the attention
    outputs for every position (valid rows only)."""
    slot = 0
    cache.assign_sep(slot, prompt_len, stripe)
    outs = []
    pos = 0
    while pos < prompt_len:
        n_valid = min(stripe, prompt_len - pos)
        pad = stripe - n_valid
        pad4 = ((0, 0), (0, pad), (0, 0), (0, 0))
        cache.begin_sep_prefill(slot, n_valid=n_valid)
        o = cache.attend(
            layer,
            Tensor(jnp.asarray(np.pad(q_all[:, pos:pos + n_valid], pad4))),
            Tensor(jnp.asarray(np.pad(k_all[:, pos:pos + n_valid], pad4))),
            Tensor(jnp.asarray(np.pad(v_all[:, pos:pos + n_valid], pad4))))
        outs.append(np.asarray(o._data)[:, :n_valid])
        cache.advance(stripe)
        pos += n_valid
    for t in range(new_tokens):
        p = prompt_len + t
        cache.begin_sep_decode(slot)
        o = cache.attend(layer, Tensor(jnp.asarray(q_all[:, p:p + 1])),
                         Tensor(jnp.asarray(k_all[:, p:p + 1])),
                         Tensor(jnp.asarray(v_all[:, p:p + 1])))
        outs.append(np.asarray(o._data))
        cache.advance(1)
    return np.concatenate(outs, axis=1)


@pytest.mark.parametrize("prompt_len", [21, 24])
def test_sep_cache_matches_dense(prompt_len):
    """Stripe-chunked sep prefill + tail decode equals dense causal
    attention over the whole sequence — with and without a trailing
    partial chunk. The prompt exceeds the 8-usable-page device pool;
    only the tail ever lives in device pages."""
    rng = np.random.default_rng(1)
    h, hk, d, stripe, new = 4, 2, 8, 8, 5
    total = prompt_len + new
    q = rng.standard_normal((1, total, h, d)).astype(np.float32)
    k = rng.standard_normal((1, total, hk, d)).astype(np.float32)
    v = rng.standard_normal((1, total, hk, d)).astype(np.float32)
    cache = _mk_sep_cache()
    got = _drive_sep(cache, object(), q, k, v, prompt_len, stripe, new)
    ref, _ = mha_reference(jnp.swapaxes(jnp.asarray(q), 1, 2),
                           jnp.swapaxes(jnp.asarray(k), 1, 2),
                           jnp.swapaxes(jnp.asarray(v), 1, 2),
                           causal=True, sm_scale=1.0 / np.sqrt(d),
                           with_lse=True)
    ref = np.asarray(jnp.swapaxes(ref, 1, 2))
    np.testing.assert_allclose(got, ref, atol=2e-5, rtol=2e-5)
    assert cache.sep_stripes_stored == prompt_len // stripe
    assert cache.sep_decode_steps == new
    view = cache.sep_view(0)
    assert view["stripes"] == prompt_len // stripe
    assert view["len"] == prompt_len            # the admitted span
    assert int(cache.lens[0]) == prompt_len + new


def test_striped_handoff_continues_bit_exact(monkeypatch):
    """export_stripes -> import_stripes onto a second cache mid-decode:
    stripes carry their sep-way home tags (PADDLE_SEP_WAYS striping) and
    the next decoded token's attention is bit-identical."""
    monkeypatch.setenv("PADDLE_SEP_WAYS", "4")
    rng = np.random.default_rng(2)
    h, hk, d, stripe, plen, new = 4, 2, 8, 8, 21, 5
    total = plen + new + 1
    layer = object()
    q = rng.standard_normal((1, total, h, d)).astype(np.float32)
    k = rng.standard_normal((1, total, hk, d)).astype(np.float32)
    v = rng.standard_normal((1, total, hk, d)).astype(np.float32)
    src = _mk_sep_cache()
    _drive_sep(src, layer, q, k, v, plen, stripe, new)
    blob = src.export_stripes(0)
    assert [st["home"] for st in blob["stripes"]] == \
        [j % 4 for j in range(len(blob["stripes"]))]
    assert blob["tail"] is not None          # mid-span decode state

    dst = _mk_sep_cache()
    # materialize dst pools with a scratch stripe, then import
    dst.assign_sep(1, 4, stripe)
    dst.begin_sep_prefill(1, n_valid=4)
    z = np.zeros((1, stripe, hk, d), np.float32)
    dst.attend(layer, Tensor(jnp.asarray(
        np.zeros((1, stripe, h, d), np.float32))),
        Tensor(jnp.asarray(z)), Tensor(jnp.asarray(z)))
    dst.advance(stripe)
    dst.free(1)
    assert dst.import_stripes(0, blob) == len(blob["stripes"])

    p = plen + new
    outs = []
    for cache in (src, dst):
        cache.begin_sep_decode(0)
        o = cache.attend(layer, Tensor(jnp.asarray(q[:, p:p + 1])),
                         Tensor(jnp.asarray(k[:, p:p + 1])),
                         Tensor(jnp.asarray(v[:, p:p + 1])))
        outs.append(np.asarray(o._data))
    np.testing.assert_array_equal(outs[0], outs[1])


def test_sep_validation():
    cache = _mk_sep_cache()
    with pytest.raises(ValueError):          # stripe % page_size != 0
        cache.assign_sep(0, 20, 6)
    with pytest.raises(ValueError):          # prompt > max_len
        cache.assign_sep(0, 100, 8)
    qcache = SlotPagedKVCache(1, page_size=4, max_len=32, num_pages=9,
                              kv_dtype="int8",
                              allow_page_overcommit=True)
    with pytest.raises(ValueError):          # int8 pools are paged-only
        qcache.assign_sep(0, 20, 8)


# ---------------------------------------------------------------------------
# engine level: long-context greedy parity vs the single-device oracle
# ---------------------------------------------------------------------------

def test_engine_long_context_parity(model):
    """A 100-token prompt against a 15-usable-page (60-token) device
    pool: inadmissible via the paged path, served by sep-ring prefill
    with greedy output bit-identical to the dense oracle. A short prompt
    on the same config still takes the paged path."""
    rng = np.random.RandomState(7)
    prompt = rng.randint(0, 128, (1, 100)).astype(np.int64)
    short = rng.randint(0, 128, (1, 6)).astype(np.int64)
    want = _oracle(model, prompt, 8)
    want_s = _oracle(model, short, 4)
    eng = ContinuousServingEngine(model, max_batch_size=2, page_size=4,
                                  max_len=256, num_pages=16,
                                  sep_prefill=True, sep_stripe_tokens=16)
    assert prompt.shape[1] > (16 - 1) * 4    # exceeds the device pool
    with eng:
        got = np.asarray(eng.generate(prompt, max_new_tokens=8,
                                      timeout=300).numpy())
        got_s = np.asarray(eng.generate(short, max_new_tokens=4,
                                        timeout=300).numpy())
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got_s, want_s)
    assert eng.sep_requests == 1             # only the long prompt
    assert eng._cache.sep_stripes_stored >= 100 // 16
    assert eng._cache.sep_chunks == -(-100 // 16)


def test_engine_env_knobs_and_validation(model, monkeypatch):
    monkeypatch.setenv("PADDLE_SEP_PREFILL", "1")
    monkeypatch.setenv("PADDLE_SEP_STRIPE_TOKENS", "32")
    monkeypatch.setenv("PADDLE_SEP_THRESHOLD_TOKENS", "77")
    eng = ContinuousServingEngine(model, page_size=16)
    assert eng.sep_prefill_enabled
    assert eng.sep_stripe == 32
    assert eng.sep_threshold == 77
    # declared observatory families for the new program shapes
    from paddle_tpu.profiler import compile_observatory as co
    try:
        co.enable()
        co.reset()
        eng2 = ContinuousServingEngine(model, page_size=16,
                                       host_pool_mb=8)
        fams = set(co.declared_families())
        assert {"serving.sep_prefill", "serving.sep_decode",
                "kv.host_promote"} <= fams
        assert eng2.sep_prefill_enabled
    finally:
        co.disable()
        co.reset()
    # stripe must be a positive multiple of page_size
    monkeypatch.setenv("PADDLE_SEP_STRIPE_TOKENS", "30")
    with pytest.raises(ValueError):
        ContinuousServingEngine(model, page_size=16)
    monkeypatch.setenv("PADDLE_SEP_STRIPE_TOKENS", "32")
    # int8 KV pools can't back the ring schedule
    with pytest.raises(ValueError):
        ContinuousServingEngine(model, page_size=16, kv_dtype="int8")
    monkeypatch.delenv("PADDLE_SEP_PREFILL")
    assert not ContinuousServingEngine(model,
                                       page_size=16).sep_prefill_enabled
