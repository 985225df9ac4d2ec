"""Pallas flash-attention kernel + ring attention (CP) tests.

Run on CPU in interpret mode (conftest forces an 8-device CPU backend);
numeric oracle is the pure-XLA ``mha_reference`` / a global-attention run.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import (
    flash_attention, flash_attention_with_lse, mha_reference,
    ring_flash_attention,
)


def _rand(shape, seed=0, dtype=np.float32):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape), dtype)


def _mk(b=1, h=2, s=128, d=32, hk=None, seed=0):
    hk = hk or h
    q = _rand((b, h, s, d), seed)
    k = _rand((b, hk, s, d), seed + 1)
    v = _rand((b, hk, s, d), seed + 2)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("s,block", [(128, 64), (96, 64)])
def test_fwd_matches_reference(causal, s, block):
    q, k, v = _mk(s=s)
    ref = mha_reference(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=block, block_k=block,
                          interpret=True, kernel_layout=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_fwd_gqa_and_lse():
    q, k, v = _mk(h=4, hk=2, s=128, d=16)
    ref, ref_lse = mha_reference(q, k, v, causal=True, with_lse=True)
    out, lse = flash_attention_with_lse(q, k, v, causal=True, block_q=64,
                                        block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=2e-4, atol=2e-4)


def test_offsets_mask_globally():
    # Q shard [64:128) of a 128-seq vs full KV == rows [64:128) of global attn
    qg, kg, vg = _mk(s=128, d=16, seed=3)
    ref = mha_reference(qg, kg, vg, causal=True)
    out = flash_attention(qg[:, :, 64:], kg, vg, causal=True, q_offset=64,
                          kv_offset=0, block_q=64, block_k=64, interpret=True,
                          kernel_layout=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref[:, :, 64:]),
                               rtol=2e-4, atol=2e-4)
    # fully-masked (KV strictly in the future): zero output
    out2, lse2 = flash_attention_with_lse(
        qg[:, :, :64], kg[:, :, 64:], vg[:, :, 64:], causal=True,
        q_offset=0, kv_offset=64, block_q=64, block_k=64, interpret=True)
    assert np.abs(np.asarray(out2)).max() == 0.0
    assert np.asarray(lse2).max() < -1e29


@pytest.mark.parametrize("causal", [True, False])
def test_grads_match_reference(causal):
    q, k, v = _mk(b=1, h=2, s=96, d=16, seed=5)
    g = _rand(q.shape, 9)

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                              interpret=True, kernel_layout=True)
        return jnp.sum(out * g)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=causal) * g)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


def test_grads_gqa():
    q, k, v = _mk(b=1, h=4, hk=2, s=64, d=16, seed=7)
    g = _rand(q.shape, 11)

    def loss(fn):
        def f(q, k, v):
            return jnp.sum(fn(q, k, v) * g)
        return f

    gf = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=32, block_k=32, interpret=True,
        kernel_layout=True)), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(lambda q, k, v: mha_reference(q, k, v, causal=True)),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


# ---------------------------------------------------------------------------
# Tiles from the shapes, operands as they arrive, the causal grid (PR 30)
# ---------------------------------------------------------------------------

import importlib  # noqa: E402

fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

#: (sq, sk, head_dim, dtype): the training cell's call, its shorter kin,
#: another model's head widths, float32 operands, lengths no tile divides
RULE_SHAPES = [(4096, 4096, 128, "bfloat16"), (2048, 2048, 128, "bfloat16"),
               (128, 128, 128, "bfloat16"), (512, 4096, 128, "bfloat16"),
               (4096, 4096, 192, "bfloat16"), (4096, 4096, 64, "bfloat16"),
               (4096, 4096, 128, "float32"), (16384, 16384, 256, "float32"),
               (4224, 4224, 128, "bfloat16"), (1000, 1000, 64, "bfloat16"),
               (96, 96, 16, "float32")]


@pytest.mark.parametrize("kernel", fa.KERNELS)
@pytest.mark.parametrize("sq,sk,d,dtype", RULE_SHAPES)
def test_tile_rule_is_a_function_of_shapes_and_dtype(kernel, sq, sk, d, dtype):
    bq, bk = fa.tile_rule(kernel, sq, sk, d, dtype)
    assert (bq, bk) == fa.tile_rule(kernel, sq, sk, d, dtype)
    for block, length in ((bq, sq), (bk, sk)):
        padded = -(-length // block) * block
        assert block <= padded < length + max(block, 128)
        if length > 128:             # whole lane rows, no more padding
            assert block % 128 == 0 and padded - length < 128 * (
                padded // block)
        else:
            assert block == max(length, 8)
    plan = fa.vmem_plan(kernel, bq, bk, d, dtype)
    assert plan["total"] == sum(v for k, v in plan.items()
                                if k not in ("total", "limit"))
    assert plan["total"] <= fa._VMEM_BUDGET
    # the set fits the limit the call states (Mosaic's own where none)
    assert plan["total"] <= (plan["limit"] or fa._VMEM_DEFAULT_SCOPE)


def test_tile_rule_shrinks_with_the_working_set():
    """A wider head or float32 operands never take a LARGER tile, and an
    explicit tile is clipped to the axis as ever."""
    for kernel in fa.KERNELS:
        base = fa.tile_rule(kernel, 8192, 8192, 128, "bfloat16")
        for d, dtype in ((256, "bfloat16"), (128, "float32"),
                         (512, "float32")):
            bq, bk = fa.tile_rule(kernel, 8192, 8192, d, dtype)
            assert bq <= base[0] and bk <= base[1]
    assert fa._tiles("fwd", 64, 64, 96, 40, 16, "float32") == (64, 40, 128, 40)
    assert fa._tiles("fwd", None, None, 200, 200, 16, "float32")[2:] == (
        256, 256)


def _brute_force_plan(bq, bk, sq, sk, causal, q_off, kv_off):
    """Count tiles from the full [sq_pad, sk_pad] pair matrix."""
    sq_pad, sk_pad = -(-sq // bq) * bq, -(-sk // bk) * bk
    rows = q_off + np.arange(sq_pad)[:, None]
    cols = kv_off + np.arange(sk_pad)[None, :]
    # what a tile would have to look at: the causal pattern over its whole
    # square (padded query rows included), and the padding behind sk
    future = (rows < cols) if causal else np.zeros((sq_pad, sk_pad), bool)
    padding = np.broadcast_to(np.arange(sk_pad)[None, :] >= sk,
                              (sq_pad, sk_pad))
    wanted = ~future & ~padding
    wanted[sq:] = False
    steps = compute = masked = 0
    for i in range(0, sq_pad, bq):
        for j in range(0, sk_pad, bk):
            steps += 1
            f = future[i:i + bq, j:j + bk]
            if f.all():
                continue
            compute += 1
            masked += bool(f.any() or padding[i:i + bq, j:j + bk].any())
    return {"tile": (bq, bk), "steps": steps, "compute_steps": compute,
            "masked_tiles": masked, "pairs_needed": int(wanted.sum()),
            "pairs_computed": compute * bq * bk}


@pytest.mark.parametrize("bq,bk,sq,sk,causal,q_off,kv_off", [
    (128, 128, 1024, 1024, True, 0, 0), (512, 256, 2048, 2048, True, 0, 0),
    (256, 512, 2048, 2048, True, 0, 0), (128, 256, 512, 1536, True, 1024, 0),
    (128, 128, 512, 512, True, 0, 1024), (128, 128, 512, 512, True, 512, 0),
    (256, 256, 1000, 1000, True, 0, 0), (256, 256, 1000, 900, False, 0, 0),
    (128, 128, 512, 512, True, 0, 64)])
def test_grid_plan_counts_match_a_brute_force_count(bq, bk, sq, sk, causal,
                                                    q_off, kv_off):
    assert fa._plan_counts(bq, bk, sq, sk, causal, q_off, kv_off) == \
        _brute_force_plan(bq, bk, sq, sk, causal, q_off, kv_off)


def test_grid_plan_at_the_training_cells_shapes():
    plan = fa.grid_plan(4096, 4096, 128, "bfloat16", True, 0, 0)
    assert set(plan) == set(fa.KERNELS)
    for kernel, counts in plan.items():
        assert counts["tile"] == fa.tile_rule(kernel, 4096, 4096, 128,
                                              "bfloat16")
        assert counts == _brute_force_plan(*counts["tile"], 4096, 4096, True,
                                           0, 0)
        assert counts["pairs_needed"] == 4096 * 4097 // 2
    # the price of a tile, as the issue states it
    fill = lambda b: 100 * fa._plan_counts(b, b, 4096, 4096, True, 0, 0)[
        "pairs_needed"] / fa._plan_counts(b, b, 4096, 4096, True, 0, 0)[
        "pairs_computed"]
    assert [round(fill(b)) for b in (128, 512, 1024)] == [97, 89, 80]


def _flash_and_ref(q, k, v, g, *, causal=True, q_offset=0, kv_offset=0,
                   block=None, cotangent_lse=None):
    """(out, lse, dq, dk, dv) of the kernel (interpret mode, inputs as they
    are) and of ``mha_reference`` on the same inputs in float32."""
    def run(fn, cast):
        def loss(q, k, v):
            out, lse = fn(cast(q), cast(k), cast(v))
            total = jnp.sum(out.astype(jnp.float32) * g)
            if cotangent_lse is not None:
                live = lse > -1e29
                total += jnp.sum(jnp.where(live, lse * cotangent_lse, 0.0))
            return total, (out, lse)
        grads, (out, lse) = jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(
            q, k, v)
        return (out, lse) + grads

    kern = run(lambda q, k, v: flash_attention_with_lse(
        q, k, v, causal=causal, q_offset=q_offset, kv_offset=kv_offset,
        block_q=block, block_k=block, interpret=True), lambda x: x)
    ref = run(lambda q, k, v: mha_reference(
        q, k, v, causal=causal, q_offset=q_offset, kv_offset=kv_offset,
        with_lse=True), lambda x: x.astype(jnp.float32))
    return kern, ref


def _assert_close(kern, ref, tol):
    for name, a, b in zip(("out", "lse", "dq", "dk", "dv"), kern, ref):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        live = b > -1e29 if name == "lse" else np.ones(b.shape, bool)
        assert (a[~live] < -1e29).all(), name
        np.testing.assert_allclose(a[live], b[live], rtol=tol, atol=tol,
                                   err_msg=name)


@pytest.mark.parametrize("h,hk", [(2, 2), (4, 2)], ids=["mha", "gqa"])
@pytest.mark.parametrize("block", [64, None], ids=["tile64", "rule"])
def test_bf16_operands_forward_and_gradients(h, hk, block):
    """bf16 q, k, v, dO go to the products as they are, p and dS rounded to
    bf16 for the second products: the reference in float32 at a bf16
    tolerance (the output alone is rounded to 2^-9 of values near one)."""
    s, d = 256, 32
    q, k, v = (_rand((1, n, s, d), 50 + i, np.float32).astype(jnp.bfloat16)
               for i, n in enumerate((h, hk, hk)))
    g = _rand((1, h, s, d), 60)
    kern, ref = _flash_and_ref(q, k, v, g, block=block)
    assert kern[0].dtype == jnp.bfloat16 and kern[1].dtype == jnp.float32
    assert [x.dtype for x in kern[2:]] == [jnp.bfloat16] * 3
    _assert_close(kern, ref, tol=3e-2)
    # and the error is rounding, not a missing term: relative to the norm
    for a, b in zip(kern[2:], ref[2:]):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.linalg.norm(a - b) < 1e-2 * np.linalg.norm(b)


@pytest.mark.parametrize("q_offset,kv_offset,sq,sk", [
    (128, 0, 128, 384),      # whole K blocks of the shard in the future
    (0, 1024, 128, 256),     # the whole shard in the future: block 0, nothing
    (0, 32, 128, 128),       # a tile whose first rows see no key at all
    (128, 0, 64, 192),       # sq != sk with the decode offset (bottom-right)
    (256, 128, 128, 128)],   # a shard wholly in the past: no tile masks
    ids=["future_blocks", "future_shard", "dead_rows", "decode_offset",
         "past_shard"])
@pytest.mark.parametrize("h,hk", [(2, 2), (4, 1)], ids=["mha", "gqa"])
def test_causal_offsets_forward_and_gradients(q_offset, kv_offset, sq, sk, h,
                                              hk):
    """The clamped index maps: a step in the causal future names the block
    already held and computes nothing; lse's cotangent rides along (the
    ring merge differentiates through it)."""
    d = 16
    q, k, v = _rand((1, h, sq, d), 70), _rand((1, hk, sk, d), 71), _rand(
        (1, hk, sk, d), 72)
    g, g_lse = _rand((1, h, sq, d), 73), _rand((1, h, sq), 74)
    kern, ref = _flash_and_ref(q, k, v, g, q_offset=q_offset,
                               kv_offset=kv_offset, block=64,
                               cotangent_lse=g_lse)
    _assert_close(kern, ref, tol=5e-4)
    if kv_offset > q_offset + sq:
        assert all(float(jnp.abs(x).max()) == 0.0
                   for x in (kern[0],) + kern[2:])


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("sq,sk", [(200, 200), (1100, 1100), (130, 300)])
def test_a_length_the_default_tile_does_not_divide(causal, sq, sk):
    """The rule's tiles pad both axes (1,100 runs as 2 x 640): padded keys
    are masked, padded query rows dropped, in all three kernels."""
    q, k, v = _rand((1, 1, sq, 16), 80), _rand((1, 1, sk, 16), 81), _rand(
        (1, 1, sk, 16), 82)
    g = _rand((1, 1, sq, 16), 83)
    kern, ref = _flash_and_ref(q, k, v, g, causal=causal,
                               q_offset=sk - sq if causal else 0)
    _assert_close(kern, ref, tol=5e-4)


# ---------------------------------------------------------------------------
# Ring attention over the sep axis
# ---------------------------------------------------------------------------

def _ring_setup(n=4, b=1, h=2, s=256, d=16, hk=None):
    import functools
    from jax.sharding import Mesh, PartitionSpec as P
    # check_vma=False: pallas_call inside shard_map needs explicit vma otherwise
    shard_map = functools.partial(jax.shard_map, check_vma=False)
    devs = np.array(jax.devices()[:n])
    mesh = Mesh(devs, ("sep",))
    q = _rand((b, s, h, d), 21)          # paddle layout [b, s, h, d]
    k = _rand((b, s, hk or h, d), 22)
    v = _rand((b, s, hk or h, d), 23)
    return mesh, P, shard_map, q, k, v


@pytest.mark.parametrize("use_kernel", [False, True])
def test_ring_matches_global(use_kernel):
    n = 4
    mesh, P, shard_map, q, k, v = _ring_setup(n=n)
    spec = P(None, "sep", None, None)

    def fn(q, k, v):
        return ring_flash_attention(q, k, v, axis_name="sep", causal=True,
                                    axis_size=n, interpret=True,
                                    use_kernel=use_kernel)

    out = jax.jit(shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                            out_specs=spec))(q, k, v)
    ref = mha_reference(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                        jnp.swapaxes(v, 1, 2), causal=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.swapaxes(ref, 1, 2)),
                               rtol=3e-4, atol=3e-4)


def test_ring_grad_matches_global():
    n = 2
    mesh, P, shard_map, q, k, v = _ring_setup(n=n, s=128, h=2, hk=1)
    spec = P(None, "sep", None, None)
    g = _rand(q.shape, 31)

    ring = shard_map(
        lambda q, k, v: ring_flash_attention(
            q, k, v, axis_name="sep", causal=True, axis_size=n,
            interpret=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)

    def loss_ring(q, k, v):
        return jnp.sum(ring(q, k, v) * g)

    def loss_ref(q, k, v):
        out = mha_reference(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                            jnp.swapaxes(v, 1, 2), causal=True)
        return jnp.sum(jnp.swapaxes(out, 1, 2) * g)

    gr_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    gr_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr_ring, gr_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


def test_ring_attention_in_hybrid_mesh():
    """User-level ring_attention under jit on a dp×sep mesh (other axes auto)."""
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.fleet.utils import ring_attention
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = mesh_mod.init_mesh({"dp": 2, "sep": 4})
    try:
        q = _rand((2, 256, 2, 16), 41)
        k = _rand((2, 256, 2, 16), 42)
        v = _rand((2, 256, 2, 16), 43)
        shard = NamedSharding(mesh, P("dp", "sep", None, None))
        qs, ks, vs = (jax.device_put(x, shard) for x in (q, k, v))

        fn = jax.jit(lambda q, k, v: ring_attention(
            q, k, v, causal=True, interpret=True))
        out = fn(qs, ks, vs)
        ref = mha_reference(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                            jnp.swapaxes(v, 1, 2), causal=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(jnp.swapaxes(ref, 1, 2)),
                                   rtol=3e-4, atol=3e-4)
    finally:
        mesh_mod.reset_mesh()


class TestXlaFlashTier:
    """Pure-XLA flash tier (_xflash): flash memory behavior without the
    Pallas kernel (SDPA's long-sequence route). Parity vs mha_reference with
    multi-block scans forced via the block-size env knobs."""

    def _check(self, b, hq, hk, sq, sk, d, causal, qo, ko, monkeypatch):
        import jax
        import jax.numpy as jnp
        monkeypatch.setenv("PADDLE_TPU_XFA_BLOCK_Q", "64")
        monkeypatch.setenv("PADDLE_TPU_XFA_BLOCK_K", "32")
        from paddle_tpu.ops.pallas.flash_attention import (
            NEG_INF, _xflash, _xflash_with_lse, mha_reference)
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((b, hq, sq, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, hk, sk, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, hk, sk, d)), jnp.float32)
        offs = jnp.asarray([qo, ko], jnp.int32)
        out, lse = jax.jit(
            lambda *a: _xflash_with_lse(*a, causal, 0.125))(q, k, v, offs)
        ref, rlse = mha_reference(q, k, v, causal=causal, sm_scale=0.125,
                                  q_offset=qo, kv_offset=ko, with_lse=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)
        live = np.asarray(rlse) > NEG_INF / 2
        np.testing.assert_allclose(np.asarray(lse)[live],
                                   np.asarray(rlse)[live], atol=2e-5)

        def loss_x(q, k, v):
            return (_xflash(q, k, v, offs, causal, 0.125) ** 2).sum()

        def loss_r(q, k, v):
            return (mha_reference(q, k, v, causal=causal, sm_scale=0.125,
                                  q_offset=qo, kv_offset=ko) ** 2).sum()

        gx = jax.jit(jax.grad(loss_x, (0, 1, 2)))(q, k, v)
        gr = jax.grad(loss_r, (0, 1, 2))(q, k, v)
        for a, b_ in zip(gx, gr):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b_), atol=5e-4)

    def test_causal_mha(self, monkeypatch):
        self._check(2, 4, 4, 128, 128, 32, True, 0, 0, monkeypatch)

    def test_causal_gqa_uneven(self, monkeypatch):
        self._check(2, 8, 2, 128, 96, 32, True, 0, 0, monkeypatch)

    def test_full_attention(self, monkeypatch):
        self._check(2, 4, 4, 128, 128, 32, False, 0, 0, monkeypatch)

    def test_decode_offset(self, monkeypatch):
        self._check(1, 4, 2, 64, 256, 32, True, 192, 0, monkeypatch)

    def test_fully_masked_rows(self, monkeypatch):
        self._check(1, 2, 2, 64, 64, 16, True, 0, 32, monkeypatch)

    def test_lse_cotangent_flows(self, monkeypatch):
        """Ring attention differentiates through lse (shard merging) — the
        XLA tier must propagate the lse cotangent like the Mosaic bwd."""
        import jax
        import jax.numpy as jnp
        monkeypatch.setenv("PADDLE_TPU_XFA_BLOCK_Q", "32")
        monkeypatch.setenv("PADDLE_TPU_XFA_BLOCK_K", "32")
        from paddle_tpu.ops.pallas.flash_attention import (
            _xflash_with_lse, mha_reference)
        rng = np.random.default_rng(2)
        q = jnp.asarray(rng.standard_normal((1, 2, 64, 16)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((1, 2, 64, 16)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, 2, 64, 16)), jnp.float32)
        offs = jnp.asarray([0, 0], jnp.int32)

        def loss_x(q, k, v):
            out, lse = _xflash_with_lse(q, k, v, offs, True, 0.25)
            return (out ** 2).sum() + (lse * 0.3).sum()

        def loss_r(q, k, v):
            out, lse = mha_reference(q, k, v, causal=True, sm_scale=0.25,
                                     with_lse=True)
            return (out ** 2).sum() + (lse * 0.3).sum()

        gx = jax.jit(jax.grad(loss_x, (0, 1, 2)))(q, k, v)
        gr = jax.grad(loss_r, (0, 1, 2))(q, k, v)
        for a, b in zip(gx, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4)


class TestChunkedFallbackTier:
    """The chunked-reference tier (_xla_fallback with sq > chunk) — the
    path long sequences take when the scan formulation is pinned off
    (PADDLE_TPU_XFA=0)."""

    def test_chunked_matches_unchunked(self):
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.flash_attention import (_xla_fallback,
                                                           mha_reference)
        rng = np.random.default_rng(3)
        q = jnp.asarray(rng.standard_normal((1, 2, 256, 16)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((1, 2, 256, 16)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, 2, 256, 16)), jnp.float32)
        out = _xla_fallback(q, k, v, True, 0.25, 0, 0, chunk=64)
        ref = mha_reference(q, k, v, causal=True, sm_scale=0.25)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)
        o2, l2 = _xla_fallback(q, k, v, True, 0.25, 0, 0, with_lse=True,
                               chunk=64)
        r2, rl2 = mha_reference(q, k, v, causal=True, sm_scale=0.25,
                                with_lse=True)
        np.testing.assert_allclose(np.asarray(o2), np.asarray(r2), atol=2e-5)
        np.testing.assert_allclose(np.asarray(l2), np.asarray(rl2), atol=2e-5)

    def test_chunked_offsets_trimmed_kv(self):
        """Bottom-right-aligned causal (decode convention, q_offset>0):
        the kv-trim must respect global positions, not local indices."""
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.flash_attention import (_xla_fallback,
                                                           mha_reference)
        rng = np.random.default_rng(5)
        q = jnp.asarray(rng.standard_normal((1, 2, 128, 16)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((1, 2, 256, 16)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, 2, 256, 16)), jnp.float32)
        out = _xla_fallback(q, k, v, True, 0.25, 128, 0, chunk=32)
        ref = mha_reference(q, k, v, causal=True, sm_scale=0.25,
                            q_offset=128, kv_offset=0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_chunked_grads_match(self):
        """The chunk remat (jax.checkpoint per chunk) must not change
        gradients — and grads must flow through k/v, which are shared
        across every chunk call."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.flash_attention import (_xla_fallback,
                                                           mha_reference)
        rng = np.random.default_rng(4)
        q = jnp.asarray(rng.standard_normal((1, 2, 256, 16)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((1, 2, 256, 16)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, 2, 256, 16)), jnp.float32)

        def loss_c(q, k, v):
            out, lse = _xla_fallback(q, k, v, True, 0.25, 0, 0,
                                     with_lse=True, chunk=64)
            return (out ** 2).sum() + (lse * 0.1).sum()

        def loss_r(q, k, v):
            out, lse = mha_reference(q, k, v, causal=True, sm_scale=0.25,
                                     with_lse=True)
            return (out ** 2).sum() + (lse * 0.1).sum()

        gc = jax.jit(jax.grad(loss_c, (0, 1, 2)))(q, k, v)
        gr = jax.grad(loss_r, (0, 1, 2))(q, k, v)
        for a, b in zip(gc, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5)

    def test_xfa_env_pin_forces_chunked(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_XFA", "0")
        from paddle_tpu.ops.pallas.flash_attention import _xflash_ok
        import jax.numpy as jnp
        q = jnp.zeros((1, 2, 512, 16))
        assert not _xflash_ok(q, q)
        monkeypatch.setenv("PADDLE_TPU_XFA", "1")
        assert _xflash_ok(q, q)


class TestScanQTier:
    """Single-level scan tier (_scanq): lax.scan over q-chunks, full-K
    per chunk, remat body — constant graph size in sequence length, no
    scan-in-scan/custom_vjp (the structures suspected in the round-4
    remote-compile hang)."""

    def _all(self, b, hq, hk, sq, sk, d, causal, qo, chunk):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.flash_attention import (_scanq,
                                                           mha_reference)
        rng = np.random.default_rng(6)
        q = jnp.asarray(rng.standard_normal((b, hq, sq, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, hk, sk, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, hk, sk, d)), jnp.float32)
        out, lse = jax.jit(lambda q, k, v: _scanq(
            q, k, v, causal, 0.25, qo, 0, with_lse=True, chunk=chunk))(
                q, k, v)
        ref, rlse = mha_reference(q, k, v, causal=causal, sm_scale=0.25,
                                  q_offset=qo, with_lse=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(rlse),
                                   atol=2e-5)

        def loss_s(q, k, v):
            return (_scanq(q, k, v, causal, 0.25, qo, 0,
                           chunk=chunk) ** 2).sum()

        def loss_r(q, k, v):
            return (mha_reference(q, k, v, causal=causal, sm_scale=0.25,
                                  q_offset=qo) ** 2).sum()

        gs = jax.jit(jax.grad(loss_s, (0, 1, 2)))(q, k, v)
        gr = jax.grad(loss_r, (0, 1, 2))(q, k, v)
        for a, b_ in zip(gs, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=5e-5)

    def test_causal_mha(self):
        self._all(1, 2, 2, 256, 256, 16, True, 0, 64)

    def test_noncausal_gqa(self):
        self._all(1, 4, 2, 128, 128, 16, False, 0, 32)

    def test_decode_aligned_offset(self):
        self._all(1, 2, 2, 128, 256, 16, True, 128, 32)

    def test_selection_knob(self, monkeypatch):
        import importlib
        import jax.numpy as jnp
        # the package re-exports the flash_attention FUNCTION under the
        # same name as the submodule, so plain `import ... as fa` binds
        # the function — load the module object explicitly
        fa = importlib.import_module(
            "paddle_tpu.ops.pallas.flash_attention")
        q = jnp.zeros((1, 2, 2048, 16))
        monkeypatch.setenv("PADDLE_TPU_XFA", "scanq")
        assert fa._scanq_ok(q) and not fa._xflash_ok(q, q)
        monkeypatch.setenv("PADDLE_TPU_XFA", "1")
        assert not fa._scanq_ok(q) and fa._xflash_ok(q, q)
        monkeypatch.setenv("PADDLE_TPU_XFA", "0")
        assert not fa._scanq_ok(q) and not fa._xflash_ok(q, q)


@pytest.mark.parametrize("causal", [True, False])
def test_sdpa_long_seq_routes_chunked(causal, monkeypatch):
    """F.scaled_dot_product_attention: no-mask attention at seq>=4096
    with flash unavailable must route through the pure-XLA tier
    dispatcher (O(chunk*S) memory) and match the full-scores
    reference. A spy asserts the route is actually taken."""
    import importlib
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

    calls = []
    real = fa.xla_attention
    monkeypatch.setattr(fa, "xla_attention",
                        lambda *a, **kw: (calls.append(1), real(*a, **kw))[1])

    rng = np.random.default_rng(8)
    q = paddle.to_tensor(rng.standard_normal((1, 4096, 1, 8)).astype("float32"))
    k = paddle.to_tensor(rng.standard_normal((1, 4096, 1, 8)).astype("float32"))
    v = paddle.to_tensor(rng.standard_normal((1, 4096, 1, 8)).astype("float32"))
    out = F.scaled_dot_product_attention(q, k, v, is_causal=causal)
    assert calls, "long-seq SDPA did not take the xla_attention route"
    ref = fa.mha_reference(jnp.swapaxes(q._data, 1, 2),
                           jnp.swapaxes(k._data, 1, 2),
                           jnp.swapaxes(v._data, 1, 2), causal=causal)
    np.testing.assert_allclose(np.asarray(out._data),
                               np.asarray(jnp.swapaxes(ref, 1, 2)),
                               atol=3e-5)
