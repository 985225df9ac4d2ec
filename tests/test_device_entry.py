"""Entry points refuse a missing device instead of standing in for it
(ISSUE 21): ``set_device``, the MFU peak table, the placeable compile
cache, and the shard_map the flash kernel needs under a mesh."""
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle


@pytest.mark.parametrize("device", ["tpu", "gpu:0", "xpu"])
def test_set_device_refuses_an_accelerator_that_is_not_there(device):
    before = paddle.get_device()
    with pytest.raises(RuntimeError, match="needs a TPU backend"):
        paddle.set_device(device)
    assert paddle.get_device() == before


def test_set_device_checks_the_index_and_the_kind():
    with pytest.raises(ValueError, match="out of range"):
        paddle.set_device(f"cpu:{len(jax.devices('cpu'))}")
    with pytest.raises(ValueError, match="unknown device"):
        paddle.set_device("npu")
    assert paddle.set_device("cpu").kind == "cpu"


def test_chip_kind_knows_or_raises():
    from paddle_tpu.profiler.mfu import PEAK_FLOPS, chip_kind
    dev = lambda plat, kind: types.SimpleNamespace(platform=plat,
                                                   device_kind=kind)
    assert chip_kind(dev("tpu", "TPU v5 lite")) == "v5e"
    assert chip_kind(dev("cpu", "cpu")) == "cpu"
    assert chip_kind(dev("tpu", "TPU v4")) in PEAK_FLOPS
    with pytest.raises(ValueError, match="no peak FLOP/s known"):
        chip_kind(dev("tpu", "TPU v9 mega"))


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set no code path sets a directory."""
    from paddle_tpu.jit import api as jit_api
    from paddle_tpu.inference import Config
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    monkeypatch.setattr(jit_api, "_PERSISTENT_CACHE", [None])
    updates = []
    real = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda k, v: updates.append(k) if k == "jax_compilation_cache_dir"
        else real(k, v))
    try:
        assert jit_api.enable_persistent_cache(str(tmp_path / "code"))
        Config().set_optim_cache_dir(str(tmp_path / "other"))
        assert jit_api._PERSISTENT_CACHE[0] == str(tmp_path / "env")
    finally:
        real("jax_persistent_cache_min_entry_size_bytes", 0)
        real("jax_persistent_cache_min_compile_time_secs", 1.0)
    assert updates == []
    assert not (tmp_path / "code").exists()
    assert not (tmp_path / "other").exists()


def test_attention_kernel_runs_per_shard_under_a_mesh():
    """GSPMD cannot partition a Mosaic kernel, so under a mesh the kernel
    must see per-device shards: batch over dp x sharding, heads over mp."""
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.ops.pallas.flash_attention import mha_reference
    seen = []

    def kernel(q, k, v):                    # paddle layout [b, s, h, d]
        seen.append((q.shape, k.shape))
        out = mha_reference(*(jnp.swapaxes(x, 1, 2) for x in (q, k, v)),
                            causal=True)
        return jnp.swapaxes(out, 1, 2)

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((4, 32, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((4, 32, 2, 16)), jnp.float32)
    want = kernel(q, k, k)
    # eager, no mesh: the kernel as is
    assert mesh_mod.shard_attention_kernel(kernel, q, k, k) is not None
    seen.clear()
    mesh = mesh_mod.init_mesh({"dp": 2, "sharding": 2, "mp": 2})
    try:
        with mesh:
            got = jax.jit(lambda q, k, v: mesh_mod.shard_attention_kernel(
                kernel, q, k, v))(q, k, k)
    finally:
        mesh_mod.reset_mesh()
    assert seen == [((1, 32, 2, 16), (1, 32, 1, 16))]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
