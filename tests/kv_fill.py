"""Put rows into a ``SlotPagedKVCache`` slot the way the engine's tick
does: one ragged span at the slot's current length, ``attend``, then
``advance`` (the q-block kernel in interpret mode on the CPU: keep the
callers' sizes small)."""
import jax.numpy as jnp


def write_rows(cache, slot, layer, q, k, v):
    """``q`` [1, n, heads, d], ``k`` / ``v`` [1, n, kv_heads, d] (arrays or
    Tensors): the slot's next ``n`` context tokens. Returns ``attend``'s
    output for them."""
    def arr(x):
        return x if hasattr(x, "_data") else jnp.asarray(x)
    n = int(k.shape[1])
    cache.begin_ragged([(slot, 0, n)])
    out = cache.attend(layer, arr(q), arr(k), arr(v))
    cache.advance(n)
    return out
