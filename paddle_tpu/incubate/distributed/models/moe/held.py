"""An expert layer that is told which experts it holds.

Expert parallelism gives each chip a contiguous run of a layer's routed
experts (``held = range(lo, lo + n)`` of ``num_experts``). The layer here
is one chip's part of that: the router keeps its full width and picks its
``top_k`` over ALL experts; the tokens routed to a held expert are sorted by
expert and run through ONE grouped matrix product a projection
(``jax.lax.ragged_dot``: on a TPU a native grouped matmul, the Mosaic call
``ragged-dot...``); what an absent expert would add is left out.

What a visited tile is. The grouped matmul walks a list of (group, row
tile) visits, only those a group really has rows in, and a visit computes a
whole ``tm x tk x tn`` tile (rows x contraction x output columns) for every
block of the expert's ``[K, N]`` slab, whatever share of the ``tm`` rows
are the group's. Left alone the TPU compiler takes ``tm`` = 512 (the
largest power of two that divides the sorted buffer) and weight blocks of
512 x 256 or 512 x 512. A held expert of a serving tick has 3-48 rows, so
the MXU computed 512 for them; and a block that splits the contraction is
fetched anew for every row tile, while one that holds it whole stays for
all the row tiles of its group. :func:`grouped_tiling` picks a tile of at
most 128 rows and the widest block of whole contractions that fits VMEM,
and :func:`held_expert_sum` hands it to the compiler (the
``ragged_dot_tiling`` attribute): the same kernel and the same numbers, bit
for bit, in other tiles, at 0.3-0.8 of the compiler's tile's time from 2
to 1,000 rows a group in buffers of 128 rows or more, and at the same
time in smaller ones (PERF.md, PR 34).

There is no capacity and no dropped token: the sorted buffer holds every
(token, choice) pair. Nothing here stands in for the other chips or their
exchange: a caller that runs every share adds the parts up
(``tests/test_deepseek_v3.py`` does, against the uncut layer).

Two routers, chosen by the layer's ``router`` argument:

* ``"sigmoid_group"``: the sigmoid / group-limited one of the DeepSeek-V3
  family (``topk_method = noaux_tc``): scores ``s = sigmoid(x W_r)`` in
  float32; the choice is made on ``s + b`` (``b`` a learned bias an expert
  that steers load and is NOT part of the weight): the experts stand in
  ``n_group`` groups, a group's score is the sum of its two best, the best
  ``topk_group`` groups are kept and the best ``top_k`` experts among them
  chosen; the weights are ``s_i / sum(s_chosen) * routed_scaling_factor``;
* ``"topk_softmax"`` (SmallThinker): the best ``top_k`` of the logits
  ``z = x W_r``, weighed by a softmax over the kept logits alone.

The experts are gated units ``W_down(act(W_gate x) * (W_up x))`` whose
``activation`` is an argument: SiLU (SwiGLU, the DeepSeek-V3 family) or
ReLU (ReGLU, SmallThinker). The router may read another input than the
experts (``forward(x, router_input=...)``): SmallThinker routes from the
layer's normalised input, before attention.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp

from .....autograd.tape import apply
from .....framework.core import Tensor
from .....nn.initializer import Normal
from .....nn.layer import Layer

from jax.custom_derivatives import SymbolicZero
from jax.experimental.xla_metadata import set_xla_metadata

__all__ = ["group_limited_topk", "sigmoid_group_route", "topk_softmax_route",
           "grouped_tiling", "held_expert_sum", "HeldExperts"]

_HI = jax.lax.Precision.HIGHEST


def group_limited_topk(choice_scores, n_group, topk_group, top_k):
    """``choice_scores`` [S, E] float32 -> indices [S, top_k] of the best
    ``top_k`` experts inside the best ``topk_group`` of ``n_group`` groups,
    a group scored by the sum of its two best experts."""
    s, e = choice_scores.shape
    per = e // n_group
    grouped = choice_scores.reshape(s, n_group, per)
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    _, kept = jax.lax.top_k(group_score, topk_group)          # [S, kept]
    keep = jnp.zeros((s, n_group), bool).at[
        jnp.arange(s)[:, None], kept].set(True)
    masked = jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(s, e)
    return jax.lax.top_k(masked, top_k)[1]


def sigmoid_group_route(x, w_router, bias, *, n_group, topk_group, top_k,
                        scale, norm_topk=True):
    """``x`` [S, h] -> (chosen experts [S, k] int32, weights [S, k]
    float32). The router's product and everything after it run in float32
    at the highest matmul precision, whatever ``x``'s type: a bf16 router
    flips choices."""
    scores = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), w_router.astype(jnp.float32), precision=_HI))
    idx = group_limited_topk(scores + bias.astype(jnp.float32)[None],
                             n_group, topk_group, top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * scale


def topk_softmax_route(x, w_router, *, top_k):
    """``x`` [S, h] -> (chosen experts [S, k] int32, weights [S, k]
    float32): the best ``top_k`` logits ``x W_r`` and a softmax over those
    alone (so the weights add up to 1 and ``norm_topk_prob`` has nothing
    left to do). Float32 at the highest matmul precision, as
    :func:`sigmoid_group_route` and for its reason."""
    logits = jnp.matmul(x.astype(jnp.float32), w_router.astype(jnp.float32),
                        precision=_HI)
    kept, idx = jax.lax.top_k(logits, top_k)
    return idx.astype(jnp.int32), jax.nn.softmax(kept, axis=-1)


#: the gate's activation, by the name a configuration gives it
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


# The constants below are a TPU v5e's (the chip this repo measures on:
# 16 MiB of scoped VMEM a kernel, 197 TFLOP/s over 819 GB/s), read from
# ``tools/grouped_product_bench.py``'s tables (PERF.md, PR 34).

#: bytes of VMEM a grouped product's blocks may fill, by the TPU compiler's
#: own count (two buffers each of the row tile, the weight block and the
#: output tile): it scopes 16 MiB to a kernel and needs up to ~1 MiB of
#: its own besides
TILE_VMEM_BUDGET = 12 * 2 ** 20
#: the row tile's range: the kernel takes 8 rows (a sublane tile) at the
#: least, whatever the type; a tile past 128 computes more than the weight
#: block's fetch hides (v5e turns memory-bound under ~240 rows a 2-byte
#: weight) and leaves the block less room
TILE_ROWS_MIN, TILE_ROWS_MAX = 8, 128


def tile_vmem_bytes(tm, k, tn, itemsize, out_itemsize):
    """What a ``tm x k x tn`` tile of a grouped product keeps in VMEM, as
    the TPU compiler counts it (its refusals say: PERF.md, PR 34)."""
    return 2 * (tm * k * itemsize + k * tn * itemsize
                + tm * tn * out_itemsize)


def grouped_tiling(rows, k, n, itemsize, out_itemsize=None):
    """The tile ``(tm, tk, tn)`` of a grouped product ``[rows, k] x
    [groups, k, n]``, from its shapes, or None: the compiler's own choice.

    ``tm``: the largest power of two up to ``TILE_ROWS_MAX`` that divides
    ``rows`` (the kernel's condition). ``tk`` = ``k``: the kernel keeps a
    block of whole contractions for all the row tiles of its group, and
    streams one of a split contraction again for every row tile. ``tn``:
    the widest block of columns, a multiple of 128 that divides ``n``, that
    keeps :func:`tile_vmem_bytes` inside ``TILE_VMEM_BUDGET``. Nothing
    here depends on the load: this tile was the best tried or within 8 % of
    it from 2 to 1,000 rows a group. None where 8 does not divide ``rows``,
    128 does not divide ``k``, or no such block fits."""
    out_itemsize = out_itemsize or itemsize
    tm = TILE_ROWS_MAX
    while tm > TILE_ROWS_MIN and rows % tm:
        tm //= 2
    fits = [tn for tn in range(128, n + 1, 128) if n % tn == 0
            and tile_vmem_bytes(tm, k, tn, itemsize, out_itemsize)
            <= TILE_VMEM_BUDGET]
    if rows % tm or k % 128 or not fits:
        return None
    return tm, k, max(fits)


@functools.partial(jax.custom_jvp, nondiff_argnums=(3,))
def _grouped_product(rows, w, sizes, out_dtype):
    """``jax.lax.ragged_dot`` under :func:`grouped_tiling`'s tile. The hint
    is the forward product's alone: JAX keeps an operation's metadata for
    its transposes, whose contraction and columns are other dimensions (a
    TPU compile of the gradient under the forward's tile fails for VMEM),
    so the derivative is written out in products that carry none and
    compile as they did."""
    tile = grouped_tiling(rows.shape[0], w.shape[1], w.shape[2],
                          rows.dtype.itemsize,
                          jnp.dtype(out_dtype or rows.dtype).itemsize)
    hint = (set_xla_metadata(ragged_dot_tiling="%d,%d,%d" % tile)
            if tile else contextlib.nullcontext())
    with hint:
        return jax.lax.ragged_dot(rows, w, sizes,
                                  preferred_element_type=out_dtype)


@functools.partial(_grouped_product.defjvp, symbolic_zeros=True)
def _grouped_product_jvp(out_dtype, primals, tangents):
    rows, w, sizes = primals
    d_rows, d_w, _ = tangents
    terms = [jax.lax.ragged_dot(a, b, sizes, preferred_element_type=out_dtype)
             for a, b in ((d_rows, w), (rows, d_w))
             if SymbolicZero not in (type(a), type(b))]
    return _grouped_product(rows, w, sizes, out_dtype), sum(terms[1:],
                                                            terms[0])


def held_expert_sum(x, idx, weights, w_gate, w_up, w_down, lo, valid=None,
                    activation=jax.nn.silu):
    """The held experts' part of a routed layer of gated units
    ``W_down(activation(W_gate x) * (W_up x))`` (SiLU: SwiGLU; ReLU: ReGLU).

    ``x`` [S, h]; ``idx`` / ``weights`` [S, k] from the router (over ALL
    experts); ``w_gate`` / ``w_up`` [n, h, m] and ``w_down`` [n, m, h] are
    the held experts ``lo .. lo + n - 1``, stacked. Returns ``(out [S, h]
    in ``x``'s type, tokens routed to each held expert [n] int32, tokens
    none of whose choices is held [] int32)``; the two counts leave out
    rows where ``valid`` [S] is False (a serving tick's bucket padding).
    """
    s, k = idx.shape
    n = w_gate.shape[0]
    local = idx - lo
    held = (local >= 0) & (local < n)                         # [S, k]
    key = jnp.where(held, local, n).reshape(-1)               # n: not here
    order = jnp.argsort(key, stable=True)                     # [S*k]
    sizes = jnp.bincount(key, length=n + 1)[:n].astype(jnp.int32)
    rows = x[order // k]                                      # [S*k, h]
    gate = _grouped_product(rows, w_gate, sizes, None)
    up = _grouped_product(rows, w_up, sizes, None)
    act = (activation(gate.astype(jnp.float32))
           * up.astype(jnp.float32)).astype(x.dtype)
    y = _grouped_product(act, w_down, sizes, jnp.float32)
    # each pair's row of ``y`` by the inverse permutation: a gather, where
    # the forward form would be a scatter-add; pairs of absent experts
    # (sorted past the last group, whose rows no group computes) weigh 0
    inv = jnp.zeros(s * k, jnp.int32).at[order].set(
        jnp.arange(s * k, dtype=jnp.int32))
    w = jnp.where(held, weights, 0.0).astype(jnp.float32)     # [S, k]
    picked = jnp.where(held.reshape(-1)[:, None], y[inv], 0.0)
    out = jnp.einsum("skh,sk->sh", picked.reshape(s, k, -1), w)
    if valid is None:
        valid = jnp.ones(s, bool)
    counted = held & valid[:, None]
    per_expert = jnp.bincount(jnp.where(counted, local, n).reshape(-1),
                              length=n + 1)[:n].astype(jnp.int32)
    unheld = jnp.sum(valid & ~jnp.any(held, axis=-1)).astype(jnp.int32)
    return out.astype(x.dtype), per_expert, unheld


class HeldExperts(Layer):
    """Router over ``num_experts`` + the stacked gate / up / down weights
    of the experts ``held = (lo, n)`` this chip holds (default: all of
    them). ``router``: ``"sigmoid_group"`` (with its bias) or
    ``"topk_softmax"``; ``activation``: ``"silu"`` or ``"relu"`` (module
    docstring). ``forward(x [.., h], valid=None, router_input=None)``
    returns ``(the held experts' sum, {"moe_expert_tokens": [n],
    "moe_unheld_tokens": []})``; the router reads ``router_input`` where
    one is given, else ``x``."""

    def __init__(self, hidden_size, expert_size, num_experts, top_k, *,
                 n_group=1, topk_group=1, scale=1.0, norm_topk=True,
                 held=None, initializer_range=0.02, router="sigmoid_group",
                 activation="silu"):
        super().__init__()
        lo, n = (0, num_experts) if held is None else map(int, held)
        if not (0 <= lo and lo + n <= num_experts and n > 0):
            raise ValueError(f"held experts {lo}..{lo + n - 1} are not "
                             f"inside 0..{num_experts - 1}")
        if num_experts % n_group:
            raise ValueError(f"{num_experts} experts do not divide into "
                             f"{n_group} groups")
        if router not in ("sigmoid_group", "topk_softmax"):
            raise ValueError(f"unknown router {router!r}")
        if activation not in ACTIVATIONS:
            raise ValueError(f"unknown activation {activation!r}")
        self.num_experts, self.top_k = num_experts, top_k
        self.n_group, self.topk_group = n_group, topk_group
        self.scale, self.norm_topk = float(scale), bool(norm_topk)
        self.held = (lo, n)
        self.router_kind, self.activation = router, activation
        init = Normal(0.0, initializer_range)
        # the router stays float32 under a bf16 model (as the published
        # checkpoints keep it): its scores decide which experts run
        self.router = self.create_parameter(
            [hidden_size, num_experts], dtype="float32",
            default_initializer=init)
        if router == "sigmoid_group":
            self.router_bias = self.create_parameter(
                [num_experts], dtype="float32", is_bias=True)
        self.w_gate = self.create_parameter(
            [n, hidden_size, expert_size], default_initializer=init)
        self.w_up = self.create_parameter(
            [n, hidden_size, expert_size], default_initializer=init)
        self.w_down = self.create_parameter(
            [n, expert_size, hidden_size], default_initializer=init)

    def _route(self, tok, wr, br):
        if self.router_kind == "topk_softmax":
            return topk_softmax_route(tok, wr, top_k=self.top_k)
        return sigmoid_group_route(
            tok, wr, br, n_group=self.n_group, topk_group=self.topk_group,
            top_k=self.top_k, scale=self.scale, norm_topk=self.norm_topk)

    def forward(self, x, valid=None, router_input=None):
        lo, _ = self.held
        shape = x.shape
        if isinstance(valid, Tensor):
            valid = valid._data
        biased = self.router_kind == "sigmoid_group"
        act = ACTIVATIONS[self.activation]

        def fn(xa, ra, wr, wg, wu, wd, *br):
            tok = xa.reshape(-1, shape[-1])
            with jax.named_scope("moe/route"):
                idx, w = self._route(ra.reshape(-1, shape[-1]), wr,
                                     br[0] if biased else None)
            with jax.named_scope("moe/experts"):
                out, per_expert, unheld = held_expert_sum(
                    tok, idx, w, wg, wu, wd, lo,
                    None if valid is None else valid.reshape(-1), act)
            return out.reshape(shape), per_expert, unheld

        out, per_expert, unheld = apply(
            fn, x, x if router_input is None else router_input, self.router,
            self.w_gate, self.w_up, self.w_down,
            *((self.router_bias,) if biased else ()), op_name="held_experts")
        return out, {"moe_expert_tokens": per_expert,
                     "moe_unheld_tokens": unheld}
