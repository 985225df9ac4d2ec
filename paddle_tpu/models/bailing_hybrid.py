"""Bailing hybrid model family (``model_type: bailing_hybrid``;
``inclusionAI/Ling-3.0-flash``): a decoder whose layers come in groups of
``layer_group_size``: linear-attention layers (Kimi Delta Attention, KDA:
Kimi Linear, arXiv:2510.26692) and, as the group's last, one layer of
multi-head latent attention (MLA); leading dense layers, then routed experts
with a shared one in every layer.

``x`` is a layer's input after ``input_layernorm``; ``H`` heads of ``d``.

**KDA mixer.** ``q~, k~, v~ = x W_q, x W_k, x W_v``; a causal depthwise
convolution of ``short_conv_kernel_size`` taps over each, then SiLU, zeros
before a sequence's start; a head: ``q = l2norm(q) d^-1/2``, ``k =
l2norm(k)``; a decay a channel ``g = kda_lower_bound * sigmoid(exp(A_log_h)
(x W_f + dt_bias))`` (the bounded gate: ``g`` in ``(kda_lower_bound, 0)``);
``beta = sigmoid(x W_b)`` a head; the recurrence of ``ops/pallas/kda.py``
over a head's state ``S`` [d, d], float32, zero at a request's start; ``o =
rmsnorm_head(o) * sigmoid(x W_g)``, then ``W_o``.

**MLA mixer**: ``deepseek_v3.py::DeepseekV3Attention`` without a query rank
and with a head-wise output gate (each head's output times ``sigmoid(x
W_hg)_h``), in both its forms.

**Experts**: DeepSeek-V3's sigmoid / group-limited router with its bias
(``held.py::sigmoid_group_route``), SwiGLU experts, one shared expert.

Behind ``SlotPagedKVCache`` a KDA layer keeps no pages but a STATE a slot
(``state_spec``: ``S`` and the convolution's last rows) and runs as two
compiled programs around its own eager kernel entry
(``llama.py::RaggedLayerPrograms``: ``pre_state`` / ``mix_state`` /
``post_state``); the MLA layers keep the latent page pool. A model has three
KINDS of layer (``kind``: ``kda_dense`` | ``kda_moe`` | ``mla_moe``, and
``mla_dense`` where a cut puts one there). Every KDA layer of a step reads
one plan of the step's spans (``step_plan``: which rows go through the
one-token kernel, the packed jobs of the chunk kernel, where the
convolution finds each token's earlier rows), made once, on the host.

Expert parallelism: ``config.held_experts = (lo, n)`` as in
``deepseek_v3.py``. Not built: the multi-token-prediction module
(``num_nextn_predict_layers`` must be 0) and the clamp of
``expert_swiglu_limit_list`` (a non-zero entry of a kept layer raises).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.core import Tensor
from ..nn.layer import Layer, LayerList
from ..nn.layers.common import Linear, Embedding
from ..nn.layers.norm import RMSNorm
from ..nn.initializer import Normal, Constant
from ..autograd.tape import apply
from ..incubate.distributed.models.moe.held import HeldExperts
from ..ops.pallas import kda
from ..profiler import spans as _spans
from .deepseek_v3 import DeepseekV3Attention, DeepseekV3MLP
from .generation import GenerationMixin, SlotPagedKVCache
from .llama import LlamaModel, _raw

__all__ = ["BailingHybridConfig", "BailingHybridModel",
           "BailingHybridForCausalLM", "bailing_hybrid_tiny"]

#: a tick's chunk-kernel job list is padded to one of two lengths: what the
#: tokens need and this many spans more, or the most a tick can make
CHUNK_SPANS_SMALL = 8


class BailingHybridConfig:
    """The published ``config.json`` keys, plus ``held_experts`` (this
    chip's ``(lo, n)`` of the routed experts; None holds them all) and, for
    a cut of the published depth, ``layer_kinds`` (``"kda"`` | ``"mla"`` a
    kept layer; default: the published rule, layer ``i`` is MLA iff ``(i +
    1) % layer_group_size == 0``) and ``layer_indices`` (a kept layer's
    published index, which the two swiglu-limit lists are read at)."""

    def __init__(self, vocab_size=157184, hidden_size=2560,
                 intermediate_size=6144, moe_intermediate_size=768,
                 num_hidden_layers=42, num_attention_heads=32, head_dim=128,
                 layer_group_size=6, kv_lora_rank=512, q_lora_rank=None,
                 qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                 num_experts=512, num_experts_per_tok=8, n_group=8,
                 topk_group=4, num_shared_experts=1,
                 moe_shared_expert_intermediate_size=768,
                 routed_scaling_factor=2.5, norm_topk_prob=True,
                 first_k_dense_replace=2, short_conv_kernel_size=4,
                 kda_lower_bound=-5.0, kda_safe_gate=True, no_kda_lora=True,
                 use_qk_norm=True, linear_silu=True, group_norm_size=1,
                 num_kv_heads_for_linear_attn=0,
                 gated_attention_proj_granularity_type="head_wise",
                 score_function="sigmoid", topk_method="noaux_tc",
                 moe_router_enable_expert_bias=True,
                 expert_swiglu_limit_list=None,
                 share_expert_swiglu_limit_list=None,
                 num_nextn_predict_layers=0, rms_norm_eps=1e-6,
                 rope_theta=6e6, rope_scaling=None, rope_interleave=True,
                 max_position_embeddings=4096, initializer_range=0.02,
                 held_experts=None, layer_kinds=None, layer_indices=None,
                 dtype="float32", **kwargs):
        refused = [what for what, bad in (
            ("a router other than sigmoid / noaux_tc with its bias",
             score_function != "sigmoid" or topk_method != "noaux_tc"
             or not moe_router_enable_expert_bias),
            ("the multi-token-prediction module",
             num_nextn_predict_layers != 0),
            ("an unbounded KDA gate (kda_safe_gate false)",
             not kda_safe_gate or kda_lower_bound >= 0),
            ("low-rank KDA gates (no_kda_lora false)", not no_kda_lora),
            ("KDA without its q / k norm, its SiLU or with a head norm "
             "over several heads",
             not use_qk_norm or not linear_silu or group_norm_size != 1),
            ("fewer KV heads in a KDA layer",
             num_kv_heads_for_linear_attn not in (0, num_attention_heads)),
            ("an output gate other than head_wise",
             gated_attention_proj_granularity_type != "head_wise"),
            ("rope that is not interleaved", not rope_interleave)) if bad]
        if refused:
            raise ValueError("not built: " + "; ".join(refused))
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.head_dim = head_dim
        self.layer_group_size = layer_group_size
        self.kv_lora_rank = kv_lora_rank
        self.q_lora_rank = q_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.n_group, self.topk_group = n_group, topk_group
        self.num_shared_experts = num_shared_experts
        self.moe_shared_expert_intermediate_size = \
            moe_shared_expert_intermediate_size
        self.routed_scaling_factor = routed_scaling_factor
        self.norm_topk_prob = norm_topk_prob
        self.first_k_dense_replace = first_k_dense_replace
        self.short_conv_kernel_size = int(short_conv_kernel_size)
        self.kda_lower_bound = float(kda_lower_bound)
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.rope_scaling = rope_scaling
        self.max_position_embeddings = max_position_embeddings
        self.initializer_range = initializer_range
        self.held_experts = held_experts
        self.dtype = dtype
        #: what ``DeepseekV3Attention`` reads beside the published keys
        self.attn_output_gate = "head_wise"
        self.layer_indices = list(
            layer_indices if layer_indices is not None
            else range(num_hidden_layers))
        self.layer_kinds = list(layer_kinds or (
            "mla" if (i + 1) % layer_group_size == 0 else "kda"
            for i in self.layer_indices))
        if len(self.layer_kinds) != num_hidden_layers \
                or len(self.layer_indices) != num_hidden_layers \
                or set(self.layer_kinds) - {"kda", "mla"}:
            raise ValueError("layer_kinds / layer_indices do not name "
                             f"{num_hidden_layers} layers of kda | mla")
        for name, limits in (
                ("expert_swiglu_limit_list", expert_swiglu_limit_list),
                ("share_expert_swiglu_limit_list",
                 share_expert_swiglu_limit_list)):
            clamped = [i for i in self.layer_indices
                       if limits and i < len(limits) and limits[i]]
            if clamped:
                raise ValueError(
                    f"{name} is not 0 at the kept layers {clamped}: the "
                    "clamp's form is not published and is not built")
        for k, v in kwargs.items():
            setattr(self, k, v)


def bailing_hybrid_tiny(**kw):
    """CI-sized: one dense KDA layer, then a whole group (5 KDA + 1 MLA)
    with 16 experts in 4 groups of which 2 are kept, 4 a token."""
    for k, v in dict(
            vocab_size=128, hidden_size=64, intermediate_size=160,
            moe_intermediate_size=32, num_hidden_layers=7,
            num_attention_heads=4, head_dim=16, kv_lora_rank=16,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            num_experts=16, num_experts_per_tok=4, n_group=4, topk_group=2,
            moe_shared_expert_intermediate_size=32, first_k_dense_replace=1,
            layer_kinds=["kda"] * 6 + ["mla"], rope_theta=10000.0,
            max_position_embeddings=128).items():
        kw.setdefault(k, v)
    return BailingHybridConfig(**kw)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


class BailingKDA(Layer):
    """The KDA mixer (module docstring). Its mathematics is in raw-array
    functions that every path shares: the cache-less forward, the eager
    path through the serving cache and the compiled programs."""

    def __init__(self, config):
        super().__init__()
        h = config.hidden_size
        self.num_heads, self.head_dim = nh, d = (config.num_attention_heads,
                                                 config.head_dim)
        self.taps = config.short_conv_kernel_size
        self.lower = config.kda_lower_bound
        self.eps = config.rms_norm_eps
        init = Normal(0.0, config.initializer_range)

        def lin(n):
            return Linear(h, n, weight_attr=init, bias_attr=False)

        self.q_proj, self.k_proj, self.v_proj = lin(nh * d), lin(nh * d), \
            lin(nh * d)
        #: a weight a tap and channel, q | k | v side by side
        self.conv_weight = self.create_parameter(
            [self.taps, 3 * nh * d], default_initializer=Normal(0.0, 0.5))
        self.f_proj, self.b_proj, self.g_proj = lin(nh * d), lin(nh), \
            lin(nh * d)
        self.A_log = self.create_parameter(
            [nh], dtype="float32", default_initializer=Constant(0.0))
        self.dt_bias = self.create_parameter(
            [nh * d], dtype="float32", default_initializer=Constant(0.0))
        #: a head's output norm; ``output`` applies its weight in float32
        self.o_norm = RMSNorm(d, config.rms_norm_eps)
        self.o_proj = Linear(nh * d, h, weight_attr=init, bias_attr=False)

    # -- the pieces ---------------------------------------------------------
    def projections(self, x):
        """``x`` [b, s, h] -> ``(q~ | k~ | v~ [b, s, 3 H d], g [b, s, H, d]
        float32, beta [b, s, H] float32, the output gate's logits [b, s, H
        d])``."""
        qkv = apply(lambda *a: jnp.concatenate(a, -1), self.q_proj(x),
                    self.k_proj(x), self.v_proj(x), op_name="kda_qkv")
        nh, d, lower = self.num_heads, self.head_dim, self.lower

        def gates(f, b, a_log, dt):
            z = jnp.exp(a_log)[:, None] * (f.astype(jnp.float32) + dt
                                           ).reshape(f.shape[:-1] + (nh, d))
            return (lower * jax.nn.sigmoid(z),
                    jax.nn.sigmoid(b.astype(jnp.float32)))

        g, beta = apply(gates, self.f_proj(x), self.b_proj(x), self.A_log,
                        self.dt_bias, op_name="kda_gates")
        return qkv, g, beta, self.g_proj(x)

    def conv_heads(self, rows, w):
        """``rows``: the ``taps`` rows [.., 3 H d] a token sees, the oldest
        first, its own last -> ``(q, k, v)`` [.., H, d] float32 (the
        recurrence reads them as they are: a rounding here is a rounding of
        everything a state ever holds): the convolution, SiLU, and the
        heads' norms."""
        u = sum(r.astype(jnp.float32) * w[j].astype(jnp.float32)
                for j, r in enumerate(rows))
        u = jax.nn.silu(u)
        nh, d = self.num_heads, self.head_dim
        q, k, v = (t.reshape(t.shape[:-1] + (nh, d))
                   for t in jnp.split(u, 3, -1))
        return _l2norm(q) * d ** -0.5, _l2norm(k), v

    def output(self, o, gate):
        """``o`` [.., H, d] from the recurrence, ``gate`` [.., H d] ->
        ``o_proj`` of the heads' norm times the gate."""
        eps = self.eps

        def fn(o, w, g):
            o = o.astype(jnp.float32)
            normed = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                                       + eps) * w.astype(jnp.float32)
            return (normed * jax.nn.sigmoid(g.astype(jnp.float32)).reshape(
                o.shape)).reshape(g.shape).astype(g.dtype)

        return self.o_proj(apply(fn, o, self.o_norm.weight, gate,
                                 op_name="kda_gate_out"))

    # -- cache-less ---------------------------------------------------------
    def forward(self, x):
        """``x`` [b, s, h] -> [b, s, h], every row a sequence from a zero
        state: the recurrence token by token."""
        qkv, g, beta, gate = self.projections(x)
        taps = self.taps

        def mix(qkv, g, beta, w):
            s = qkv.shape[1]
            padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
            q, k, v = self.conv_heads(
                [padded[:, j:j + s] for j in range(taps)], w)
            return jax.vmap(lambda *a: kda.kda_recurrence(*a)[0])(
                q, k, v, g, beta)

        return self.output(apply(mix, qkv, g, beta, self.conv_weight,
                                 op_name="kda_recurrence"), gate)


class _BailingLayer(Layer):
    """What the two kinds of layer share: norms and the feed-forward."""

    def __init__(self, config, position, mixer):
        super().__init__()
        dense = position < config.first_k_dense_replace
        #: which compiled programs serve this layer (RaggedLayerPrograms)
        self.kind = f"{mixer}_{'dense' if dense else 'moe'}"
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       config.rms_norm_eps)
        self._dense = dense

    def _build_feed_forward(self, config):
        """The feed-forward half, after the mixer (parameters register in
        the order the weights' table lists them): the post-attention norm
        and a dense SwiGLU or the routed experts + the shared one."""
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                config.rms_norm_eps)
        if self._dense:
            self.mlp = DeepseekV3MLP(config, config.intermediate_size)
            return
        self.experts = HeldExperts(
            config.hidden_size, config.moe_intermediate_size,
            config.num_experts, config.num_experts_per_tok,
            n_group=config.n_group, topk_group=config.topk_group,
            scale=config.routed_scaling_factor,
            norm_topk=config.norm_topk_prob, held=config.held_experts,
            initializer_range=config.initializer_range)
        self.shared_experts = DeepseekV3MLP(
            config, config.moe_shared_expert_intermediate_size
            * config.num_shared_experts)

    def _feed_forward(self, hidden, valid=None):
        x = self.post_attention_layernorm(hidden)
        if self._dense:
            return hidden + self.mlp(x), {}
        routed, counts = self.experts(x, valid)
        return hidden + routed + self.shared_experts(x), counts


class BailingMLALayer(_BailingLayer):
    def __init__(self, config, position):
        super().__init__(config, position, "mla")
        self.self_attn = DeepseekV3Attention(config)
        self._build_feed_forward(config)

    def pre_attention(self, hidden, position_ids=None, cache=None, rope=None):
        return self.self_attn.absorbed_qkv(self.input_layernorm(hidden),
                                           position_ids, cache, rope)

    def post_attention(self, hidden, attn_out, valid=None):
        """-> ``(hidden, counters)``; the output gate reads the mixer's
        input, normed again here (cheaper than carrying it from one
        compiled program to the next)."""
        hidden = hidden + self.self_attn.absorbed_project(
            attn_out, self.input_layernorm(hidden))
        return self._feed_forward(hidden, valid)

    def forward(self, hidden, attn_mask=None, position_ids=None, cache=None):
        if isinstance(cache, SlotPagedKVCache):
            q, row = self.pre_attention(hidden, position_ids, cache)
            out = cache.attend_latent(self, q, row,
                                      **self.self_attn.ragged_kwargs)
            return self.post_attention(hidden, out)[0]
        hidden = hidden + self.self_attn.expanded(
            self.input_layernorm(hidden), attn_mask, position_ids, cache)
        return self._feed_forward(hidden)[0]


class BailingKDALayer(_BailingLayer):
    #: the state arrays that ``pre_state`` updates inside its program
    state_kept_in_program = ("conv",)

    def __init__(self, config, position):
        super().__init__(config, position, "kda")
        self.linear_attn = BailingKDA(config)
        self._build_feed_forward(config)

    def state_spec(self):
        """A slot's state: ``S`` [H, d, d] float32 and the convolution's
        last ``taps - 1`` rows of ``q~ | k~ | v~`` in the model's type."""
        m = self.linear_attn
        return {"S": ((m.num_heads, m.head_dim, m.head_dim), jnp.float32),
                "conv": ((m.taps - 1, 3 * m.num_heads * m.head_dim),
                         m.q_proj.weight._data.dtype)}

    # -- the three pieces of a ragged step -----------------------------------
    def pre_state(self, hidden, kept, plan):
        """``hidden`` [1, s, h]; ``kept["conv"]`` [slots + 1, taps - 1, 3 H
        d]; ``plan``: ``conv_idx`` [s, taps - 1] (where a token's earlier
        rows stand in ``[this step's rows | every slot's kept rows]``),
        ``tail_src`` [spans, taps - 1] and ``tail_slots`` [spans] (the rows
        a span leaves behind, and whose they are) -> ``((q, k, v, g, beta)
        a token, the output gate's logits, the kept rows updated)``."""
        m = self.linear_attn
        qkv, g, beta, gate = m.projections(self.input_layernorm(hidden))

        def conv(qkv, tails, w, conv_idx, tail_src, tail_slots):
            x = qkv[0]
            ext = jnp.concatenate([x, tails.reshape(-1, x.shape[-1])])
            q, k, v = m.conv_heads(
                [ext[conv_idx[:, j]] for j in range(m.taps - 1)] + [x], w)
            return q, k, v, tails.at[tail_slots].set(ext[tail_src])

        q, k, v, tails = apply(
            conv, qkv, kept["conv"], m.conv_weight, plan["conv_idx"],
            plan["tail_src"], plan["tail_slots"], op_name="kda_conv")
        return (q, k, v, g[0], beta[0]), gate, {"conv": tails}

    def mix_state(self, cache, plan, q, k, v, g, beta):
        """The eager kernel entry: the step's one-token rows through
        ``kda_step``, its longer spans through ``kda_chunk``, each from and
        to the slot's ``S`` -> ``o`` [s, H, d]."""
        state = cache.layer_state(self, self.state_spec)
        with _spans.span("attn/kda_step", rows=plan["step_rows"]):
            o, s = kda.kda_step(q, k, v, g, beta, state["S"], plan["rows"],
                                plan["row_slots"])
        if plan["chunk"] is not None:
            with _spans.span("attn/kda_chunk", **plan["chunk_args"]):
                o, s = kda.kda_chunk(q, k, v, g, beta, s, plan["chunk"], o)
        state["S"] = s
        return o

    def post_state(self, hidden, mixed, carried, valid=None):
        hidden = hidden + self.linear_attn.output(mixed, carried)
        return self._feed_forward(hidden, valid)

    def step_plan(self, cache, tokens):
        """The armed step's plan, made once a step and shared by the KDA
        layers (``cache.step_memo``)."""
        if "kda" not in cache.step_memo:
            cache.step_memo["kda"] = _step_plan(
                cache, tokens, self.linear_attn.taps)
        return cache.step_memo["kda"]

    def forward(self, hidden, attn_mask=None, position_ids=None, cache=None):
        if isinstance(cache, SlotPagedKVCache):
            if not cache.ragged_armed:
                raise NotImplementedError(
                    "a layer with a state a slot is served through the "
                    "ragged step only (begin_ragged)")
            plan = self.step_plan(cache, hidden.shape[1])
            state = cache.layer_state(self, self.state_spec)
            mix_in, gate, kept = self.pre_state(
                hidden, {"conv": state["conv"]}, plan["program"])
            state["conv"] = _raw(kept["conv"])
            o = self.mix_state(cache, plan, *(_raw(t) for t in mix_in))
            return self.post_state(hidden, Tensor(o[None]), gate)[0]
        if cache is not None or attn_mask is not None:
            raise NotImplementedError(
                "a KDA layer runs cache-less and causal, or behind "
                "SlotPagedKVCache")
        hidden = hidden + self.linear_attn(self.input_layernorm(hidden))
        return self._feed_forward(hidden)[0]


def _step_plan(cache, tokens, taps):
    """Host arithmetic on the armed step's spans -> the plan every KDA
    layer of the step reads (module docstring); counts what it planned on
    ``cache.state_counters``."""
    spans = cache.ragged_spans()
    scratch, slots = cache.scratch_slot, cache.max_batch
    keep = taps - 1
    back = np.arange(keep) - keep                       # -keep .. -1
    slot, qs, n = (np.asarray([s[i] for s in spans], np.int64)
                   for i in range(3))
    kept = tokens + slot * keep          # a slot's kept rows in the step's
    conv_idx = np.empty((tokens, keep), np.int32)
    tail_src = np.empty((slots, keep), np.int32)
    # padding reads and leaves the scratch slot's own rows
    conv_idx[:] = tail_src[:] = tokens + scratch * keep + np.arange(keep)
    tail_slots = np.full(slots, scratch, np.int32)
    valid = np.zeros(tokens, bool)
    # the rows a span leaves behind: the last ``keep`` of [kept rows | span]
    last = n[:, None] + back[None]
    tail_src[:len(spans)] = np.where(last >= 0, qs[:, None] + last,
                                     kept[:, None] + keep + last)
    tail_slots[:len(spans)] = slot
    # a one-token span (a decode row) sees its slot's kept rows alone; the
    # few longer spans are walked one by one
    one = n == 1
    conv_idx[qs[one]] = kept[one, None] + np.arange(keep)
    valid[qs[one]] = True
    longer = [(int(q), int(m), int(sl)) for sl, q, m in
              zip(slot[~one], qs[~one], n[~one])]
    for q, m, sl in longer:
        at = np.arange(m)[:, None] + back[None]         # in the span
        conv_idx[q:q + m] = np.where(at >= 0, q + at,
                                     tokens + sl * keep + keep + at)
        valid[q:q + m] = True
    rows, row_slots = kda.step_rows(tokens, qs[one], slot[one], scratch,
                                    min(slots, tokens))
    chunk, chunk_args = None, {"tokens": 0, "padded_tokens": 0}
    if longer:
        need = sum(-(-m // kda.SUB) for _, m, _ in longer)
        small = kda.chunk_jobs_bound(tokens, CHUNK_SPANS_SMALL)
        jobs = small if need <= small else kda.chunk_jobs_bound(
            tokens, min(slots, tokens // 2))
        chunk = kda.chunk_plan(tokens, *zip(*longer), scratch, jobs=jobs)
        chunk_args = {"spans": len(longer), "tokens": chunk["tokens"],
                      "padded_tokens": jobs * kda.SUB,
                      "chunks": chunk["jobs"]}
        chunk = {k: jnp.asarray(chunk[k]) for k in ("pack", "unpack", "meta")}
    counted = cache.state_counters
    for name, count in (("kda_step_rows", int(one.sum())),
                        ("kda_chunk_tokens", chunk_args["tokens"]),
                        ("kda_chunk_padded_tokens",
                         chunk_args["padded_tokens"]), ("kda_steps", 1)):
        counted[name] = counted.get(name, 0) + count
    return {"program": {"conv_idx": jnp.asarray(conv_idx),
                        "tail_src": jnp.asarray(tail_src),
                        "tail_slots": jnp.asarray(tail_slots),
                        "valid": jnp.asarray(valid)},
            "rows": jnp.asarray(rows), "row_slots": jnp.asarray(row_slots),
            "step_rows": int(one.sum()), "chunk": chunk,
            "chunk_args": chunk_args}


class BailingHybridModel(Layer):
    def __init__(self, config):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=Normal(0.0, config.initializer_range))
        self.layers = LayerList(
            [(BailingMLALayer if kind == "mla" else BailingKDALayer)(
                config, i) for i, kind in enumerate(config.layer_kinds)])
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)
        self._programs = None        # RaggedLayerPrograms, on first use

    _ragged_programs = LlamaModel._ragged_programs

    def forward(self, input_ids, attn_mask=None, position_ids=None,
                cache=None):
        hidden = self.embed_tokens(input_ids)
        programs = self._ragged_programs(cache, hidden, position_ids)
        if programs is not None:
            pos = jnp.asarray(_raw(position_ids))     # one upload a tick
        for i, layer in enumerate(self.layers):
            if programs is not None:
                with _spans.span("model/layer", i=i, compiled=1,
                                 kind=layer.kind):
                    hidden = Tensor(programs.run(layer, hidden._data, pos,
                                                 cache))
            else:
                with _spans.span("model/layer", i=i, compiled=0,
                                 kind=layer.kind):
                    hidden = layer(hidden, attn_mask, position_ids, cache)
        if cache is not None:
            cache.advance(input_ids.shape[1])
        return self.norm(hidden)


class BailingHybridForCausalLM(GenerationMixin, Layer):
    #: ``generate`` recomputes: its concat / paged caches keep no state
    supports_cache = False

    def __init__(self, config):
        super().__init__()
        self.config = config
        self.model = BailingHybridModel(config)
        self.lm_head = Linear(config.hidden_size, config.vocab_size,
                              weight_attr=Normal(0.0,
                                                 config.initializer_range),
                              bias_attr=False)

    @property
    def kv_state_layers(self):
        """How many layers keep a state a slot instead of pages: the
        serving engine builds its cache for them (``state_layers``)."""
        return sum(hasattr(l, "state_spec") for l in self.model.layers)

    def forward(self, input_ids, labels=None, attn_mask=None,
                position_ids=None, cache=None):
        hidden = self.model(input_ids, attn_mask, position_ids, cache)
        logits = self.lm_head(hidden)
        if labels is None:
            return logits
        from .llama import LlamaPretrainingCriterion
        return LlamaPretrainingCriterion()(logits, labels), logits
