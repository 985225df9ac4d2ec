"""DeepSeek-V3 model family (``model_type: deepseek_v3``; reference
behavior: the published ``modeling_deepseek.py``): multi-head latent
attention (MLA), a sigmoid / group-limited router over routed experts
plus a shared expert, leading dense layers, and a multi-token-prediction
(MTP) module.

Attention has two forms that compute the same function:

* **expanded** (``model(input_ids)``, ``generate`` over the concat cache):
  ``[k_nope | v] = c_kv W_kvb`` is expanded to every head and plain causal
  attention runs over ``[q_nope | q_rope] . [k_nope | k_rope]``;
* **absorbed** (behind ``SlotPagedKVCache``: the serving engine's ragged
  tick): the cache keeps ONE row ``[c_kv | k_rope]`` (``kv_lora_rank +
  qk_rope_head_dim`` values) a token a layer, shared by all heads.
  ``W_kvb``'s key half is folded into the query (``q' = [q_nope W_k^T |
  q_rope]``), the values are the row's first ``kv_lora_rank`` lanes, and
  ``W_kvb``'s value half is applied to the attention output.

A decoder layer exposes the ``pre_attention`` / ``post_attention`` split
that ``llama.py::RaggedLayerPrograms`` compiles, so a ragged serving tick
is two compiled programs a layer around the eager kernel entry. A model
has two KINDS of layer (``kind``: ``dense`` | ``moe``); the programs are
keyed by kind.

Expert parallelism: ``config.held_experts = (lo, n)`` tells every expert
layer which routed experts this chip holds
(``incubate/distributed/models/moe/held.py``); the router keeps its
``n_routed_experts`` outputs.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..framework.core import Tensor
from ..nn.layer import Layer, LayerList
from ..nn.layers.common import Linear, Embedding
from ..nn.layers.norm import RMSNorm
from ..nn import functional as F
from ..nn.initializer import Normal
from ..ops import fused as fused_ops
from ..autograd.tape import apply
from ..incubate.distributed.models.moe.held import HeldExperts
from ..profiler import spans as _spans
from .generation import GenerationMixin, SlotPagedKVCache
from .llama import LlamaModel, _raw

__all__ = ["DeepseekV3Config", "DeepseekV3Model", "DeepseekV3ForCausalLM",
           "DeepseekV3MTP", "deepseek_v3_tiny", "yarn_rope_tables",
           "yarn_mscale"]


class DeepseekV3Config:
    """The published ``config.json`` keys, plus ``held_experts`` (this
    chip's ``(lo, n)`` of the routed experts; None holds them all)."""

    def __init__(self, vocab_size=129280, hidden_size=7168,
                 intermediate_size=18432, moe_intermediate_size=2048,
                 num_hidden_layers=61, num_nextn_predict_layers=1,
                 num_attention_heads=128, n_shared_experts=1,
                 n_routed_experts=256, routed_scaling_factor=2.5,
                 kv_lora_rank=512, q_lora_rank=1536, qk_rope_head_dim=64,
                 v_head_dim=128, qk_nope_head_dim=128, n_group=8,
                 topk_group=4, num_experts_per_tok=8,
                 first_k_dense_replace=3, norm_topk_prob=True,
                 scoring_func="sigmoid", topk_method="noaux_tc",
                 rms_norm_eps=1e-6, rope_theta=10000.0, rope_scaling=None,
                 max_position_embeddings=4096, initializer_range=0.02,
                 held_experts=None, dtype="float32", **kwargs):
        if scoring_func != "sigmoid" or topk_method != "noaux_tc":
            raise ValueError("only the sigmoid / noaux_tc router is built "
                             f"(got {scoring_func!r} / {topk_method!r})")
        if q_lora_rank is None or kwargs.get("attn_output_gate"):
            # ``DeepseekV3Attention`` builds both (another family's
            # configuration asks it for them); this family has neither
            raise ValueError("the DeepSeek-V3 family has a query rank and "
                             "no output gate")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_nextn_predict_layers = num_nextn_predict_layers
        self.num_attention_heads = num_attention_heads
        self.n_shared_experts = n_shared_experts
        self.n_routed_experts = n_routed_experts
        self.routed_scaling_factor = routed_scaling_factor
        self.kv_lora_rank = kv_lora_rank
        self.q_lora_rank = q_lora_rank
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.qk_nope_head_dim = qk_nope_head_dim
        self.n_group = n_group
        self.topk_group = topk_group
        self.num_experts_per_tok = num_experts_per_tok
        self.first_k_dense_replace = first_k_dense_replace
        self.norm_topk_prob = norm_topk_prob
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.rope_scaling = rope_scaling
        self.max_position_embeddings = max_position_embeddings
        self.initializer_range = initializer_range
        self.held_experts = held_experts
        self.dtype = dtype
        for k, v in kwargs.items():
            setattr(self, k, v)


def deepseek_v3_tiny(**kw):
    """CI-sized: every mechanism of the family at widths a CPU test runs
    (16 experts in 4 groups of which 2 are kept, 4 a token, 1 dense + 2
    expert layers, yarn over an original length of 32)."""
    for k, v in dict(
            vocab_size=128, hidden_size=64, intermediate_size=160,
            moe_intermediate_size=32, num_hidden_layers=3,
            num_nextn_predict_layers=0, num_attention_heads=4,
            n_shared_experts=1, n_routed_experts=16,
            routed_scaling_factor=2.5, kv_lora_rank=16, q_lora_rank=32,
            qk_rope_head_dim=8, v_head_dim=24, qk_nope_head_dim=16,
            n_group=4, topk_group=2, num_experts_per_tok=4,
            first_k_dense_replace=1, rope_theta=10000.0,
            rope_scaling={"rope_type": "yarn", "factor": 4,
                          "original_max_position_embeddings": 32,
                          "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                          "mscale_all_dim": 1},
            max_position_embeddings=128).items():
        kw.setdefault(k, v)
    return DeepseekV3Config(**kw)


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_rope_tables(dim, max_position, base, scaling):
    """cos / sin [max_position, dim // 2] float32. With ``scaling`` of type
    yarn: the published blend of interpolated and extrapolated frequencies
    (linear ramp between the dimensions that turn ``beta_fast`` and
    ``beta_slow`` times over the original length), and the tables scaled
    by ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``."""
    idx = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    extra = 1.0 / (base ** idx)
    scale = 1.0
    if scaling and scaling.get("rope_type", scaling.get("type")) == "yarn":
        factor = float(scaling["factor"])
        orig = float(scaling["original_max_position_embeddings"])

        def corr(rot):
            return dim * math.log(orig / (rot * 2 * math.pi)) / (
                2 * math.log(base))

        low = max(math.floor(corr(scaling.get("beta_fast", 32))), 0)
        high = min(math.ceil(corr(scaling.get("beta_slow", 1))), dim - 1)
        if low == high:
            high += 0.001
        ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                        / (high - low), 0.0, 1.0)
        inv = extra / factor * ramp + extra * (1.0 - ramp)
        scale = (yarn_mscale(factor, scaling.get("mscale", 1))
                 / yarn_mscale(factor, scaling.get("mscale_all_dim", 0)))
    elif scaling:
        raise ValueError(f"rope scaling {scaling!r} is not built")
    else:
        inv = extra
    ang = jnp.outer(jnp.arange(max_position, dtype=jnp.float32), inv)
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def _rope_interleaved(x, cos, sin):
    """Rotate the pairs ``(x[2i], x[2i+1])`` by the position's angle ``i``
    and return them in the half-split order ``[x'[0::2] | x'[1::2]]``, as
    the published code does (it de-interleaves, then rotates halves): q
    and k are permuted alike, so their product is the interleaved one's.
    ``x`` [..., d]; ``cos`` / ``sin`` broadcast to [..., d // 2]."""
    xf = x.astype(jnp.float32)
    a, b = xf[..., 0::2], xf[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


class DeepseekV3MLP(Layer):
    def __init__(self, config, width):
        super().__init__()
        h = config.hidden_size
        init = Normal(0.0, config.initializer_range)
        self.gate_proj = Linear(h, width, weight_attr=init, bias_attr=False)
        self.up_proj = Linear(h, width, weight_attr=init, bias_attr=False)
        self.down_proj = Linear(width, h, weight_attr=init, bias_attr=False)

    def forward(self, x):
        return self.down_proj(
            fused_ops.fused_swiglu(self.gate_proj(x), self.up_proj(x)))


class DeepseekV3MoE(Layer):
    """The held routed experts' sum + the shared expert on every token."""

    def __init__(self, config):
        super().__init__()
        self.experts = HeldExperts(
            config.hidden_size, config.moe_intermediate_size,
            config.n_routed_experts, config.num_experts_per_tok,
            n_group=config.n_group, topk_group=config.topk_group,
            scale=config.routed_scaling_factor,
            norm_topk=config.norm_topk_prob, held=config.held_experts,
            initializer_range=config.initializer_range)
        self.shared_experts = DeepseekV3MLP(
            config, config.moe_intermediate_size * config.n_shared_experts)

    def forward(self, x, valid=None):
        routed, counts = self.experts(x, valid)
        return routed + self.shared_experts(x), counts


class DeepseekV3Attention(Layer):
    """Multi-head latent attention, both forms (module docstring). Two
    things that another family's configuration may ask for: no query rank
    (``config.q_lora_rank`` None: one full-rank ``q_proj``) and a head-wise
    output gate (``config.attn_output_gate`` "head_wise": each head's
    output times ``sigmoid(x W_g)_h`` before ``o_proj``; ``x`` is the
    mixer's input)."""

    def __init__(self, config):
        super().__init__()
        self.config = config
        h = config.hidden_size
        self.num_heads = nh = config.num_attention_heads
        self.nope, self.rope = config.qk_nope_head_dim, \
            config.qk_rope_head_dim
        self.v_dim, self.kv_rank = config.v_head_dim, config.kv_lora_rank
        self.qk_dim = self.nope + self.rope
        init = Normal(0.0, config.initializer_range)
        if config.q_lora_rank is None:
            self.q_proj = Linear(h, nh * self.qk_dim, weight_attr=init,
                                 bias_attr=False)
        else:
            self.q_a_proj = Linear(h, config.q_lora_rank, weight_attr=init,
                                   bias_attr=False)
            self.q_a_layernorm = RMSNorm(config.q_lora_rank,
                                         config.rms_norm_eps)
            self.q_b_proj = Linear(config.q_lora_rank, nh * self.qk_dim,
                                   weight_attr=init, bias_attr=False)
        self.kv_a_proj_with_mqa = Linear(h, self.kv_rank + self.rope,
                                         weight_attr=init, bias_attr=False)
        self.kv_a_layernorm = RMSNorm(self.kv_rank, config.rms_norm_eps)
        self.kv_b_proj = Linear(self.kv_rank, nh * (self.nope + self.v_dim),
                                weight_attr=init, bias_attr=False)
        gate = getattr(config, "attn_output_gate", None)
        if gate not in (None, "head_wise"):
            raise ValueError(f"output gate {gate!r} is not built")
        if gate:
            self.g_proj = Linear(h, nh, weight_attr=init, bias_attr=False)
        self.o_proj = Linear(nh * self.v_dim, h, weight_attr=init,
                             bias_attr=False)
        self._cos, self._sin = yarn_rope_tables(
            self.rope, config.max_position_embeddings, config.rope_theta,
            config.rope_scaling)
        scaling = config.rope_scaling or {}
        m = yarn_mscale(float(scaling.get("factor", 1)),
                        scaling.get("mscale_all_dim", 0))
        #: on the scores of either form: ``qk_dim^-0.5 * mscale^2``
        self.sm_scale = self.qk_dim ** -0.5 * m * m
        #: what ``SlotPagedKVCache`` needs to know of this layer's rows
        self.latent_dim = self.kv_rank + self.rope
        self.ragged_kwargs = {"sm_scale": self.sm_scale,
                              "value_dim": self.kv_rank}

    def kv_pool_spec(self, dtype):
        """One pool a layer: one head of ``[c_kv | k_rope]`` rows."""
        return 1, self.latent_dim, dtype, True

    def _kv_b(self):
        """``W_kvb`` as ``(W_k [heads, nope, rank], W_v [heads, rank,
        v_dim])`` Tensors (views of the one parameter)."""
        def fn(w):
            w = w.reshape(self.kv_rank, self.num_heads,
                          self.nope + self.v_dim)
            return (jnp.transpose(w[..., :self.nope], (1, 2, 0)),
                    jnp.transpose(w[..., self.nope:], (1, 0, 2)))
        return apply(fn, self.kv_b_proj.weight, op_name="mla_kv_b")

    def latent(self, hidden, position_ids=None, cache=None, rope=None):
        """-> ``(q_nope [b, s, heads, nope], q_rope [b, s, heads, rope],
        c_kv [b, s, rank] (normed), k_rope [b, s, rope])``, rope applied at
        ``position_ids`` ([s] or [b, s]; default ``cache.pos + arange``)."""
        b, s, _ = hidden.shape
        if hasattr(self, "q_proj"):
            q = self.q_proj(hidden)
        else:
            q = self.q_b_proj(self.q_a_layernorm(self.q_a_proj(hidden)))
        kv = self.kv_a_proj_with_mqa(hidden)
        if position_ids is None:
            start = cache.pos if cache is not None else 0
            position_ids = jnp.arange(start, start + s, dtype=jnp.int32)
        pos = _raw(position_ids)
        cos_t, sin_t = (self._cos, self._sin) if rope is None \
            else map(_raw, rope)
        nh, nope, rank = self.num_heads, self.nope, self.kv_rank

        def split_q(qa):
            qa = qa.reshape(b, s, nh, self.qk_dim)
            cos, sin = cos_t[pos], sin_t[pos]            # [(b,) s, rope/2]
            cos = cos.reshape((-1, s, 1, cos.shape[-1]))
            sin = sin.reshape((-1, s, 1, sin.shape[-1]))
            return qa[..., :nope], _rope_interleaved(qa[..., nope:],
                                                     cos, sin)

        def rope_k(kva):
            cos, sin = cos_t[pos], sin_t[pos]
            cos = cos.reshape((-1, s, cos.shape[-1]))
            sin = sin.reshape((-1, s, sin.shape[-1]))
            return _rope_interleaved(kva[..., rank:], cos, sin)

        q_nope, q_rope = apply(split_q, q, op_name="mla_split_q")
        k_rope = apply(rope_k, kv, op_name="mla_rope_k")
        c_kv = self.kv_a_layernorm(kv[..., :rank])
        return q_nope, q_rope, c_kv, k_rope

    # -- absorbed form ----------------------------------------------------
    def absorbed_qkv(self, hidden, position_ids=None, cache=None, rope=None):
        """-> ``(q' [b, s, heads, rank + rope], row [b, s, 1, rank +
        rope])``: the query with ``W_k`` folded in, and the cache row."""
        q_nope, q_rope, c_kv, k_rope = self.latent(hidden, position_ids,
                                                   cache, rope)
        w_k, _ = self._kv_b()

        def fn(qn, qr, ck, kr, wk):
            q_abs = jnp.einsum("bshn,hnr->bshr", qn, wk)
            return (jnp.concatenate([q_abs, qr], -1),
                    jnp.concatenate([ck, kr], -1)[:, :, None, :])
        return apply(fn, q_nope, q_rope, c_kv, k_rope, w_k,
                     op_name="mla_absorb")

    def _gated(self, heads_out, gate_input):
        """``heads_out`` [b, s, heads, v] times the head-wise gate of
        ``gate_input`` (the mixer's input), where the layer has one."""
        if not hasattr(self, "g_proj"):
            return heads_out
        return apply(lambda o, g: o * jax.nn.sigmoid(
            g.astype(jnp.float32))[..., None].astype(o.dtype),
            heads_out, self.g_proj(gate_input), op_name="mla_gate")

    def absorbed_project(self, attn_out, gate_input=None):
        """``[b, s, heads, rank]`` (attention over the latent rows) ->
        ``W_v`` a head, the output gate where the layer has one
        (``gate_input``: the mixer's input), then ``o_proj``."""
        _, w_v = self._kv_b()
        b, s = attn_out.shape[:2]
        out = apply(lambda o, wv: jnp.einsum("bshr,hrv->bshv", o, wv),
                    attn_out, w_v, op_name="mla_unabsorb")
        return self.o_proj(self._gated(out, gate_input).reshape([b, s, -1]))

    # -- expanded form ----------------------------------------------------
    def expanded(self, hidden, attn_mask=None, position_ids=None, cache=None):
        q_nope, q_rope, c_kv, k_rope = self.latent(hidden, position_ids,
                                                   cache)
        b, s = hidden.shape[:2]
        nh, nope, vd = self.num_heads, self.nope, self.v_dim
        kv = self.kv_b_proj(c_kv)
        # sdpa scales by qk_dim^-0.5 itself: the query carries mscale^2
        extra = self.sm_scale * self.qk_dim ** 0.5
        pad = max(self.qk_dim - vd, 0)

        def fn(qn, qr, kva, kr):
            kva = kva.reshape(b, s, nh, nope + vd)
            q = jnp.concatenate([qn, qr], -1) * jnp.asarray(extra, qn.dtype)
            k = jnp.concatenate(
                [kva[..., :nope],
                 jnp.broadcast_to(kr[:, :, None, :], (b, s, nh, self.rope))],
                -1)
            v = jnp.pad(kva[..., nope:], ((0, 0),) * 3 + ((0, pad),))
            return q, k, v

        if vd > self.qk_dim:
            raise ValueError("v_head_dim wider than the query's head is "
                             "not built")
        q, k, v = apply(fn, q_nope, q_rope, kv, k_rope,
                        op_name="mla_expand")
        if cache is not None:
            out = cache.attend(self, q, k, v, training=self.training)
        else:
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=attn_mask, is_causal=attn_mask is None,
                training=self.training)
        return self.o_proj(self._gated(out[..., :vd], hidden).reshape(
            [b, s, nh * vd]))


class DeepseekV3DecoderLayer(Layer):
    def __init__(self, config, layer_idx):
        super().__init__()
        self.self_attn = DeepseekV3Attention(config)
        #: which compiled programs serve this layer (RaggedLayerPrograms)
        self.kind = ("moe" if layer_idx >= config.first_k_dense_replace
                     else "dense")
        self.mlp = (DeepseekV3MoE(config) if self.kind == "moe"
                    else DeepseekV3MLP(config, config.intermediate_size))
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       config.rms_norm_eps)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                config.rms_norm_eps)

    def _feed_forward(self, hidden, valid=None):
        x = self.post_attention_layernorm(hidden)
        if self.kind == "moe":
            out, counts = self.mlp(x, valid)
            return hidden + out, counts
        return hidden + self.mlp(x), {}

    # the absorbed form's two pieces around the attention over the latent
    # pool (``RaggedLayerPrograms`` compiles them; the eager path runs them
    # in order)
    def pre_attention(self, hidden, position_ids=None, cache=None, rope=None):
        return self.self_attn.absorbed_qkv(self.input_layernorm(hidden),
                                           position_ids, cache, rope)

    def post_attention(self, hidden, attn_out, valid=None):
        """-> ``(hidden, counters)``; ``counters`` (device scalars and
        vectors the serving tick reads with its one sync) is empty for a
        dense layer. ``valid`` [s] marks the rows that are real tokens."""
        hidden = hidden + self.self_attn.absorbed_project(attn_out)
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


class DeepseekV3Model(Layer):
    def __init__(self, config):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=Normal(0.0, config.initializer_range))
        self.layers = LayerList(
            [DeepseekV3DecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)
        self._programs = None        # RaggedLayerPrograms, on first use

    # the compiled layer programs where a ragged step is armed and the
    # inputs are concrete: the same rule over ``layers`` / ``_programs``
    _ragged_programs = LlamaModel._ragged_programs

    def forward(self, input_ids, attn_mask=None, position_ids=None,
                cache=None, return_prenorm=False):
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
        normed = self.norm(hidden)
        return (normed, hidden) if return_prenorm else normed


class DeepseekV3MTP(Layer):
    """One multi-token-prediction module: ``h' = [RMSNorm(h_t) |
    RMSNorm(Emb(x_{t+1}))] W_p``, one further decoder layer of the expert
    kind; the caller applies the shared final norm and head. The embedding
    is the model's own (the published checkpoints share it)."""

    def __init__(self, config):
        super().__init__()
        h = config.hidden_size
        self.hnorm = RMSNorm(h, config.rms_norm_eps)
        self.enorm = RMSNorm(h, config.rms_norm_eps)
        self.eh_proj = Linear(2 * h, h, weight_attr=Normal(
            0.0, config.initializer_range), bias_attr=False)
        self.block = DeepseekV3DecoderLayer(config,
                                            config.first_k_dense_replace)

    def forward(self, hidden, next_embeds):
        from ..ops import manipulation as manip
        x = self.eh_proj(manip.concat(
            [self.hnorm(hidden), self.enorm(next_embeds)], axis=-1))
        return self.block(x)


class DeepseekV3ForCausalLM(GenerationMixin, Layer):
    supports_cache = True

    def __init__(self, config):
        super().__init__()
        self.config = config
        self.model = DeepseekV3Model(config)
        self.lm_head = Linear(config.hidden_size, config.vocab_size,
                              weight_attr=Normal(0.0,
                                                 config.initializer_range),
                              bias_attr=False)
        self.mtp = LayerList([DeepseekV3MTP(config) for _ in range(
            config.num_nextn_predict_layers)])

    def forward(self, input_ids, labels=None, attn_mask=None,
                position_ids=None, cache=None):
        hidden = self.model(input_ids, attn_mask, position_ids, cache)
        logits = self.lm_head(hidden)
        if labels is None:
            return logits
        from .llama import LlamaPretrainingCriterion
        return LlamaPretrainingCriterion()(logits, labels), logits

    def mtp_logits(self, input_ids):
        """Cache-less: logits ``[b, s - 1, vocab]`` whose row ``t`` predicts
        ``x_{t+2}`` from the main model's last hidden state (before the
        final norm) at ``t`` and the embedding of ``x_{t+1}``. Needs
        ``num_nextn_predict_layers`` 1."""
        if len(self.mtp) != 1:
            raise ValueError("the model was built without its MTP module "
                             "(num_nextn_predict_layers is not 1)")
        ids = input_ids if isinstance(input_ids, Tensor) \
            else Tensor(jnp.asarray(input_ids))
        _, prenorm = self.model(ids, return_prenorm=True)
        nxt = self.model.embed_tokens(ids[:, 1:])
        hidden = self.mtp[0](prenorm[:, :-1], nxt)
        return self.lm_head(self.model.norm(hidden))
