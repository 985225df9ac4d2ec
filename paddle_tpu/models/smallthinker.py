"""SmallThinker model family (``PowerInfer/SmallThinker-21BA3B-Instruct``;
the family's report is arXiv:2507.20984): a decoder whose layers alternate
between two kinds of attention and carry routed experts in every layer.

One layer over ``x`` [T, hidden], layer ``l``::

    h   = RMSNorm_in(x)
    z   = h W_r                          # [T, experts], float32: the router
    idx = top_k(z);  p = softmax(z[idx])   # reads the layer's INPUT
    q, k, v = h W_q, h W_k, h W_v        # GQA heads of head_dim, no bias
    if rope_layout[l]:            q, k = rotary(q, k)     # rotate-half
    visible(i, j) = j <= i and (not sliding_window_layout[l]
                                or j > i - sliding_window_size)
    x1  = x + softmax(q k^T / sqrt(d) | visible) v W_o
    g   = RMSNorm_post(x1)
    out = x1 + sum_{e in idx} p_e W_down^e (relu(W_gate^e g) * (W_up^e g))

then a final RMSNorm and an untied head. In the published model a layer
``l % 4 == 0`` attends its whole context WITHOUT rotary embedding and the
other three a 4,096-token window WITH it.

Behind ``SlotPagedKVCache`` the two kinds keep their pages in two GROUPS
(``layer.kv_window``: None or the window's length; the engine builds the
cache with one group a window): a window layer's pages go back to its
group as soon as no later query can see them. A decoder layer exposes the
``pre_attention`` / ``post_attention`` split that
``llama.py::RaggedLayerPrograms`` compiles, so a ragged serving tick is two
compiled programs a layer around the eager kernel entry, one pair a KIND
(``kind``: ``full`` | ``window``). The cache-less forward masks the window
in plain XLA (the flash kernels take no window).

Expert parallelism: ``config.held_experts = (lo, n)`` as in
``deepseek_v3.py``; None holds all of them.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..framework.core import Tensor
from ..nn.layer import Layer, LayerList
from ..nn.layers.common import Linear, Embedding
from ..nn.layers.norm import RMSNorm
from ..nn import functional as F
from ..nn.initializer import Normal
from ..incubate.distributed.models.moe.held import HeldExperts
from ..profiler import spans as _spans
from .generation import GenerationMixin, SlotPagedKVCache
from .llama import LlamaAttention, LlamaModel, _raw

__all__ = ["SmallThinkerConfig", "SmallThinkerModel",
           "SmallThinkerForCausalLM", "smallthinker_tiny"]


class SmallThinkerConfig:
    """The published ``config.json`` keys, plus ``held_experts`` (this
    chip's ``(lo, n)`` of the experts; None holds them all). The two
    layouts may be longer than ``num_hidden_layers`` (a cut of the
    published depth keeps the published lists): a layer reads its own
    entry."""

    def __init__(self, vocab_size=151936, hidden_size=2560,
                 num_hidden_layers=52, num_attention_heads=28,
                 num_key_value_heads=4, head_dim=128,
                 moe_ffn_hidden_size=768, moe_num_primary_experts=64,
                 moe_num_active_primary_experts=6,
                 moe_primary_router_apply_softmax=True, norm_topk_prob=True,
                 rope_layout=None, sliding_window_layout=None,
                 sliding_window_size=4096, rms_norm_eps=1e-6,
                 rope_theta=1.5e6, rope_scaling=None,
                 max_position_embeddings=16384, tie_word_embeddings=False,
                 initializer_range=0.02, held_experts=None, dtype="float32",
                 **kwargs):
        if not moe_primary_router_apply_softmax:
            raise ValueError("only the softmax-over-the-kept router is built")
        if rope_scaling or tie_word_embeddings:
            raise ValueError("rope scaling and a tied head are not built")
        periodic = [0 if i % 4 == 0 else 1 for i in range(num_hidden_layers)]
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.moe_ffn_hidden_size = moe_ffn_hidden_size
        self.moe_num_primary_experts = moe_num_primary_experts
        self.moe_num_active_primary_experts = moe_num_active_primary_experts
        self.norm_topk_prob = norm_topk_prob
        self.rope_layout = list(rope_layout or periodic)
        self.sliding_window_layout = list(sliding_window_layout or periodic)
        self.sliding_window_size = int(sliding_window_size)
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.max_position_embeddings = max_position_embeddings
        self.initializer_range = initializer_range
        self.held_experts = held_experts
        self.dtype = dtype
        if min(len(self.rope_layout),
               len(self.sliding_window_layout)) < num_hidden_layers:
            raise ValueError("a layout is shorter than num_hidden_layers")
        for k, v in kwargs.items():
            setattr(self, k, v)

    def window_of(self, layer_idx):
        """None, or the length of layer ``layer_idx``'s sliding window."""
        return (self.sliding_window_size
                if self.sliding_window_layout[layer_idx] else None)


def smallthinker_tiny(**kw):
    """CI-sized: two periods of (full without rotary, 3 x window with it),
    a window of 8 tokens, 8 experts of which 3 a token."""
    for k, v in dict(
            vocab_size=128, hidden_size=64, num_hidden_layers=8,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            moe_ffn_hidden_size=32, moe_num_primary_experts=8,
            moe_num_active_primary_experts=3, sliding_window_size=8,
            rope_theta=10000.0, max_position_embeddings=128).items():
        kw.setdefault(k, v)
    return SmallThinkerConfig(**kw)


class SmallThinkerAttention(LlamaAttention):
    """``LlamaAttention``'s projections; rotary embedding on or off and a
    window or none, by the layer's entries in the two layouts."""

    def __init__(self, config, layer_idx):
        super().__init__(config)
        self.use_rope = bool(config.rope_layout[layer_idx])
        #: what ``SlotPagedKVCache`` groups this layer's pages by
        self.kv_window = config.window_of(layer_idx)
        if not self.use_rope:
            # ``RaggedLayerPrograms`` passes every layer's tables; a layer
            # that never reads them keeps one row
            self._cos, self._sin = self._cos[:1], self._sin[:1]

    def kv_pool_spec(self, dtype):
        """K and V pools in the model's own type (bf16 under a bf16 model),
        whatever the rotary product's."""
        return (self.num_kv_heads, self.head_dim,
                self.k_proj.weight._data.dtype)

    def qkv(self, hidden, position_ids=None, cache=None, rope=None):
        if self.use_rope:
            q, k, v = super().qkv(hidden, position_ids, cache, rope)
        else:
            b, s, _ = hidden.shape
            q = self.q_proj(hidden).reshape(
                [b, s, self.num_heads, self.head_dim])
            k = self.k_proj(hidden).reshape(
                [b, s, self.num_kv_heads, self.head_dim])
            v = self.v_proj(hidden).reshape(
                [b, s, self.num_kv_heads, self.head_dim])
        # the rotary product comes back float32: the rows go to the pages
        # in the model's type, and both kinds of layer hand the kernel the
        # same types
        return tuple(t.astype(v.dtype) for t in (q, k, v))

    def attend(self, q, k, v, attn_mask=None, cache=None):
        if cache is not None:
            if not isinstance(cache, SlotPagedKVCache):
                raise NotImplementedError(
                    "a model with window layers is served through "
                    "SlotPagedKVCache (the serving engine) alone")
            return cache.attend(self, q, k, v, training=self.training)
        if attn_mask is not None:
            raise NotImplementedError("an attention mask beside the window")
        s = q.shape[1]
        i = jnp.arange(s)[:, None]
        j = jnp.arange(s)[None, :]
        visible = j <= i
        if self.kv_window is not None:
            visible &= j > i - self.kv_window
        return F.scaled_dot_product_attention(
            q, k, v, attn_mask=visible[None, None], is_causal=False,
            training=self.training)


class SmallThinkerDecoderLayer(Layer):
    def __init__(self, config, layer_idx):
        super().__init__()
        self.self_attn = SmallThinkerAttention(config, layer_idx)
        self.kv_window = self.self_attn.kv_window
        #: which compiled programs serve this layer (RaggedLayerPrograms)
        self.kind = "full" if self.kv_window is None else "window"
        self.experts = HeldExperts(
            config.hidden_size, config.moe_ffn_hidden_size,
            config.moe_num_primary_experts,
            config.moe_num_active_primary_experts,
            held=config.held_experts,
            initializer_range=config.initializer_range,
            router="topk_softmax", activation="relu")
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       config.rms_norm_eps)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                config.rms_norm_eps)

    # the layer in two pieces around the attention (``RaggedLayerPrograms``
    # compiles them; the eager path runs them in order)
    def pre_attention(self, hidden, position_ids=None, cache=None, rope=None):
        return self.self_attn.qkv(self.input_layernorm(hidden), position_ids,
                                  cache, rope)

    def post_attention(self, hidden, attn_out, valid=None):
        """-> ``(hidden, counters)``. The router reads the layer's
        normalised INPUT (``hidden`` is the layer's input still: the norm
        is computed again here, which is cheaper than carrying the router's
        choice from one compiled program to the next); the experts read the
        normalised sum of input and attention."""
        routed_from = self.input_layernorm(hidden)
        hidden = hidden + self.self_attn.project(attn_out)
        out, counts = self.experts(self.post_attention_layernorm(hidden),
                                   valid, router_input=routed_from)
        return hidden + out, counts

    def forward(self, hidden, attn_mask=None, position_ids=None, cache=None):
        q, k, v = self.pre_attention(hidden, position_ids, cache)
        return self.post_attention(
            hidden, self.self_attn.attend(q, k, v, attn_mask, cache))[0]


class SmallThinkerModel(Layer):
    def __init__(self, config):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=Normal(0.0, config.initializer_range))
        self.layers = LayerList(
            [SmallThinkerDecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])
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


class SmallThinkerForCausalLM(GenerationMixin, Layer):
    #: ``generate`` recomputes: its concat / paged caches know no window
    supports_cache = False

    def __init__(self, config):
        super().__init__()
        self.config = config
        self.model = SmallThinkerModel(config)
        self.lm_head = Linear(config.hidden_size, config.vocab_size,
                              weight_attr=Normal(0.0,
                                                 config.initializer_range),
                              bias_attr=False)

    @property
    def kv_layer_windows(self):
        """A layer: None, or the length of its sliding window; the serving
        engine builds one page group a distinct window from this."""
        return [layer.kv_window for layer in self.model.layers]

    def forward(self, input_ids, labels=None, attn_mask=None,
                position_ids=None, cache=None):
        hidden = self.model(input_ids, attn_mask, position_ids, cache)
        logits = self.lm_head(hidden)
        if labels is None:
            return logits
        from .llama import LlamaPretrainingCriterion
        return LlamaPretrainingCriterion()(logits, labels), logits
