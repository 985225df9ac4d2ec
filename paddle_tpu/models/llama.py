"""Llama model family (reference behavior: PaddleNLP ``modeling.py`` for
Llama — RMSNorm pre-norm, RoPE, GQA, SwiGLU MLP, untied lm_head; the north
star config is Llama-3-8B pretrain, BASELINE.json configs[4]).

TPU-first design: the model is plain eager layers; parallelism is NOT baked
into the module graph (no Column/RowParallelLinear forks). Instead
``sharding_rules()`` maps parameter names to PartitionSpecs over the hybrid
mesh axes, and the train-step engine / ``dryrun_multichip`` place the params
— XLA SPMD then derives exactly the Megatron collectives the reference
implements by hand in ``fleet/layers/mpu/mp_layers.py`` (SURVEY.md §2.3).
"""
from __future__ import annotations

import copy
import functools
import inspect
import math
import threading

import jax
import jax.numpy as jnp

from ..framework.core import Tensor
from ..framework.functional import FunctionalModule
from ..nn.layer import Layer, LayerList
from ..nn.layers.common import Linear, Embedding
from ..nn.layers.norm import RMSNorm
from ..nn import functional as F
from ..nn.initializer import Normal
from ..ops import fused as fused_ops
from ..ops import math as pmath
from ..autograd.tape import apply
from ..profiler import spans as _spans
from .generation import GenerationMixin, scatter_kv_rows


def _raw(x):
    return x._data if isinstance(x, Tensor) else x


def shard_activation(x):
    """Pin a [B, T, H] activation to the canonical data layout (batch over
    dp+sharding, seq over sep) when tracing under a multi-device mesh.
    Without this, GSPMD can propagate a weight's ZeRO 'sharding'-axis split
    into the residual stream and fall back to replicate-repartition
    ("Involuntary full rematerialization") — the maxtext-style activation
    annotation recipe. No-op in eager / single-device."""
    from ..distributed import mesh as mesh_mod

    spec = mesh_mod.batch_spec(3)
    if spec is None:
        return x

    sh = mesh_mod.sharding(*spec)

    def fn(a):
        if isinstance(a, jax.core.Tracer):
            return jax.lax.with_sharding_constraint(a, sh)
        return a

    return apply(fn, x, op_name="shard_activation")


class LlamaConfig:
    def __init__(self, vocab_size=32000, hidden_size=4096,
                 intermediate_size=11008, num_hidden_layers=32,
                 num_attention_heads=32, num_key_value_heads=None,
                 max_position_embeddings=4096, rms_norm_eps=1e-5,
                 rope_theta=10000.0, initializer_range=0.02,
                 tie_word_embeddings=False, use_recompute=False,
                 recompute_granularity="full", sequence_parallel=False,
                 context_parallel=False, cp_mode="ring", dtype="float32",
                 **kwargs):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads or num_attention_heads
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.initializer_range = initializer_range
        self.tie_word_embeddings = tie_word_embeddings
        self.use_recompute = use_recompute
        self.recompute_granularity = recompute_granularity
        self.sequence_parallel = sequence_parallel
        self.context_parallel = context_parallel
        self.cp_mode = cp_mode            # "ring" | "ulysses" (SURVEY §5.7)
        self.dtype = dtype
        for k, v in kwargs.items():
            setattr(self, k, v)

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads


def llama3_8b(**kw):
    """Llama-3-8B (north star, BASELINE.json configs[4]); keyword
    overrides cut it to size (e.g. ``num_hidden_layers=8`` for one chip)."""
    for k, v in dict(vocab_size=128256, hidden_size=4096,
                     intermediate_size=14336, num_hidden_layers=32,
                     num_attention_heads=32, num_key_value_heads=8,
                     max_position_embeddings=8192, rms_norm_eps=1e-5,
                     rope_theta=500000.0).items():
        kw.setdefault(k, v)
    return LlamaConfig(**kw)


def llama_tiny(**kw):
    """CI-sized config exercising GQA + RoPE + SwiGLU."""
    kw.setdefault("vocab_size", 128)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("intermediate_size", 176)
    kw.setdefault("num_hidden_layers", 2)
    kw.setdefault("num_attention_heads", 4)
    kw.setdefault("num_key_value_heads", 2)
    kw.setdefault("max_position_embeddings", 128)
    return LlamaConfig(**kw)


class LlamaMLP(Layer):
    def __init__(self, config):
        super().__init__()
        h, m = config.hidden_size, config.intermediate_size
        init = Normal(0.0, config.initializer_range)
        self.gate_proj = Linear(h, m, weight_attr=init, bias_attr=False)
        self.up_proj = Linear(h, m, weight_attr=init, bias_attr=False)
        self.down_proj = Linear(m, h, weight_attr=init, bias_attr=False)

    def forward(self, x):
        return self.down_proj(
            fused_ops.fused_swiglu(self.gate_proj(x), self.up_proj(x)))


class LlamaAttention(Layer):
    def __init__(self, config):
        super().__init__()
        self.config = config
        h = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = config.head_dim
        init = Normal(0.0, config.initializer_range)
        self.q_proj = Linear(h, self.num_heads * self.head_dim,
                             weight_attr=init, bias_attr=False)
        self.k_proj = Linear(h, self.num_kv_heads * self.head_dim,
                             weight_attr=init, bias_attr=False)
        self.v_proj = Linear(h, self.num_kv_heads * self.head_dim,
                             weight_attr=init, bias_attr=False)
        self.o_proj = Linear(self.num_heads * self.head_dim, h,
                             weight_attr=init, bias_attr=False)
        self._cos, self._sin = fused_ops.rope_freqs(
            self.head_dim, config.max_position_embeddings, config.rope_theta)

    def _use_ring_attention(self):
        if not getattr(self.config, "context_parallel", False):
            return False
        from ..distributed import mesh as mesh_mod
        return mesh_mod.has_mesh() and mesh_mod.axis_size("sep") > 1

    def qkv(self, hidden, position_ids=None, cache=None, rope=None):
        """Projections, head reshapes and rope -> ``(q, k, v)``, each
        ``[b, s, heads, head_dim]``. ``rope`` = ``(cos, sin)`` replaces
        the layer's own tables (a compiled program passes them as
        arguments)."""
        b, s, _ = hidden.shape
        q = self.q_proj(hidden).reshape([b, s, self.num_heads, self.head_dim])
        k = self.k_proj(hidden).reshape([b, s, self.num_kv_heads, self.head_dim])
        v = self.v_proj(hidden).reshape([b, s, self.num_kv_heads, self.head_dim])
        if cache is not None and position_ids is None:
            # raw jnp: consumed as a closure constant by the rope op
            position_ids = jnp.arange(cache.pos, cache.pos + s,
                                      dtype=jnp.int32)
        cos, sin = (self._cos, self._sin) if rope is None else map(_raw, rope)
        q, k, _ = fused_ops.fused_rotary_position_embedding(
            q, k, sin=sin, cos=cos, position_ids=_raw(position_ids))
        return q, k, v

    def attend(self, q, k, v, attn_mask=None, cache=None):
        if cache is not None:
            # decode: the cache owns its layout (concat or paged) and the
            # cache-aware attention over the filled prefix
            return cache.attend(self, q, k, v, training=self.training)
        if self._use_ring_attention():
            # context parallelism: seq dim sharded over 'sep'. cp_mode
            # picks the mechanism (SURVEY.md §5.7): "ring" rotates KV
            # blocks with ppermute (3); "ulysses" swaps seq<->head with
            # one all-to-all each way (2)
            if getattr(self.config, "cp_mode", "ring") == "ulysses":
                from ..distributed.fleet.utils import ulysses_attention
                return ulysses_attention(q, k, v, causal=True)
            from ..distributed.fleet.utils import ring_attention
            return ring_attention(q, k, v, causal=True)
        return F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask, is_causal=attn_mask is None,
            training=self.training)

    def project(self, out):
        """``[b, s, heads, head_dim]`` attention output -> ``o_proj``."""
        b, s = out.shape[:2]
        return self.o_proj(out.reshape([b, s, self.num_heads * self.head_dim]))

    def forward(self, hidden, attn_mask=None, position_ids=None, cache=None):
        q, k, v = self.qkv(hidden, position_ids, cache)
        return self.project(self.attend(q, k, v, attn_mask, cache))


class LlamaDecoderLayer(Layer):
    def __init__(self, config):
        super().__init__()
        self.self_attn = LlamaAttention(config)
        self.mlp = LlamaMLP(config)
        self.input_layernorm = RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                config.rms_norm_eps)

    # The layer's mathematics, in two pieces around the attention: every
    # caller runs these, eagerly in order (``forward``) or as two compiled
    # programs around the eager kernel entry (``RaggedLayerPrograms``).
    def pre_attention(self, hidden, position_ids=None, cache=None, rope=None):
        return self.self_attn.qkv(self.input_layernorm(hidden), position_ids,
                                  cache, rope)

    def post_attention(self, hidden, attn_out):
        hidden = hidden + self.self_attn.project(attn_out)
        return hidden + self.mlp(self.post_attention_layernorm(hidden))

    def forward(self, hidden, attn_mask=None, position_ids=None, cache=None):
        q, k, v = self.pre_attention(hidden, position_ids, cache)
        return self.post_attention(
            hidden, self.self_attn.attend(q, k, v, attn_mask, cache))


def _structural_twin(layer):
    """A copy of ``layer``'s structure whose parameters and buffers hold no
    data (no array is copied: the copy shares them until they are dropped)."""
    shared = {id(t._data): t._data
              for t in list(layer.parameters()) + list(layer.buffers())
              if t is not None}
    for sub in layer.sublayers(include_self=True):
        shared.update((id(v), v) for v in vars(sub).values()
                      if isinstance(v, jax.Array))
    twin = copy.deepcopy(layer, shared)
    for t in list(twin.parameters()) + list(twin.buffers()):
        if t is not None:
            t._data = None
    return twin


class RaggedLayerPrograms:
    """A decoder layer of a ragged serving tick as two compiled programs
    around the unchanged eager kernel entry:

    1. pre-attention: ``pre_attention`` (norm, projections, reshapes, rope
       at the tick's positions) -> ``(q, *rows)`` and the scatter of the
       rows into the layer's page pools, which are donated, so the scatter
       updates them in place (``rows`` is ``k, v`` for a K pool and a V
       pool, one latent row for a latent layer's single pool);
    2. ``cache.ragged_attention``: ``ragged_paged_attention`` eagerly, its
       descriptors host values;
    3. post-attention: ``post_attention`` (o_proj, residual, norm, MLP or
       experts, residual). A layer whose ``post_attention`` takes ``valid``
       returns ``(hidden, counters)``: device values the tick reads with
       its one sync (``cache.add_step_counters``).

    A layer that keeps a STATE a slot and no pages (``layer.state_spec``:
    linear attention) has the same three pieces under other names
    (:meth:`_build_state`, :meth:`_run_state`): ``pre_state`` (norm,
    projections and whatever the layer updates of its state inside the
    program: those arrays are donated), the layer's own eager kernel entry
    ``mix_state``, ``post_state``.

    Weights, rope tables, positions, page and slot ids and pools are all
    ARGUMENTS: the programs are traced once a KIND of layer (``layer.kind``
    where a model has several, e.g. dense and expert layers) over a twin of
    the kind's first layer and keyed by shapes and dtypes alone, so every
    layer of a kind, every tick and every cache of one geometry share one
    executable a token bucket, weights swapped after construction are
    followed, and nothing here keeps an array alive. That sharing is sound
    only while a layer's state is its parameters and buffers: ``usable()``
    says whether it is."""

    def __init__(self, layers):
        self._layers = list(layers)
        self._state = {id(l): ([p for p in l.parameters() if p is not None],
                               [b for b in l.buffers() if b is not None])
                       for l in self._layers}
        # a trace swaps the traced layer's arrays for tracers and the
        # global generator for the trace's key (``FunctionalModule``), and
        # thread-tier replicas share one model: the programs are traced
        # over a private twin that holds no data, one trace at a time, so
        # no thread ever reads a tracer out of a served layer
        self._tracing = threading.Lock()
        self._kinds = {}             # kind -> (qkv, pre, post)
        for layer in self._layers:
            kind = self.kind_of(layer)
            if kind not in self._kinds:
                build = (self._build_state if hasattr(layer, "state_spec")
                         else self._build)
                self._kinds[kind] = build(_structural_twin(layer))
        self._kv_dtype = {}          # (kind, hidden dtype) -> rows' dtype

    @staticmethod
    def kind_of(layer):
        return getattr(layer, "kind", "layer")

    def _build(self, twin):
        pre_mod = FunctionalModule(twin, method=twin.pre_attention,
                                   training=False)
        post_mod = FunctionalModule(twin, method=twin.post_attention,
                                    training=False)
        counts = "valid" in inspect.signature(twin.post_attention).parameters

        def qkv(p, b, cos, sin, hidden, pos):
            with self._tracing:       # this body runs only under a trace
                # no op of the pieces draws from the key (inference)
                out, _ = pre_mod(p, b, jax.random.key(0), hidden, pos,
                                 rope=(cos, sin))
            return out

        def pre_fn(p, b, cos, sin, hidden, pos, page_ids, slot_ids, pools,
                   touched):
            q, *rows = qkv(p, b, cos, sin, hidden, pos)
            rows = [jnp.moveaxis(r[0], 1, 0) for r in rows]   # [kv, s, d]
            return q[0], scatter_kv_rows(pools, *rows, page_ids=page_ids,
                                         slot_ids=slot_ids, touched=touched)

        def post_fn(p, b, hidden, attn_out, page_ids):
            with self._tracing:
                if not counts:
                    return post_mod(p, b, jax.random.key(0), hidden,
                                    attn_out[None])[0], {}
                # bucket padding scatters to the scratch page 0
                (out, found), _ = post_mod(
                    p, b, jax.random.key(0), hidden, attn_out[None],
                    valid=page_ids > 0)
            return out, found

        return qkv, jax.jit(pre_fn, donate_argnums=(8,)), jax.jit(post_fn)

    def _build_state(self, twin):
        """The two programs of a layer that keeps a state a slot:
        ``pre_state(hidden, kept, plan) -> (what the kernel entry takes,
        what ``post_state`` takes beside it, the kept arrays updated)`` with
        ``kept`` donated, and ``post_state(hidden, mixed, carried, valid) ->
        (hidden, counters)``."""
        pre_mod = FunctionalModule(twin, method=twin.pre_state,
                                   training=False)
        post_mod = FunctionalModule(twin, method=twin.post_state,
                                    training=False)

        def pre_fn(p, b, hidden, kept, plan):
            with self._tracing:
                return pre_mod(p, b, jax.random.key(0), hidden, kept,
                               plan)[0]

        def post_fn(p, b, hidden, mixed, carried, valid):
            with self._tracing:
                return post_mod(p, b, jax.random.key(0), hidden, mixed,
                                carried, valid)[0]

        return None, jax.jit(pre_fn, donate_argnums=(3,)), jax.jit(post_fn)

    def _run_state(self, layer, hidden, cache, p, b):
        _, pre, post = self._kinds[self.kind_of(layer)]
        plan = layer.step_plan(cache, hidden.shape[1])
        state = cache.layer_state(layer, layer.state_spec)
        kept = {k: state[k] for k in layer.state_kept_in_program}
        mix_in, carried, kept = pre(p, b, hidden, kept, plan["program"])
        state.update(kept)
        cache.compiled_layer_calls += 1
        out, found = post(p, b, hidden,
                          layer.mix_state(cache, plan, *mix_in), carried,
                          plan["program"]["valid"])
        if found:
            cache.add_step_counters(found)
        return out

    def usable(self):
        """False where some layer carries state the programs would bake
        in as the traced layer's constants: int8 weight streams
        (``quantization.quantize_linears``) live outside the parameters."""
        return not any(getattr(sub, "_w_int8", None) is not None
                       for l in self._layers
                       for sub in l.sublayers(include_self=True))

    def program_counts(self):
        """Executables held, by piece: one a token bucket and kind met so
        far."""
        return {"pre": sum(k[1]._cache_size()
                           for k in self._kinds.values()),
                "post": sum(k[2]._cache_size()
                            for k in self._kinds.values())}

    def run(self, layer, hidden, pos, cache):
        """One layer over ``hidden`` [1, tokens, hidden] (raw array)."""
        params, buffers = self._state[id(layer)]
        p = [t._data for t in params]
        b = [t._data for t in buffers]
        kind = self.kind_of(layer)
        if hasattr(layer, "state_spec"):
            return self._run_state(layer, hidden, cache, p, b)
        qkv, pre, post = self._kinds[kind]
        attn = layer.self_attn
        cos, sin = attn._cos, attn._sin

        def kv_spec():
            key = (kind, hidden.dtype)
            if key not in self._kv_dtype:
                row = jax.eval_shape(qkv, p, b, cos, sin, hidden, pos)[1]
                self._kv_dtype[key] = row.dtype
            spec = getattr(attn, "kv_pool_spec", None)
            if spec is not None:
                return spec(self._kv_dtype[key])
            return attn.num_kv_heads, attn.head_dim, self._kv_dtype[key]

        page_ids, slot_ids = cache.ragged_scatter_ids(hidden.shape[1], layer)
        pools = cache.layer_pools(layer, kv_spec)
        # a latent layer's one pool is written a touched page at a time
        touched = (cache.ragged_touched_pages(hidden.shape[1])
                   if len(pools) == 1 else ())
        q, pools = pre(p, b, cos, sin, hidden, pos, page_ids, slot_ids,
                       pools, touched)
        cache.set_layer_pools(layer, pools)
        cache.compiled_layer_calls += 1
        out, found = post(p, b, hidden, cache.ragged_attention(
            layer, q, **getattr(attn, "ragged_kwargs", {})), page_ids)
        if found:
            cache.add_step_counters(found)
        return out


class LlamaModel(Layer):
    def __init__(self, config):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=Normal(0.0, config.initializer_range))
        self.layers = LayerList(
            [LlamaDecoderLayer(config) for _ in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)
        self._programs = None        # RaggedLayerPrograms, on first use

    def _ragged_programs(self, cache, hidden, position_ids):
        """The compiled layer programs where this forward can run them:
        a ragged step armed on the cache (``begin_ragged``), concrete
        inputs (a caller that traces the model sees the eager pieces) and
        explicit positions; else None."""
        if not getattr(cache, "ragged_armed", False) or position_ids is None \
                or isinstance(hidden._data, jax.core.Tracer):
            return None
        if self._programs is None:
            self._programs = RaggedLayerPrograms(self.layers)
        return self._programs if self._programs.usable() else None

    def forward(self, input_ids, attn_mask=None, position_ids=None,
                cache=None):
        hidden = self.embed_tokens(input_ids)
        hidden = shard_activation(hidden)
        recompute = (self.config.use_recompute and self.training
                     and cache is None)
        if recompute:
            # per-layer remat (reference recompute_granularity='full'):
            # under jit this wraps each decoder layer in jax.checkpoint
            from ..distributed.fleet.utils import recompute as remat
        programs = self._ragged_programs(cache, hidden, position_ids)
        if programs is not None:
            pos = jnp.asarray(_raw(position_ids))     # one upload a tick
        for i, layer in enumerate(self.layers):
            if recompute:
                hidden = remat(layer, hidden, attn_mask, position_ids)
            elif programs is not None:
                with _spans.span("model/layer", i=i, compiled=1):
                    hidden = Tensor(programs.run(layer, hidden._data, pos,
                                                 cache))
            else:
                with _spans.span("model/layer", i=i, compiled=0):
                    hidden = layer(hidden, attn_mask, position_ids, cache)
            hidden = shard_activation(hidden)
        hidden = self.norm(hidden)
        if cache is not None:
            cache.advance(input_ids.shape[1])
        return hidden


def _causal_lm_loss_terms(lg, lb, ign):
    """The loss, and what its gradient is made of."""
    lg = lg.astype(jnp.float32)
    logp = lg - jax.nn.logsumexp(lg, axis=-1, keepdims=True)
    valid = lb != ign
    lb_safe = jnp.where(valid, lb, 0)
    tok = jnp.take_along_axis(logp, lb_safe[..., None], axis=-1)[..., 0]
    tok = jnp.where(valid, tok, 0.0)
    n = jnp.maximum(valid.sum(), 1)
    return -tok.sum() / n, logp, valid, lb_safe, n


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def causal_lm_loss(lg, lb, ign):
    """Mean over the labels that are not ``ign`` of ``-log_softmax(lg)`` at
    the label, in float32 whatever the logits' dtype. This body is what an
    undifferentiated call runs; under differentiation the two rules below."""
    return _causal_lm_loss_terms(lg, lb, ign)[0]


def _causal_lm_loss_fwd(lg, lb, ign):
    """The same loss and, in the same pass over the logits, their gradient
    ``(softmax - onehot) * valid / n``: rounded ONCE to the logits' dtype
    (the cotangent the source defines for them anyway) and fenced, so it is
    the one array kept for the backward pass. Without the fence XLA keeps
    the float32 logits instead and clones ``exp(logit - lse) - onehot``
    into the operand of each of the head's two gradient products, where it
    is recomputed on every pass of the product's tiling."""
    loss, logp, valid, lb_safe, n = _causal_lm_loss_terms(lg, lb, ign)
    onehot = lb_safe[..., None] == jnp.arange(lg.shape[-1], dtype=lb.dtype)
    scale = (valid / n.astype(jnp.float32))[..., None]
    dlogits = ((jnp.exp(logp) - onehot) * scale).astype(lg.dtype)
    return loss, jax.lax.optimization_barrier(dlogits)


def _causal_lm_loss_bwd(ign, dlogits, cot):
    return (cot * dlogits.astype(jnp.float32)).astype(dlogits.dtype), None


causal_lm_loss.defvjp(_causal_lm_loss_fwd, _causal_lm_loss_bwd)


class LlamaPretrainingCriterion(Layer):
    """Causal-LM loss; mean over non-ignored tokens (ignore_index=-100).
    Computed in fp32 regardless of model dtype (reference: vocab-parallel
    softmax-CE kernel accumulates in fp32). Under differentiation the
    forward pass also computes the logits' gradient and keeps that, in the
    logits' dtype, as its only residual (``causal_lm_loss``): the float32
    logits are not kept."""

    def __init__(self, ignore_index=-100):
        super().__init__()
        self.ignore_index = ignore_index

    def forward(self, logits, labels):
        ign = self.ignore_index
        return apply(lambda lg, lb: causal_lm_loss(lg, lb, ign),
                     logits, labels, op_name="causal_lm_loss")


class LlamaForCausalLM(GenerationMixin, Layer):
    supports_cache = True

    @classmethod
    def from_pretrained(cls, model_dir, dtype="float32", **overrides):
        """Build from a LOCAL HF-format Llama checkpoint directory
        (config.json + safetensors/bin; PaddleNLP-``from_pretrained``
        surface, zero-egress — see models/pretrained.py)."""
        from .pretrained import llama_config_from_hf, load_llama_from_hf
        cfg = llama_config_from_hf(model_dir, dtype=dtype, **overrides)
        model = cls(cfg)
        return load_llama_from_hf(model, model_dir, dtype=dtype)

    def __init__(self, config):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = Linear(config.hidden_size, config.vocab_size,
                                  weight_attr=Normal(0.0, config.initializer_range),
                                  bias_attr=False)
        self.criterion = LlamaPretrainingCriterion()

    def forward(self, input_ids, labels=None, attn_mask=None,
                position_ids=None, cache=None):
        hidden = self.llama(input_ids, attn_mask, position_ids, cache)
        if self.lm_head is not None:
            logits = self.lm_head(hidden)
        else:
            logits = pmath.matmul(hidden, self.llama.embed_tokens.weight,
                                  transpose_y=True)
        if labels is None:
            return logits
        return self.criterion(logits, labels), logits

    @staticmethod
    def sharding_rules():
        """(param-name regex, PartitionSpec tuple) over hybrid mesh axes.
        Megatron TP: column-parallel q/k/v/gate/up + lm_head, row-parallel
        o/down, vocab-parallel embedding. The 'sharding' (ZeRO/FSDP) axis is
        composed on top by the engine (stage>=3 shards dim 0 residually)."""
        mp = "mp"
        return [
            (r"embed_tokens\.weight$", (mp, None)),
            (r"(q_proj|k_proj|v_proj|gate_proj|up_proj)\.weight$", (None, mp)),
            (r"(o_proj|down_proj)\.weight$", (mp, None)),
            (r"lm_head\.weight$", (None, mp)),
            (r".*", ()),   # norms etc. replicated
        ]


# ---------------------------------------------------------------------------
# pipeline-parallel model description (reference: PaddleNLP
# ``LlamaForCausalLMPipe`` built on ``PipelineLayer`` with EmbeddingPipe /
# decoder LayerDescs / RMSNormPipe / LMHeadPipe, tied embeddings via
# ``SharedLayerDesc`` — fleet pp_layers.py)
# ---------------------------------------------------------------------------

class LlamaEmbeddingPipe(Layer):
    """Embedding stage: ids -> hidden. Doubles as the tied lm head via
    ``SharedLayerDesc(forward_func=_tied_head_forward)``."""

    def __init__(self, config):
        super().__init__()
        self.word_embeddings = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=Normal(0.0, config.initializer_range))

    def forward(self, input_ids):
        return shard_activation(self.word_embeddings(input_ids))


def _tied_head_forward(layer, hidden):
    """Head forward for the tied-embedding SharedLayerDesc instance:
    logits = hidden @ E^T (same Parameter object as the embedding stage —
    no shared-weight allreduce needed; grads sum through jax.grad)."""
    return pmath.matmul(hidden, layer.word_embeddings.weight,
                        transpose_y=True)


class LlamaLMHeadPipe(Layer):
    def __init__(self, config):
        super().__init__()
        self.lm_head = Linear(config.hidden_size, config.vocab_size,
                              weight_attr=Normal(0.0, config.initializer_range),
                              bias_attr=False)

    def forward(self, hidden):
        return self.lm_head(hidden)


def build_llama_pipe(config, **pp_kwargs):
    """``LlamaForCausalLMPipe``: the PipelineLayer description of Llama.
    Layer list = [embedding, L decoder blocks, final RMSNorm, head]; the
    jitted SPMD engine (``distributed/engine.py::PipelinedModule``) maps
    the decoder run onto the pp mesh axis and runs embedding/norm/head as
    whole-mesh sharded compute."""
    from ..distributed.fleet.meta_parallel.pp_layers import (
        PipelineLayer, LayerDesc, SharedLayerDesc)

    descs = []
    if config.tie_word_embeddings:
        descs.append(SharedLayerDesc(
            "llama_embed", LlamaEmbeddingPipe, config,
            shared_weight_attr="word_embeddings"))
    else:
        descs.append(LayerDesc(LlamaEmbeddingPipe, config))
    descs += [LayerDesc(LlamaDecoderLayer, config)
              for _ in range(config.num_hidden_layers)]
    descs.append(LayerDesc(RMSNorm, config.hidden_size, config.rms_norm_eps))
    if config.tie_word_embeddings:
        descs.append(SharedLayerDesc(
            "llama_embed", LlamaEmbeddingPipe, config,
            forward_func=_tied_head_forward,
            shared_weight_attr="word_embeddings"))
    else:
        descs.append(LayerDesc(LlamaLMHeadPipe, config))
    pp_kwargs.setdefault("loss_fn", LlamaPretrainingCriterion())
    pipe = PipelineLayer(descs, **pp_kwargs)
    pipe.config = config
    return pipe


LlamaForCausalLMPipe = build_llama_pipe
