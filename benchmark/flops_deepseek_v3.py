"""Operations and bytes from shapes, for a DeepSeek-V3-family configuration
cut to one chip's share (``benchmark/flops.py``'s rules: what the
mathematics requires, never what an implementation executes; one
multiply-add is two operations; the embedding lookup is no matrix product).

Latent attention has two forms (``benchmark/reference/deepseek_v3.py`` and
``paddle_tpu/models/deepseek_v3.py``). Per token the projections cost the
same in both: ``W_kvb`` is applied once a new token, to its latent row
(expanded) or to its query and its output (absorbed). They differ in the
core: the expanded form pays ``(nope + rope) + v`` a head a (query, key)
pair but has to expand every CACHED row again (``W_kvb`` a row a call); the
absorbed form pays ``(rank + rope) + rank`` a pair and expands nothing. The
whole step's share counts the cheaper of the two, span by span; the latent
kernel's own roofline counts the absorbed form, which is what it computes.
"""
from __future__ import annotations


def attention_proj_params(cfg):
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    return (h * qr + qr * nh * (nope + rope) + h * (kvr + rope)
            + kv_b_params(cfg) + nh * vd * h)


def kv_b_params(cfg):
    return cfg["kv_lora_rank"] * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["v_head_dim"])


def expert_params(cfg):
    """One routed (or shared) expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_mlp_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def router_params(cfg):
    return cfg["hidden_size"] * cfg["n_routed_experts"]


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def pairs(q_len, context_len):
    """(query, key) pairs of a causal span of ``q_len`` new tokens whose
    last token sees ``context_len`` keys."""
    return q_len * (context_len - q_len) + q_len * (q_len + 1) // 2


def absorbed_attention_flops(cfg, q_len, context_len):
    """QK^T over the latent row and PV over its value prefix, ONE layer."""
    rank, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return 2 * cfg["num_attention_heads"] * (2 * rank + rope) * pairs(
        q_len, context_len)


def expanded_attention_flops(cfg, q_len, context_len):
    """QK^T and PV over expanded heads, plus ``W_kvb`` on every row of the
    context that this call did not bring (those it brought are counted
    with the token's projections), ONE layer."""
    core = 2 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"]) * pairs(q_len, context_len)
    return core + 2 * kv_b_params(cfg) * (context_len - q_len)


def attention_flops(cfg, q_len, context_len):
    """What the mathematics requires of one layer's attention core for one
    span: the cheaper form."""
    return min(absorbed_attention_flops(cfg, q_len, context_len),
               expanded_attention_flops(cfg, q_len, context_len))


def layer_counts(cfg):
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return dense, cfg["num_hidden_layers"] - dense


def serve_flops(cfg, spans, sampled_tokens, held_pairs):
    """``spans``: every (q_len, context_len) the window processed, once a
    tick; ``sampled_tokens``: tokens sampled (the head runs for those);
    ``held_pairs``: (token, held expert) pairs routed in the window, summed
    over the expert layers (the program's counter): an absent expert's
    work is not required here and is not counted."""
    dense, moe = layer_counts(cfg)
    tokens = sum(q for q, _ in spans)
    per_token = (cfg["num_hidden_layers"] * attention_proj_params(cfg)
                 + dense * dense_mlp_params(cfg)
                 + moe * (router_params(cfg)
                          + cfg["n_shared_experts"] * expert_params(cfg)))
    attn = sum(attention_flops(cfg, q, c) for q, c in spans)
    return (2 * per_token * tokens + cfg["num_hidden_layers"] * attn
            + 2 * expert_params(cfg) * held_pairs
            + 2 * head_params(cfg) * sampled_tokens)


def latent_attention_bytes(cfg, spans, itemsize=2):
    """Least HBM traffic of one latent ragged-attention call: each span's
    absorbed queries read and outputs written, each context's latent rows
    read ONCE (``kv_lora_rank + qk_rope_head_dim`` values a token: keys and
    values are the same row)."""
    nh, rank, rope = (cfg["num_attention_heads"], cfg["kv_lora_rank"],
                      cfg["qk_rope_head_dim"])
    tokens = sum(q for q, _ in spans)
    rows = sum(c for _, c in spans)
    return itemsize * (tokens * nh * (2 * rank + rope)
                       + rows * (rank + rope))
