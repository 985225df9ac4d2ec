"""Operations and bytes from shapes, for a SmallThinker-family
configuration (``benchmark/flops.py``'s rules: what the mathematics
requires, never what an implementation executes; one multiply-add is two
operations; the embedding lookup is no matrix product).

A layer's attention sees either every earlier key (a FULL layer) or the
last ``sliding_window_size`` of them, the query's own among them (a WINDOW
layer): a query whose context bound is ``c`` needs ``c`` keys in the first
and ``min(c, window)`` in the second. The feed-forward work is that of the
(token, expert) pairs the router chose among the held experts, which the
program counts on the device; the router's own product is a token's.
"""
from __future__ import annotations


def attention_proj_params(cfg):
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return h * nq * d + 2 * h * nkv * d + nq * d * h


def router_params(cfg):
    return cfg["hidden_size"] * cfg["moe_num_primary_experts"]


def expert_params(cfg):
    """One expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"]


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def layer_windows(cfg):
    """A layer: None (full) or its window's length."""
    n = cfg["num_hidden_layers"]
    return [cfg["sliding_window_size"] if on else None
            for on in cfg["sliding_window_layout"][:n]]


def keys_seen(q_len, context_len, window=None):
    """(query, key) pairs of a causal span of ``q_len`` new tokens whose
    last token's bound is ``context_len``: token ``j`` of the span sees
    ``c_j = context_len - q_len + j + 1`` keys, or the last ``window`` of
    them."""
    first = context_len - q_len + 1
    if window is None or context_len <= window:
        return q_len * (first + context_len) // 2
    if first >= window:
        return q_len * window
    below = window - first                 # tokens whose bound is under it
    return below * (first + window - 1) // 2 + (q_len - below) * window


def keys_read(q_len, context_len, window=None):
    """Distinct keys a span reads: its whole context, or from the first
    key its first token sees."""
    if window is None:
        return context_len
    return min(context_len, window + q_len - 1)


def attention_flops(cfg, q_len, context_len, window=None):
    """QK^T and PV of ONE layer for one span."""
    return 4 * cfg["num_attention_heads"] * cfg["head_dim"] * keys_seen(
        q_len, context_len, window)


def attention_bytes(cfg, spans, window=None, itemsize=2):
    """Least HBM traffic of one ragged paged-attention call of a layer of
    this kind: each span's queries read and outputs written, the keys and
    values its rows can see read once."""
    nq, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    q = sum(q for q, _ in spans) * nq * d * itemsize
    kv = sum(keys_read(q, c, window) for q, c in spans) * nkv * d * itemsize
    return 2 * q + 2 * kv


def serve_flops(cfg, spans, sampled_tokens, expert_pairs):
    """``spans``: every (q_len, context_len) the window processed, once a
    tick; ``sampled_tokens``: tokens sampled (the head runs for those);
    ``expert_pairs``: (token, held expert) pairs routed in the window,
    summed over the layers (the program's counter)."""
    tokens = sum(q for q, _ in spans)
    per_token = cfg["num_hidden_layers"] * (attention_proj_params(cfg)
                                            + router_params(cfg))
    attn = sum(attention_flops(cfg, q, c, w)
               for w in layer_windows(cfg) for q, c in spans)
    return (2 * per_token * tokens + attn
            + 2 * expert_params(cfg) * expert_pairs
            + 2 * head_params(cfg) * sampled_tokens)
