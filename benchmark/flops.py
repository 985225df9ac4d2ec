"""Operations and bytes from shapes, for a Llama-family configuration.

The yardstick's own arithmetic: what the mathematics of a step or a kernel
call requires, never what an implementation happens to execute (recomputed
operations and padding do not count), and the embedding lookup is no matrix
product. One multiply-add is two operations.
"""
from __future__ import annotations


def layer_matmul_params(cfg):
    h, m, d = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return h * nq * d + 2 * h * nkv * d + nq * d * h + 3 * h * m


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def attention_flops(cfg, q_len, context_len):
    """Forward QK^T and PV of ONE layer for a span of ``q_len`` new tokens
    whose last token sees ``context_len`` keys (causal: token i of the span
    sees context_len - q_len + i + 1)."""
    keys = q_len * (context_len - q_len) + q_len * (q_len + 1) // 2
    return 4 * cfg["num_attention_heads"] * cfg["head_dim"] * keys


def train_flops_per_token(cfg, seq):
    """Forward plus backward (twice the forward) of every matrix product
    and of causal attention, per trained token."""
    n = cfg["num_hidden_layers"]
    matmul = 2 * (n * layer_matmul_params(cfg) + head_params(cfg))
    attn = n * attention_flops(cfg, seq, seq) / seq
    return 3 * (matmul + attn)


def serve_flops(cfg, spans, sampled_tokens):
    """``spans`` is every (q_len, context_len) the window processed, once a
    tick (not once a layer); ``sampled_tokens`` is how many tokens were
    sampled, the only positions whose logits the mathematics needs."""
    n = cfg["num_hidden_layers"]
    tokens = sum(q for q, _ in spans)
    attn = sum(attention_flops(cfg, q, c) for q, c in spans)
    return (2 * n * layer_matmul_params(cfg) * tokens + n * attn
            + 2 * head_params(cfg) * sampled_tokens)


def flash_flops(cfg, batch, seq, backward):
    """Causal attention of one layer over ``batch`` sequences: the forward's
    two products, or the backward's four (dV, dP, dQ, dK). The score
    recomputation that a flash backward performs is not required work."""
    return (2 if backward else 1) * batch * attention_flops(cfg, seq, seq)


def flash_bytes(cfg, batch, seq, backward, itemsize=2):
    """Least HBM traffic: q, k, v read and o written once; the backward
    also reads o and do and writes dq, dk, dv."""
    nq, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    q = batch * seq * nq * d * itemsize
    kv = batch * seq * nkv * d * itemsize
    return (4 * q + 4 * kv) if backward else (2 * q + 2 * kv)


def ragged_attention_bytes(cfg, spans, itemsize=2):
    """Least HBM traffic of one ragged paged-attention call: each span's
    queries read and outputs written, each sequence's context keys and
    values read once."""
    nq, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    q = sum(q for q, _ in spans) * nq * d * itemsize
    kv = sum(c for _, c in spans) * nkv * d * itemsize
    return 2 * q + 2 * kv


def roofline_seconds(flops, nbytes, peaks, flops_key="bf16_flops"):
    """(least seconds, which bound) on one chip."""
    t_c = flops / peaks[flops_key]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
