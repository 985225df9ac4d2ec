"""Operations and bytes from shapes, for a bailing-hybrid configuration
(Ling-3.0-flash) cut to one chip's share (``benchmark/flops.py``'s rules:
what the mathematics requires, never what an implementation executes; one
multiply-add is two operations; the embedding lookup is no matrix product).

A KDA layer's recurrence costs a head a token three passes over its ``d x
d`` state whatever form computes it: the read ``S'^T k``, the write ``k
v'^T`` and the output ``S^T q`` (``2 x 3 x d x d``; the decay is
elementwise and not counted); the chunkwise form's products inside a chunk
are an implementation's and not required. The counts read the WORK (rows,
tokens, spans), never the kernel's chunk size. The MLA layer counts as
``flops_deepseek_v3`` counts it (the cheaper of its two forms, span by
span), without a query rank and with the output gate's projection.
"""
from __future__ import annotations

from benchmark import flops_deepseek_v3 as ds


def _heads(cfg):
    return cfg["num_attention_heads"], cfg["head_dim"]


def kda_proj_params(cfg):
    """q, k, v, decay gate, output gate (hidden x H d each), beta (hidden x
    H) and the output projection."""
    h = cfg["hidden_size"]
    nh, d = _heads(cfg)
    return 5 * h * nh * d + h * nh + nh * d * h


def mla_proj_params(cfg):
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    return (h * nh * (nope + rope) + h * (cfg["kv_lora_rank"] + rope)
            + ds.kv_b_params(cfg) + h * nh + nh * vd * h)


def expert_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_expert_params(cfg):
    return (3 * cfg["hidden_size"] * cfg["num_shared_experts"]
            * cfg["moe_shared_expert_intermediate_size"])


def router_params(cfg):
    return cfg["hidden_size"] * cfg["num_experts"]


def layer_counts(cfg):
    """(KDA layers, MLA layers, dense layers, expert layers)."""
    kinds = cfg["layer_kinds"]
    dense = min(cfg["first_k_dense_replace"], len(kinds))
    return (kinds.count("kda"), kinds.count("mla"), dense,
            len(kinds) - dense)


def recurrence_flops(cfg, tokens):
    """ONE KDA layer's recurrence over ``tokens`` tokens."""
    nh, d = _heads(cfg)
    return 2 * 3 * d * d * nh * tokens


def conv_flops(cfg, tokens):
    nh, d = _heads(cfg)
    return 2 * cfg["short_conv_kernel_size"] * 3 * nh * d * tokens


def state_bytes(cfg):
    """A slot's ``S`` of one layer, float32."""
    nh, d = _heads(cfg)
    return 4 * nh * d * d


def _row_bytes(cfg, itemsize=2):
    """A token's rows in and out of a KDA kernel: q, k, v in and o out in
    the model's type, the decay (float32 a channel) and beta (float32 a
    head) in."""
    nh, d = _heads(cfg)
    return 4 * nh * d * itemsize + 4 * nh * d + 4 * nh


def kda_step_bytes(cfg, rows):
    """Least HBM traffic of one ``kda_step`` call: each row's state read
    and written once, its rows in and out."""
    return rows * (2 * state_bytes(cfg) + _row_bytes(cfg))


def kda_chunk_bytes(cfg, tokens, spans):
    """Least HBM traffic of one ``kda_chunk`` call: each span's state read
    and written once, each token's rows in and out."""
    return spans * 2 * state_bytes(cfg) + tokens * _row_bytes(cfg)


def serve_flops(cfg, spans, sampled_tokens, held_pairs):
    """``spans``: every (q_len, context_len) the window processed, once a
    tick; ``sampled_tokens``: tokens sampled (the head runs for those);
    ``held_pairs``: (token, held expert) pairs routed in the window, summed
    over the expert layers (the program's counter)."""
    kda, mla, dense, moe = layer_counts(cfg)
    tokens = sum(q for q, _ in spans)
    per_token = (kda * kda_proj_params(cfg) + mla * mla_proj_params(cfg)
                 + dense * ds.dense_mlp_params(cfg)
                 + moe * (router_params(cfg) + shared_expert_params(cfg)))
    attn = sum(ds.attention_flops(cfg, q, c) for q, c in spans)
    return (2 * per_token * tokens
            + kda * (recurrence_flops(cfg, tokens) + conv_flops(cfg, tokens))
            + mla * attn + 2 * expert_params(cfg) * held_pairs
            + 2 * ds.head_params(cfg) * sampled_tokens)
