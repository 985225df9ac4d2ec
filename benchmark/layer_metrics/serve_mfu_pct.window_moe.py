"""Model step: the whole serving step's share of the chip's bf16 peak for a
model of window and full attention layers with routed experts. Required
operations (``flops_smallthinker.serve_flops``) of every token the traced
window processed (the kernel entry's recorded descriptors, one call a layer
a tick: a full layer counts the keys ``[0, c)`` of a query whose bound is
``c``, a window layer the last 4,096 of them), of the (token, expert) pairs
the program counted on the device and of the head once a sampled token,
over window x chips x peak."""
from benchmark import flops_smallthinker as flops, harness


def read(run):
    calls, counters = run.get("kernel_calls"), run.get("counters") or {}
    config = run.get("config") or {}
    if not calls or "moe_expert_tokens" not in counters \
            or "sliding_window_layout" not in config:
        return None
    ticks = calls[::config["num_hidden_layers"]]
    spans = [(q, c) for _, qs, cs, *_ in ticks for q, c in zip(qs, cs)]
    total = flops.serve_flops(config, spans, run["window"]["delivered"],
                              int(sum(counters["moe_expert_tokens"])))
    return harness.mfu_pct(total, run["window_s"], run["chips"],
                           run["peaks"]["bf16_flops"])
