"""Model step: the whole serving step's share of the chip's bf16 peak for a
latent-attention / routed-expert model. Required operations
(``flops_deepseek_v3.serve_flops``) of every token the traced window
processed (the kernel entry's recorded descriptors, one call a layer a
tick; attention in the cheaper of its two forms, span by span), of the
(token, held expert) pairs the program counted on the device (an absent
expert's work is not required here) and of the head once a sampled token,
over window x chips x peak."""
from benchmark import flops_deepseek_v3 as flops, harness


def read(run):
    calls, counters = run.get("kernel_calls"), run.get("counters") or {}
    if not calls or "moe_expert_tokens" not in counters:
        return None
    config = run["config"]
    ticks = calls[::config["num_hidden_layers"]]
    spans = [(q, c) for _, qs, cs in ticks for q, c in zip(qs, cs)]
    total = flops.serve_flops(config, spans, run["window"]["delivered"],
                              int(sum(counters["moe_expert_tokens"])))
    return harness.mfu_pct(total, run["window_s"], run["chips"],
                           run["peaks"]["bf16_flops"])
