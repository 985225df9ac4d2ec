"""Model step: the whole serving step's share of the chip's bf16 peak for a
model of linear-attention (KDA) and latent-attention layers with routed
experts. Required operations (``flops_bailing_hybrid.serve_flops``) of every
token the traced window processed (the latent kernel entry's recorded
descriptors, one call a tick: the model has one MLA layer): projections, the
recurrence at ``2 x 3 x d x d`` a head a token whatever form computes it,
the MLA layer in the cheaper of its two forms, the (token, held expert)
pairs the program counted on the device, the head once a sampled token; over
window x chips x peak."""
from benchmark import flops_bailing_hybrid as flops, harness


def read(run):
    calls, counters = run.get("kernel_calls"), run.get("counters") or {}
    config = run.get("config") or {}
    if not calls or "moe_expert_tokens" not in counters \
            or "kda_lower_bound" not in config:
        return None
    mla = max(config["layer_kinds"].count("mla"), 1)
    spans = [(q, c) for _, qs, cs, *_ in calls[::mla]
             for q, c in zip(qs, cs)]
    total = flops.serve_flops(config, spans, run["window"]["delivered"],
                              int(sum(counters["moe_expert_tokens"])))
    return harness.mfu_pct(total, run["window_s"], run["chips"],
                           run["peaks"]["bf16_flops"])
