"""Whole-step share of the chip's bf16 peak while serving: the matrix
products and attention of every token the traced window processed (prefill
and decode, from the q-block kernel's recorded descriptors: one call a layer
a tick) and the head for every token sampled, over window x chips x peak."""
from benchmark import flops, harness


def read(run):
    calls = run.get("kernel_calls")
    if not calls:
        return None
    config = run["config"]
    layers = config["num_hidden_layers"]
    # every layer of a tick repeats the same descriptors: keep one a tick
    ticks = calls[::layers]
    spans = [(q, c) for _, qs, cs in ticks for q, c in zip(qs, cs)]
    total = flops.serve_flops(config, spans, run["window"]["delivered"])
    return harness.mfu_pct(total, run["window_s"], run["chips"],
                           run["peaks"]["bf16_flops"])
