"""MFU accounting (SURVEY.md §7.1 M5 "MFU dashboard", §6 sanity anchors).

Model-flops utilization = achieved FLOP/s ÷ peak FLOP/s. Transformer FLOPs
use the standard 6·N·tokens fwd+bwd estimate plus the attention term
12·L·h·s²·(causal ½) — the same accounting the reference community uses for
Megatron/PaddleNLP MFU claims.
"""
from __future__ import annotations

import time

# bf16 peak FLOP/s per chip
PEAK_FLOPS = {
    "v4": 275e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v6e": 918e12,
    "cpu": 1e12,          # nominal; for smoke runs only
}


def chip_kind(device=None):
    """Map a jax device to a PEAK_FLOPS key (e.g. 'TPU v5 lite' -> 'v5e').
    An accelerator that is not in the table is an error, not a default."""
    if device is None:
        import jax
        device = jax.devices()[0]
    if device.platform == "cpu":
        return "cpu"
    k = (getattr(device, "device_kind", "") or "").lower()
    if "v5 lite" in k or "v5e" in k or "v5litepod" in k:
        return "v5e"
    if "v5p" in k or "v5" in k:
        return "v5p"
    if "v6" in k:
        return "v6e"
    if "v4" in k:
        return "v4"
    raise ValueError(f"no peak FLOP/s known for device kind "
                     f"{device.device_kind!r} ({device.platform})")


def transformer_train_flops(num_params, tokens, num_layers=None,
                            hidden_size=None, seq_len=None, causal=True):
    """6·N·tokens (fwd 2N + bwd 4N) + attention 12·L·h·s²·b term."""
    total = 6.0 * num_params * tokens
    if num_layers and hidden_size and seq_len:
        batch_tokens = tokens / seq_len
        attn = 12.0 * num_layers * hidden_size * (seq_len ** 2) * batch_tokens
        if causal:
            attn *= 0.5
        total += attn
    return total


def llama_train_flops(config, batch, seq_len):
    """FLOPs of one train step of a Llama-config model."""
    n = llama_param_count(config)
    return transformer_train_flops(
        n, batch * seq_len, num_layers=config.num_hidden_layers,
        hidden_size=config.hidden_size, seq_len=seq_len)


def llama_param_count(config):
    h = config.hidden_size
    i = config.intermediate_size
    v = config.vocab_size
    kvh = config.num_key_value_heads * config.head_dim
    per_layer = (h * h + 2 * h * kvh + h * h    # q, k, v, o
                 + 3 * h * i                    # gate, up, down
                 + 2 * h)                       # norms
    n = config.num_hidden_layers * per_layer + v * h + h
    if not getattr(config, "tie_word_embeddings", False):
        n += v * h
    return n


class MFUMonitor:
    """Per-step MFU/throughput meter.

    monitor = MFUMonitor(step_flops=llama_train_flops(cfg, b, s),
                         chip="v5p", n_chips=64)
    for ...: step(); monitor.step(tokens=b*s)
    print(monitor.summary())
    """

    def __init__(self, step_flops, chip="v5p", n_chips=1, peak_flops=None):
        self.step_flops = float(step_flops)
        self.peak = (peak_flops if peak_flops is not None
                     else PEAK_FLOPS.get(chip, PEAK_FLOPS["v5p"])) * n_chips
        self.reset()

    def reset(self):
        self._t0 = time.perf_counter()
        self._steps = 0
        self._tokens = 0

    def step(self, tokens=0):
        self._steps += 1
        self._tokens += tokens

    @property
    def elapsed(self):
        return time.perf_counter() - self._t0

    def mfu(self):
        if not self._steps:
            return 0.0
        achieved = self.step_flops * self._steps / max(self.elapsed, 1e-9)
        return achieved / self.peak

    def tokens_per_sec(self):
        return self._tokens / max(self.elapsed, 1e-9)

    def summary(self):
        return (f"steps={self._steps} "
                f"tokens/s={self.tokens_per_sec():,.0f} "
                f"MFU={self.mfu() * 100:.1f}%")
