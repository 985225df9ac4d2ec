"""Continuous-batching serving: mixed-length traffic through ONE
fixed-shape decode loop (reference: the vLLM-style serving tier around
fused_multi_transformer).

Run:  python examples/serve_continuous.py
"""
import os
import sys
import threading

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.inference import ContinuousServingEngine
from paddle_tpu.models import LlamaForCausalLM, llama_tiny


def main():
    paddle.seed(0)
    model = LlamaForCausalLM(llama_tiny(num_hidden_layers=2))
    engine = ContinuousServingEngine(model, max_batch_size=4, max_len=128)
    rng = np.random.RandomState(0)

    results = {}

    def client(name, prompt_len, budget):
        prompt = rng.randint(0, 128, (1, prompt_len)).astype(np.int64)
        out = engine.generate(prompt, max_new_tokens=budget, timeout=600)
        results[name] = tuple(out.shape)

    with engine:
        # six clients with different prompt lengths and budgets share
        # every decode step; slots are reused as requests finish
        threads = [threading.Thread(target=client,
                                    args=(f"req{i}", 4 + 3 * i, 4 + i))
                   for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    for name in sorted(results):
        print(f"{name}: output shape {results[name]}")
    print(f"prefills={engine.prefills} decode_steps={engine.decode_steps} "
          f"(sum of per-request budgets would be "
          f"{sum(4 + i for i in range(6))} steps unbatched)")


if __name__ == "__main__":
    main()
