"""Serving walkthrough: load an HF-format Llama checkpoint from a local
directory, stand up the batched ServingEngine (paged KV cache), and serve
concurrent generate() calls.

    python examples/serve_llama_hf.py --model-dir /path/to/hf_llama
    python examples/serve_llama_hf.py            # tiny random demo model
    JAX_PLATFORMS=cpu python examples/serve_llama_hf.py   # no chip

Runs on whatever backend jax finds; on a TPU host the decode path runs the
in-repo paged-attention Pallas kernel — same API either way.
"""
import argparse
import os
import sys
import threading

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np                                        # noqa: E402

import paddle_tpu as paddle                               # noqa: E402
from paddle_tpu.models import LlamaForCausalLM, llama_tiny  # noqa: E402
from paddle_tpu.inference.serving import ServingEngine    # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model-dir", default=None,
                    help="local HF checkpoint dir (config.json + weights)")
    ap.add_argument("--clients", type=int, default=3)
    ap.add_argument("--new-tokens", type=int, default=12)
    args = ap.parse_args()

    paddle.seed(0)
    if args.model_dir:
        model = LlamaForCausalLM.from_pretrained(args.model_dir)
        print(f"loaded HF checkpoint from {args.model_dir}")
    else:
        model = LlamaForCausalLM(llama_tiny(num_hidden_layers=2))
        print("no --model-dir: using a tiny random demo model")
    model.eval()
    vocab = model.config.vocab_size

    engine = ServingEngine(model, max_batch_size=8,
                           batch_window_s=0.02).start()
    rng = np.random.RandomState(0)
    prompts = [paddle.to_tensor(
        rng.randint(0, vocab, (1, 4 + i)).astype(np.int64))
        for i in range(args.clients)]

    outs = {}

    def client(i):
        outs[i] = engine.generate(prompts[i],
                                  max_new_tokens=args.new_tokens,
                                  timeout=600)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(args.clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    engine.stop()

    for i in range(args.clients):
        print(f"client {i}: prompt {tuple(prompts[i].shape)} -> "
              f"output {tuple(outs[i].shape)}; "
              f"batches_run={engine.batches_run}")
    assert all(tuple(outs[i].shape)[1]
               == tuple(prompts[i].shape)[1] + args.new_tokens
               for i in range(args.clients))
    print("serving demo OK")


if __name__ == "__main__":
    main()
