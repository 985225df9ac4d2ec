"""BERT/ERNIE fine-tune under @to_static (BASELINE.json configs[2]) — the
dy2static flow: eager model wrapped by paddle.jit.to_static compiles the
step through jax.jit → HLO; AMP GradScaler included.

    python examples/finetune_bert_to_static.py --steps 8
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.models import BertForSequenceClassification, bert_tiny


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=32)
    args = ap.parse_args()

    paddle.seed(0)
    cfg = bert_tiny()
    cfg.num_labels = 2
    model = BertForSequenceClassification(cfg)
    model = paddle.jit.to_static(model)          # compile the forward
    opt = paddle.optimizer.AdamW(learning_rate=3e-4,
                                 parameters=model.parameters())
    scaler = paddle.amp.GradScaler(init_loss_scaling=2.0 ** 10)
    loss_fn = paddle.nn.CrossEntropyLoss()

    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(0, cfg.vocab_size,
                                        (args.batch, args.seq)), "int64")
    labels = paddle.to_tensor(rng.integers(0, 2, (args.batch,)), "int64")

    losses = []
    for step in range(args.steps):
        with paddle.amp.auto_cast(level="O1"):
            logits = model(ids)
            loss = loss_fn(logits, labels)
        scaled = scaler.scale(loss)
        scaled.backward()
        scaler.step(opt)
        scaler.update()
        opt.clear_grad()
        losses.append(float(loss))
        print(f"step {step} loss {losses[-1]:.4f} "
              f"(loss_scale {float(scaler.get_scale_ratio()):.0f})")
    assert losses[-1] < losses[0]
    print("done")


if __name__ == "__main__":
    main()
