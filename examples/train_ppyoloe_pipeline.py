"""PP-YOLOE data-pipeline config (BASELINE.json configs[3]): detection model
fed by a heavy multiprocess DataLoader (augmentation in workers, shared-memory
transport, device prefetch) — the flow the reference runs with
``paddle.io.DataLoader`` + ``buffered_reader`` H2D double-buffering.

    python examples/train_ppyoloe_pipeline.py --steps 6
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.io import DataLoader, Dataset
from paddle_tpu.models import ppyoloe_lite, DetectionLoss


class SyntheticDetection(Dataset):
    """Worker-side augmentation heavy enough to need the pipeline: random
    crop-ish jitter + flip + normalize on 64x64 images, dense targets."""

    def __init__(self, size=64, img=64, classes=4):
        self.size = size
        self.img = img
        self.classes = classes

    def __len__(self):
        return self.size

    def __getitem__(self, i):
        rng = np.random.default_rng(i)
        img = rng.integers(0, 256, (3, self.img, self.img)).astype(np.float32)
        if rng.random() < 0.5:
            img = img[:, :, ::-1]
        img = (img / 127.5) - 1.0
        jitter = rng.normal(0, 0.01, img.shape).astype(np.float32)
        img = img + jitter
        # dense per-level targets (cls one-hot-ish, ltrb distances, pos mask)
        tcls, treg, mask = [], [], []
        for stride in (8, 16, 32):
            g = self.img // stride
            tcls.append(rng.random((self.classes, g, g)).astype(np.float32)
                        < 0.02)
            treg.append(rng.random((4, g, g)).astype(np.float32) * 4)
            mask.append((rng.random((4, g, g)) < 0.1).astype(np.float32))
        return (img.astype(np.float32),
                [t.astype(np.float32) for t in tcls], treg, mask)


def collate(batch):
    imgs = np.stack([b[0] for b in batch])
    tcls = [np.stack([b[1][l] for b in batch]) for l in range(3)]
    treg = [np.stack([b[2][l] for b in batch]) for l in range(3)]
    mask = [np.stack([b[3][l] for b in batch]) for l in range(3)]
    return imgs, tcls, treg, mask


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--workers", type=int, default=2)
    args = ap.parse_args()

    paddle.seed(0)
    model = ppyoloe_lite(num_classes=4)
    loss_fn = DetectionLoss()
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    ds = SyntheticDetection(size=args.batch * args.steps)
    loader = DataLoader(ds, batch_size=args.batch, num_workers=args.workers,
                        collate_fn=collate, use_shared_memory=True,
                        prefetch_factor=2)

    t0 = time.time()
    losses = []
    for step, (imgs, tcls, treg, mask) in enumerate(loader):
        cls_outs, reg_outs = model(imgs)
        loss = loss_fn(cls_outs, reg_outs, tcls, treg, mask)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
        print(f"step {step} loss {losses[-1]:.4f} "
              f"({(time.time() - t0) / (step + 1):.2f}s/step)")
        if step + 1 >= args.steps:
            break

    # post-processing end-to-end
    dets = model.predict(imgs[:1], score_thresh=0.3, top_k=10)
    print(f"done: loss {losses[0]:.4f} -> {losses[-1]:.4f}; "
          f"{len(dets[0]['boxes'])} detections on sample 0")
    assert losses[-1] < losses[0]


if __name__ == "__main__":
    main()
