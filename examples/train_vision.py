"""Train a small conv net with the high-level Model API (the reference's
config-1 workflow: datasets + transforms + Model.fit).

python examples/train_vision.py [--epochs 1] [--tiny]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # repo checkout; unnecessary if installed

if "--cpu" in sys.argv:  # run on the CPU backend (e.g. no chip attached)
    sys.argv.remove("--cpu")
    import jax
    jax.config.update("jax_platforms", "cpu")


import numpy as np

import paddle_tpu as paddle
from paddle_tpu.vision import transforms


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=1)
    ap.add_argument("--tiny", action="store_true",
                    help="64-sample synthetic run (CI smoke)")
    args = ap.parse_args()

    tf = transforms.Compose([transforms.Normalize(mean=0.5, std=0.5)])
    if args.tiny:
        from paddle_tpu.vision.datasets import FakeData
        train = FakeData(64, (1, 28, 28), 10, transform=tf)
    else:
        from paddle_tpu.vision.datasets import MNIST
        train = MNIST(mode="train", transform=tf)

    net = paddle.nn.Sequential(
        paddle.nn.Conv2D(1, 16, 3, padding=1), paddle.nn.ReLU(),
        paddle.nn.MaxPool2D(2, 2),
        paddle.nn.Conv2D(16, 32, 3, padding=1), paddle.nn.ReLU(),
        paddle.nn.MaxPool2D(2, 2),
        paddle.nn.Flatten(),
        paddle.nn.Linear(32 * 7 * 7, 10),
    )
    model = paddle.Model(net)
    model.prepare(paddle.optimizer.Adam(1e-3,
                                        parameters=net.parameters()),
                  paddle.nn.CrossEntropyLoss(),
                  paddle.metric.Accuracy())
    model.fit(train, epochs=args.epochs, batch_size=32, verbose=1)


if __name__ == "__main__":
    main()
