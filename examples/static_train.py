"""Static-graph training walkthrough (the reference's classic
program_guard → append_backward/minimize → Executor.run loop).

python examples/static_train.py [--cpu]
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

import paddle_tpu as P
from paddle_tpu import static


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=100)
    args = ap.parse_args()

    P.seed(42)
    main_prog = static.Program()
    startup = static.Program()
    with static.program_guard(main_prog, startup):
        x = static.data("x", [32, 64], "float32")
        y = static.data("y", [32, 1], "float32")
        net = P.nn.Sequential(P.nn.Linear(64, 128), P.nn.ReLU(),
                              P.nn.Linear(128, 1))
        pred = net(x)
        loss = P.nn.functional.mse_loss(pred, y)
        opt = P.optimizer.Adam(learning_rate=1e-2,
                               parameters=net.parameters())
        opt.minimize(loss)   # appends backward + update records

    exe = static.Executor()
    exe.run(startup)         # parameters are already live Tensors

    rng = np.random.default_rng(0)
    true_w = rng.standard_normal((64, 1)).astype(np.float32)
    for step in range(args.steps):
        xb_ = rng.standard_normal((32, 64)).astype(np.float32)
        yb = xb_ @ true_w + 0.01 * rng.standard_normal(
            (32, 1)).astype(np.float32)
        (lv,) = exe.run(main_prog, feed={"x": xb_, "y": yb},
                        fetch_list=[loss])
        if step % 20 == 0 or step == args.steps - 1:
            print(f"step {step:4d}  loss {float(lv):.4f}")

    # trained parameters are the SAME live tensors the dynamic API sees
    print("final weight norm:",
          float(np.linalg.norm(net[0].weight.numpy())))


if __name__ == "__main__":
    main()
