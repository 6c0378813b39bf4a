"""Export a model and run it through all three deployment tiers
(docs/DEPLOY.md): Python predictor, ctypes PJRT runner, pd_infer CLI.

python examples/deploy_cpp.py [--plugin /path/to/pjrt_plugin.so]
Without a plugin/chip this stops after the export + Python-predictor
tiers (the C++ tiers need a PJRT .so to dlopen).
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

import os
import tempfile

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.jit import save
from paddle_tpu.jit.save_load import InputSpec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--plugin", default=None)
    args = ap.parse_args()

    paddle.seed(0)
    net = paddle.nn.Sequential(paddle.nn.Linear(16, 32),
                               paddle.nn.ReLU(),
                               paddle.nn.Linear(32, 8))
    net.eval()
    paddle.inference.optimize(net)  # IR passes (BN fold, dropout strip)

    prefix = os.path.join(tempfile.mkdtemp(), "model")
    save(net, prefix, input_spec=[InputSpec([4, 16], "float32")])
    print("exported:", sorted(os.listdir(os.path.dirname(prefix))))

    x = np.random.default_rng(0).standard_normal((4, 16)).astype(
        np.float32)
    ref = np.asarray(net(paddle.to_tensor(x))._data)
    print("python forward ok:", ref.shape)

    cfg = paddle.inference.Config(prefix)
    pred = paddle.inference.create_predictor(cfg)
    h = pred.get_input_handle(pred.get_input_names()[0])
    h.copy_from_cpu(x)
    pred.run()
    out = pred.get_output_handle(pred.get_output_names()[0]).copy_to_cpu()
    print("predictor ok, max err vs eager:",
          float(np.abs(out - ref).max()))

    if args.plugin:
        from paddle_tpu.native import PjrtRunner
        runner = PjrtRunner(args.plugin)
        runner.compile(open(prefix + ".mlir", "rb").read())
        params = [np.asarray(t._data) for _, t in net.named_parameters()]
        raw = runner.run(params + [x])
        got = np.frombuffer(raw[0], np.float32).reshape(4, 8)
        print("C++ runner ok, max err:", float(np.abs(got - ref).max()))
        runner.close()


if __name__ == "__main__":
    main()
