"""Contrastive image-text pretraining (CLIP) — local and GLOBAL batch.

Walkthrough of the reference multimodal workflow (PaddleMIX CLIP-style
two-tower contrastive training) on the TPU-native stack, with the part
the reference does over NCCL done the TPU way: the global-batch InfoNCE
gathers features across the data-parallel mesh axis inside ONE traced
SPMD step (`clip_global_loss` — the gather's backward is the exact
transpose, so per-shard gradients equal the full-batch oracle's).

    python examples/train_clip_contrastive.py --cpu            # local batch
    python examples/train_clip_contrastive.py --cpu --mesh     # dp=4 global batch

(--cpu selects the CPU backend; JAX_PLATFORMS=cpu does the same.)
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

if "--cpu" in sys.argv:
    import jax
    jax.config.update("jax_platforms", "cpu")
    if "--mesh" in sys.argv:
        jax.config.update("jax_num_cpu_devices", 4)

import paddle_tpu as P  # noqa: E402
from paddle_tpu.models import CLIPConfig, CLIPModel, clip_loss  # noqa: E402
from paddle_tpu.models import clip_global_loss  # noqa: E402
from paddle_tpu.optimizer import AdamW  # noqa: E402


def synthetic_batch(rng, b):
    """Paired image/caption surrogates: class k gets a bright patch at
    row k and caption tokens centered on k — enough correlation for the
    contrastive objective to separate the batch."""
    k = rng.integers(0, 4, (b,))
    px = rng.standard_normal((b, 3, 32, 32)).astype(np.float32) * 0.1
    for i, ki in enumerate(k):
        px[i, :, ki * 8:(ki + 1) * 8] += 1.0
    ids = np.zeros((b, 12), np.int64)
    ids[:, 0] = 97
    for i, ki in enumerate(k):
        ids[i, 1:9] = 10 + ki * 20 + rng.integers(0, 5, (8,))
    ids[:, 9] = 98
    return px, ids


def train_local(steps=20):
    rng = np.random.default_rng(0)
    model = CLIPModel(CLIPConfig.tiny())
    model.train()
    opt = AdamW(learning_rate=1e-3, parameters=model.parameters())
    for step in range(steps):
        px, ids = synthetic_batch(rng, 8)
        _, lt = model(P.to_tensor(ids.astype(np.int32)),
                      P.to_tensor(px))
        loss = clip_loss(lt)
        loss.backward()
        opt.step()
        opt.clear_grad()
        if step % 5 == 0 or step == steps - 1:
            print(f"step {step:3d}  local-batch loss {float(loss):.4f}")
    return float(loss)


def train_mesh_global(steps=8):
    """dp=4 mesh: every step computes the GLOBAL-batch contrastive loss
    over 4x the per-device batch via the traced all-gather."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as Pspec
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed._axis import axis_env

    rng = np.random.default_rng(0)
    n_dev = 4
    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("dp",))
    g = dist.new_group(list(range(n_dev)), axis_name="dp")

    # feature towers stay on one device here for brevity; the traced
    # global loss is the piece the reference needs NCCL for
    model = CLIPModel(CLIPConfig.tiny())
    model.train()
    opt = AdamW(learning_rate=1e-3, parameters=model.parameters())

    # one program, built once: per-step rebuilds would retrace/recompile
    # (jax caches on callable identity)
    def body(i, t, s):
        loss = clip_global_loss(P.Tensor(i), P.Tensor(t), P.Tensor(s),
                                group=g)
        return jax.lax.pmean(loss._data.reshape(()), "dp")[None]

    # jitted: a bare shard_map runs its body op by op, and compiles every
    # one of those ops again on every call
    fm = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(Pspec("dp"), Pspec("dp"), Pspec(None)),
        out_specs=Pspec("dp")))

    def global_loss(img_f, txt_f, scale):
        with axis_env("dp"):
            return float(np.asarray(fm(img_f, txt_f, scale))[0])

    for step in range(steps):
        px, ids = synthetic_batch(rng, 4 * n_dev)  # global batch 16
        pxt = P.to_tensor(px)
        idt = P.to_tensor(ids.astype(np.int32))
        # run each tower ONCE; the local loss derives from the same
        # features (clip_global_loss with group=None is the in-batch
        # form), and the mesh pass reuses them
        img_f = model.get_image_features(pxt)
        txt_f = model.get_text_features(idt)
        loss = clip_global_loss(img_f, txt_f, model.logit_scale)
        loss.backward()
        opt.step()
        opt.clear_grad()
        # np round-trip: the eager features are committed to device 0;
        # the mesh program re-shards host arrays over all 4 devices
        gl = global_loss(np.asarray(img_f._data),
                         np.asarray(txt_f._data),
                         np.asarray(model.logit_scale._data))
        print(f"step {step:3d}  local {float(loss):.4f}  "
              f"global-batch(mesh dp=4) {gl:.4f}")
    return gl


if __name__ == "__main__":
    if "--mesh" in sys.argv:
        final = train_mesh_global()
    else:
        final = train_local()
    print(f"CLIP contrastive training OK (final loss {final:.4f})")
