"""paddle.version parity (reference: generated python/paddle/version.py)."""
full_version = "0.2.0"
major = "0"
minor = "2"
patch = "0"
rc = "0"
cuda_version = "False"   # reference reports the CUDA toolkit; TPU build
cudnn_version = "False"
tpu_backend = "pjrt/xla"
istaged = True
commit = "tpu-native"
with_pip_cuda_libraries = "OFF"


def show():
    print(f"full_version: {full_version}")
    print(f"major: {major}\nminor: {minor}\npatch: {patch}\nrc: {rc}")
    print(f"tpu_backend: {tpu_backend}")


def cuda():
    return cuda_version


def cudnn():
    return cudnn_version
