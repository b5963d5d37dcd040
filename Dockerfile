# horovod_tpu container — the packaging analog of the reference's
# Dockerfile (/root/reference/Dockerfile:1): a ready-to-run image with the
# framework, its examples, and the test suite.
#
# The TPU analog of the reference's CUDA base + MPI stack is simply the
# jax[tpu] wheel: XLA collectives over ICI replace NCCL/MPI, and
# jax.distributed.initialize replaces mpirun (docs/running.md). The same
# image drives real TPU VMs (default) or the simulated CPU pod (CI /
# development — see docs/docker.md).

FROM python:3.12-slim

# On a TPU VM, swap the extra for the libtpu-bundled wheel:
#   pip install 'jax[tpu]' -f https://storage.googleapis.com/jax-releases/libtpu_releases.html
RUN pip install --no-cache-dir \
        jax flax optax orbax-checkpoint chex einops numpy pytest

WORKDIR /horovod_tpu
COPY setup.py README.md ./
COPY horovod_tpu ./horovod_tpu
COPY examples ./examples
COPY tests ./tests
COPY docs ./docs
RUN pip install --no-cache-dir -e .

# Default: prove the install by running the suite on the simulated
# 8-device pod (no TPU needed — the reference's Travis flow in a box).
ENV HOROVOD_CPU_DEVICES=8 \
    JAX_PLATFORMS=cpu
CMD ["python", "-m", "pytest", "tests/", "-x", "-q"]
