"""Packaging for horovod_tpu.

Reference parity: the reference's setup.py (396 LoC) is a feature-probing
build that compiles test programs to detect MPI flags, C++ ABI, CUDA and
NCCL (setup.py:170-363) — none of which exist on TPU. The package is pure
Python: nothing is compiled at install time or at run time.

    pip install .
"""

from setuptools import find_packages, setup

setup(
    name="horovod_tpu",
    version="0.1.0",
    description=("TPU-native Horovod-style data-parallel training: XLA "
                 "collectives over ICI, custom groups as replica_groups, "
                 "DistributedOptimizer, sequence parallelism."),
    packages=find_packages(include=["horovod_tpu", "horovod_tpu.*"]),
    python_requires=">=3.10",
    install_requires=["jax", "flax", "optax", "numpy"],
)
