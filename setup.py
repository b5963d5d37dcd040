"""Packaging for horovod_tpu.

Reference parity: the reference's setup.py (396 LoC) is a feature-probing
build that compiles test programs to detect MPI flags, C++ ABI, CUDA and
NCCL (setup.py:170-363) — none of which exist on TPU. What remains to build
is the native control-plane core (`hvd_core.cc`), compiled here as a plain
shared library (no Python ABI dependency — it is loaded via ctypes, the same
channel the reference uses, mpi_ops.py:68-77). If no compiler is available
the package still works: every native path has a pure-Python fallback with
identical semantics.

    pip install .            # builds _hvd_core.<hash>.so beside hvd_core.cc
    python setup.py build    # same, in-place tree
"""

from __future__ import annotations

import importlib.util
import os

from setuptools import find_packages, setup
from setuptools.command.build_py import build_py


def _build_core(base: str) -> str | None:
    """Run the package's own native build (core/native/__init__.py
    ``_build`` — stdlib only, so it loads without jax) inside ``base``:
    one build recipe, one naming rule (the library's name carries the
    hash of hvd_core.cc, and only that name is ever loaded)."""
    init = os.path.join(base, "horovod_tpu", "core", "native", "__init__.py")
    if not os.path.exists(init):
        return None
    spec = importlib.util.spec_from_file_location("_hvd_native_build", init)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod._build()


class BuildWithNativeCore(build_py):
    def run(self):
        super().run()
        for base in ([self.build_lib] if not self.editable_mode else ["."]):
            out = _build_core(base)
            if out:
                print(f"built native control-plane core: {out}")
            else:
                print("WARNING: native core build failed; the "
                      "pure-Python control plane will be used.")


setup(
    name="horovod_tpu",
    version="0.1.0",
    description=("TPU-native Horovod-style data-parallel training: XLA "
                 "collectives over ICI, custom groups as replica_groups, "
                 "DistributedOptimizer, sequence parallelism."),
    packages=find_packages(include=["horovod_tpu", "horovod_tpu.*"]),
    package_data={"horovod_tpu.core.native": ["hvd_core.cc"]},
    python_requires=">=3.10",
    install_requires=["jax", "flax", "optax", "numpy"],
    cmdclass={"build_py": BuildWithNativeCore},
)
