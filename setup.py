"""Packaging for horovod_tpu.

Role parity with the reference's setup.py (one native core + framework
bindings): builds ``cpp/libhvd_core.so`` via the Makefile during
``build_ext`` and installs the ``hvdrun`` console script. Framework extras
mirror the reference's install flavors.
"""

import os
import subprocess

from setuptools import Command, find_packages, setup
from setuptools.command.build_ext import build_ext
from setuptools.dist import Distribution


class BuildNativeCore(build_ext):
    def run(self):
        cpp_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "cpp")
        subprocess.run(["make", "-C", cpp_dir], check=True)
        super().run()


class BinaryDistribution(Distribution):
    def has_ext_modules(self):
        return True


setup(
    name="horovod_tpu",
    version="0.1.0",
    description=(
        "TPU-native distributed training framework with Horovod-capability "
        "parity: named-tensor async collectives with fusion, coordinator "
        "negotiation, response cache, Adasum, Join, autotune, and timeline "
        "— lowered to XLA collectives over ICI/DCN."
    ),
    packages=find_packages(include=["horovod_tpu", "horovod_tpu.*"]),
    package_data={"horovod_tpu": ["../cpp/libhvd_core.so"]},
    python_requires=">=3.10",
    # jax range pinned deliberately: elastic in-process
    # recovery rides two private surfaces (xla_bridge._clear_backends,
    # the jax_enable_recoverability flag) that are capability-probed at
    # init — outside this validated range the probe may flip recovery to
    # the public-API respawn fallback, which still works but restarts
    # worker processes instead of re-forming the world in place.
    install_requires=["numpy", "jax>=0.9,<0.11", "pyyaml"],
    extras_require={
        "flax": ["flax", "optax"],
        "pytorch": ["torch"],
        "tensorflow": ["tensorflow"],
        "keras": ["tensorflow"],
        "dev": ["pytest"],
    },
    entry_points={
        "console_scripts": [
            "hvdrun = horovod_tpu.run.run:main",
            "horovodrun = horovod_tpu.run.run:main",
        ]
    },
    cmdclass={"build_ext": BuildNativeCore},
    distclass=BinaryDistribution,
)
