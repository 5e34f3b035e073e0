"""Build hook: compile the native PS engine (libps_core.so) at install.

The C-ABI library (no pybind dependency — loaded via ctypes) is the one
native component; everything device-side is jax/XLA. The binary's name
carries a hash of its source (`ps/_native.py:lib_path`), and
`_native.py` builds it on first use when no binary of that name is
there, so no install can load a stale one.
"""
import runpy
import subprocess

from setuptools import setup
from setuptools.command.build_py import build_py


class BuildWithNative(build_py):
    def run(self):
        super().run()
        import os
        src = os.path.join("paddle_tpu", "ps", "csrc", "ps_core.cpp")
        lib_name = os.path.basename(runpy.run_path(
            os.path.join("paddle_tpu", "ps", "_native.py"))["lib_path"](src))
        for root in (self.build_lib, "."):
            out_dir = os.path.join(root, "paddle_tpu", "ps", "csrc")
            if not os.path.isdir(out_dir):
                continue
            out = os.path.join(out_dir, lib_name)
            cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", src,
                   "-o", out, "-lpthread"]
            print("building native ps_core:", " ".join(cmd))
            subprocess.run(cmd, check=True)


setup(cmdclass={"build_py": BuildWithNative})
