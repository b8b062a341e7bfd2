"""Build glue: compile the native C++ runtime into the wheel.

The reference installs via CMake (root CMakeLists.txt -> libmultiverso.so
+ headers); the TPU build's wheel carries the equivalent
``libmultiverso_tpu.so`` as package data under ``multiverso_tpu/native/``
(the ctypes loader loads it from there in installed trees; a source
checkout builds and loads the repo's ``native/`` copy instead, and a tree
with no library says once that the python fallbacks run —
multiverso_tpu/native/__init__.py).

The library is built with the same flags as native/Makefile. A missing
C++ toolchain degrades gracefully: the wheel ships pure-python and the
fast readers / native CPU store are unavailable (the module contract).
"""

import shutil
import subprocess
import sys
from pathlib import Path

from setuptools import setup
from setuptools.command.build_py import build_py

ROOT = Path(__file__).resolve().parent
NATIVE = ROOT / "native"


def _build_native(out_path: Path) -> bool:
    """Build via the Makefile — the single source of truth for the native
    source list and flags (a parallel list here would silently drop new
    .cc files from wheels)."""
    if shutil.which("make") is None or not (NATIVE / "Makefile").exists():
        print("multiverso-tpu: no make/Makefile; wheel ships pure-python",
              file=sys.stderr)
        return False
    result = subprocess.run(["make", "-C", str(NATIVE), "-j4",
                             "libmultiverso_tpu.so"],
                            capture_output=True, text=True)
    if result.returncode != 0:
        print(f"multiverso-tpu: native build failed (pure-python wheel):\n"
              f"{result.stderr[-2000:]}", file=sys.stderr)
        return False
    shutil.copy2(NATIVE / "libmultiverso_tpu.so", out_path)
    return True


class BuildPyWithNative(build_py):
    def run(self):
        super().run()
        dest = Path(self.build_lib) / "multiverso_tpu" / "native"
        dest.mkdir(parents=True, exist_ok=True)
        _build_native(dest / "libmultiverso_tpu.so")


setup(cmdclass={"build_py": BuildPyWithNative})
