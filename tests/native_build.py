"""Build the optional C++ extensions (csrc/) once per checkout.

``*.so`` and ``build/`` are gitignored, so a clean checkout has no
binaries and ``distkeras_tpu`` binds its pure-Python fallbacks AT IMPORT.
Under pytest-xdist every worker imports the package while it collects —
before any test could build anything — and the tests of the native legs
(wire codec, apply kernel, CSV loader) then skip in every worker but the
one that happened to build.  So the build runs from ``conftest.py``'s
``pytest_configure``, before collection, and under a file lock: the
workers (and the two test files that used to build on their own, into the
same ``build/`` at once) wait for one ``setup.py build_ext --inplace``
instead of racing it.
"""

import fcntl
import glob
import os
import subprocess
import sys
from typing import Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXTENSIONS = ("_wirecodec", "_applykernel", "_csvloader")


def _all_built() -> bool:
    pkg = os.path.join(REPO, "distkeras_tpu")
    return all(glob.glob(os.path.join(pkg, name + ".*.so"))
               for name in EXTENSIONS)


def ensure_built() -> Optional[str]:
    """None once all three binaries are in place (built here if they were
    not); otherwise the end of the build's output — no toolchain."""
    if _all_built():
        return None
    os.makedirs(os.path.join(REPO, "build"), exist_ok=True)
    with open(os.path.join(REPO, "build", ".native_build.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)  # released when the file closes
        if _all_built():  # another worker built while this one waited
            return None
        run = subprocess.run(
            [sys.executable, "setup.py", "build_ext", "--inplace"],
            cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        # the extensions are optional=True: a failed compile still exits 0
        return None if _all_built() else run.stdout[-300:]
