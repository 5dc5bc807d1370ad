"""Install-time hook that builds blit's native C++ libraries.

All package metadata lives in pyproject.toml; this file exists only to
compile ``blit/native`` (bitshuffle+LZ4 codec, GUPPI block reader) during
``pip install`` / wheel builds.  A host without a C++ toolchain still
installs, for inventory and plain SIGPROC/FBH5 work: without the
libraries bitshuffle-compressed FBH5 can be neither read nor written
(there is no NumPy codec), RAW blocks read through a memmap copy on the
CPU, and the device paths refuse to run (blit/io/guppi.py
``require_native_reader``).
"""

import os
import subprocess
import sys

from setuptools import setup
from setuptools.command.build_py import build_py


class build_py_with_native(build_py):
    def run(self):
        native = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "blit", "native")
        try:
            subprocess.run(["make", "-C", native], check=True)
        except (OSError, subprocess.CalledProcessError) as e:
            print(
                f"blit: native build skipped ({e}); no bitshuffle codec "
                "and no native RAW reader until you run "
                "`make -C blit/native` inside the installed tree",
                file=sys.stderr,
            )
        super().run()


setup(cmdclass={"build_py": build_py_with_native})
