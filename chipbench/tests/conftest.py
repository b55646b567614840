"""CPU tests of the benchmark at small sizes.

    python -m pytest chipbench/tests

They sit outside the repository's tier-1 ``testpaths``.  The CPU backend
gets four host devices, so the 2x2 ring runs here too."""

import atexit
import os
import shutil
import sys
import tempfile
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

# compiled programs of the test sizes stay out of the checkout's cache
os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
    prefix="chipbench-test-cache-")
atexit.register(shutil.rmtree, os.environ["JAX_COMPILATION_CACHE_DIR"],
                ignore_errors=True)

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))
