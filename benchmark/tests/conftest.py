"""The harness's own tests: ``JAX_PLATFORMS=cpu python -m pytest
benchmark/tests -q`` (not part of ``tests/``).  They run on four virtual
CPU devices; the command line itself has no CPU mode."""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from rayfed_tpu.utils import force_cpu_devices, use_compilation_cache  # noqa: E402

force_cpu_devices(4)
use_compilation_cache()
