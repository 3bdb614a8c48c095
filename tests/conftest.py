"""Test config: CPU JAX with an 8-device virtual mesh.

The tests never need an accelerator: this pins the pytest process to
the CPU platform before the first backend initialization (pytest loads
conftest before test modules).  Multi-party integration tests spawn
fresh processes that apply the same pin (see ``tests/multiproc.py``).
On a machine with a chip the program runs through ``chip_smoke.py``
instead — one process, in-process parties.
"""

import os

# Runtime lock-order sanitizer (rayfed_tpu/_sanitizer.py): every tier-1
# test — including party subprocesses, which inherit the env — runs with
# repo-constructed locks tracked and a LockOrderError raised the moment
# two locks are acquired in conflicting orders.  The static FED007 pass
# (tool/fedlint) sees only lexical nesting; this catches the dynamic,
# callback-driven orderings.  setdefault: RAYFED_SANITIZE=0 disables.
os.environ.setdefault("RAYFED_SANITIZE", "1")

from rayfed_tpu.utils import force_cpu_devices, use_compilation_cache  # noqa: E402

force_cpu_devices(8)

# Persistent XLA compilation cache, shared by the pytest process AND the
# spawned party subprocesses (which inherit the environment; jax reads
# the variable at import).  Multi-party tests re-jit the SAME
# trainer/fold programs in every fresh child — per-subprocess compiles
# dominate tier-1 wall time (ROADMAP budget item), and with the cache N
# party children pay one compile instead of N, and repeat runs pay none.
# Concurrent writers are safe: the cache writes via temp-file + rename,
# and a cache miss (or corrupt read) falls back to a normal compile.
os.environ["JAX_COMPILATION_CACHE_DIR"] = use_compilation_cache()
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")


import pytest  # noqa: E402


@pytest.fixture(params=["ragged", "megablox"])
def grouped(request, monkeypatch):
    """Both grouped products of ``models/moe.py``: ``ragged_dot`` (the
    CPU's) and the Pallas kernel under the interpreter."""
    if request.param == "megablox":
        from rayfed_tpu.models import moe

        monkeypatch.setattr(moe, "_grouped_impl", lambda: "megablox-interpret")
    return request.param
