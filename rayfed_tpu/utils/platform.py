"""Platform and compile-cache helpers.

Two ways to run the program.  On the CPU (tests and the
multi-process examples) a process pins JAX to
the CPU platform with N virtual devices — :func:`force_cpu_devices`,
called before the first backend initialization.  On the chip JAX's
default platform is the accelerator and ONE process holds it, so every
party runs in that process (:mod:`rayfed_tpu.inprocess`, driven by
``chip_smoke.py``).  Both share one persistent compile cache, placed by
:func:`use_compilation_cache`.
"""

from __future__ import annotations

import logging
import os

logger = logging.getLogger(__name__)

# The in-checkout compile cache used when the environment names none
# (listed in .gitignore).  A FIXED path: the directory is part of every
# cache key, so one built from a temp dir, a pid or the time never hits.
_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_COMPILATION_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def force_cpu_devices(n: int = 8) -> None:
    """Pin JAX to the CPU platform with ``n`` virtual devices.

    Must run before any JAX backend initialization (e.g. first
    ``jax.devices()`` / jit execution) in the calling process.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n)


def use_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the cache lives there and
    no other directory is set in code (JAX reads the variable itself);
    otherwise it lives at :data:`DEFAULT_COMPILATION_CACHE_DIR`.  Every
    program is cached, however quick its compile: a run's many small
    programs are most of a warm start.
    """
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_COMPILATION_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache_dir


def guard_subslice_mesh(mesh) -> bool:
    """Turn the persistent compile cache OFF, process-wide, when ``mesh``
    is a multi-chip PROPER sub-slice of this process's accelerator
    chips.  Returns whether ``mesh`` is such a sub-slice.

    On jax 0.9.0 / libtpu 0.0.34 a multi-device executable RESTORED from
    the persistent cache for such a mesh halts the cores it runs on
    (``schecklt: Invalid logical z: enhanced-barrier-parent-phase-1``)
    and the TPU runtime then terminates the process without a Python
    traceback.  Measured on a v5e 2x2 with two in-process parties x a
    two-chip mesh (``chip_smoke.py --chips 4``, PR 22): compiled fresh
    the programs run; the same programs restored from the cache halt
    chips 2 and 3; with the cache off they run.  Single-chip meshes and
    meshes over all of the process's chips are not affected.  A cold
    compile at every start is the price of not losing the chip; the CPU
    backend shows no such fault and keeps its cache.
    """
    import jax

    devices = list(mesh.devices.flat)
    if (
        len(devices) < 2
        or devices[0].platform == "cpu"
        or len(devices) >= len(jax.local_devices())
    ):
        return False
    if jax.config.jax_enable_compilation_cache:
        from jax.experimental.compilation_cache import compilation_cache

        logger.warning(
            "party mesh %s is a multi-chip sub-slice of this process's %d "
            "chips: turning JAX's persistent compilation cache off for the "
            "process (executables restored from it for such a mesh halt "
            "the chips on jax 0.9.0 / libtpu 0.0.34)",
            sorted(d.id for d in devices), len(jax.local_devices()),
        )
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
    return True
