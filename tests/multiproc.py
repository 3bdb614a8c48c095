"""Multi-party test harness: N OS processes, one per party, real transport.

Mirrors the reference's dominant test pattern (SURVEY §4): simulate N
parties as processes on one host, each running the same ``run(party, ...)``
function, assert both exit 0.  Uses the ``spawn`` start method so each
child gets a clean interpreter (safe with JAX/threads), and pins the child
to the CPU platform before any backend initialization.

Every child is CPU-only BY CONSTRUCTION: an accelerator chip belongs to
one process at a time, so one-process-per-party cannot put party compute
on a chip.  Parties that must share a chip run as threads of one process
(``rayfed_tpu.inprocess``; ``tests/test_inprocess_parties.py``).
"""

from __future__ import annotations

import multiprocessing as mp
import os
import socket
from typing import Callable, Dict, Iterable, Optional, Sequence

_CHILD_ENV = {"JAX_PLATFORMS": "cpu"}


def get_free_ports(n: int) -> list[int]:
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def make_cluster(parties: Sequence[str], ports: Optional[Sequence[int]] = None) -> Dict:
    if ports is None:
        ports = get_free_ports(len(parties))
    return {p: {"address": f"127.0.0.1:{port}"} for p, port in zip(parties, ports)}


def _child_entry(env: Dict[str, str], module: str, fn_name: str, party: str, args: tuple):
    os.environ.update(env)
    from rayfed_tpu.utils import force_cpu_devices

    force_cpu_devices(8)
    import importlib

    run = getattr(importlib.import_module(module), fn_name)
    run(party, *args)


def run_parties(
    run_fn: Callable,
    parties: Iterable[str],
    args: tuple = (),
    timeout: float = 180,
    expect_exitcodes: Optional[Dict[str, int]] = None,
    start_delays: Optional[Dict[str, float]] = None,
):
    """Run ``run_fn(party, *args)`` in one spawned process per party.

    Asserts every process exits 0 (or ``expect_exitcodes[party]``).
    ``start_delays`` delays individual party startup (async-startup tests).
    """
    import time

    ctx = mp.get_context("spawn")
    procs: Dict[str, mp.Process] = {}
    order = list(parties)
    for party in order:
        procs[party] = ctx.Process(
            target=_child_entry,
            args=(_CHILD_ENV, run_fn.__module__, run_fn.__name__, party, args),
            name=f"party-{party}",
        )
    for party in order:
        if start_delays and party in start_delays:
            time.sleep(start_delays[party])
        procs[party].start()
    for party in order:
        procs[party].join(timeout=timeout)
    for party in order:
        proc = procs[party]
        if proc.is_alive():
            proc.terminate()
            proc.join(5)
            raise AssertionError(f"party {party} timed out after {timeout}s")
    for party in order:
        expected = (expect_exitcodes or {}).get(party, 0)
        assert procs[party].exitcode == expected, (
            f"party {party} exited with {procs[party].exitcode}, expected {expected}"
        )
