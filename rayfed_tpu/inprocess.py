"""Several parties in ONE process: one thread per party.

An accelerator chip belongs to one process at a time, so the
one-OS-process-per-party launch of ``examples/`` cannot put party
compute on a one-chip machine (the second party fails or hangs opening
the device), nor give each party its own chip of a four-chip host.
Here every party is a thread of the calling process, initialized with
``fed.init(..., process_default=False)``: each gets its own
:class:`~rayfed_tpu.runtime.Runtime`, executor, actors and transport
listener on a loopback port, so the same driver function runs unchanged
and every cross-party byte still crosses the real TCP transport — only
the process boundary is gone.  A party's ``mesh`` pins its compute to
its own device(s) (see :meth:`rayfed_tpu.runtime.Runtime.bind_thread`).
"""

from __future__ import annotations

import socket
import threading
import time
from typing import Any, Callable, Dict, Optional, Sequence

import rayfed_tpu as fed


def loopback_cluster(parties: Sequence[str]) -> Dict[str, Dict[str, str]]:
    """A cluster dict placing each party on a free loopback port."""
    socks = []
    try:
        for _ in parties:
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            socks.append(s)
        return {
            p: {"address": f"127.0.0.1:{s.getsockname()[1]}"}
            for p, s in zip(parties, socks)
        }
    finally:
        for s in socks:
            s.close()


def run_parties(
    fn: Callable[[str], Any],
    cluster: Dict[str, Dict],
    *,
    meshes: Optional[Dict[str, Any]] = None,
    timeout: Optional[float] = None,
    **init_kwargs: Any,
) -> Dict[str, Any]:
    """Run ``fn(party)`` for every party of ``cluster``, one thread each.

    Each thread brackets ``fn`` with ``fed.init(address="local",
    cluster=cluster, party=party, process_default=False,
    mesh=meshes[party], **init_kwargs)`` and ``fed.shutdown()``.
    Parties shut down together once all returned (a finished party may
    still owe its peers a blob serve); a party that raises shuts down at
    once, so its peers' parked recvs fail fast instead of hanging.

    Returns ``{party: fn(party)}``.  Raises ``RuntimeError`` naming the
    parties that raised (chained to the first failure), or
    ``TimeoutError`` naming those still running after ``timeout``
    seconds (their threads are daemons and die with the process).
    """
    # First imports of the fl package must not race across the party
    # threads (see the pre-warm note at the end of fed.init).
    import rayfed_tpu.fl  # noqa: F401

    parties = list(cluster)
    results: Dict[str, Any] = {}
    errors: Dict[str, BaseException] = {}
    all_returned = threading.Barrier(len(parties))

    def _party_main(party: str) -> None:
        try:
            fed.init(
                address="local",
                cluster=cluster,
                party=party,
                process_default=False,
                mesh=(meshes or {}).get(party),
                **init_kwargs,
            )
            try:
                results[party] = fn(party)
                try:
                    all_returned.wait()
                except threading.BrokenBarrierError:
                    pass  # a peer failed; nothing left to wait for
            finally:
                fed.shutdown()
        # fedlint: disable=FED004 — transferred, not swallowed: the launcher re-raises every party's failure from the calling thread
        except BaseException as e:
            errors[party] = e
            all_returned.abort()

    threads = {
        p: threading.Thread(
            target=_party_main, args=(p,), name=f"rayfed-party-{p}",
            daemon=True,
        )
        for p in parties
    }
    for t in threads.values():
        t.start()
    deadline = None if timeout is None else time.monotonic() + timeout
    for t in threads.values():
        t.join(
            None if deadline is None
            else max(0.0, deadline - time.monotonic())
        )
    hung = [p for p, t in threads.items() if t.is_alive()]
    if errors:
        first = next(p for p in parties if p in errors)
        raise RuntimeError(
            f"in-process parties failed: "
            f"{ {p: repr(e) for p, e in errors.items()} }"
            + (f"; still running: {hung}" if hung else "")
        ) from errors[first]
    if hung:
        raise TimeoutError(
            f"in-process parties still running after {timeout}s: {hung}"
        )
    return results
