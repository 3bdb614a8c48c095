"""Per-party Runtime — the single-controller replacement for Ray.

The reference spreads per-party state across a Ray cluster: config in the
GCS internal KV, proxies as named actors, a module-global seq counter.
Here everything a party owns lives on one :class:`Runtime` object:

- the deterministic sequence counter (:class:`~rayfed_tpu.context.GlobalContext`),
- the local :class:`~rayfed_tpu.executor.TaskExecutor`,
- the cross-party send/recv proxies (asyncio transport),
- the cleanup/send-watchdog,
- the party-local JAX device mesh for sharded compute.

Runtime resolution is thread-local with a process-wide default.  This is
what enables *multi-party-in-one-process* runs (:mod:`rayfed_tpu.inprocess`):
each party gets its own Runtime bound to its own threads, so all parties
can share the one local TPU chip — which belongs to ONE process at a time
— while still exercising the real wire transport.

Binding a thread to a Runtime (:meth:`Runtime.bind_thread`) also makes
the party's device that thread's JAX default device: uncommitted arrays
(``jnp.zeros``, a bare ``jax.device_put``, numpy inputs to a jit) and
the computations that follow them land on the party's chip rather than
on ``jax.devices()[0]``.  Every helper thread that touches JAX for a
party — executor workers, actor lanes, transport codec/fetch pools, the
streaming aggregator's fold worker — binds before it runs.
"""

from __future__ import annotations

import logging
import threading
from typing import Any, Optional

from rayfed_tpu.config import ClusterConfig, JobConfig
from rayfed_tpu.context import GlobalContext
from rayfed_tpu.executor import ActorInstance, TaskExecutor

logger = logging.getLogger(__name__)

_tls = threading.local()
_process_default_runtime: Optional["Runtime"] = None
_default_lock = threading.Lock()


class Runtime:
    def __init__(
        self,
        cluster_config: ClusterConfig,
        job_config: JobConfig,
        max_workers: int = 16,
        mesh: Optional[Any] = None,
    ) -> None:
        self.cluster_config = cluster_config
        self.job_config = job_config
        self.global_context = GlobalContext()
        self.mesh = mesh  # party-local jax.sharding.Mesh (or None)
        self.executor = TaskExecutor(
            max_workers=max_workers,
            thread_name_prefix=f"rayfed-{cluster_config.current_party}",
            bind_runtime_fn=self.bind_thread,
        )
        # fl.quantize's per-sender error-feedback state, by stream scope.
        self.quant_compressors: dict = {}
        self._actors: list[ActorInstance] = []
        self._actors_lock = threading.Lock()
        # Late-bound by api.init(): transport proxies + cleanup manager.
        self.send_proxy = None
        self.recv_proxy = None
        self.transport = None
        self.cleanup_manager = None
        self.sequence_tracer = None

    @property
    def party(self) -> str:
        return self.cluster_config.current_party

    @property
    def default_device(self) -> Optional[Any]:
        """The device this party's uncommitted arrays land on: the first
        device of its mesh that this process addresses (``None`` without
        a mesh — JAX's own default)."""
        return None if self.mesh is None else self.mesh.local_devices[0]

    def local_devices(self) -> list:
        """The devices of this process the party computes on: its
        mesh's, or without a mesh the first local one (where JAX puts
        an uncommitted array)."""
        if self.mesh is not None:
            return list(self.mesh.local_devices)
        import jax

        return jax.local_devices()[:1]

    def bind_thread(self) -> None:
        """Bind the calling thread to this party: ``get_runtime()``, log
        records and JAX's default device all resolve to it.  Idempotent
        (pool workers call it before every task)."""
        _bind_thread(self)

    def register_actor(self, actor: ActorInstance) -> None:
        with self._actors_lock:
            self._actors.append(actor)

    def next_seq_id(self) -> int:
        return self.global_context.next_seq_id()

    def shutdown_actors(self) -> None:
        with self._actors_lock:
            actors, self._actors = self._actors, []
        for actor in actors:
            actor.kill()


def _bind_thread(runtime: Optional[Runtime]) -> None:
    from rayfed_tpu.utils.logging_utils import set_thread_party

    _tls.runtime = runtime
    set_thread_party(None if runtime is None else runtime.party)
    device = None if runtime is None else runtime.default_device
    if getattr(_tls, "device", None) is device:
        return
    # jax.default_device is thread-local only as a context manager; a
    # bound thread keeps it entered until it binds elsewhere or unbinds.
    scope = getattr(_tls, "device_scope", None)
    if scope is not None:
        scope.__exit__(None, None, None)
    _tls.device = device
    _tls.device_scope = None
    if device is not None:
        import jax

        scope = jax.default_device(device)
        scope.__enter__()
        _tls.device_scope = scope


def set_current_runtime(runtime: Optional[Runtime], process_default: bool = True):
    """Bind ``runtime`` for the current thread (and optionally the process)."""
    global _process_default_runtime
    _bind_thread(runtime)
    if process_default:
        with _default_lock:
            _process_default_runtime = runtime


def clear_current_runtime(runtime: Runtime) -> None:
    """Unbind the current thread, and drop ``runtime`` as the process
    default only if it IS the default — one in-process party shutting
    down must not take the other parties' default away."""
    global _process_default_runtime
    _bind_thread(None)
    with _default_lock:
        if _process_default_runtime is runtime:
            _process_default_runtime = None


def get_runtime() -> Runtime:
    runtime = getattr(_tls, "runtime", None)
    if runtime is None:
        runtime = _process_default_runtime
    if runtime is None:
        raise RuntimeError(
            "rayfed_tpu is not initialized in this thread; call fed.init() first"
        )
    return runtime


def get_runtime_or_none() -> Optional[Runtime]:
    runtime = getattr(_tls, "runtime", None)
    return runtime if runtime is not None else _process_default_runtime
